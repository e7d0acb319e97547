"""Batched serving example: prefill + greedy decode on any assigned arch.

  PYTHONPATH=src python examples/serve_decode.py --arch zamba2-1.2b --reduced
  PYTHONPATH=src python examples/serve_decode.py --arch whisper-small --reduced
"""
from repro.launch.serve import main

if __name__ == "__main__":
    main()
