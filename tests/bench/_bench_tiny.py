"""Tiny cells for the benchmark's CPU tests: the harness's code paths at a
size a test run can hold, with no chip."""
from __future__ import annotations

import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for p in (ROOT / "bench", ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

from harness.spec import Cell  # noqa: E402

TINY = {"name": "tiny", "family": "dense", "n_layers": 2, "d_model": 64,
        "n_heads": 4, "n_kv_heads": 2, "head_dim": 16, "d_ff": 128,
        "vocab": 256, "mlp_act": "swiglu", "tie_embeddings": False,
        "rope_theta": 10000.0, "norm_eps": 1e-6, "dtype": "bfloat16"}

TRAIN = {"kind": "train", "batch": 4, "seq": 32, "peak_lr": 0.003,
         "warmup": 20, "total_steps": 100}

OPEN = {"kind": "serve", "rate_per_s": 4.0, "slots": 4,
        "capacity": 64, "prompt": {"median": 12, "sigma": 0.6, "min": 4,
                                   "max": 32, "round_up_to": 8},
        "new_tokens": {"median": 6, "sigma": 0.5, "min": 2, "max": 12}}


class NoTrace:
    on = False

    def start(self):
        pass

    def stop(self):
        pass


class Counter:
    armed = False
    count = 0


def cell(traffic: dict, numerics: dict, limits: dict, tied: bool = False,
         check_requests: int = 3) -> Cell:
    config = {**TINY, "tie_embeddings": tied}
    workload = {"numerics": numerics, "limits": limits, "check_requests": check_requests,
                "peak": "bf16_flops_per_s"}
    return Cell("tiny", 1, config, traffic, workload, (), ())
