"""Serving launcher: continuous-batching engine over the slot-decode path.

  PYTHONPATH=src python -m repro.launch.serve --arch gemma-2b --reduced \
      --requests 8 --slots 4 --prompt-len 32 --gen 32 \
      --numerics amr_kernel --border 8 --rank 8

Thin CLI over ``repro.serve.ServeEngine``: requests enter a FIFO queue,
map onto fixed decode slots of one shared KV cache, and every live slot
advances with a single jitted masked decode step (no recompiles as
requests finish / join). ``--numerics`` overrides the config's matmul
policy (choices come from the numerics mode registry) so serving
exercises the approximate multiplier end to end.

Throughput reporting: ``--warmup`` (default on) first runs one throwaway
request cycle so prefill+decode compilation is paid OUTSIDE the timed
window, then the report separates steady-state decode tokens/s (decode
steps only) from end-to-end wall time (queue + prefill + decode).
"""
from __future__ import annotations

import argparse
import dataclasses
import time
from pathlib import Path

import jax
import numpy as np

from repro.configs.registry import get_config, get_reduced_config
from repro.launch.cli import add_numerics_args, apply_pallas_interpret, numerics_from_args
from repro.launch.mesh import make_host_mesh
from repro.models import init_params
from repro.numerics import root_key
from repro.runtime import Heartbeat
from repro.serve import Request, ServeEngine


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma-2b")
    ap.add_argument("--reduced", action="store_true",
                    help="use the smoke-scale config (CPU-friendly)")
    ap.add_argument("--requests", type=int, default=8,
                    help="number of generation requests to serve")
    ap.add_argument("--slots", type=int, default=4,
                    help="concurrent decode slots (continuous batching width)")
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--no-warmup", dest="warmup", action="store_false",
                    help="skip the compile-warmup request cycle (timings then "
                         "include compilation)")
    ap.add_argument("--heartbeat", default=None,
                    help="path for the serve heartbeat JSON (runtime.fault)")
    add_numerics_args(ap)
    args = ap.parse_args(argv)

    apply_pallas_interpret(args, tag="serve")
    cfg = get_reduced_config(args.arch) if args.reduced else get_config(args.arch)
    nm = numerics_from_args(args)
    if nm is not None:
        from repro.launch.cli import policy_label

        cfg = dataclasses.replace(cfg, numerics=nm)
        print(f"[serve] numerics policy: {policy_label(nm)}")

    mesh = make_host_mesh()
    rng = np.random.default_rng(args.seed)
    capacity = args.prompt_len + args.gen
    prompts = [tuple(int(t) for t in rng.integers(0, cfg.vocab, args.prompt_len))
               for _ in range(args.requests)]
    hb = Heartbeat(Path(args.heartbeat)) if args.heartbeat else None

    with jax.set_mesh(mesh):
        params = init_params(cfg, root_key(args.seed))
        engine = ServeEngine(cfg, params, n_slots=args.slots, capacity=capacity,
                             heartbeat=hb, log=print)
        if args.warmup:
            # one throwaway cycle compiles prefill (this prompt length),
            # insert and the masked decode step outside the timed window
            print("[serve] warmup: compiling prefill + decode")
            engine.submit(Request(prompt=prompts[0], max_new_tokens=2))
            engine.run()
            engine.completions.clear()
            engine.counters.reset()

        for p in prompts:
            engine.submit(Request(prompt=p, max_new_tokens=args.gen))
        t0 = time.monotonic()
        done = engine.run()
        wall = time.monotonic() - t0

    total_tokens = sum(len(c.tokens) for c in done)
    lat = sorted(c.total_s for c in done)
    print(f"[serve] {len(done)} requests, {total_tokens} tokens in {wall:.2f}s "
          f"({total_tokens / wall:.1f} tok/s end-to-end)")
    ctr = engine.counters
    if ctr.decode_seconds > 0:
        # steady-state decode rate: tokens produced by masked decode steps
        # only (excludes queue wait + prefill + any compile)
        print(f"[serve] steady-state decode: {ctr.decode_tokens} tokens / "
              f"{ctr.decode_seconds:.2f}s = "
              f"{ctr.decode_tokens / ctr.decode_seconds:.1f} tok/s")
    print(f"[serve] latency p50 {lat[len(lat) // 2] * 1e3:.0f}ms "
          f"max {lat[-1] * 1e3:.0f}ms; stats {engine.stats()}")
    print("[serve] sample:", list(done[0].tokens)[:16])


if __name__ == "__main__":
    main()
