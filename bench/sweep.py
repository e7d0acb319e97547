#!/usr/bin/env python3
"""Find the knee of an open-loop serving cell: the highest offered rate the
engine sustains (not run by the benchmark).

    python bench/sweep.py --workload <name> --rates 2,4,6,8 --seconds 30 --seed 7

In one process, one window per rate with the cell's traffic at that rate;
prints per rate the offered and completed requests, tokens per second, TTFT
p50/p95, ITL p95, and how many requests got their first token only after
the window closed (a queue that grew through the window).
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path[:0] = [str(BENCH), str(ROOT / "src")]

from harness import common, serve, spec  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, default=7)
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload)

    import jax

    jax.config.update("jax_compilation_cache_dir", str(ROOT / ".bench_cache" / "jax"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    common.device_info(cell.chips)
    for rate in (float(r) for r in args.rates.split(",")):
        c = dataclasses.replace(cell, traffic={**cell.traffic, "rate_per_s": rate})
        prog = serve.run(c, args.seed, args.seconds, common.Tracer(None),
                         common.CompileCounter())
        print(json.dumps({"rate_per_s": rate, "attempted": prog["attempted"],
                          "failed": prog["failed"], **prog["e2e"],
                          **{k: prog["counters"][k] for k in
                             ("ttft_p50_ms", "late_first_tokens", "loop_s")}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
