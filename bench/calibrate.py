#!/usr/bin/env python3
"""Readings that set a cell's correctness limits (not run by the benchmark).

    python bench/calibrate.py --workload <name> --seeds 11,12,... --seconds <s> \
        [--controls 3] [--out calibrate-<name>.jsonl]

In one process, for each seed: a run of the cell (set-up, a window of
``--seconds``, the program's side of the check) and the numbers its check
compares (the lower readings).  For the first ``--controls`` seeds also
the control, the reference computed one precision lower in the program's
place, and for training cells the fault of half the batch left out (the
upper readings); under AMR numerics also the reference with its seam
inputs rounded to bfloat16, against itself (what that rounding alone
moves).  Every reading is judged by the cell's limits as a run's check
judges it (``correct``).  One JSON line per seed on stdout and in ``--out``.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path[:0] = [str(BENCH), str(ROOT / "src")]

from harness import common, spec  # noqa: E402


def judged(nums: dict, limits: dict) -> dict:
    """The readings, with ``correct`` as a run's check would give it."""
    return {**nums, "correct": all(nums[k] <= lim for k, lim in limits.items())}


def readings(cell, seed: int, seconds: float, control: bool) -> dict:
    from harness import serve, train
    from reference import model as ref

    t0 = time.monotonic()
    driver = train if cell.kind == "train" else serve
    prog = driver.run(cell, seed, seconds, common.Tracer(None), common.CompileCounter())
    out = {"seed": seed, "e2e": prog["e2e"], "failed": prog["failed"],
           "attempted": prog["attempted"], "run_s": time.monotonic() - t0}
    t1 = time.monotonic()
    lim = cell.limits
    if cell.kind == "train":
        want = train.reference_readings(cell, seed)
        out["sound"] = judged(train.compare(prog["program"], want), lim)
        out["reference_s"] = time.monotonic() - t1
        if control:
            mode = ref.Mode.of(cell.numerics)
            ctl = train.reference_readings(cell, seed, mode=mode.control())
            out["control"] = judged(train.compare(ctl, want), lim)
            half = train.reference_readings(cell, seed, rows=cell.traffic["batch"] // 2)
            out["half_batch"] = judged(train.compare(half, want), lim)
            if mode.kind == "amr":
                rounded = train.reference_readings(cell, seed, mode=mode.rounded())
                out["rounding"] = judged(train.compare(rounded, want), lim)
    else:
        out["sound"] = judged({"logit_gap": max(serve.gaps(cell, seed, prog["served"]))}, lim)
        out["reference_s"] = time.monotonic() - t1
        if control:
            out["control"] = judged({"logit_gap": max(serve.gaps(
                cell, seed, prog["served"], control=True))}, lim)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--controls", type=int, default=3)
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload)

    import jax

    jax.config.update("jax_compilation_cache_dir", str(ROOT / ".bench_cache" / "jax"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    common.device_info(cell.chips)
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        line = json.dumps(readings(cell, seed, args.seconds, i < args.controls))
        print(line, flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
