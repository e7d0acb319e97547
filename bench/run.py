#!/usr/bin/env python3
"""Run one benchmark cell once on the chips of this machine.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Loads the cell's files (``bench/harness/spec.py``), makes weights and
inputs from ``--seed``, warms up every shape the cell uses (set-up), runs
the measured window, checks what the timed path produced against the
plain reference, and prints one JSON line last on stdout:

    {"correct", "attempted", "failed", "metrics", "device", ["breakdown"], "checks"}

``--trace 0`` reports the cell's end-to-end metrics; ``--trace 1`` runs the
window under the profiler and reports its per-layer metrics.  Without a
TPU, or with fewer chips than the cell asks for, it exits non-zero and
prints no result.  JAX's persistent compilation cache lives in
``.bench_cache/jax`` inside the checkout.
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path[:0] = [str(BENCH), str(ROOT / "src")]

from harness import common, spec  # noqa: E402

CACHE = ROOT / ".bench_cache"


def check(cell, kind: str, seed: int, prog: dict) -> dict:
    """Compare the timed path's output with the reference: {name: (value, limit)}."""
    from harness import serve, train

    limits = cell.limits
    if kind == "train":
        nums = train.compare(prog["program"], train.reference_readings(cell, seed))
    else:
        nums = {"logit_gap": max(serve.gaps(cell, seed, prog["served"]), default=math.inf)}
    for k, v in nums.items():
        if k not in limits:
            print(f"reported {k} {v!r} (no limit)", file=sys.stderr)
    return {k: (nums[k], lim) for k, lim in limits.items()}


def layer_metrics(cell, prog: dict, trace, device: dict) -> dict:
    """Per-layer metrics whose readers find something to read."""
    from types import SimpleNamespace

    ctx = SimpleNamespace(cell=cell, e2e=prog["e2e"], counters=prog["counters"],
                          trace=trace, peaks=common.peaks(device["kind"]),
                          chips=cell.chips)
    out = {}
    for m in cell.per_layer:
        v = spec.metric_reader(m["name"])(ctx)
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be a non-negative whole number")
    cell = spec.load_cell(args.workload)

    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE / "jax"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    try:
        device = common.device_info(cell.chips)
    except common.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    tracer = common.Tracer(CACHE / "trace" if args.trace else None)
    result = execute(cell, args.seed, args.seconds, tracer, device)
    for k, c in result["checks"].items():
        print(f"check {k} {c['value']!r} limit {c['limit']!r}", file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


def execute(cell, seed: int, seconds: float, tracer, device: dict) -> dict:
    """Set-up, window, check and metrics of one run: the result line."""
    from harness import serve, train

    counter = common.CompileCounter()
    driver = train if cell.kind == "train" else serve
    try:
        prog = driver.run(cell, seed, seconds, tracer, counter)
    finally:
        tracer.stop()
    setup_s = prog["t_window"] - T_START
    print(f"compiles_in_window {counter.count}", flush=True)
    device = {**device, "memory_peak_bytes": prog["memory_peak_bytes"]}

    checks = check(cell, cell.kind, seed, prog)
    correct = (prog["failed"] == 0 and prog["attempted"] > 0
               and all(v <= lim for v, lim in checks.values()))
    result = {"correct": correct, "attempted": prog["attempted"], "failed": prog["failed"]}
    if tracer.on:
        from harness import trace as tr

        t = tr.load(str(next(tracer.dir.glob("plugins/profile/*/*.xplane.pb"))))
        device["busy_s"] = tr.busy_s(t)
        device["window_s"] = t.window_s
        result["metrics"] = layer_metrics(cell, prog, t, device)
        result["device"] = device
        result["breakdown"] = {"device_ops": tr.top_ops(t), "idle_gaps": tr.idle_by_span(t)}
        shutil.rmtree(tracer.dir, ignore_errors=True)
    else:
        values = {**prog["e2e"], "setup_s": setup_s}
        result["metrics"] = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                             for m in cell.end_to_end}
        result["device"] = device
    result["checks"] = {k: {"value": v, "limit": lim} for k, (v, lim) in checks.items()}
    return result


if __name__ == "__main__":
    sys.exit(main())
