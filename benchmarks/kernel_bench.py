"""amr_matmul kernel sweep: {low-rank, full-LUT, exact XLA} x borders x sizes.

Times each variant AND measures its max-abs-error against the schedule
engine's exact AMR replay (``ref_bitexact_int8`` — per-element products
from the engine-built table), so accuracy and speed land in one run, and
writes the ``BENCH_kernel.json`` artifact (schema below; CI uploads it
from the tier-1 job).  On CPU the Pallas kernels run in interpreter mode
(backend autodetect — timings are correctness-path only; real wall times
come from TPU runs of the same sweep); the full-LUT variant must be
bit-exact vs the replay on every backend.

  PYTHONPATH=src python -m benchmarks.kernel_bench --quick --out BENCH_kernel.json

JSON schema (``BENCH_kernel.json``)::

  {"schema": "BENCH_kernel/v1", "backend": str, "interpret": bool,
   "engine": str,
   "results": [{"variant": "lowrank|lut|exact", "border": int|null,
                "rank": int|null, "m": int, "n": int, "k": int,
                "bm": int, "bn": int, "bk": int,
                "us_per_call": float, "max_abs_err_vs_amr": float,
                "bit_exact_vs_amr": bool}]}
"""
from __future__ import annotations

import argparse
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.amr_matmul.kernel import amr_matmul_int8, amr_matmul_int8_lut
from repro.kernels.amr_matmul.ops import lut_factors
from repro.kernels.amr_matmul.ref import ref_bitexact_int8
from repro.kernels.amr_matmul.tiling import pick_tiles
from repro.kernels.pallas_config import backend_kind, default_interpret
from repro.kernels.ssd_scan.kernel import ssd_scan
from repro.kernels.ssd_scan.ref import ref_ssd
from repro.core import lut as lut_lib

RANK = 8  # low-rank variant's rank in the sweep


def _time(fn, *args, reps=3):
    fn(*args)  # compile
    t0 = time.time()
    for _ in range(reps):
        jax.block_until_ready(fn(*args))
    return (time.time() - t0) / reps * 1e6


def _sweep_point(a8, b8, want, border: int | None, variant: str, engine: str) -> dict:
    m, k = a8.shape
    n = b8.shape[1]
    rank = None
    if variant == "exact":
        bm = bn = bk = 0  # XLA picks its own tiling
        fn = jax.jit(lambda x, y: jnp.matmul(
            x.astype(jnp.float32), y.astype(jnp.float32)))
        got = np.asarray(fn(a8, b8)).astype(np.float64)
        us = _time(fn, a8, b8)
    elif variant == "lowrank":
        rank = RANK
        t = pick_tiles(m, n, k, variant="lowrank")
        bm, bn, bk = t.bm, t.bn, t.bk
        u, v = lut_factors(border, RANK, engine)
        fn = lambda x, y: amr_matmul_int8(x, y, u, v)  # noqa: E731  (same tiles)
        got = np.asarray(fn(a8, b8)).astype(np.float64)
        us = _time(fn, a8, b8)
    elif variant == "lut":
        t = pick_tiles(m, n, k, variant="lut")
        bm, bn, bk = t.bm, t.bn, t.bk
        table = lut_lib.table_array(border, engine)
        fn = lambda x, y: amr_matmul_int8_lut(x, y, table)  # noqa: E731  (same tiles)
        got = np.asarray(fn(a8, b8)).astype(np.float64)
        us = _time(fn, a8, b8)
    else:
        raise ValueError(variant)
    err = float(np.abs(got - want).max())
    return {
        "variant": variant, "border": border, "rank": rank,
        "m": m, "n": n, "k": k, "bm": bm, "bn": bn, "bk": bk,
        "us_per_call": round(us, 1),
        "max_abs_err_vs_amr": err,
        "bit_exact_vs_amr": bool(err == 0.0),
    }


def run(quick: bool = False, engine: str = "jax", out: str | None = None) -> list[str]:
    rows = []
    rng = np.random.default_rng(0)
    sizes = [(128, 128, 128)] if quick else [(128, 128, 128), (256, 256, 256)]
    borders = (4, 8) if quick else (None, 4, 8)
    # one fused engine call builds every border's table up front
    lut_lib.build_int8_luts(borders, engine=engine)

    results = []
    for (m, n, k) in sizes:
        a8 = jnp.asarray(rng.integers(-128, 128, (m, k)), jnp.int8)
        b8 = jnp.asarray(rng.integers(-128, 128, (k, n)), jnp.int8)
        for border in borders:
            # one oracle per (size, border), shared by all three variants
            want = ref_bitexact_int8(
                np.asarray(a8), np.asarray(b8), border=border).astype(np.float64)
            for variant in ("exact", "lowrank", "lut"):
                r = _sweep_point(a8, b8, want, border, variant, engine)
                results.append(r)
                btag = "exact" if border is None else f"b{border}"
                rows.append(
                    f"kernel_amr_{variant}_{m}x{n}x{k}_{btag},{r['us_per_call']:.0f},"
                    f"max_abs_err={r['max_abs_err_vs_amr']:.3g};"
                    f"bit_exact={r['bit_exact_vs_amr']}")

    artifact = {
        "schema": "BENCH_kernel/v1",
        "backend": backend_kind(),
        "interpret": default_interpret(),
        "engine": engine,
        "results": results,
    }
    out = out or os.environ.get("REPRO_BENCH_OUT", "BENCH_kernel.json")
    with open(out, "w") as f:
        json.dump(artifact, f, indent=1)
    rows.append(f"kernel_bench_artifact,0,{out}:{len(results)}_results")

    # ssd_scan timing kept for continuity with the pre-sweep bench
    B, S, H, P, N, chunk = 1, 512, 4, 64, 64, 128
    x = jnp.asarray(rng.normal(size=(B, S, H, P)), jnp.float32)
    dt = jnp.asarray(rng.uniform(0.01, 0.2, (B, S, H)), jnp.float32)
    al = jnp.asarray(rng.uniform(0, 1.5, (H,)), jnp.float32)
    bb = jnp.asarray(rng.normal(size=(B, S, H, N)), jnp.float32)
    cc = jnp.asarray(rng.normal(size=(B, S, H, N)), jnp.float32)
    us_k = _time(lambda *t: ssd_scan(*t, chunk, interpret=True), x, dt, al, bb, cc)
    us_r = _time(lambda *t: ref_ssd(*t, chunk), x, dt, al, bb, cc)
    rows.append(f"kernel_ssd_scan_512_interp,{us_k:.0f},jnp_ref={us_r:.0f}us")
    return rows


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--engine", choices=["jax", "numpy"], default="jax")
    ap.add_argument("--out", default=None, help="artifact path (BENCH_kernel.json)")
    args = ap.parse_args(argv)
    for row in run(quick=args.quick, engine=args.engine, out=args.out):
        print(row)


if __name__ == "__main__":
    main()
