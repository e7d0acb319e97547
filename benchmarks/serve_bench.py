"""Continuous-batching serving bench: latency/throughput + batching exactness.

Two row kinds over the tiny LM (same scale as train_numerics_bench):

  * ``throughput`` — serve a fixed request set through ``ServeEngine`` at
    several concurrency levels (slot counts) and record p50/p99 request
    latency, end-to-end tokens/s and steady-state decode tokens/s (decode
    steps only — compile and prefill excluded; a warmup cycle runs first).
  * ``bit_exact`` — the continuous-batching correctness gate: the same
    mixed-length request set is served batched (3 slots) and solo (1 slot,
    identical code path) under each numerics mode; token streams must match
    and the recorded per-token logit streams must agree BITWISE
    (``max_abs_diff`` exactly 0.0). This covers the integer AMR modes
    (amr_lut / amr_inject / amr_kernel-rank0) and exact.

  PYTHONPATH=src python -m benchmarks.serve_bench --quick --out BENCH_serve.json

JSON schema (``BENCH_serve/v1``)::

  {"schema": "BENCH_serve/v1", "engine": "jax", "quick": bool,
   "gen": int, "capacity": int, "border": int,
   "config": {"d_model": int, "d_ff": int, "vocab": int, "n_layers": int},
   "results": [{"kind": "throughput", "mode": str, "concurrency": int,
                "requests": int, "tokens": int, "complete": bool,
                "p50_latency_ms": float, "p99_latency_ms": float,
                "tokens_per_s": float, "steady_tokens_per_s": float},
               {"kind": "bit_exact", "mode": str, "concurrency": int,
                "requests": int, "bit_exact": bool, "tokens_match": bool,
                "max_abs_diff": float}],
   "wall_clock_s": float}

``scripts/check_bench.py`` gates ``complete`` / ``bit_exact`` /
``tokens_match`` / ``max_abs_diff`` exactly against
``benchmarks/baselines/BENCH_serve.json``; the latency/throughput numbers
are advisory (host-speed dependent).
"""
from __future__ import annotations

import argparse
import json
import os
import time

import numpy as np

BORDER = 8
CFG = dict(d_model=32, d_ff=64, vocab=64, n_layers=2)
CONCURRENCIES = (1, 2, 4)
BATCHED_SLOTS = 3
# mixed prompt lengths on purpose: slots decode at different cache depths
PROMPT_LENS = (4, 6, 2, 5, 7, 3)


def _tiny_config(numerics):
    from repro.configs.base import ModelConfig

    return ModelConfig(
        name="serve-bench-tiny", family="dense", n_layers=CFG["n_layers"],
        d_model=CFG["d_model"], n_heads=2, n_kv_heads=2, head_dim=16,
        d_ff=CFG["d_ff"], vocab=CFG["vocab"], mlp_act="swiglu",
        tie_embeddings=True, remat="none", numerics=numerics)


def _requests(n, gen, vocab):
    from repro.serve import Request

    rng = np.random.default_rng(0)
    out = []
    for i in range(n):
        plen = PROMPT_LENS[i % len(PROMPT_LENS)]
        prompt = tuple(int(t) for t in rng.integers(0, vocab, plen))
        out.append(Request(prompt=prompt, max_new_tokens=gen))
    return out


def _serve(cfg, params, requests, n_slots, capacity, *, record_logits,
           warmup=True):
    from repro.serve import Request, ServeEngine

    engine = ServeEngine(cfg, params, n_slots=n_slots, capacity=capacity,
                         record_logits=record_logits)
    if warmup:
        for r in requests:  # compile every distinct prompt length + decode
            engine.submit(Request(prompt=r.prompt, max_new_tokens=2))
        engine.run()
        engine.completions.clear()
        engine.counters.reset()
    for r in requests:
        engine.submit(Request(prompt=r.prompt, max_new_tokens=r.max_new_tokens,
                              eos_id=r.eos_id))
    t0 = time.monotonic()
    done = engine.run()
    wall = time.monotonic() - t0
    return engine, done, wall


def _throughput_row(cfg, params, concurrency, gen, capacity, n_requests):
    reqs = _requests(n_requests, gen, cfg.vocab)
    engine, done, wall = _serve(cfg, params, reqs, concurrency, capacity,
                                record_logits=False)
    lat = sorted(c.total_s for c in done)
    total_tokens = sum(len(c.tokens) for c in done)
    complete = (len(done) == n_requests
                and all(len(c.tokens) == gen for c in done))
    ctr = engine.counters
    steady = ctr.decode_tokens / ctr.decode_seconds if ctr.decode_seconds > 0 else 0.0
    return {
        "kind": "throughput", "mode": cfg.numerics.mode,
        "concurrency": concurrency, "requests": n_requests,
        "tokens": total_tokens, "complete": bool(complete),
        "p50_latency_ms": round(lat[len(lat) // 2] * 1e3, 3),
        "p99_latency_ms": round(lat[min(int(len(lat) * 0.99), len(lat) - 1)] * 1e3, 3),
        "tokens_per_s": round(total_tokens / wall, 1),
        "steady_tokens_per_s": round(steady, 1),
    }


def _bit_exact_row(make_cfg, gen, capacity, n_requests):
    """Batched (3 slots) vs solo (1 slot) token+logit streams, one mode."""
    import jax

    from repro.models import init_params

    cfg = make_cfg()
    params = init_params(cfg, jax.random.PRNGKey(0))
    reqs = _requests(n_requests, gen, cfg.vocab)
    _, batched, _ = _serve(cfg, params, reqs, BATCHED_SLOTS, capacity,
                           record_logits=True, warmup=False)
    _, solo, _ = _serve(cfg, params, reqs, 1, capacity,
                        record_logits=True, warmup=False)
    tokens_match = all(b.tokens == s.tokens for b, s in zip(batched, solo))
    max_diff = 0.0
    for b, s in zip(batched, solo):
        for lb, ls in zip(b.logits, s.logits):
            max_diff = max(max_diff, float(np.max(np.abs(lb - ls))))
    return {
        "kind": "bit_exact", "mode": cfg.numerics.mode,
        "concurrency": BATCHED_SLOTS, "requests": n_requests,
        "bit_exact": bool(tokens_match and max_diff == 0.0),
        "tokens_match": bool(tokens_match),
        "max_abs_diff": max_diff,
    }


def run(quick: bool = False, out: str | None = None) -> list[str]:
    import jax

    from repro.models import init_params
    from repro.numerics import AMRNumerics

    t0 = time.time()
    gen = 4 if quick else 8
    n_requests = 4 if quick else 6
    capacity = max(PROMPT_LENS) + gen
    rows: list[str] = []
    results: list[dict] = []

    # -- latency / throughput at several concurrency levels (exact mode) ----
    cfg = _tiny_config(AMRNumerics("exact"))
    params = init_params(cfg, jax.random.PRNGKey(0))
    for conc in CONCURRENCIES:
        r = _throughput_row(cfg, params, conc, gen, capacity, n_requests)
        results.append(r)
        rows.append(f"serve_throughput_c{conc},0,"
                    f"p50={r['p50_latency_ms']}ms;p99={r['p99_latency_ms']}ms;"
                    f"steady={r['steady_tokens_per_s']}tok/s")

    # -- batched-vs-solo exactness per numerics mode -------------------------
    policies = [
        lambda: _tiny_config(AMRNumerics("exact")),
        lambda: _tiny_config(AMRNumerics("amr_lut", border=BORDER)),
        lambda: _tiny_config(AMRNumerics("amr_inject", border=BORDER)),
        lambda: _tiny_config(AMRNumerics("amr_kernel", border=BORDER, rank=0)),
    ]
    for make_cfg in policies:
        r = _bit_exact_row(make_cfg, gen, capacity, n_requests)
        results.append(r)
        rows.append(f"serve_bit_exact_{r['mode']},0,"
                    f"bit_exact={r['bit_exact']};max_abs_diff={r['max_abs_diff']}")

    artifact = {
        "schema": "BENCH_serve/v1",
        "engine": "jax",
        "quick": quick,
        "gen": gen,
        "capacity": capacity,
        "border": BORDER,
        "config": CFG,
        "results": results,
        "wall_clock_s": round(time.time() - t0, 2),
    }
    out = out or os.environ.get("REPRO_BENCH_SERVE_OUT", "BENCH_serve.json")
    with open(out, "w") as f:
        json.dump(artifact, f, indent=1)
    rows.append(f"serve_bench_artifact,0,{out}:{len(results)}_results")

    # Hard gates mirrored from check_bench: incomplete serving or any
    # batching-dependent numerics drift fails the bench run itself.
    bad = [r["mode"] for r in results
           if r["kind"] == "bit_exact" and not r["bit_exact"]]
    if bad:
        raise RuntimeError(
            f"slot-batched decode is not bit-identical to solo decode under "
            f"mode(s): {bad}")
    incomplete = [r["concurrency"] for r in results
                  if r["kind"] == "throughput" and not r["complete"]]
    if incomplete:
        raise RuntimeError(
            f"serve run did not complete all requests at concurrency "
            f"{incomplete}")
    return rows


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--out", default=None, help="artifact path (BENCH_serve.json)")
    args = ap.parse_args(argv)
    for row in run(quick=args.quick, out=args.out):
        print(row)


if __name__ == "__main__":
    main()
