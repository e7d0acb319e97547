"""Serving cells: the program's ``ServeEngine`` driven by open-loop traffic
over a window, and the tokens it served compared with the reference.

Each request is sent at its due time (the traffic file's Poisson
arrivals).  The harness calls ``engine.run(max_steps=1)`` in a loop: each
call admits what it can (prefill, slot insert, first token) and runs one
decode step for every live slot.  Token times follow from that: a request's first
token is stamped by the engine, and its later tokens come one per call
from the call that admitted it on, so the k-th arrives at the end of the
(k-2)-th call after that one.  Requests due in the window are served to
the end after it closes; latencies count every wait.

``logit_gap``: once the window has closed and the engine is freed, a
sample of the finished requests drawn from the seed, the longest among
them, is run through the reference, prompt and served tokens in one
sequence; the number compared is the widest gap by which a served token's
reference logit lies below the reference's best at that position.
"""
from __future__ import annotations

import bisect
import dataclasses
import gc
import time

import numpy as np

from . import common, flops
from .common import percentile, span
from .spec import Cell
from .traffic import distinct_prompt_lengths, requests


@dataclasses.dataclass
class Served:
    req: object            # traffic.Req
    due: float             # host time it was due (monotonic clock)
    uid: int = -1
    tokens: tuple = ()
    t_first: float = 0.0
    times: list = dataclasses.field(default_factory=list)


def _warm(engine, traffic: dict) -> None:
    """Compile every shape the traffic uses: one prefill per prompt length,
    the slot insert and the decode step."""
    from repro.serve import Request

    for n in distinct_prompt_lengths(traffic):
        engine.submit(Request(prompt=(1,) * n, max_new_tokens=2))
    engine.run()


def run(cell: Cell, seed: int, seconds: float, tracer, counter) -> dict:
    import jax

    from repro.serve import Request, ServeEngine

    t = cell.traffic
    params = common.make_weights(cell.config, seed)
    engine = ServeEngine(common.program_config(cell), params, n_slots=t["slots"],
                         capacity=t["capacity"])
    _warm(engine, t)
    reqs = requests(t, seed, seconds, cell.config["vocab"])
    common.log(f"set-up done: {len(distinct_prompt_lengths(t))} prompt lengths warmed")

    served: list[Served] = []
    by_uid: dict[int, Served] = {}
    calls: list[tuple[float, float, float]] = []   # (start, end, decode seconds)
    seen = len(engine.completions)

    tracer.start()
    counter.armed = True
    t0 = time.monotonic()
    t_close = t0 + seconds
    nxt = 0
    with span("window", tracer):
        while True:
            now = time.monotonic()
            with span("client", tracer):
                while nxt < len(reqs) and t0 + reqs[nxt].due <= min(now, t_close):
                    r = reqs[nxt]
                    s = Served(r, t0 + r.due)
                    s.uid = engine.submit(Request(prompt=r.prompt,
                                                  max_new_tokens=r.new_tokens))
                    served.append(s)
                    by_uid[s.uid] = s
                    nxt += 1
            st = engine.stats()
            if not st["queued"] and not st["active_slots"]:
                if nxt < len(reqs) and t0 + reqs[nxt].due < t_close:
                    with span("wait", tracer):
                        time.sleep(max(t0 + reqs[nxt].due - time.monotonic(), 0.0))
                    continue
                break
            d0 = engine.decode_seconds
            with span("engine_run", tracer):
                a = time.monotonic()
                engine.run(max_steps=1)
                b = time.monotonic()
            calls.append((a, b, engine.decode_seconds - d0))
            with span("client", tracer):
                for comp in engine.completions[seen:]:
                    s = by_uid[comp.uid]
                    s.tokens = comp.tokens
                    s.t_first = comp.t_first_token
                seen = len(engine.completions)
    counter.armed = False
    tracer.stop()

    starts = [c[0] for c in calls]
    for s in served:
        if not s.tokens:
            continue
        i = bisect.bisect_right(starts, s.t_first) - 1      # the admitting call
        s.times = [s.t_first] + [calls[j][1] for j in range(i, i + len(s.tokens) - 1)]
    done = [s for s in served if len(s.tokens) == s.req.new_tokens]
    gaps = [b - a for s in done for a, b in zip(s.times, s.times[1:])]
    in_window = sum(1 for s in served for x in s.times if x <= t_close)
    # per-layer counters cover every call of the traced loop: the window
    # and the drain after it
    decode_flops = sum(flops.decode_flops(cell.config, len(s.req.prompt) + k)
                       for s in served for k in range(1, len(s.times)))
    out = {
        "t_window": t0,
        "attempted": len(served),
        "failed": len(served) - len(done),
        "e2e": {"serve_tokens_per_s": in_window / seconds,
                "itl_p95_ms": percentile(gaps, 95) * 1e3,
                "ttft_p95_ms": percentile([s.t_first - s.due for s in done], 95) * 1e3},
        "counters": {
            "loop_s": calls[-1][1] - t0,
            "engine_s": sum(c[1] - c[0] for c in calls),
            "decode_s": sum(c[2] for c in calls),
            "decode_steps": sum(1 for c in calls if c[2] > 0),
            "decode_flops": decode_flops,
            "prefill_rows": [len(s.req.prompt) for s in served],
            "slots": t["slots"],
            "requests_done": len(done),
            "itl_samples": len(gaps),
            "ttft_p50_ms": percentile([s.t_first - s.due for s in done], 50) * 1e3,
            "late_first_tokens": sum(1 for s in served if s.t_first > t_close),
        },
        "memory_peak_bytes": common.memory_peak_bytes(cell.chips),
        "served": [(s.req.prompt, s.tokens) for s in done],
    }
    del engine, params
    gc.collect()
    jax.clear_caches()
    return out


# ----------------------------------------------------------- reference
def sample(served: list, seed: int, n: int) -> list:
    """n finished requests drawn from the seed, the longest among them."""
    if len(served) <= n:
        return list(served)
    longest = max(range(len(served)), key=lambda i: len(served[i][0]) + len(served[i][1]))
    rest = [i for i in range(len(served)) if i != longest]
    pick = np.random.default_rng((seed, 2)).choice(rest, n - 1, replace=False)
    return [served[i] for i in [longest, *sorted(pick)]]


def reference_logits_fn(cell: Cell, mode):
    """f(weights, tokens) -> (S, vocab) logits of the reference under mode."""
    import jax

    from reference import model as ref

    sz = ref.Sizes.of(cell.config)
    return jax.jit(lambda w, toks: ref.logits(w, toks, sz, mode))


def gaps(cell: Cell, seed: int, served: list, control: bool = False) -> list[float]:
    """Per sampled request, the widest gap below the reference's best of
    the served tokens (or, with ``control``, of the tokens that the
    lower-precision reference puts first at the same positions)."""
    import jax.numpy as jnp

    from reference import model as ref

    mode = ref.Mode.of(cell.numerics)
    weights = common.make_weights(cell.config, seed)
    f = reference_logits_fn(cell, mode)
    fc = reference_logits_fn(cell, mode.control()) if control else None
    out = []
    for prompt, toks in sample(served, seed, cell.workload["check_requests"]):
        ids = prompt + toks[:-1]
        # exact products are causal, so trailing pad tokens change no earlier
        # logit: padding every sequence to the capacity compiles the
        # reference once (the control's int8 scales then see the pads too)
        pad = cell.traffic["capacity"] - len(ids) if mode.kind == "exact" else 0
        seq = jnp.asarray(ids + (0,) * pad, jnp.int32)
        at = slice(len(prompt) - 1, len(ids))
        lg = np.asarray(f(weights, seq))[at]
        pick = np.asarray(toks) if fc is None else np.asarray(fc(weights, seq))[at].argmax(-1)
        out.append(float(np.max(lg.max(-1) - lg[np.arange(len(pick)), pick])))
    return out
