"""The one traffic generator: reads a traffic file's parameters, makes the
inputs of a run from ``--seed``.

Every seed gets the same work (the same multiset of sizes and of arrival
gaps) in another order, so runs with different seeds measure the same
thing; the seed changes the order and the token ids.  The order keeps the
mix even along the run: every ``BLOCK`` consecutive requests take one
size (and one gap) from each of ``BLOCK`` strata of the multiset, so the
requests that fall in a window are alike for every seed.
"""
from __future__ import annotations

import dataclasses
import math

import numpy as np

BLOCK = 8


def train_batch(seed: int, index: int, vocab: int, batch: int, seq: int) -> dict:
    """Batch ``index`` of a run: packed sequences of a noisy affine
    recurrence over the vocabulary, rows all different (the synthetic
    language-model data the program's training launcher uses)."""
    rng = np.random.default_rng((seed, index, 0))
    a = 6364136223846793005 % vocab or 5
    c = 1442695040888963407 % vocab or 7
    toks = [rng.integers(0, vocab, (batch, 1))]
    for _ in range(seq):
        nxt = (a * toks[-1] + c) % vocab
        flip = rng.random((batch, 1)) < 0.05
        toks.append(np.where(flip, rng.integers(0, vocab, (batch, 1)), nxt))
    s = np.concatenate(toks, axis=1).astype(np.int32)
    return {"tokens": s[:, :seq], "targets": s[:, 1:seq + 1]}


@dataclasses.dataclass(frozen=True)
class Req:
    due: float          # seconds after the window opens
    prompt: tuple
    new_tokens: int


def _stratified(spec: dict, n: int) -> np.ndarray:
    """n sizes at the mid-quantiles of a clipped, rounded log-normal: the
    same multiset for every seed."""
    from statistics import NormalDist

    z = np.array([NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)])
    x = spec["median"] * np.exp(spec["sigma"] * z)
    x = np.clip(x, spec["min"], spec["max"])
    step = spec.get("round_up_to", 1)
    return (np.ceil(x / step) * step).astype(int)


def _even_order(rng, x: np.ndarray) -> np.ndarray:
    """x in an order drawn from rng in which each block of ``BLOCK``
    consecutive items holds one item of each of ``BLOCK`` strata."""
    strata = [rng.permutation(s) for s in np.array_split(np.sort(x), BLOCK)]
    return np.array([v for j in range(len(strata[0]))
                     for v in rng.permutation([s[j] for s in strata if j < len(s)])])


def distinct_prompt_lengths(traffic: dict) -> list[int]:
    """Every prompt length the traffic can send (the shapes to warm up)."""
    p = traffic["prompt"]
    step = p.get("round_up_to", 1)
    lo = math.ceil(p["min"] / step) * step
    return list(range(lo, math.ceil(p["max"] / step) * step + 1, step))


def requests(traffic: dict, seed: int, seconds: float, vocab: int) -> list[Req]:
    """The requests of one run, in sending order: Poisson arrivals at
    ``rate_per_s``, due times from stratified exponential gaps in an even
    order, for the whole window."""
    rng = np.random.default_rng((seed, 1))
    n = int(math.ceil(traffic["rate_per_s"] * seconds)) + 1
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n) / traffic["rate_per_s"]
    due = np.concatenate([[0.0], np.cumsum(_even_order(rng, gaps))[:-1]])
    plen = _even_order(rng, _stratified(traffic["prompt"], n))
    gen = _even_order(rng, _stratified(traffic["new_tokens"], n))
    return [Req(float(due[i]), tuple(int(t) for t in rng.integers(0, vocab, int(plen[i]))),
                int(gen[i]))
            for i in range(n)]
