"""Plain float32 reference of the dense decoder the benchmark's cells run.

One sequence at a time, every float32 matmul at ``Precision.HIGHEST``
(what ``jax.default_matmul_precision("highest")`` sets), no kernels, no
cache, no batching; table lookups are exact one-hot products.  It follows the program's published
description of the model (``repro.models``: pre-norm RMSNorm blocks, RoPE,
grouped-query attention, gated SiLU MLP, tied or untied LM head) and
computes every product of the numerics seam under the cell's mode:

* ``exact``: the float product;
* ``amr``: int8 operands (absmax scale per row of A and per column of B)
  multiplied as ``a*b + sum_j u_j(a) v_j(b)``, the rank-r factors of this
  benchmark's own AMR-MUL error table (``reference/amr``);
* ``int8`` / ``int4``: exact products of operands rounded to that grid, the
  lower-precision controls of the correctness check.

The embedding lookup and the LM head are exact in every mode, as in the
program.  Departures: activations, softmax and the residual stream are
float32 where the program keeps bfloat16; the backward of a quantized
product is the float product's (the straight-through surrogate the program
uses).  Parameters arrive in the program's layout (stacked layers) and are
read as float32.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from . import amr

HIGHEST = jax.lax.Precision.HIGHEST
_CHUNK = 1 << 20  # lookup rows per one-hot block (a 512 MiB bf16 block)
_HEAD_ROWS = 32768  # LM head rows read as float32 at a time


@dataclasses.dataclass(frozen=True)
class Sizes:
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    tie_embeddings: bool
    rope_theta: float = 10000.0
    norm_eps: float = 1e-6

    @classmethod
    def of(cls, config: dict) -> "Sizes":
        return cls(**{f.name: config[f.name] for f in dataclasses.fields(cls)
                      if f.name in config})


@dataclasses.dataclass(frozen=True)
class Mode:
    """How the seam's products are computed: ``exact``, ``amr``, ``int8``
    or ``int4``."""

    kind: str
    border: int = 8
    rank: int = 16
    bf16_inputs: bool = False   # round the seam's inputs to bfloat16 first

    @classmethod
    def of(cls, numerics: dict) -> "Mode":
        """The reference mode for a program numerics mode."""
        m = numerics["mode"]
        if m == "exact":
            return cls("exact")
        if m in ("amr_lowrank", "amr_kernel") and numerics.get("rank", 0) > 0:
            return cls("amr", numerics["border"], numerics["rank"])
        raise ValueError(f"no reference for numerics {numerics}")

    def rounded(self) -> "Mode":
        """The same products with their inputs rounded to bfloat16, as the
        program's activations are: the reference's own reading of what
        that rounding alone moves."""
        return dataclasses.replace(self, bf16_inputs=True)

    def control(self) -> "Mode":
        """The nearest precision below the one the mode states: int8 for
        bfloat16 products, int4 for int8 (AMR) products."""
        return Mode("int4") if self.kind in ("amr", "int8") else Mode("int8")


def _planes(table: np.ndarray) -> np.ndarray:
    """(256, r) f32 -> (256, 3r) three bf16-exact planes that sum back to it
    (split on the host, by masking mantissa bits)."""
    t = np.asarray(table, np.float32)
    out = []
    for _ in range(3):
        hi = (t.view(np.uint32) & np.uint32(0xFFFF0000)).view(np.float32)
        out.append(hi)
        t = (t - hi).astype(np.float32)
    return np.concatenate(out, axis=1)


def _lookup(planes: jnp.ndarray, q: jnp.ndarray) -> jnp.ndarray:
    """``table[q + 128]`` for integer-valued q, as (..., r) f32, by exact
    one-hot products in row blocks (TPU gathers of this size are slow)."""
    r = planes.shape[1] // 3
    flat = q.reshape(-1).astype(jnp.int32) + 128
    n = flat.shape[0]
    chunk = min(_CHUNK, n)
    pad = (-n) % chunk
    blocks = jnp.pad(flat, (0, pad)).reshape(-1, chunk)
    pb = planes.astype(jnp.bfloat16)

    def one(idx):
        hot = (idx[:, None] == jnp.arange(256, dtype=jnp.int32)[None]).astype(jnp.bfloat16)
        got = jnp.matmul(hot, pb, preferred_element_type=jnp.float32)
        return got[:, :r] + got[:, r:2 * r] + got[:, 2 * r:]

    out = jax.lax.map(one, blocks).reshape(-1, r)[:n]
    return out.reshape(*q.shape, r)


def _quant(x, axis, levels):
    scale = jnp.maximum(jnp.max(jnp.abs(x), axis=axis, keepdims=True), 1e-8) / levels
    return jnp.clip(jnp.round(x / scale), -levels - 1, levels), scale


def _products(a, b, mode: Mode):
    """Forward of one seam product ``a @ b`` (batch dims equal) under mode."""
    if mode.bf16_inputs:
        a, b = (x.astype(jnp.bfloat16).astype(jnp.float32) for x in (a, b))
    if mode.kind == "exact":
        return jnp.matmul(a, b, precision=HIGHEST)
    levels = 7.0 if mode.kind == "int4" else 127.0
    qa, sa = _quant(a, -1, levels)
    qb, sb = _quant(b, -2, levels)
    out = jnp.matmul(qa, qb, precision=HIGHEST)
    if mode.kind == "amr":
        u, v = amr.error_factors(mode.border, mode.rank)
        ua = _lookup(jnp.asarray(_planes(u)), qa)          # (..., M, K, r)
        vb = _lookup(jnp.asarray(_planes(v)), qb)          # (..., K, N, r)
        out = out + jnp.einsum("...mkr,...knr->...mn", ua, vb, precision=HIGHEST)
    return out * sa * sb


@partial(jax.custom_vjp, nondiff_argnums=(2,))
def seam(a, b, mode: Mode):
    """One product through the seam: quantized forward, float backward."""
    return _products(a, b, mode)


def _seam_fwd(a, b, mode):
    return _products(a, b, mode), (a, b)


def _seam_bwd(mode, res, g):
    a, b = res
    _, vjp = jax.vjp(lambda x, y: jnp.matmul(x, y, precision=HIGHEST), a, b)
    return vjp(g)


seam.defvjp(_seam_fwd, _seam_bwd)


def _mm(a, b, mode: Mode):
    return jnp.matmul(a, b, precision=HIGHEST) if mode.kind == "exact" else seam(a, b, mode)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * (1.0 + scale)


def _rope(x, theta):
    """x: (S, H, D) at positions 0..S-1."""
    d = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _attention(p, h, sz: Sizes, mode: Mode):
    s = h.shape[0]
    hq, hk, d = sz.n_heads, sz.n_kv_heads, sz.head_dim
    g = hq // hk
    q = _rope(_mm(h, p["wq"], mode).reshape(s, hq, d), sz.rope_theta)
    k = _rope(_mm(h, p["wk"], mode).reshape(s, hk, d), sz.rope_theta)
    v = _mm(h, p["wv"], mode).reshape(s, hk, d)
    # query heads grouped under their kv head: head = kv * g + i
    qa = q.reshape(s, hk, g, d).transpose(1, 2, 0, 3).reshape(hk, g * s, d)
    scores = _mm(qa, k.transpose(1, 2, 0), mode) / (d ** 0.5)     # (hk, g*s, s)
    scores = scores.reshape(hk, g, s, s)
    causal = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    out = _mm(probs.reshape(hk, g * s, s), v.transpose(1, 0, 2), mode)  # (hk, g*s, d)
    out = out.reshape(hk, g, s, d).transpose(2, 0, 1, 3).reshape(s, hq * d)
    return _mm(out, p["wo"], mode)


def _layer(x, lp, sz: Sizes, mode: Mode):
    x = x + _attention(lp["attn"], _rms(x, lp["ln1"], sz.norm_eps), sz, mode)
    h = _rms(x, lp["ln2"], sz.norm_eps)
    m = lp["mlp"]
    y = jax.nn.silu(_mm(h, m["w_gate"], mode)) * _mm(h, m["w_up"], mode)
    return x + _mm(y, m["w_down"], mode)


def hidden(params, tokens, sz: Sizes, mode: Mode):
    """Final-norm hidden states (S, d_model) of one token sequence."""
    f32 = lambda t: jax.tree.map(lambda a: a.astype(jnp.float32), t)
    x = params["embed"][tokens].astype(jnp.float32)
    (layers,) = params["layers"]

    def body(x, lp):
        return _layer(x, f32(lp), sz, mode), None

    x, _ = jax.lax.scan(body, x, layers)
    return _rms(x, params["final_norm"].astype(jnp.float32), sz.norm_eps)


def logits(params, tokens, sz: Sizes, mode: Mode):
    """(S, vocab) f32 logits of one sequence."""
    x = hidden(params, tokens, sz, mode)
    head = params["embed"] if sz.tie_embeddings else params["lm_head"]
    blocks = -(-head.shape[0] // _HEAD_ROWS)
    if blocks == 1 or head.shape[0] % blocks:
        return jnp.matmul(x, head.astype(jnp.float32).T, precision=HIGHEST)
    # a large vocabulary in row blocks, each read as float32 on its own
    part = jax.lax.map(lambda h: jnp.matmul(x, h.astype(jnp.float32).T, precision=HIGHEST),
                       head.reshape(blocks, -1, head.shape[1]))
    return part.transpose(1, 0, 2).reshape(x.shape[0], head.shape[0])


def seq_loss(params, tokens, targets, sz: Sizes, mode: Mode):
    """Mean next-token cross-entropy of one sequence."""
    lg = logits(params, tokens, sz, mode)
    ll = jax.nn.log_softmax(lg, axis=-1)
    return -jnp.mean(jnp.take_along_axis(ll, targets[:, None], axis=-1))


# ------------------------------------------------------------- optimizer
# AdamW with float32 master weights, global-norm clipping and the
# warmup-cosine schedule: the update rule the program states for training.
B1, B2, EPS, WEIGHT_DECAY, GRAD_CLIP = 0.9, 0.95, 1e-8, 0.1, 1.0


def lr_at(step: int, *, peak_lr: float, warmup: int, total: int, floor: float = 0.1):
    if step < warmup:
        return peak_lr * step / max(warmup, 1)
    frac = min(max((step - warmup) / max(total - warmup, 1), 0.0), 1.0)
    return peak_lr * (floor + (1 - floor) * 0.5 * (1 + np.cos(np.pi * frac)))


def clip(grads):
    """Gradients as the optimizer takes them (after global-norm clipping)."""
    gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g)) for g in jax.tree.leaves(grads)))
    scale = jnp.minimum(1.0, GRAD_CLIP / jnp.maximum(gnorm, 1e-9))
    return jax.tree.map(lambda g: g * scale, grads)


def adamw(master, mu, nu, grads, count: int, lr: float):
    """One AdamW update of f32 ``master``; ``grads`` already clipped."""
    def upd(p, m, n, g):
        m = B1 * m + (1 - B1) * g
        n = B2 * n + (1 - B2) * g * g
        step = (m / (1 - B1 ** count)) / (jnp.sqrt(n / (1 - B2 ** count)) + EPS)
        return p - lr * (step + WEIGHT_DECAY * p), m, n
    leaves, tree = jax.tree.flatten(master)
    out = [upd(*xs) for xs in zip(leaves, *(tree.flatten_up_to(t) for t in (mu, nu, grads)))]
    return tuple(jax.tree.unflatten(tree, [o[i] for o in out]) for i in range(3))
