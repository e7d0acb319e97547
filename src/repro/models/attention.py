"""GQA/MQA attention with qk-norm, sliding windows, RoPE, and KV caches.

Three entry points per layer:
  * ``attend_full``  — training / prefill over a whole sequence (causal,
    optionally sliding-window masked).
  * ``attend_decode`` — one-token step against a (possibly ring-buffered)
    KV cache; this is what ``serve_step`` lowers for decode_* shapes.
Cache layout: (batch, cache_len, n_kv, head_dim) — batch shards on "data",
kv heads on "model" when divisible (parallel/sharding.py decides).
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp

from repro.numerics import AMRNumerics, resolve_numerics
from repro.numerics.approx_matmul import approx_matmul
from repro.parallel.constraints import ambient_axis_size, pin

from .layers import apply_rope, dense, init_rms_norm, rms_norm, seam_scope

NEG_INF = -2.0e38


def init_attention(key, d_model, n_heads, n_kv, head_dim, qk_norm, dtype) -> dict:
    ks = jax.random.split(key, 4)
    s = d_model ** -0.5
    p = {
        "wq": (jax.random.normal(ks[0], (d_model, n_heads * head_dim)) * s).astype(dtype),
        "wk": (jax.random.normal(ks[1], (d_model, n_kv * head_dim)) * s).astype(dtype),
        "wv": (jax.random.normal(ks[2], (d_model, n_kv * head_dim)) * s).astype(dtype),
        "wo": (jax.random.normal(ks[3], (n_heads * head_dim, d_model))
               * (n_heads * head_dim) ** -0.5).astype(dtype),
    }
    if qk_norm:
        p["q_norm"] = init_rms_norm(head_dim)
        p["k_norm"] = init_rms_norm(head_dim)
    return p


def _project_qkv(params, x, n_heads, n_kv, head_dim, positions, theta, qk_norm,
                 numerics: AMRNumerics | None, eps: float):
    B, S, _ = x.shape
    q = dense(x, params["wq"], numerics, site="attn.wq").reshape(B, S, n_heads, head_dim)
    k = dense(x, params["wk"], numerics, site="attn.wk").reshape(B, S, n_kv, head_dim)
    v = dense(x, params["wv"], numerics, site="attn.wv").reshape(B, S, n_kv, head_dim)
    if qk_norm:
        q = rms_norm(q, params["q_norm"], eps)
        k = rms_norm(k, params["k_norm"], eps)
    q = apply_rope(q, positions, theta)
    k = apply_rope(k, positions, theta)
    q = pin(q, "batch", None, "tp", None)
    k = pin(k, "batch", None, "tp", None)
    v = pin(v, "batch", None, "tp", None)
    return q, k, v


def _seam_scores(q, k, numerics: AMRNumerics):
    """QK^T through the activation×activation numerics seam (``attn.qk``).

    Folds the GQA group into the row dim — one batched seam call
    (B, Hkv, g*S, D) @ (B, Hkv, D, T) — so per-row quantization is per
    (batch, kv head, group, query) row and a slot-batched decode row
    quantizes exactly as its solo decode would (no cross-slot reduction).
    """
    B, S, Hq, D = q.shape
    T, Hkv = k.shape[1], k.shape[2]
    g = Hq // Hkv
    qa = q.reshape(B, S, Hkv, g, D).transpose(0, 2, 3, 1, 4)
    qa = qa.reshape(B, Hkv, g * S, D)
    kb = k.transpose(0, 2, 3, 1)                               # (B, Hkv, D, T)
    scores = approx_matmul(qa, kb, numerics, site="attn.qk") / (D ** 0.5)
    return scores.reshape(B, Hq, S, T)


def _gqa_scores(q, k, numerics=None):
    """q: (B,S,Hq,D), k: (B,T,Hkv,D) -> (B, Hq, S, T) with head grouping.

    Exact numerics keep the historical einsum formulation; approximate
    modes route through the seam at site ``attn.qk`` (resolved against a
    ``NumericsPolicy`` here, so per-layer assignments can pin it)."""
    numerics = resolve_numerics(numerics, "attn.qk")
    with seam_scope("attn.qk"):
        if numerics is not None and not numerics.is_exact():
            return _seam_scores(q, k, numerics)
        B, S, Hq, D = q.shape
        Hkv = k.shape[2]
        g = Hq // Hkv
        q = q.reshape(B, S, Hkv, g, D)
        scores = jnp.einsum("bskgd,btkd->bkgst", q, k) / (D ** 0.5)
        return scores.reshape(B, Hkv * g, S, k.shape[1])


def _seam_combine(probs, v, numerics: AMRNumerics):
    """PV through the seam (``attn.pv``): (B, Hkv, g*S, T) @ (B, Hkv, T, D)
    with the same group folding (and bit-exactness argument) as
    ``_seam_scores`` — probabilities quantize per query row, values per
    (kv head, channel) column over the cache axis."""
    B, Hq, S, T = probs.shape
    Hkv, D = v.shape[2], v.shape[3]
    g = Hq // Hkv
    pa = probs.reshape(B, Hkv, g * S, T)
    vb = v.transpose(0, 2, 1, 3)                               # (B, Hkv, T, D)
    out = approx_matmul(pa, vb, numerics, site="attn.pv")
    out = out.reshape(B, Hkv, g, S, D).transpose(0, 3, 1, 2, 4)
    return out.reshape(B, S, Hq, D).astype(probs.dtype)


def _gqa_combine(probs, v, numerics=None):
    """probs: (B, Hq, S, T), v: (B,T,Hkv,D) -> (B,S,Hq,D)."""
    numerics = resolve_numerics(numerics, "attn.pv")
    with seam_scope("attn.pv"):
        if numerics is not None and not numerics.is_exact():
            return _seam_combine(probs, v, numerics)
        B, Hq, S, T = probs.shape
        Hkv = v.shape[2]
        g = Hq // Hkv
        probs = probs.reshape(B, Hkv, g, S, T)
        out = jnp.einsum("bkgst,btkd->bskgd", probs, v)
        return out.reshape(B, S, Hq, v.shape[-1])


def attend_full(
    params: dict,
    x: jnp.ndarray,
    *,
    n_heads: int,
    n_kv: int,
    head_dim: int,
    theta: float,
    qk_norm: bool = False,
    window: int = 0,
    causal: bool = True,
    numerics: AMRNumerics | None = None,
    eps: float = 1e-6,
    unroll: bool = False,
) -> jnp.ndarray:
    """Self-attention over the full sequence (training / prefill).

    causal=False gives the bidirectional form (encoder stacks)."""
    B, S, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    q, k, v = _project_qkv(params, x, n_heads, n_kv, head_dim, positions, theta,
                           qk_norm, numerics, eps)
    if S >= _CHUNKED_THRESHOLD and S % _Q_CHUNK == 0 and causal:
        out = _chunked_attention(q, k, v, window, numerics, unroll=unroll)
    else:
        scores = _gqa_scores(q, k, numerics).astype(jnp.float32)
        i = jnp.arange(S)[:, None]
        j = jnp.arange(S)[None, :]
        mask = (j <= i) if causal else jnp.ones((S, S), bool)
        if window > 0:
            mask &= jnp.abs(i - j) < window
        scores = jnp.where(mask[None, None], scores, NEG_INF)
        probs = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
        out = _gqa_combine(probs, v, numerics)
    out = pin(out.reshape(B, S, n_heads * head_dim), "batch", None, "tp")
    return pin(dense(out, params["wo"], numerics, site="attn.wo"), "batch", None, None)


_Q_CHUNK = 2048            # query-block size for chunked attention
_CHUNKED_THRESHOLD = 16384  # use chunked attention from this sequence length


def _chunked_attention(q, k, v, window: int, numerics=None, *,
                       unroll: bool = False):
    """Query-block attention: never materialises the S x S score matrix.

    Memory per block is (B, H, Q_CHUNK, S) — the production path for 32k+
    prefill (a Pallas flash kernel would stream K too; this is the XLA
    formulation of the same idea). The block loop is a lax.scan so the HLO
    stays small; cost-extraction unrolls it like the layer scans.
    """
    B, S, Hq, D = q.shape
    nb = S // _Q_CHUNK
    qb = jnp.moveaxis(q.reshape(B, nb, _Q_CHUNK, Hq, D), 1, 0)  # (nb,B,qc,H,D)
    offs = jnp.arange(nb) * _Q_CHUNK

    def block(_, inp):
        qi, off = inp
        scores = _gqa_scores(qi, k, numerics).astype(jnp.float32)  # (B,H,qc,S)
        rows = off + jnp.arange(_Q_CHUNK)[:, None]
        cols = jnp.arange(S)[None, :]
        mask = cols <= rows
        if window > 0:
            mask &= (rows - cols) < window
        scores = jnp.where(mask[None, None], scores, NEG_INF)
        probs = jax.nn.softmax(scores, axis=-1).astype(qi.dtype)
        return None, _gqa_combine(probs, v, numerics)           # (B,qc,H,D)

    _, outs = jax.lax.scan(block, None, (qb, offs), unroll=nb if unroll else 1)
    return jnp.moveaxis(outs, 0, 1).reshape(B, S, Hq, D)


# ------------------------------------------------------------------ decode
@partial(jax.tree_util.register_dataclass, data_fields=["k", "v", "length"],
         meta_fields=[])
@dataclasses.dataclass
class KVCache:
    """Ring-buffered KV cache. ``length`` = logical tokens written so far.

    ``length`` is either a scalar (one shared position — single-prompt
    batch decode, the historical layout) or a ``(B,)`` vector of PER-SLOT
    positions (continuous batching: each batch row is an independent
    request admitted at a different time — serve/engine.py).  All decode
    math broadcasts over both.
    """

    k: jnp.ndarray  # (B, C, n_kv, D)
    v: jnp.ndarray
    length: jnp.ndarray  # () or (B,) int32 — logical position of the next token

    @classmethod
    def zeros(cls, batch, capacity, n_kv, head_dim, dtype, per_slot=False):
        shape = (batch, capacity, n_kv, head_dim)
        length = jnp.zeros((batch,) if per_slot else (), jnp.int32)
        return cls(jnp.zeros(shape, dtype), jnp.zeros(shape, dtype), length)


def attend_decode(
    params: dict,
    x: jnp.ndarray,               # (B, 1, d_model)
    cache: KVCache,
    *,
    n_heads: int,
    n_kv: int,
    head_dim: int,
    theta: float,
    qk_norm: bool = False,
    window: int = 0,
    numerics: AMRNumerics | None = None,
    eps: float = 1e-6,
) -> tuple[jnp.ndarray, KVCache]:
    """One decode step: write K/V at the cache slot, attend over valid slots.

    ``cache.length`` may be per-slot (``(B,)`` — continuous batching); all
    position math below is row-wise, so a batched step computes exactly
    what each request's solo decode would.
    """
    B = x.shape[0]
    C = cache.k.shape[1]
    pos = cache.length  # () shared or (B,) per-slot logical position
    pos_b = jnp.broadcast_to(pos.astype(jnp.int32), (B,))
    positions = pos_b[:, None]
    q, k, v = _project_qkv(params, x, n_heads, n_kv, head_dim, positions, theta,
                           qk_norm, numerics, eps)
    slot = jnp.where(window > 0, pos_b % C, jnp.minimum(pos_b, C - 1)).astype(jnp.int32)
    # masked select instead of dynamic_update_slice: a DUS with a dynamic
    # index on the model-sharded cache dim makes GSPMD replicate the whole
    # cache per layer ("involuntary full rematerialization"); the select is
    # elementwise — it shards, fuses, and aliases in place under donation
    with jax.named_scope("kv.write"):
        hit = (jnp.arange(C, dtype=jnp.int32)[None, :] == slot[:, None])[:, :, None, None]
        new_k = jnp.where(hit, k.astype(cache.k.dtype), cache.k)
        new_v = jnp.where(hit, v.astype(cache.v.dtype), cache.v)

    scores = _gqa_scores(q, new_k, numerics).astype(jnp.float32)  # (B, Hq, 1, C)
    idx = jnp.arange(C)[None, :]
    valid = idx <= slot[:, None] if window <= 0 else (
        (idx <= slot[:, None]) | (pos_b[:, None] >= C)  # full ring: all live
    )
    scores = jnp.where(valid[:, None, None, :], scores, NEG_INF)
    # scores sharding must FOLLOW the cache layout (parallel/sharding.py):
    # kv heads divisible -> head-sharded; otherwise the cache seq dim is
    # model-sharded (flash-decoding) and scores shard on C — pinning heads
    # there would make XLA all-gather the whole cache (measured 135 GB/step)
    if n_kv % ambient_axis_size("model") == 0:
        scores = pin(scores, "batch", "tp", None, None)
    else:
        scores = pin(scores, "batch", None, None, "tp")
    probs = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
    out = _gqa_combine(probs, new_v, numerics).reshape(B, 1, n_heads * head_dim)
    out = pin(dense(out, params["wo"], numerics, site="attn.wo"), "batch", None, None)
    return out, KVCache(new_k, new_v, pos + 1)


# --------------------------------------------------------------- cross-attn
def init_cross_attention(key, d_model, n_heads, head_dim, dtype) -> dict:
    ks = jax.random.split(key, 4)
    s = d_model ** -0.5
    return {
        "wq": (jax.random.normal(ks[0], (d_model, n_heads * head_dim)) * s).astype(dtype),
        "wk": (jax.random.normal(ks[1], (d_model, n_heads * head_dim)) * s).astype(dtype),
        "wv": (jax.random.normal(ks[2], (d_model, n_heads * head_dim)) * s).astype(dtype),
        "wo": (jax.random.normal(ks[3], (n_heads * head_dim, d_model))
               * (n_heads * head_dim) ** -0.5).astype(dtype),
    }


def attend_cross(params, x, enc_kv: tuple[jnp.ndarray, jnp.ndarray], *,
                 n_heads: int, head_dim: int,
                 numerics: AMRNumerics | None = None) -> jnp.ndarray:
    """Decoder cross-attention; enc_kv = precomputed (K, V) over encoder frames."""
    B, S, _ = x.shape
    q = dense(x, params["wq"], numerics, site="xattn.wq").reshape(B, S, n_heads, head_dim)
    k, v = enc_kv
    # Hq == Hkv here, so the GQA helpers apply with group size 1 — cross
    # attention shares the attn.qk / attn.pv seam sites with self-attention
    scores = _gqa_scores(q, k, numerics).astype(jnp.float32)
    probs = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
    out = _gqa_combine(probs, v, numerics).reshape(B, S, n_heads * head_dim)
    return dense(out, params["wo"], numerics, site="xattn.wo")


def encode_cross_kv(params, enc_out: jnp.ndarray, *, n_heads: int, head_dim: int,
                    numerics: AMRNumerics | None = None):
    B, T, _ = enc_out.shape
    k = dense(enc_out, params["wk"], numerics, site="xattn.wk").reshape(B, T, n_heads, head_dim)
    v = dense(enc_out, params["wv"], numerics, site="xattn.wv").reshape(B, T, n_heads, head_dim)
    return k, v


def attend_prefill(
    params: dict,
    x: jnp.ndarray,
    capacity: int,
    *,
    n_heads: int,
    n_kv: int,
    head_dim: int,
    theta: float,
    qk_norm: bool = False,
    window: int = 0,
    numerics: AMRNumerics | None = None,
    eps: float = 1e-6,
    unroll: bool = False,
) -> tuple[jnp.ndarray, KVCache]:
    """Full-sequence attention that ALSO builds the decode KV cache
    (prefill -> decode handoff). capacity >= S for full attention; for
    sliding-window layers capacity == min(window, S) ring slots."""
    B, S, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    q, k, v = _project_qkv(params, x, n_heads, n_kv, head_dim, positions, theta,
                           qk_norm, numerics, eps)
    if S >= _CHUNKED_THRESHOLD and S % _Q_CHUNK == 0:
        out = _chunked_attention(q, k, v, window, numerics, unroll=unroll)
    else:
        scores = _gqa_scores(q, k, numerics).astype(jnp.float32)
        i = jnp.arange(S)[:, None]
        j = jnp.arange(S)[None, :]
        mask = j <= i
        if window > 0:
            mask &= (i - j) < window
        scores = jnp.where(mask[None, None], scores, NEG_INF)
        probs = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
        out = _gqa_combine(probs, v, numerics)
    out = pin(out.reshape(B, S, n_heads * head_dim), "batch", None, "tp")
    out = pin(dense(out, params["wo"], numerics, site="attn.wo"), "batch", None, None)

    C = capacity
    with jax.named_scope("kv.write"):
        if window > 0 and C <= S:
            # ring layout: token t lives at slot t % C; the last C tokens survive
            roll = S % C
            k_c = jnp.roll(k[:, -C:], roll, axis=1)
            v_c = jnp.roll(v[:, -C:], roll, axis=1)
        else:
            pad = C - S
            k_c = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
            v_c = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        cache = KVCache(k_c, v_c, jnp.asarray(S, jnp.int32))
    return out, cache
