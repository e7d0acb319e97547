"""LM model assembly: scan-over-layers blocks, heterogeneous layer patterns,
train / prefill / decode entry points.

Depth is organised as *groups*: ``pattern.kinds`` describes one group's
layer sequence (e.g. 5 sliding-window + 1 global for gemma3; 5 mamba + 1
shared-attention for zamba2); parameters are stacked over ``n_repeat``
group copies and the model scans over them — the traced HLO contains ONE
group body regardless of depth, keeping 512-way SPMD compiles fast
(DESIGN.md §3). Shared (zamba-style) attention params are captured by the
scan body un-stacked, giving true weight sharing.

Caches for decode are pytrees mirroring the grouped structure: stacked
leaves with a leading ``n_repeat`` axis, scanned in lockstep with params.

Approximate numerics: every matmul in every layer routes through
``cfg.numerics`` via layers.dense — a single ``AMRNumerics`` design point
or a site-resolved ``NumericsPolicy`` (repro.numerics.policy).  Per-layer
heterogeneous policies resolve at trace time against a STATIC flat layer
index: when the policy is invariant across scanned group copies the layer
loops keep their compact ``lax.scan`` (resolving at the group-0
representative index — bit-for-bit the legacy trace), otherwise they
statically unroll one body per group (``_needs_static_unroll``).  Encoder
layers sit outside the decoder's flat index space and resolve with
``layer=None`` (site/default entries only).  This includes
the ``amr_kernel`` mode that dispatches to the Pallas amr_matmul kernel,
whose interpret/compiled execution is backend-autodetected and overridable
with ``REPRO_PALLAS_INTERPRET`` (docs/kernels.md). launch/serve.py exposes
the policy (``--numerics/--border/--rank/--pallas-interpret``) so the
serving path exercises the approximate multiplier end to end.
"""
from __future__ import annotations

from functools import partial
from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.numerics import numerics_scope
from repro.parallel.constraints import pin

from . import attention as attn
from . import moe as moe_lib
from . import ssm as ssm_lib
from .layers import dense, embed, init_embedding, init_mlp, init_rms_norm, mlp, rms_norm, unembed


# --------------------------------------------------------------------------
# per-layer init / apply
# --------------------------------------------------------------------------

def _init_layer(key, cfg: ModelConfig, kind: str) -> dict:
    km, kf = jax.random.split(key)
    dtype = jnp.dtype(cfg.dtype)
    p: dict[str, Any] = {"ln1": init_rms_norm(cfg.d_model), "ln2": init_rms_norm(cfg.d_model)}
    if kind in ("full", "swa", "cross"):
        p["attn"] = attn.init_attention(km, cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
                                        cfg.head_dim, cfg.qk_norm, dtype)
        if kind == "cross" or cfg.encoder_layers:
            p["xattn"] = attn.init_cross_attention(jax.random.fold_in(km, 1), cfg.d_model,
                                                   cfg.n_heads, cfg.head_dim, dtype)
            p["ln_x"] = init_rms_norm(cfg.d_model)
    elif kind == "ssm":
        p["ssm"] = ssm_lib.init_ssm(km, cfg.d_model, cfg.ssm, dtype)
    elif kind == "shared_attn":
        pass  # shared params live at model level
    else:
        raise ValueError(kind)
    if cfg.moe is not None and kind != "shared_attn":
        p["moe"] = moe_lib.init_moe(kf, cfg.d_model, cfg.moe, dtype)
    elif kind != "ssm":  # ssm blocks in mamba-family have no separate MLP
        p["mlp"] = init_mlp(kf, cfg.d_model, cfg.d_ff, cfg.mlp_act, dtype)
    return p


def _mixer_full(cfg: ModelConfig, p, x, kind, numerics):
    window = cfg.sliding_window if kind == "swa" else 0
    return attn.attend_full(
        p["attn"], x, n_heads=cfg.n_heads, n_kv=cfg.n_kv_heads, head_dim=cfg.head_dim,
        theta=cfg.rope_theta, qk_norm=cfg.qk_norm, window=window,
        numerics=numerics, eps=cfg.norm_eps, unroll=cfg.unroll_layers)


def _apply_layer_full(cfg: ModelConfig, params: dict, x: jnp.ndarray, kind: str,
                      shared: dict | None, enc_kv, numerics) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Full-sequence layer (train/prefill). Returns (x, aux_loss)."""
    aux = jnp.zeros((), jnp.float32)
    if kind == "shared_attn":
        h = rms_norm(x, shared["ln1"], cfg.norm_eps)
        x = x + _mixer_full(cfg, shared, h, "full", numerics)
        h = rms_norm(x, shared["ln2"], cfg.norm_eps)
        x = x + mlp(shared["mlp"], h, cfg.mlp_act, numerics)
        return x, aux
    h = rms_norm(x, params["ln1"], cfg.norm_eps)
    if kind == "ssm":
        x = x + ssm_lib.ssm_forward(params["ssm"], h, cfg.d_model, cfg.ssm,
                                    numerics, cfg.norm_eps)
        return x, aux
    x = x + _mixer_full(cfg, params, h, kind, numerics)
    if "xattn" in params and enc_kv is not None:
        h = rms_norm(x, params["ln_x"], cfg.norm_eps)
        x = x + attn.attend_cross(params["xattn"], h, enc_kv, n_heads=cfg.n_heads,
                                  head_dim=cfg.head_dim, numerics=numerics)
    h = rms_norm(x, params["ln2"], cfg.norm_eps)
    if cfg.moe is not None:
        y, aux = moe_lib.moe_forward(params["moe"], h, cfg.moe, numerics=numerics)
        x = x + y
    else:
        x = x + mlp(params["mlp"], h, cfg.mlp_act, numerics)
    return x, aux


def _apply_layer_decode(cfg: ModelConfig, params: dict, x, kind: str, cache,
                        shared: dict | None, enc_kv, numerics):
    """One-token layer step. Returns (x, new_cache)."""
    if kind == "shared_attn":
        h = rms_norm(x, shared["ln1"], cfg.norm_eps)
        y, cache = attn.attend_decode(
            shared["attn"], h, cache, n_heads=cfg.n_heads, n_kv=cfg.n_kv_heads,
            head_dim=cfg.head_dim, theta=cfg.rope_theta, qk_norm=cfg.qk_norm,
            window=0, numerics=numerics, eps=cfg.norm_eps)
        x = x + y
        h = rms_norm(x, shared["ln2"], cfg.norm_eps)
        return x + mlp(shared["mlp"], h, cfg.mlp_act, numerics), cache
    h = rms_norm(x, params["ln1"], cfg.norm_eps)
    if kind == "ssm":
        y, cache = ssm_lib.ssm_decode(params["ssm"], h, cache, cfg.d_model, cfg.ssm,
                                      numerics, cfg.norm_eps)
        return x + y, cache  # mamba-family blocks have no separate MLP
    else:
        window = cfg.sliding_window if kind == "swa" else 0
        y, cache = attn.attend_decode(
            params["attn"], h, cache, n_heads=cfg.n_heads, n_kv=cfg.n_kv_heads,
            head_dim=cfg.head_dim, theta=cfg.rope_theta, qk_norm=cfg.qk_norm,
            window=window, numerics=numerics, eps=cfg.norm_eps)
        x = x + y
        if "xattn" in params and enc_kv is not None:
            hx = rms_norm(x, params["ln_x"], cfg.norm_eps)
            x = x + attn.attend_cross(params["xattn"], hx, enc_kv, n_heads=cfg.n_heads,
                                      head_dim=cfg.head_dim, numerics=numerics)
    h = rms_norm(x, params["ln2"], cfg.norm_eps)
    if cfg.moe is not None:
        y, _ = moe_lib.moe_forward(params["moe"], h, cfg.moe, numerics=numerics)
        x = x + y
    else:
        x = x + mlp(params["mlp"], h, cfg.mlp_act, numerics)
    return x, cache


# --------------------------------------------------------------------------
# model init
# --------------------------------------------------------------------------

def group_structure(cfg: ModelConfig) -> tuple[tuple[str, ...], int]:
    """(kinds within one group, n_repeat)."""
    if cfg.pattern is not None:
        return cfg.pattern.kinds, cfg.pattern.n_repeat
    return (cfg.default_mixer,), cfg.n_layers


def _needs_static_unroll(numerics, kinds: tuple[str, ...], n_repeat: int) -> bool:
    """True when the numerics policy varies ACROSS scanned group copies.

    Per-layer design points are static (baked into the jit trace), so a
    policy that assigns different multipliers to different group repeats
    forces the layer loop to unroll with a concrete flat index per copy.
    Bare ``AMRNumerics``, ``UniformPolicy`` and repeat-invariant
    ``PerLayerPolicy`` keep the compact one-body ``lax.scan`` — bit-for-bit
    the legacy trace.  Inside the scan the policy resolves at the
    representative in-group flat index (group 0), which by invariance is
    every copy's answer.
    """
    inv = getattr(numerics, "repeat_invariant", None)
    return inv is not None and not inv(len(kinds), n_repeat)


def init_params(cfg: ModelConfig, key: jax.Array) -> dict:
    kinds, n_repeat = group_structure(cfg)
    dtype = jnp.dtype(cfg.dtype)
    keys = jax.random.split(key, 8)

    def group_params(gkey):
        return [
            _init_layer(jax.random.fold_in(gkey, i), cfg, kind)
            for i, kind in enumerate(kinds)
        ]

    stacked = jax.vmap(lambda k: _stack_to_tree(group_params(k)))(
        jax.random.split(keys[0], n_repeat))

    params: dict[str, Any] = {
        "embed": init_embedding(keys[1], cfg.vocab, cfg.d_model, dtype),
        "final_norm": init_rms_norm(cfg.d_model),
        "layers": stacked,
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = init_embedding(keys[2], cfg.vocab, cfg.d_model, dtype)
    if "shared_attn" in kinds:
        params["shared"] = {
            "attn": attn.init_attention(keys[3], cfg.d_model, cfg.n_heads,
                                        cfg.n_kv_heads, cfg.head_dim, cfg.qk_norm, dtype),
            "ln1": init_rms_norm(cfg.d_model),
            "ln2": init_rms_norm(cfg.d_model),
            "mlp": init_mlp(keys[4], cfg.d_model, cfg.d_ff, cfg.mlp_act, dtype),
        }
    if cfg.encoder_layers:
        enc_keys = jax.random.split(keys[5], cfg.encoder_layers)
        enc_layers = [_init_enc_layer(k, cfg) for k in enc_keys]
        params["encoder"] = jax.tree.map(lambda *xs: jnp.stack(xs), *enc_layers)
        params["enc_norm"] = init_rms_norm(cfg.d_model)
    if cfg.vision_prefix:
        params["vision_proj"] = (jax.random.normal(keys[6], (cfg.d_model, cfg.d_model))
                                 * cfg.d_model ** -0.5).astype(dtype)
    return params


def _init_enc_layer(key, cfg: ModelConfig) -> dict:
    km, kf = jax.random.split(key)
    dtype = jnp.dtype(cfg.dtype)
    return {
        "ln1": init_rms_norm(cfg.d_model), "ln2": init_rms_norm(cfg.d_model),
        "attn": attn.init_attention(km, cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
                                    cfg.head_dim, cfg.qk_norm, dtype),
        "mlp": init_mlp(kf, cfg.d_model, cfg.d_ff, cfg.mlp_act, dtype),
    }


def _stack_to_tree(trees: list):
    """List of identical pytrees -> single pytree with leading stack axis.

    Heterogeneous group members (different kinds) are kept as a tuple —
    only the *repeat* axis is stacked (outer vmap handles that).
    """
    return tuple(trees)


# --------------------------------------------------------------------------
# forward passes
# --------------------------------------------------------------------------

def _encoder_forward(cfg: ModelConfig, params, frames, numerics):
    """Whisper-style encoder over precomputed frame embeddings (stub frontend)."""
    def enc_body(carry, lp):
        x, g = carry
        # encoder layers get their own numerics-PRNG coordinate space so
        # amr_noise draws decorrelate from the decoder stack (layer < 0);
        # per-layer policies see layer=None here (no static coordinate) and
        # resolve through their site/default entries
        with numerics_scope(layer=-1 - g):
            h = rms_norm(x, lp["ln1"], cfg.norm_eps)
            x = x + attn.attend_full(lp["attn"], h, n_heads=cfg.n_heads,
                                     n_kv=cfg.n_kv_heads, head_dim=cfg.head_dim,
                                     theta=cfg.rope_theta, qk_norm=cfg.qk_norm,
                                     window=0, causal=False, numerics=numerics,
                                     eps=cfg.norm_eps)
            h = rms_norm(x, lp["ln2"], cfg.norm_eps)
            x = x + mlp(lp["mlp"], h, cfg.mlp_act, numerics)
        return (x, g + 1), None

    (x, _), _ = jax.lax.scan(enc_body, (frames, jnp.zeros((), jnp.int32)),
                             params["encoder"],
                             unroll=cfg.encoder_layers if cfg.unroll_layers else 1)
    return rms_norm(x, params["enc_norm"], cfg.norm_eps)


def encode(cfg: ModelConfig, params: dict, frames: jnp.ndarray) -> jnp.ndarray:
    """Public encoder entry point (whisper-family): frame embeddings
    (B, P, D) -> encoder output (B, P, D) under ``cfg.numerics``.

    ``decode_step`` takes this as ``enc_out`` so a decode loop can attend
    the same encoder state ``forward``/``prefill_with_cache`` computed —
    the decode-vs-forward parity arm of the conformance matrix needs it.
    """
    if not cfg.encoder_layers:
        raise ValueError("encode() requires cfg.encoder_layers > 0")
    return _encoder_forward(cfg, params, frames, cfg.numerics)


def forward(cfg: ModelConfig, params: dict, tokens: jnp.ndarray,
            extra_embeddings: jnp.ndarray | None = None,
            last_only: bool = False) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Full-sequence forward (training / prefill). Returns (logits, aux_loss).

    tokens: (B, S) int32. extra_embeddings: (B, P, D) stub-frontend prefix
    (vision patches / audio frames) prepended to the token embeddings.
    last_only: unembed only the final position (prefill — sliced BEFORE the
    LM head so the (B, S, vocab) tensor is never built).
    """
    kinds, n_repeat = group_structure(cfg)
    numerics = cfg.numerics
    x = pin(embed(params["embed"], tokens), "batch", None, None)
    if cfg.vision_prefix and extra_embeddings is not None:
        vis = dense(extra_embeddings, params["vision_proj"], None,
                    site="vision.proj")
        x = jnp.concatenate([vis.astype(x.dtype), x], axis=1)

    enc_kv = None
    if cfg.encoder_layers and extra_embeddings is not None:
        enc_out = _encoder_forward(cfg, params, extra_embeddings, numerics)
        enc_kv = "defer"  # computed per-layer (cross params are per-layer)

    shared = params.get("shared")

    def group_body(carry, group_params, g_static=None):
        # g rides in the carry so scanned group copies see distinct layer
        # indices for the numerics PRNG scope (re-established inside the
        # body: a remat re-trace rebuilds identical noise keys).  g_static
        # is the STATIC group index of the unrolled per-layer-policy path
        # (None when scanning — the policy then resolves at the group-0
        # representative flat index, valid by repeat invariance).
        x, aux, g = carry
        for i, kind in enumerate(kinds):
            lp = group_params[i]
            flat = i if g_static is None else g_static * len(kinds) + i
            with numerics_scope(layer=g * len(kinds) + i, static_layer=flat):
                ekv = None
                if enc_kv is not None and "xattn" in lp:
                    ekv = attn.encode_cross_kv(lp["xattn"], enc_out, n_heads=cfg.n_heads,
                                               head_dim=cfg.head_dim, numerics=numerics)
                x, a = _apply_layer_full(cfg, lp, x, kind, shared, ekv, numerics)
            aux = aux + a
        return (x, aux, g + 1), None

    carry = (x, jnp.zeros((), jnp.float32), jnp.zeros((), jnp.int32))
    if _needs_static_unroll(numerics, kinds, n_repeat):
        for gi in range(n_repeat):
            body = partial(group_body, g_static=gi)
            if cfg.remat == "block":
                body = jax.checkpoint(body, prevent_cse=False)
            carry, _ = body(carry, jax.tree.map(lambda l: l[gi], params["layers"]))
        x, aux, _ = carry
    else:
        body = group_body
        if cfg.remat == "block":
            body = jax.checkpoint(group_body, prevent_cse=False)
        (x, aux, _), _ = jax.lax.scan(
            body, carry, params["layers"],
            unroll=n_repeat if cfg.unroll_layers else 1)
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    if last_only:
        x = x[:, -1:, :]
    head = params["embed"] if cfg.tie_embeddings else params["lm_head"]
    logits = pin(unembed(x, head), "batch", None, "tp")
    if cfg.vision_prefix and extra_embeddings is not None and not last_only:
        logits = logits[:, cfg.vision_prefix:]
    return logits, aux


# --------------------------------------------------------------------------
# decode
# --------------------------------------------------------------------------

def init_cache(cfg: ModelConfig, batch: int, capacity: int,
               per_slot: bool = False) -> Any:
    """Grouped cache pytree: leaves stacked over n_repeat (scan axis).

    ``per_slot=True`` gives each batch row its own KV position vector
    (``KVCache.length`` of shape ``(B,)``) — the continuous-batching slot
    cache used by serve/engine.py, where rows decode at different depths.
    """
    kinds, n_repeat = group_structure(cfg)
    dtype = jnp.dtype(cfg.dtype)

    def one(kind):
        if kind == "ssm":
            return ssm_lib.SSMState.zeros(batch, cfg.d_model, cfg.ssm, dtype)
        cap = (min(capacity, cfg.sliding_window)
               if kind == "swa" and cfg.sliding_window else capacity)
        return attn.KVCache.zeros(batch, cap, cfg.n_kv_heads, cfg.head_dim, dtype,
                                  per_slot=per_slot)

    group = tuple(one(k) for k in kinds)
    return jax.tree.map(lambda l: jnp.broadcast_to(l[None], (n_repeat,) + l.shape), group)


def _cache_position(cache: Any):
    """Logical decode position from the first KVCache in the tree (None for
    pure-SSM caches, which carry no position) — folds into the numerics
    PRNG scope so amr_noise draws decorrelate across generated tokens.

    Returns a scalar for shared-position caches or a ``(B,)`` vector for
    per-slot caches (each request then folds its OWN position, keeping
    batched amr_noise draws identical to each request's solo decode)."""
    found: list = []

    def is_kv(node):
        if isinstance(node, attn.KVCache):
            found.append(node.length)
            return True
        return False

    jax.tree_util.tree_flatten(cache, is_leaf=is_kv)
    if not found:
        return None
    length = found[0]  # stacked over n_repeat: every copy holds the same pos
    return length[0] if getattr(length, "ndim", 0) else length


def _merge_active(old: Any, new: Any, active: jnp.ndarray) -> Any:
    """Keep ``new`` cache state only for active slots; inactive rows retain
    ``old`` bit-for-bit (positions don't advance, K/V writes are discarded).

    Cache leaves are stacked ``(n_repeat, B, ...)``; per-slot length leaves
    are ``(n_repeat, B)``. Anything without a batch axis (shared scalar
    positions) passes through unmasked — active-masked decode is only
    meaningful on per-slot caches.
    """
    B = active.shape[0]

    def merge(o, n):
        if n.ndim >= 2 and n.shape[1] == B:
            m = active.reshape((1, B) + (1,) * (n.ndim - 2))
            return jnp.where(m, n, o)
        return n

    return jax.tree.map(merge, old, new)


def decode_step(cfg: ModelConfig, params: dict, token: jnp.ndarray, cache: Any,
                enc_out: jnp.ndarray | None = None,
                active: jnp.ndarray | None = None) -> tuple[jnp.ndarray, Any]:
    """One serving step: token (B, 1) int32 -> (logits (B, 1, V), new cache).

    ``active`` (optional, (B,) bool): continuous-batching slot mask. All
    rows compute (a single fixed-shape jit trace regardless of which slots
    are live), but inactive rows' cache writes and position advances are
    rolled back, so their state — and therefore the next admitted request's
    prefill handoff — is untouched. Logits of inactive rows are garbage;
    callers ignore them.
    """
    kinds, _ = group_structure(cfg)
    numerics = cfg.numerics
    pos = _cache_position(cache)
    x = embed(params["embed"], token)
    shared = params.get("shared")

    def group_body(carry, scanned, g_static=None):
        # cache rides in the CARRY (indexed by the group counter) rather than
        # as scan xs/ys: carry buffers alias in place across iterations,
        # while xs->ys caches double/triple-buffer (measured: 12.8 GB of
        # temps on a 4.3 GB qwen3 decode cache)
        x, cache_all, g = carry
        group_params, _ = scanned
        gi = g if g_static is None else g_static
        with jax.named_scope("kv.carry"):
            group_cache = jax.tree.map(lambda l: l[gi], cache_all)
        new_caches = []
        for i, kind in enumerate(kinds):
            lp = group_params[i]
            flat = i if g_static is None else g_static * len(kinds) + i
            with numerics_scope(step=pos, layer=g * len(kinds) + i,
                                static_layer=flat):
                ekv = None
                if enc_out is not None and "xattn" in lp:
                    ekv = attn.encode_cross_kv(lp["xattn"], enc_out, n_heads=cfg.n_heads,
                                               head_dim=cfg.head_dim, numerics=numerics)
                x, c = _apply_layer_decode(cfg, lp, x, kind, group_cache[i], shared,
                                           ekv, numerics)
            new_caches.append(c)
        with jax.named_scope("kv.carry"):
            cache_all = jax.tree.map(
                lambda full, new: jax.lax.dynamic_update_index_in_dim(full, new, gi, 0),
                cache_all, tuple(new_caches))
        return (x, cache_all, g + 1), None

    kinds2, n_repeat = group_structure(cfg)
    carry = (x, cache, jnp.zeros((), jnp.int32))
    if _needs_static_unroll(numerics, kinds, n_repeat):
        # per-layer heterogeneous policy: statically unrolled copies, still
        # ONE jit trace per engine — serve's no-recompile property holds
        for gi in range(n_repeat):
            group_params = jax.tree.map(lambda l: l[gi], params["layers"])
            carry, _ = group_body(carry, (group_params, gi), g_static=gi)
        x, new_cache, _ = carry
    else:
        (x, new_cache, _), _ = jax.lax.scan(
            group_body, carry,
            (params["layers"], jnp.arange(n_repeat)),
            unroll=n_repeat if cfg.unroll_layers else 1)
    if active is not None:
        with jax.named_scope("kv.merge"):
            new_cache = _merge_active(cache, new_cache, active)
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    head = params["embed"] if cfg.tie_embeddings else params["lm_head"]
    return unembed(x, head), new_cache


# --------------------------------------------------------------------------
# prefill -> decode handoff
# --------------------------------------------------------------------------

def _apply_layer_prefill(cfg: ModelConfig, params: dict, x, kind: str, capacity: int,
                         shared, enc_kv, numerics):
    """Full-sequence layer that also emits its decode cache entry."""
    def attn_prefill(p, h, window):
        cap = min(capacity, cfg.sliding_window) if window else capacity
        return attn.attend_prefill(
            p, h, cap, n_heads=cfg.n_heads, n_kv=cfg.n_kv_heads,
            head_dim=cfg.head_dim, theta=cfg.rope_theta, qk_norm=cfg.qk_norm,
            window=cfg.sliding_window if window else 0, numerics=numerics,
            eps=cfg.norm_eps, unroll=cfg.unroll_layers)

    if kind == "shared_attn":
        h = rms_norm(x, shared["ln1"], cfg.norm_eps)
        y, cache = attn_prefill(shared["attn"], h, window=False)
        x = x + y
        h = rms_norm(x, shared["ln2"], cfg.norm_eps)
        return x + mlp(shared["mlp"], h, cfg.mlp_act, numerics), cache
    h = rms_norm(x, params["ln1"], cfg.norm_eps)
    if kind == "ssm":
        y, cache = ssm_lib.ssm_prefill(params["ssm"], h, cfg.d_model, cfg.ssm,
                                       numerics, cfg.norm_eps)
        return x + y, cache
    y, cache = attn_prefill(params["attn"], h, window=(kind == "swa"))
    x = x + y
    if "xattn" in params and enc_kv is not None:
        hx = rms_norm(x, params["ln_x"], cfg.norm_eps)
        x = x + attn.attend_cross(params["xattn"], hx, enc_kv, n_heads=cfg.n_heads,
                                  head_dim=cfg.head_dim, numerics=numerics)
    h = rms_norm(x, params["ln2"], cfg.norm_eps)
    if cfg.moe is not None:
        y, _ = moe_lib.moe_forward(params["moe"], h, cfg.moe, numerics=numerics)
        x = x + y
    else:
        x = x + mlp(params["mlp"], h, cfg.mlp_act, numerics)
    return x, cache


def prefill_with_cache(cfg: ModelConfig, params: dict, tokens: jnp.ndarray,
                       capacity: int,
                       extra_embeddings: jnp.ndarray | None = None
                       ) -> tuple[jnp.ndarray, Any]:
    """One-shot prefill: last-position logits + a ready decode cache.

    The production serving path: O(1) dispatches instead of S sequential
    decode steps (launch/serve.py uses this; consistency vs step-by-step
    prefill is property-tested)."""
    kinds, n_repeat = group_structure(cfg)
    numerics = cfg.numerics
    x = pin(embed(params["embed"], tokens), "batch", None, None)
    if cfg.vision_prefix and extra_embeddings is not None:
        vis = dense(extra_embeddings, params["vision_proj"], None,
                    site="vision.proj")
        x = jnp.concatenate([vis.astype(x.dtype), x], axis=1)

    enc_out = None
    if cfg.encoder_layers and extra_embeddings is not None:
        enc_out = _encoder_forward(cfg, params, extra_embeddings, numerics)

    shared = params.get("shared")

    def group_body(carry, group_params, g_static=None):
        x, g = carry
        caches = []
        for i, kind in enumerate(kinds):
            lp = group_params[i]
            flat = i if g_static is None else g_static * len(kinds) + i
            with numerics_scope(layer=g * len(kinds) + i, static_layer=flat):
                ekv = None
                if enc_out is not None and "xattn" in lp:
                    ekv = attn.encode_cross_kv(lp["xattn"], enc_out, n_heads=cfg.n_heads,
                                               head_dim=cfg.head_dim, numerics=numerics)
                x, c = _apply_layer_prefill(cfg, lp, x, kind, capacity, shared, ekv,
                                            numerics)
            caches.append(c)
        return (x, g + 1), tuple(caches)

    carry = (x, jnp.zeros((), jnp.int32))
    if _needs_static_unroll(numerics, kinds, n_repeat):
        per_group = []
        for gi in range(n_repeat):
            carry, caches = group_body(
                carry, jax.tree.map(lambda l: l[gi], params["layers"]),
                g_static=gi)
            per_group.append(caches)
        # stack the per-group cache entries into the leading n_repeat axis
        # the scan path's ys would have produced (decode consumes either)
        cache = jax.tree.map(lambda *ls: jnp.stack(ls), *per_group)
        x, _ = carry
    else:
        (x, _), cache = jax.lax.scan(group_body, carry, params["layers"],
                                     unroll=n_repeat if cfg.unroll_layers else 1)
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    head = params["embed"] if cfg.tie_embeddings else params["lm_head"]
    logits = unembed(x[:, -1:, :], head)
    return logits, cache
