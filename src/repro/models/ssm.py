"""Mamba2 (SSD — state-space duality) mixer: chunked train/prefill + O(1) decode.

Follows the SSD algorithm (Dao & Gu 2024): sequences are split into chunks;
within a chunk the dual quadratic form runs on matmuls (MXU-friendly —
kernels/ssd_scan provides the Pallas version), across chunks a small state
recurrence carries (H, N, P) per-head states. Decode keeps a conv ring
buffer + SSM state and costs O(1) per token.

TP layout: projections are kept as *separate* parameters (wz/wx/wb/wc/wdt
and per-segment depthwise convs) instead of one fused in_proj — fused
concat boundaries do not align with "model"-axis shards and would force
XLA to reshard mid-layer (DESIGN.md §3). x/z shard by heads on "model";
B/C (n_groups * d_state, small) replicate.

Projections route through the numerics policy (the paper's approximate
multiplier applies to in/out projections; the state recurrence accumulates
and is kept exact — DESIGN.md §Arch-applicability).
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp

from repro.configs.base import SSMConfig
from repro.numerics import AMRNumerics, resolve_numerics
from repro.numerics.approx_matmul import approx_matmul
from repro.parallel.constraints import pin

from .layers import dense, init_rms_norm, rms_norm, seam_scope


def ssm_dims(d_model: int, cfg: SSMConfig) -> dict:
    d_inner = cfg.expand * d_model
    n_heads = d_inner // cfg.head_dim
    return dict(d_inner=d_inner, n_heads=n_heads, d_bc=cfg.n_groups * cfg.d_state)


def init_ssm(key, d_model: int, cfg: SSMConfig, dtype) -> dict:
    dims = ssm_dims(d_model, cfg)
    d_inner, d_bc, H = dims["d_inner"], dims["d_bc"], dims["n_heads"]
    ks = jax.random.split(key, 8)
    s = d_model ** -0.5
    proj = lambda k, n: (jax.random.normal(k, (d_model, n)) * s).astype(dtype)
    return {
        "wz": proj(ks[0], d_inner),
        "wx": proj(ks[1], d_inner),
        "wb": proj(ks[2], d_bc),
        "wc": proj(ks[3], d_bc),
        "wdt": proj(ks[4], H),
        "conv_x": (jax.random.normal(ks[5], (cfg.conv_width, d_inner)) * 0.1).astype(dtype),
        "conv_b": (jax.random.normal(ks[6], (cfg.conv_width, d_bc)) * 0.1).astype(dtype),
        "conv_c": (jax.random.normal(ks[7], (cfg.conv_width, d_bc)) * 0.1).astype(dtype),
        "conv_bias_x": jnp.zeros((d_inner,), dtype),
        "conv_bias_b": jnp.zeros((d_bc,), dtype),
        "conv_bias_c": jnp.zeros((d_bc,), dtype),
        "a_log": jnp.log(jnp.linspace(1.0, 16.0, H).astype(jnp.float32)),
        "dt_bias": jnp.zeros((H,), jnp.float32),
        "d_skip": jnp.ones((H,), jnp.float32),
        "norm": init_rms_norm(d_inner),
        "out_proj": (jax.random.normal(jax.random.fold_in(key, 99), (d_inner, d_model))
                     * d_inner ** -0.5).astype(dtype),
    }


def _causal_conv(xs: jnp.ndarray, w: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """Depthwise causal conv, width W: xs (B,S,C), w (W,C)."""
    W = w.shape[0]
    pad = jnp.pad(xs, ((0, 0), (W - 1, 0), (0, 0)))
    out = sum(pad[:, i : i + xs.shape[1], :] * w[i] for i in range(W))
    return jax.nn.silu(out + b)


def ssd_chunked(x, dt, a_log, b, c, chunk: int, return_state: bool = False,
                numerics=None):
    """SSD scan. x:(B,S,H,P) dt:(B,S,H) b,c:(B,S,G,N) -> y:(B,S,H,P).

    return_state: also return the final (B,H,N,P) state (prefill->decode
    handoff). Pure-jnp reference implementation (kernels/ssd_scan/ref.py
    re-exports this; the Pallas kernel matches it in the sweep tests).

    ``numerics`` routes the inter-chunk state readout (the C · h_prev
    contraction) through the activation×activation seam at site
    ``ssm.scan``; None / exact keeps the historical einsum bit-for-bit.
    The intra-chunk dual quadratic form stays exact: its masked-decay
    weighting has no plain matmul form (DESIGN.md §Arch-applicability).
    """
    B, S, H, P = x.shape
    G, N = b.shape[2], b.shape[3]
    if S % chunk:
        # right-pad to a chunk multiple; dt=0 makes padding state-neutral
        # (decay exp(0)=1, contribution x*dt=0) — outputs sliced back below
        pad = chunk - S % chunk
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        b = jnp.pad(b, ((0, 0), (0, pad), (0, 0), (0, 0)))
        c = jnp.pad(c, ((0, 0), (0, pad), (0, 0), (0, 0)))
    S_pad = x.shape[1]
    nc = S_pad // chunk
    rep = H // G

    a = -jnp.exp(a_log.astype(jnp.float32))                    # (H,)
    la = a * dt.astype(jnp.float32)                            # (B,S,H) log decay
    xdt = x.astype(jnp.float32) * dt.astype(jnp.float32)[..., None]

    # chunk views
    lac = la.reshape(B, nc, chunk, H)
    cum = jnp.cumsum(lac, axis=2)                              # (B,nc,Q,H)
    xc = xdt.reshape(B, nc, chunk, H, P)
    bc_ = b.astype(jnp.float32).reshape(B, nc, chunk, G, N)
    cc_ = c.astype(jnp.float32).reshape(B, nc, chunk, G, N)
    bh = jnp.repeat(bc_, rep, axis=3)                          # (B,nc,Q,H,N)
    ch = jnp.repeat(cc_, rep, axis=3)

    # intra-chunk (dual quadratic form); mask BEFORE exp — the upper triangle
    # holds positive log-decays that overflow and would leak NaN into grads
    seg = cum[:, :, :, None, :] - cum[:, :, None, :, :]        # (B,nc,Q,Q,H) t,s
    tri = jnp.tril(jnp.ones((chunk, chunk), bool))
    seg = jnp.where(tri[None, None, :, :, None], seg, -1e30)
    decay = jnp.exp(seg)
    cb = jnp.einsum("bnthi,bnshi->bntsh", ch, bh)              # (B,nc,Q,Q,H)
    y_intra = jnp.einsum("bntsh,bntsh,bnshp->bnthp", cb, decay, xc)

    # chunk states: S_c = sum_s exp(cum_Q - cum_s) * b_s x_s^T
    tail = jnp.exp(cum[:, :, -1:, :] - cum)                    # (B,nc,Q,H)
    states = jnp.einsum("bnsh,bnshi,bnshp->bnhip", tail, bh, xc)  # (B,nc,H,N,P)

    # inter-chunk recurrence: h_{c} = exp(sum la_c) h_{c-1} + S_c
    chunk_decay = jnp.exp(cum[:, :, -1, :])                    # (B,nc,H)

    def step(h, inp):
        dec, s_c = inp
        h_new = dec[..., None, None] * h + s_c
        return h_new, h

    h0 = jnp.zeros((B, H, N, P), jnp.float32)
    h_final, h_prev = jax.lax.scan(
        step, h0,
        (jnp.moveaxis(chunk_decay, 1, 0), jnp.moveaxis(states, 1, 0)),
    )
    h_prev = jnp.moveaxis(h_prev, 0, 1)                        # (B,nc,H,N,P) state BEFORE chunk

    nm = resolve_numerics(numerics, "ssm.scan")
    with seam_scope("ssm.scan"):
        if nm is not None and not nm.is_exact():
            # decay-weighted C panel against the carried state, grouped per
            # (batch, chunk, head): (B,nc,H,Q,N) @ (B,nc,H,N,P) seam call
            dc = (ch * jnp.exp(cum)[..., None]).transpose(0, 1, 3, 2, 4)
            y_inter = approx_matmul(dc, h_prev, nm,
                                    site="ssm.scan").transpose(0, 1, 3, 2, 4)
        else:
            y_inter = jnp.einsum("bnthi,bnth,bnhip->bnthp", ch, jnp.exp(cum), h_prev)
    y = (y_intra + y_inter).reshape(B, S_pad, H, P)[:, :S]
    if return_state:
        # note: state axes are (H, N, P); SSMState stores (H, N, P) too
        return y, h_final
    return y


def ssm_forward(params: dict, xin: jnp.ndarray, d_model: int, cfg: SSMConfig,
                numerics: AMRNumerics | None = None, eps: float = 1e-6) -> jnp.ndarray:
    """Full-sequence Mamba2 mixer (train / prefill)."""
    dims = ssm_dims(d_model, cfg)
    d_inner, H = dims["d_inner"], dims["n_heads"]
    z = pin(dense(xin, params["wz"], numerics, site="ssm.wz"), "batch", None, "tp")
    x = pin(dense(xin, params["wx"], numerics, site="ssm.wx"), "batch", None, "tp")
    b = pin(dense(xin, params["wb"], numerics, site="ssm.wb"), "batch", None, None)
    c = pin(dense(xin, params["wc"], numerics, site="ssm.wc"), "batch", None, None)
    dt = dense(xin, params["wdt"], numerics, site="ssm.wdt")

    x = _causal_conv(x, params["conv_x"], params["conv_bias_x"])
    b = _causal_conv(b, params["conv_b"], params["conv_bias_b"])
    c = _causal_conv(c, params["conv_c"], params["conv_bias_c"])

    B_, S, _ = x.shape
    x = pin(x.reshape(B_, S, H, cfg.head_dim), "batch", None, "tp", None)
    b = b.reshape(B_, S, cfg.n_groups, cfg.d_state)
    c = c.reshape(B_, S, cfg.n_groups, cfg.d_state)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + params["dt_bias"])
    y = ssd_chunked(x, dt, params["a_log"], b, c, cfg.chunk, numerics=numerics)
    y = y + params["d_skip"][None, None, :, None] * x.astype(jnp.float32)
    y = pin(y.reshape(B_, S, d_inner), "batch", None, "tp").astype(xin.dtype)
    y = y * jax.nn.silu(z)
    y = rms_norm(y, params["norm"], eps)
    return pin(dense(y, params["out_proj"], numerics, site="ssm.out_proj"), "batch", None, None)


# ------------------------------------------------------------------ decode
@partial(jax.tree_util.register_dataclass,
         data_fields=["conv_x", "conv_b", "conv_c", "h"], meta_fields=[])
@dataclasses.dataclass
class SSMState:
    conv_x: jnp.ndarray  # (B, W-1, d_inner) ring of recent x projections
    conv_b: jnp.ndarray  # (B, W-1, d_bc)
    conv_c: jnp.ndarray  # (B, W-1, d_bc)
    h: jnp.ndarray       # (B, H, N, P) SSM state

    @classmethod
    def zeros(cls, batch, d_model, cfg: SSMConfig, dtype):
        dims = ssm_dims(d_model, cfg)
        W = cfg.conv_width - 1
        return cls(
            jnp.zeros((batch, W, dims["d_inner"]), dtype),
            jnp.zeros((batch, W, dims["d_bc"]), dtype),
            jnp.zeros((batch, W, dims["d_bc"]), dtype),
            jnp.zeros((batch, dims["n_heads"], cfg.d_state, cfg.head_dim), jnp.float32),
        )


def _conv_step(ring, new, w, bias):
    window = jnp.concatenate([ring, new[:, None, :]], axis=1)  # (B, W, C)
    out = (window * w[None]).sum(axis=1) + bias
    return jax.nn.silu(out), window[:, 1:, :]


def ssm_decode(params: dict, xin: jnp.ndarray, state: SSMState, d_model: int,
               cfg: SSMConfig, numerics: AMRNumerics | None = None,
               eps: float = 1e-6) -> tuple[jnp.ndarray, SSMState]:
    """One-token step. xin: (B, 1, d_model)."""
    dims = ssm_dims(d_model, cfg)
    d_inner, H = dims["d_inner"], dims["n_heads"]
    x1 = xin[:, 0]
    z = dense(x1, params["wz"], numerics, site="ssm.wz")
    x = dense(x1, params["wx"], numerics, site="ssm.wx")
    b = dense(x1, params["wb"], numerics, site="ssm.wb")
    c = dense(x1, params["wc"], numerics, site="ssm.wc")
    dt = dense(x1, params["wdt"], numerics, site="ssm.wdt")

    x, ring_x = _conv_step(state.conv_x, x, params["conv_x"], params["conv_bias_x"])
    b, ring_b = _conv_step(state.conv_b, b, params["conv_b"], params["conv_bias_b"])
    c, ring_c = _conv_step(state.conv_c, c, params["conv_c"], params["conv_bias_c"])

    Bt = x.shape[0]
    x = x.reshape(Bt, H, cfg.head_dim).astype(jnp.float32)
    b = b.reshape(Bt, cfg.n_groups, cfg.d_state).astype(jnp.float32)
    c = c.reshape(Bt, cfg.n_groups, cfg.d_state).astype(jnp.float32)
    rep = H // cfg.n_groups
    bh = jnp.repeat(b, rep, axis=1)                            # (B,H,N)
    ch = jnp.repeat(c, rep, axis=1)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + params["dt_bias"])  # (B,H)
    a = -jnp.exp(params["a_log"].astype(jnp.float32))
    decay = jnp.exp(a[None] * dt)                              # (B,H)

    xdt = x * dt[..., None]                                    # (B,H,P)
    h_new = decay[..., None, None] * state.h + bh[..., None] * xdt[:, :, None, :]
    nm = resolve_numerics(numerics, "ssm.scan")
    with seam_scope("ssm.scan"):
        if nm is not None and not nm.is_exact():
            # one-row state readout through the seam: (B,H,1,N) @ (B,H,N,P)
            yss = approx_matmul(ch[:, :, None, :], h_new, nm,
                                site="ssm.scan")[:, :, 0, :]
        else:
            yss = jnp.einsum("bhn,bhnp->bhp", ch, h_new)
    y = yss + params["d_skip"][None, :, None] * x
    y = y.reshape(Bt, d_inner).astype(xin.dtype)
    y = y * jax.nn.silu(z)
    y = rms_norm(y, params["norm"], eps)
    out = dense(y, params["out_proj"], numerics, site="ssm.out_proj")[:, None, :]
    return out, SSMState(ring_x, ring_b, ring_c, h_new)


def ssm_prefill(params: dict, xin: jnp.ndarray, d_model: int, cfg: SSMConfig,
                numerics: AMRNumerics | None = None, eps: float = 1e-6
                ) -> tuple[jnp.ndarray, SSMState]:
    """Full-sequence forward that ALSO returns the decode state
    (prefill -> decode handoff): final SSM state + conv ring tails."""
    dims = ssm_dims(d_model, cfg)
    d_inner, H = dims["d_inner"], dims["n_heads"]
    z = pin(dense(xin, params["wz"], numerics, site="ssm.wz"), "batch", None, "tp")
    x_raw = pin(dense(xin, params["wx"], numerics, site="ssm.wx"), "batch", None, "tp")
    b_raw = pin(dense(xin, params["wb"], numerics, site="ssm.wb"), "batch", None, None)
    c_raw = pin(dense(xin, params["wc"], numerics, site="ssm.wc"), "batch", None, None)
    dt = dense(xin, params["wdt"], numerics, site="ssm.wdt")

    W = cfg.conv_width
    def tail(t):  # last W-1 raw inputs, zero-padded for short sequences
        pad = jnp.zeros((t.shape[0], max(W - 1 - t.shape[1], 0), t.shape[2]), t.dtype)
        return jnp.concatenate([pad, t[:, -(W - 1):, :]], axis=1)

    x = _causal_conv(x_raw, params["conv_x"], params["conv_bias_x"])
    b = _causal_conv(b_raw, params["conv_b"], params["conv_bias_b"])
    c = _causal_conv(c_raw, params["conv_c"], params["conv_bias_c"])

    B_, S, _ = x.shape
    x = pin(x.reshape(B_, S, H, cfg.head_dim), "batch", None, "tp", None)
    b = b.reshape(B_, S, cfg.n_groups, cfg.d_state)
    c = c.reshape(B_, S, cfg.n_groups, cfg.d_state)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + params["dt_bias"])
    y, h_final = ssd_chunked(x, dt, params["a_log"], b, c, cfg.chunk,
                             return_state=True, numerics=numerics)
    y = y + params["d_skip"][None, None, :, None] * x.astype(jnp.float32)
    y = pin(y.reshape(B_, S, d_inner), "batch", None, "tp").astype(xin.dtype)
    y = y * jax.nn.silu(z)
    y = rms_norm(y, params["norm"], eps)
    out = pin(dense(y, params["out_proj"], numerics, site="ssm.out_proj"), "batch", None, None)
    state = SSMState(tail(x_raw), tail(b_raw), tail(c_raw), h_final)
    return out, state
