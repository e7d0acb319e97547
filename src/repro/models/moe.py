"""Mixture-of-Experts with sorted-capacity dispatch (GShard/Switch-style).

Design (DESIGN.md §3): tokens are routed top-k, sorted by expert id, and
scattered into fixed (E, C, D) capacity buffers; expert FFNs run as plain
einsums (MXU-friendly, cleanly partitionable by XLA SPMD: E or F shard on
"model"); outputs are combined by weighted scatter-add. Fully
differentiable; overflow beyond capacity_factor drops (standard).

The router stays in exact numerics — top-k decisions are sensitive to small
logit perturbations and the paper's technique targets bulk matmuls
(DESIGN.md §Arch-applicability). Expert FFNs follow the numerics policy.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import MoEConfig
from repro.numerics import AMRNumerics
from repro.parallel.constraints import pin

from .layers import seam_scope


def init_moe(key, d_model: int, cfg: MoEConfig, dtype) -> dict:
    ks = jax.random.split(key, 4)
    s_in = d_model ** -0.5
    s_ff = cfg.d_ff_expert ** -0.5
    E, F = cfg.n_experts, cfg.d_ff_expert
    return {
        "router": (jax.random.normal(ks[0], (d_model, E)) * s_in).astype(jnp.float32),
        "w_gate": (jax.random.normal(ks[1], (E, d_model, F)) * s_in).astype(dtype),
        "w_up": (jax.random.normal(ks[2], (E, d_model, F)) * s_in).astype(dtype),
        "w_down": (jax.random.normal(ks[3], (E, F, d_model)) * s_ff).astype(dtype),
    }


def moe_forward(
    params: dict,
    x: jnp.ndarray,                  # (B, S, D)
    cfg: MoEConfig,
    *,
    capacity_factor: float = 1.25,
    numerics: AMRNumerics | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Returns (output (B,S,D), aux load-balancing loss scalar)."""
    if cfg.dispatch_shard == "local":
        return _moe_forward_local(params, x, cfg, capacity_factor=capacity_factor,
                                  numerics=numerics)
    return _moe_forward_global(params, x, cfg, capacity_factor=capacity_factor,
                               numerics=numerics)


def _moe_forward_global(
    params: dict,
    x: jnp.ndarray,
    cfg: MoEConfig,
    *,
    capacity_factor: float = 1.25,
    numerics: AMRNumerics | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    B, S, D = x.shape
    E, K = cfg.n_experts, cfg.top_k
    T = B * S
    xf = x.reshape(T, D)

    logits = jnp.matmul(xf.astype(jnp.float32), params["router"])      # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    top_w, top_e = jax.lax.top_k(probs, K)                             # (T, K)
    top_w = top_w / jnp.maximum(top_w.sum(-1, keepdims=True), 1e-9)

    # aux loss (Switch): E * sum_e f_e * p_e
    me = probs.mean(axis=0)
    ce = jnp.zeros((E,), jnp.float32).at[top_e.reshape(-1)].add(1.0) / (T * K)
    aux = E * jnp.sum(me * ce)

    # ---- sorted-capacity dispatch. Small token counts (decode steps,
    # short prefills) run DROPLESS (C = T*K): capacity dropping there is
    # degenerate and would make decode disagree with prefill routing.
    C = max(int(T * K * capacity_factor / E + 0.999), 1)
    if T * K <= 4096:
        C = T * K
    fid = top_e.reshape(-1)                                            # (T*K,)
    fw = top_w.reshape(-1)
    tok = jnp.arange(T * K, dtype=jnp.int32) // K
    order = jnp.argsort(fid, stable=True)
    fid_s, fw_s, tok_s = fid[order], fw[order], tok[order]
    counts = jnp.zeros((E,), jnp.int32).at[fid].add(1)
    starts = jnp.concatenate([jnp.zeros((1,), jnp.int32), jnp.cumsum(counts)[:-1]])
    pos = jnp.arange(T * K, dtype=jnp.int32) - starts[fid_s]           # slot in expert
    keep = pos < C
    slot = jnp.where(keep, pos, C)                                     # C drops (mode=drop)

    xbuf = jnp.zeros((E, C + 1, D), x.dtype).at[fid_s, slot].set(
        xf[tok_s], mode="drop")[:, :C]
    if cfg.dispatch_shard == "batch":
        xbuf = pin(xbuf, None, "batch", None)
    elif cfg.dispatch_shard == "expert":
        xbuf = pin(xbuf, "tp", None, None)

    if cfg.dispatch_shard == "batch":
        hidden_pin = lambda t: pin(t, None, "batch", "tp")
        out_pin = lambda t: pin(t, None, "batch", None)
    elif cfg.dispatch_shard == "expert":
        hidden_pin = lambda t: pin(t, "tp", None, None)
        out_pin = lambda t: pin(t, "tp", None, None)
    else:
        hidden_pin = lambda t: pin(t, None, None, "tp")
        out_pin = lambda t: t
    if numerics is None or numerics.is_exact():
        with seam_scope("moe.expert.w_gate"):
            g = hidden_pin(jnp.einsum("ecd,edf->ecf", xbuf, params["w_gate"]))
        with seam_scope("moe.expert.w_up"):
            u = hidden_pin(jnp.einsum("ecd,edf->ecf", xbuf, params["w_up"]))
        h = (jax.nn.silu(g) * u).astype(x.dtype)
        with seam_scope("moe.expert.w_down"):
            ybuf = out_pin(jnp.einsum("ecf,efd->ecd", h, params["w_down"]))  # (E, C, D)
    else:
        from repro.numerics.approx_matmul import approx_matmul

        # ONE grouped seam call per projection: the (E, C, D) @ (E, D, F)
        # activation-form batched matmul (sites "moe.expert.*", resolvable
        # by the "moe.expert" policy prefix — numerics/policy.py).  The
        # grouped route quantizes per expert (per-row of the capacity
        # buffer, per-column of each expert's weight panel), bit-identical
        # to the old per-expert vmap; amr_noise draws ONE (E, C, F) tensor,
        # so experts decorrelate without the unit-scope key plumbing.
        def expert_mm(a, w, site):
            with seam_scope(site):
                return approx_matmul(a, w, numerics, site=site).astype(x.dtype)

        g = hidden_pin(expert_mm(xbuf, params["w_gate"], "moe.expert.w_gate"))
        u = hidden_pin(expert_mm(xbuf, params["w_up"], "moe.expert.w_up"))
        h = (jax.nn.silu(g) * u).astype(x.dtype)
        ybuf = out_pin(expert_mm(h, params["w_down"], "moe.expert.w_down"))

    ypad = jnp.pad(ybuf, ((0, 0), (0, 1), (0, 0)))                     # slot C reads 0
    gathered = ypad[fid_s, slot] * (fw_s * keep)[:, None].astype(x.dtype)
    out = jnp.zeros((T, D), x.dtype).at[tok_s].add(gathered)
    return pin(out.reshape(B, S, D), "batch", None, None), aux


# ---------------------------------------------------------------------------
# shard_map-local dispatch (dispatch_shard == "local")
# ---------------------------------------------------------------------------

def _moe_local_body(xf, router, w_gate, w_up, w_down, cfg: MoEConfig,
                    capacity_factor: float, batch_axes, model_axis: str | None):
    """Per-shard MoE: local routing/sort/capacity + TP experts.

    xf: (T_local, D). Weights: router (D, E) replicated; w_gate/w_up
    (E, D, F_local), w_down (E, F_local, D) — model-axis TP shards.
    One psum over the model axis after w_down; NO cross-data collectives:
    every token is dispatched and combined on the shard that owns it.
    """
    import jax
    import jax.numpy as jnp

    T, D = xf.shape
    E, K = cfg.n_experts, cfg.top_k
    logits = jnp.matmul(xf.astype(jnp.float32), router)
    probs = jax.nn.softmax(logits, axis=-1)
    top_w, top_e = jax.lax.top_k(probs, K)
    top_w = top_w / jnp.maximum(top_w.sum(-1, keepdims=True), 1e-9)

    me = probs.mean(axis=0)
    ce = jnp.zeros((E,), jnp.float32).at[top_e.reshape(-1)].add(1.0) / (T * K)
    aux = E * jnp.sum(me * ce)
    if batch_axes:
        aux = jax.lax.pmean(aux, batch_axes)

    C = max(int(T * K * capacity_factor / E + 0.999), 1)
    if T * K <= 4096:
        C = T * K  # dropless for small token counts (see _moe_forward_global)
    fid = top_e.reshape(-1)
    fw = top_w.reshape(-1)
    tok = jnp.arange(T * K, dtype=jnp.int32) // K
    order = jnp.argsort(fid, stable=True)
    fid_s, fw_s, tok_s = fid[order], fw[order], tok[order]
    counts = jnp.zeros((E,), jnp.int32).at[fid].add(1)
    starts = jnp.concatenate([jnp.zeros((1,), jnp.int32), jnp.cumsum(counts)[:-1]])
    pos = jnp.arange(T * K, dtype=jnp.int32) - starts[fid_s]
    keep = pos < C
    slot = jnp.where(keep, pos, C)

    xbuf = jnp.zeros((E, C + 1, D), xf.dtype).at[fid_s, slot].set(
        xf[tok_s], mode="drop")[:, :C]
    with seam_scope("moe.expert.w_gate"):
        g = jnp.einsum("ecd,edf->ecf", xbuf, w_gate)
    with seam_scope("moe.expert.w_up"):
        u = jnp.einsum("ecd,edf->ecf", xbuf, w_up)
    h = (jax.nn.silu(g) * u).astype(xf.dtype)
    with seam_scope("moe.expert.w_down"):
        ybuf = jnp.einsum("ecf,efd->ecd", h, w_down)

    ypad = jnp.pad(ybuf, ((0, 0), (0, 1), (0, 0)))
    gathered = ypad[fid_s, slot] * (fw_s * keep)[:, None].astype(xf.dtype)
    out = jnp.zeros((T, D), xf.dtype).at[tok_s].add(gathered)
    if model_axis:
        # TP partial sums: reduce AFTER the combine — (T, D) is top_k *
        # capacity_factor (= 7.5x for moonshot) smaller than (E, C, D)
        out = jax.lax.psum(out, model_axis)
    return out, aux


def _moe_forward_local(params, x, cfg: MoEConfig, *, capacity_factor, numerics):
    """shard_map dispatch: tokens never leave their data shard."""
    import jax
    from jax.sharding import PartitionSpec as P

    from repro.parallel.constraints import _ambient_axes

    axes = _ambient_axes()
    if not axes:  # no mesh (unit tests): run the body on the whole array
        B, S, D = x.shape
        out, aux = _moe_local_body(
            x.reshape(B * S, D), params["router"], params["w_gate"],
            params["w_up"], params["w_down"], cfg, capacity_factor, None, None)
        return out.reshape(B, S, D), aux

    batch_axes = tuple(a for a in ("pod", "data") if a in axes)
    model_axis = "model" if "model" in axes else None
    F = params["w_gate"].shape[-1]
    tp_ok = model_axis and F % axes[model_axis] == 0

    B, S, D = x.shape
    xf = x.reshape(B * S, D)
    x_spec = P(batch_axes if len(batch_axes) > 1 else (batch_axes[0] if batch_axes else None), None)
    w_col = P(None, None, "model" if tp_ok else None)
    w_row = P(None, "model" if tp_ok else None, None)

    body = lambda xs, r, wg, wu, wd: _moe_local_body(
        xs, r, wg, wu, wd, cfg, capacity_factor, batch_axes,
        model_axis if tp_ok else None)
    out, aux = jax.shard_map(
        body,
        mesh=jax.sharding.get_abstract_mesh(),
        in_specs=(x_spec, P(None, None), w_col, w_col, w_row),
        out_specs=(x_spec, P()),
        check_vma=False,
    )(xf, params["router"], params["w_gate"], params["w_up"], params["w_down"])
    return out.reshape(B, S, D), aux
