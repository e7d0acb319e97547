"""Pallas kernel: fused AMR attention — QK^T, masked softmax, PV, one pass.

The activation×activation seam (numerics/approx_matmul.py) computes a
decode/prefill attention step as two separate grouped matmuls with an XLA
softmax between them: quantize Q/K, LUT-gather or circuit-replay the score
products, rescale, mask, softmax, re-quantize the probabilities, and
contract against V.  This kernel runs that whole chain inside ONE grid
block per (group, query-row tile), so the (bm, T) score block never
round-trips to HBM between QK^T and PV.

Two methods, mirroring the seam's integer paths:

  * ``lut``    — both contractions gather from the full 256x256 product
    table (``_lut_gather_accum``); bit-identical to the ``amr_lut`` seam
    composition by construction.
  * ``inject`` — both contractions replay the reduction circuit on
    lane-packed operand words (``inject_replay._replay_block`` — the exact
    kernel body of the matmul-shaped replay, called twice back to back),
    so ANY registered ``reduction.Schedule`` runs fused, LUT-free.  K and
    V are lane-packed outside the kernel (in-trace, per group — traced
    activations never touch the identity-keyed WEIGHT_PACKS cache).

Bitwise contract (asserted in tests/test_attn_fused.py and gated by the
attention benchmark): the output equals the UNFUSED seam composition —
``approx_matmul(q, kT) / scale`` -> mask -> softmax -> re-quantize ->
``approx_matmul(p, v)`` — bit for bit.  Everything the kernel fuses is
either integer math (gather/replay products, int32 accumulation: exactly
associative) or the identical sequence of f32 elementwise ops and row
reductions the seam's XLA program runs, in the same order.  The softmax is
NOT the online/streaming form — a flash-style rescaling accumulator would
change f32 summation order and break the bit-identity bar — so T, D and P
live whole in VMEM and only the query-row dim is tiled
(``tiling.ATTN_AUTOTUNE``, head-dim-bucketed: bigger head dims shrink the
row tile).  That sizes the kernel for decode/short-prefill shapes, the
serving hot path the paper's Table 2 energy claim turns on.

Masking: the caller passes an explicit per-row validity mask (int32 0/1,
(G, M, T)) — causal, sliding-window and ragged decode masks all reduce to
it.  Invalid columns take ``NEG_INF`` (the same fill models/attention.py
uses) BEFORE the softmax, exactly like the unfused path.  For the inject
method the replayed score block is word-padded (32 columns per lane word);
the pad is sliced off (statically) before the softmax, and the padded PV
columns are sliced off by the op wrapper after the kernel.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.engine import _LANE_BITS
from repro.kernels.inject_replay.kernel import _replay_block, _replay_inputs

NEG_INF = -2.0e38  # the models/attention.py mask fill, bit for bit


def _lut_gather_accum(a, b, flat, acc):
    """acc + sum_k LUT[a_k, b_k] outer products, gathered from the flat
    table (interpreter only: Mosaic lowers neither the gather nor the
    dynamic lane slice)."""
    bm, bk = a.shape
    bn = b.shape[1]
    ia = a.astype(jnp.int32) + 128
    ib = b.astype(jnp.int32) + 128

    def body(k, acc):
        # flat index LUT[a_k, b_k] = flat[a_k * 256 + b_k], outer-product shaped
        iak = jax.lax.dynamic_index_in_dim(ia, k, axis=1, keepdims=True)   # (bm, 1)
        ibk = jax.lax.dynamic_index_in_dim(ib, k, axis=0, keepdims=True)   # (1, bn)
        idx = iak * 256 + ibk                                              # (bm, bn)
        return acc + jnp.take(flat, idx.reshape(-1), axis=0).reshape(bm, bn)

    return jax.lax.fori_loop(0, bk, body, acc)


def _quantize_probs(probs):
    """In-kernel int8 quantization of the softmax rows.

    Bitwise the ``quantize_int8`` / ``quantize_int8_ste`` index computation
    (numerics/quant.py): the two share ``_absmax_scale`` (absmax over the
    row, eps=1e-8, /127) and the round/clip, differing only in the returned
    dtype/gradient — neither of which reaches the integer contraction.
    Returns (q on the int8 grid as f32, per-row scale (bm, 1) f32).
    """
    amax = jnp.max(jnp.abs(probs), axis=-1, keepdims=True)
    ps = jnp.maximum(amax, 1e-8) / 127.0
    qp = jnp.clip(jnp.round(probs / ps), -128.0, 127.0)
    return qp, ps


def _attn_fused_lut_kernel(q_ref, k_ref, v_ref, sq_ref, sk_ref, sv_ref,
                           mask_ref, lut_ref, out_ref, *, scale: float):
    """One (bm, P) output block: full-LUT QK^T -> masked softmax -> PV."""
    flat = lut_ref[...].reshape(-1)                # (65536,) int32
    q = q_ref[0]                                   # (bm, D) int8
    kt = k_ref[0]                                  # (D, T) int8
    v = v_ref[0]                                   # (T, P) int8
    bm = q.shape[0]
    t_len = kt.shape[1]
    p_len = v.shape[1]
    acc = _lut_gather_accum(q, kt, flat, jnp.zeros((bm, t_len), jnp.int32))
    scores = acc.astype(jnp.float32) * sq_ref[0] * sk_ref[0] / scale
    scores = jnp.where(mask_ref[0] != 0, scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    qp, ps = _quantize_probs(probs)
    acc = _lut_gather_accum(qp, v, flat, jnp.zeros((bm, p_len), jnp.int32))
    out_ref[0] = acc.astype(jnp.float32) * ps * sv_ref[0]


@functools.partial(jax.jit, static_argnames=("bm", "scale", "interpret"))
def _attn_fused_lut_jit(q, kt, v, sq, sk, sv, mask, table, *, bm, scale,
                        interpret):
    """q (G,M,D) / kt (G,D,T) / v (G,T,P) int8, per-seam scales, mask
    (G,M,T) int32, table (256,256) int32 -> (G, M, P) f32."""
    G, M, D = q.shape
    T = kt.shape[-1]
    P = v.shape[-1]
    assert M % bm == 0, (M, bm)
    return pl.pallas_call(
        functools.partial(_attn_fused_lut_kernel, scale=scale),
        grid=(G, M // bm),
        in_specs=[
            pl.BlockSpec((1, bm, D), lambda g, i: (g, i, 0)),
            pl.BlockSpec((1, D, T), lambda g, i: (g, 0, 0)),
            pl.BlockSpec((1, T, P), lambda g, i: (g, 0, 0)),
            pl.BlockSpec((1, bm, 1), lambda g, i: (g, i, 0)),
            pl.BlockSpec((1, 1, T), lambda g, i: (g, 0, 0)),
            pl.BlockSpec((1, 1, P), lambda g, i: (g, 0, 0)),
            pl.BlockSpec((1, bm, T), lambda g, i: (g, i, 0)),
            pl.BlockSpec(table.shape, lambda g, i: (0, 0)),  # whole LUT
        ],
        out_specs=pl.BlockSpec((1, bm, P), lambda g, i: (g, i, 0)),
        out_shape=jax.ShapeDtypeStruct((G, M, P), jnp.float32),
        interpret=interpret,
    )(q, kt, v, sq, sk, sv, mask, table)


def _make_attn_fused_inject_kernel(stage_bounds, *, n_final: int, offset: int,
                                   t_len: int, scale: float):
    """Inject-method body: two back-to-back ``_replay_block`` calls."""

    def kernel(iq_ref, kw_ref, vw_ref, masks_ref, sq_ref, sk_ref, sv_ref,
               mask_ref, gate_ref, xi_ref, yi_ref, in3_ref, sm_ref, cm_ref,
               perm_ref, fin_ref, bw_ref, out_ref):
        masks = masks_ref[...]
        consts = (gate_ref[...], xi_ref[...], yi_ref[...], in3_ref[...],
                  sm_ref[...], cm_ref[...], perm_ref[...], fin_ref[...],
                  bw_ref[...])
        qk = _replay_block(iq_ref[0], kw_ref[0], masks, *consts,
                           stage_bounds=stage_bounds, n_final=n_final,
                           offset=offset)          # (bm, Tw*32), word-padded
        scores = (qk[:, :t_len].astype(jnp.float32)
                  * sq_ref[0] * sk_ref[0] / scale)
        scores = jnp.where(mask_ref[0] != 0, scores, NEG_INF)
        probs = jax.nn.softmax(scores, axis=-1)
        qp, ps = _quantize_probs(probs)
        ip = qp.astype(jnp.int32) + 128            # replay operand indices
        pv = _replay_block(ip, vw_ref[0], masks, *consts,
                           stage_bounds=stage_bounds, n_final=n_final,
                           offset=offset)          # (bm, Pw*32), word-padded
        out_ref[0] = pv.astype(jnp.float32) * ps * sv_ref[0]

    return kernel


@functools.partial(jax.jit, static_argnames=("lowered", "bm", "scale",
                                             "interpret"))
def _attn_fused_inject_jit(iq, kw, vw, masks, sq, sk, sv, mask, *, lowered,
                           bm, scale, interpret):
    """iq (G,M,D) int32 indices, kw (G,D,nb,Tw) / vw (G,T,nb,Pw) lane-packed
    words, masks (256,nb), sv padded to whole words -> (G, M, Pw*32) f32
    (pad columns carry garbage; the op wrapper slices [:, :, :P])."""
    G, M, D = iq.shape
    nb, tw = kw.shape[2], kw.shape[3]
    t_len = vw.shape[1]
    pw = vw.shape[-1]
    npad = pw * _LANE_BITS
    assert M % bm == 0, (M, bm)
    consts, stage_bounds = _replay_inputs(lowered)
    whole = [pl.BlockSpec(c.shape, lambda g, i, nd=c.ndim: (0,) * nd)
             for c in (masks, *consts)]
    return pl.pallas_call(
        _make_attn_fused_inject_kernel(
            stage_bounds, n_final=len(lowered.final_ids),
            offset=int(lowered.offset_total), t_len=t_len, scale=scale),
        grid=(G, M // bm),
        in_specs=[
            pl.BlockSpec((1, bm, D), lambda g, i: (g, i, 0)),
            pl.BlockSpec((1, D, nb, tw), lambda g, i: (g, 0, 0, 0)),
            pl.BlockSpec((1, t_len, nb, pw), lambda g, i: (g, 0, 0, 0)),
            whole[0],                                   # value->mask table
            pl.BlockSpec((1, bm, 1), lambda g, i: (g, i, 0)),
            pl.BlockSpec((1, 1, t_len), lambda g, i: (g, 0, 0)),
            pl.BlockSpec((1, 1, npad), lambda g, i: (g, 0, 0)),
            pl.BlockSpec((1, bm, t_len), lambda g, i: (g, i, 0)),
            *whole[1:],                                 # lowering consts
        ],
        out_specs=pl.BlockSpec((1, bm, npad), lambda g, i: (g, i, 0)),
        out_shape=jax.ShapeDtypeStruct((G, M, npad), jnp.float32),
        interpret=interpret,
    )(iq, kw, vw, masks, sq, sk, sv, mask, *consts)
