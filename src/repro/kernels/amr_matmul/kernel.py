"""Pallas TPU kernels: AMR-MUL approximate matmul, low-rank and full-LUT forms.

The paper's multiplier, as deployed on TPU (DESIGN.md §2 L2): for int8
operands the approximate product is exactly ``a*b + E(a,b)`` with E the
256x256 error table of the bit-accurate 2-digit AMR-MUL.  Two kernel
variants trade fidelity against the work they put on the MXU:

**Low-rank** — E factors as ``E ~= U V^T`` (SVD, rank r), so with the
augmented factors ``U' = [a | U]`` and ``V' = [b | V]`` (the exact product
rides as lane 0) a block is one contraction over K*(1+r) lanes:

    acc += sum_k U'[a_k]^T V'[b_k]

Per-product error vs the full table is bounded by the first dropped
singular value ``sigma_{r+1}`` (see core/lut.py), i.e. <= K*sigma_{r+1}
per output element.

**Full-LUT** — bit-exact: the whole 256x256 product table sits in VMEM and
each K step adds the (bm, bn) block ``LUT[a_k, b_k]`` into an int32
accumulator.  Zero error vs the schedule engine's replay (asserted in
tests/test_kernels.py).

Neither variant gathers: the TPU compiler (Mosaic) lowers no general
gather.  Both look up table rows with one-hot matmuls instead.  Row k of a
block's operand indices becomes a (256, w) one-hot (value on sublanes, the
block's rows or columns on lanes), and ``table^T @ one_hot`` picks the
table entries on the MXU.  A one-hot product has one nonzero term, so the
lookup is exact when the table is exact in bf16: the factors ride as three
bf16 planes that sum back to the f32 value exactly, the LUT as two byte
planes.  The low-rank contraction of the looked-up f32 values does not
rely on the precision Mosaic gives an f32 dot: it splits both sides into
three bf16 planes again and sums the six plane products an f32 dot keeps.  The A operand arrives transposed, (K, M), so that its one-hot rows
are sublane slices too, and the final contraction runs over the leading
axis of both sides.

Tiling (both variants): grid (M/bm, N/bn, K/bk), K innermost so the
accumulator scratch carries across the K sweep.  The jitted entry points
pad M and N up to the block dims (padded rows/columns are sliced off); K
is never padded, since AMR(0, b) need not be 0, so bk divides K.  Block
dims come from ``tiling.pick_tiles``.

``interpret=None`` (default) autodetects per backend — compiled Mosaic on
real TPU, interpreter mode on CPU and GPU (the kernels use pltpu memory
spaces the Triton lowering lacks) — overridable via the
``REPRO_PALLAS_INTERPRET`` env var (see kernels/pallas_config.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.pallas_config import resolve_interpret

from .tiling import pick_tiles

_VALUES = 256            # int8 operand values; value + 128 indexes the tables
_LEADING = (((0,), (0,)), ((), ()))   # dot_general: contract axis 0 of both
_LUT_PLANE_LIMIT = 1 << 15            # byte planes are bf16-exact below this


def _one_hot_row(idx_ref, k):
    """(256, w) bf16 one-hot of row ``k`` of an int32 index scratch (w lanes):
    entry [t, j] is 1 where idx[k, j] == t."""
    row = idx_ref[pl.ds(k, 1), :]
    iota = jax.lax.broadcasted_iota(jnp.int32, (_VALUES, row.shape[1]), 0)
    return (iota == row).astype(jnp.bfloat16)


def _load_indices(ia_ref, ib_ref, at, b):
    """Block operands (int8, values) -> int32 table indices in scratch."""
    ia_ref[...] = at.astype(jnp.int32) + 128
    ib_ref[...] = b.astype(jnp.int32) + 128


def _pad_operands(a, b, bm: int, bn: int):
    """A (..., M, K) -> A^T (..., K, Mp) and B (..., K, N) -> (..., K, Np),
    with Mp / Np the next multiples of bm / bn (zero padding)."""
    m, n = a.shape[-2], b.shape[-1]
    at = jnp.swapaxes(a, -1, -2)
    lead = [(0, 0)] * (at.ndim - 1)
    return (jnp.pad(at, lead + [(0, -m % bm)]),
            jnp.pad(b, lead + [(0, -n % bn)]))


def _top_bf16(x):
    """x (f32) cut to its top 16 bits: a bf16 value, exactly, in f32.  Bit
    masking, not a round trip through bf16: on TPU, XLA drops an
    f32 -> bf16 -> f32 round trip as excess precision, which made the
    lower planes 0."""
    bits = jax.lax.bitcast_convert_type(x, jnp.int32) & jnp.int32(-65536)
    return jax.lax.bitcast_convert_type(bits, jnp.float32)


def _split3(x):
    """f32 -> three bf16 parts whose f32 sum, taken as (p0 + p1) + p2, is x
    exactly (8 + 8 + 8 significand bits; each subtraction is exact)."""
    hi = _top_bf16(x)
    rest = x - hi
    mid = _top_bf16(rest)
    return (hi.astype(jnp.bfloat16), mid.astype(jnp.bfloat16),
            (rest - mid).astype(jnp.bfloat16))


# (i, j) plane pairs of a product of two 3-way splits down to 2**-24
# relative, smallest terms first: the products an f32 dot keeps
_F32_PAIRS = ((1, 1), (2, 0), (0, 2), (1, 0), (0, 1), (0, 0))


def _augmented_planes(f):
    """(256, r) factors -> bf16 planes of [value | f | 0]^T, rows padded to
    a multiple of 8 (zero rows add nothing to the contraction)."""
    values = jnp.arange(-128, 128, dtype=jnp.float32)[:, None]
    aug = jnp.concatenate([values, f.astype(jnp.float32)], axis=1)
    aug = jnp.pad(aug, [(0, 0), (0, -aug.shape[1] % 8)])
    return jnp.concatenate(_split3(aug.T), axis=0)


def _lowrank_kernel(at_ref, b_ref, ut_ref, vt_ref, out_ref,
                    ia_ref, ib_ref, ua_ref, vb_ref, acc_ref, *, n_k: int):
    """One (bm, bn) output block; K swept by the innermost grid dim."""
    k_idx = pl.program_id(2)

    @pl.when(k_idx == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    _load_indices(ia_ref, ib_ref, at_ref[...], b_ref[...])
    ut = ut_ref[...]                                # (3R, 256) bf16
    vt = vt_ref[...]
    rows = ut.shape[0] // 3

    def lookup(planes, idx_ref, k):                 # -> (R, w) f32, exact
        got = jnp.dot(planes, _one_hot_row(idx_ref, k),
                      preferred_element_type=jnp.float32)
        return (got[:rows] + got[rows:2 * rows]) + got[2 * rows:]

    def body(k, carry):
        off = pl.multiple_of(k * rows, 8)
        ua_ref[pl.ds(off, rows), :] = lookup(ut, ia_ref, k)
        vb_ref[pl.ds(off, rows), :] = lookup(vt, ib_ref, k)
        return carry

    jax.lax.fori_loop(0, ia_ref.shape[0], body, 0)
    # rows of ua/vb run (k, lane): one (bk*R)-deep contraction per block,
    # f32-accurate from bf16 dots
    ua, vb = _split3(ua_ref[...]), _split3(vb_ref[...])
    acc = acc_ref[...]
    for i, j in _F32_PAIRS:
        acc = acc + jax.lax.dot_general(ua[i], vb[j], _LEADING,
                                        preferred_element_type=jnp.float32)
    acc_ref[...] = acc

    @pl.when(k_idx == n_k - 1)
    def _store():
        out_ref[...] = acc_ref[...]


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def _amr_matmul_int8_jit(a, b, u, v, *, bm, bn, bk, interpret):
    M, K = a.shape
    N = b.shape[1]
    assert K % bk == 0, (K, bk)
    at, bp = _pad_operands(a, b, bm, bn)
    ut, vt = _augmented_planes(u), _augmented_planes(v)
    rows = ut.shape[0] // 3
    n_k = K // bk
    grid = (at.shape[1] // bm, bp.shape[1] // bn, n_k)
    out = pl.pallas_call(
        functools.partial(_lowrank_kernel, n_k=n_k),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bk, bm), lambda i, j, k: (k, i)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
            pl.BlockSpec(ut.shape, lambda i, j, k: (0, 0)),  # whole factors
            pl.BlockSpec(vt.shape, lambda i, j, k: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((at.shape[1], bp.shape[1]), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bk, bm), jnp.int32),
                        pltpu.VMEM((bk, bn), jnp.int32),
                        pltpu.VMEM((bk * rows, bm), jnp.float32),
                        pltpu.VMEM((bk * rows, bn), jnp.float32),
                        pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
    )(at, bp, ut, vt)
    return out[:M, :N]


def amr_matmul_int8(a: jnp.ndarray, b: jnp.ndarray, u: jnp.ndarray, v: jnp.ndarray,
                    *, bm: int | None = None, bn: int | None = None,
                    bk: int | None = None,
                    interpret: bool | None = None) -> jnp.ndarray:
    """a (M,K) int8, b (K,N) int8, u/v (256,r) f32 -> (M,N) f32 approx products.

    Tiles left ``None`` come from ``pick_tiles``.  ``interpret=None``
    resolves via pallas_config (env override / backend autodetect) BEFORE
    the jitted inner function, so the jit cache is always keyed on a
    concrete bool."""
    t = pick_tiles(a.shape[0], b.shape[1], a.shape[1], variant="lowrank",
                   bm=bm, bn=bn, bk=bk)
    return _amr_matmul_int8_jit(a, b, u, v, bm=t.bm, bn=t.bn, bk=t.bk,
                                interpret=resolve_interpret(interpret))


def check_lut_range(max_abs: int) -> None:
    """The byte planes are bf16-exact only while every |entry| < 2**15."""
    if max_abs >= _LUT_PLANE_LIMIT:
        raise ValueError(
            f"full-LUT kernel splits the table into two bf16 byte planes, "
            f"exact only for |entry| < {_LUT_PLANE_LIMIT}; got {max_abs}")


def _lut_planes(table):
    """(256, 256) int32 LUT[a, b] -> (512, 256) bf16 [high byte; low byte]
    planes, table = 256 * high + low (see ``check_lut_range``)."""
    table = table.astype(jnp.int32)
    planes = jnp.concatenate([table >> 8, table & 255], axis=0)
    return planes.astype(jnp.bfloat16)


def _lut_block(ia_ref, ib_ref, lut_ref, acc_ref):
    """acc += sum over the block's K rows of LUT[a_k, b_k] — bit-exact.

    Per row k: ``planes @ one_hot(b_k)`` gathers the table columns of b_k
    (512, bn), and ``one_hot(a_k)^T @ those`` picks each row's entry.  Each
    dot has one nonzero term per output, and the f32 byte-plane sums stay
    below 2**24 for any K < 2**16, so both are exact; they recombine in
    int32."""
    planes = lut_ref[...]                           # (512, 256) bf16

    def body(k, carry):
        hi, lo = carry
        cols = jnp.dot(planes, _one_hot_row(ib_ref, k),
                       preferred_element_type=jnp.float32).astype(jnp.bfloat16)
        oh_a = _one_hot_row(ia_ref, k)
        hi = hi + jax.lax.dot_general(oh_a, cols[:_VALUES], _LEADING,
                                      preferred_element_type=jnp.float32)
        lo = lo + jax.lax.dot_general(oh_a, cols[_VALUES:], _LEADING,
                                      preferred_element_type=jnp.float32)
        return hi, lo

    zero = jnp.zeros(acc_ref.shape, jnp.float32)
    hi, lo = jax.lax.fori_loop(0, ia_ref.shape[0], body, (zero, zero))
    acc_ref[...] += hi.astype(jnp.int32) * 256 + lo.astype(jnp.int32)


def _lut_scratch(bm: int, bn: int, bk: int):
    return [pltpu.VMEM((bk, bm), jnp.int32), pltpu.VMEM((bk, bn), jnp.int32),
            pltpu.VMEM((bm, bn), jnp.int32)]


def _amr_matmul_lut_kernel(at_ref, b_ref, lut_ref, out_ref,
                           ia_ref, ib_ref, acc_ref, *, n_k: int):
    """Full-table variant: one (bm, bn) int32 block, K innermost."""
    k_idx = pl.program_id(2)

    @pl.when(k_idx == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    _load_indices(ia_ref, ib_ref, at_ref[...], b_ref[...])
    _lut_block(ia_ref, ib_ref, lut_ref, acc_ref)

    @pl.when(k_idx == n_k - 1)
    def _store():
        out_ref[...] = acc_ref[...]


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def _amr_matmul_int8_lut_jit(a, b, table, *, bm, bn, bk, interpret):
    M, K = a.shape
    N = b.shape[1]
    assert K % bk == 0, (K, bk)
    at, bp = _pad_operands(a, b, bm, bn)
    planes = _lut_planes(table)
    n_k = K // bk
    grid = (at.shape[1] // bm, bp.shape[1] // bn, n_k)
    out = pl.pallas_call(
        functools.partial(_amr_matmul_lut_kernel, n_k=n_k),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bk, bm), lambda i, j, k: (k, i)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
            pl.BlockSpec(planes.shape, lambda i, j, k: (0, 0)),  # whole LUT
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((at.shape[1], bp.shape[1]), jnp.int32),
        scratch_shapes=_lut_scratch(bm, bn, bk),
        interpret=interpret,
    )(at, bp, planes)
    return out[:M, :N]


def _amr_matmul_lut_grouped_kernel(at_ref, b_ref, lut_ref, out_ref,
                                   ia_ref, ib_ref, acc_ref, *, n_k: int):
    """Grouped full-LUT variant: independent (M, K) @ (K, N) per group.

    Grid ``(G, M/bm, N/bn, K/bk)`` — one leading grid axis per group (the
    MoE expert buffers / flattened attention batch·head groups), K still
    innermost so the int32 accumulator scratch carries across the K sweep.
    """
    k_idx = pl.program_id(3)

    @pl.when(k_idx == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    _load_indices(ia_ref, ib_ref, at_ref[0], b_ref[0])
    _lut_block(ia_ref, ib_ref, lut_ref, acc_ref)

    @pl.when(k_idx == n_k - 1)
    def _store():
        out_ref[0] = acc_ref[...]


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def _amr_matmul_int8_lut_grouped_jit(a, b, table, *, bm, bn, bk, interpret):
    G, M, K = a.shape
    N = b.shape[2]
    assert K % bk == 0, (K, bk)
    at, bp = _pad_operands(a, b, bm, bn)
    planes = _lut_planes(table)
    n_k = K // bk
    grid = (G, at.shape[2] // bm, bp.shape[2] // bn, n_k)
    out = pl.pallas_call(
        functools.partial(_amr_matmul_lut_grouped_kernel, n_k=n_k),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bk, bm), lambda g, i, j, k: (g, k, i)),
            pl.BlockSpec((1, bk, bn), lambda g, i, j, k: (g, k, j)),
            pl.BlockSpec(planes.shape, lambda g, i, j, k: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, bm, bn), lambda g, i, j, k: (g, i, j)),
        out_shape=jax.ShapeDtypeStruct((G, at.shape[2], bp.shape[2]), jnp.int32),
        scratch_shapes=_lut_scratch(bm, bn, bk),
        interpret=interpret,
    )(at, bp, planes)
    return out[:, :M, :N]


def amr_matmul_int8_lut(a: jnp.ndarray, b: jnp.ndarray, table: jnp.ndarray,
                        *, bm: int | None = None, bn: int | None = None,
                        bk: int | None = None,
                        interpret: bool | None = None) -> jnp.ndarray:
    """Bit-exact variant: a (M,K) int8, b (K,N) int8, table (256,256) int32
    -> (M,N) int32 — int32 accumulation of true AMR products (exact for
    K * 2^16 < 2^31, i.e. any realistic K).  Tiles as ``amr_matmul_int8``."""
    check_lut_range(int(np.abs(np.asarray(table)).max()))
    t = pick_tiles(a.shape[0], b.shape[1], a.shape[1], variant="lut",
                   bm=bm, bn=bn, bk=bk)
    return _amr_matmul_int8_lut_jit(a, b, table, bm=t.bm, bn=t.bn, bk=t.bk,
                                    interpret=resolve_interpret(interpret))


def amr_matmul_int8_lut_grouped(a: jnp.ndarray, b: jnp.ndarray,
                                table: jnp.ndarray, *, bm: int | None = None,
                                bn: int | None = None, bk: int | None = None,
                                interpret: bool | None = None) -> jnp.ndarray:
    """Grouped bit-exact variant: a (G,M,K) int8, b (G,K,N) int8 -> (G,M,N)
    int32, one independent LUT matmul per group."""
    check_lut_range(int(np.abs(np.asarray(table)).max()))
    t = pick_tiles(a.shape[1], b.shape[2], a.shape[2], variant="lut_grouped",
                   bm=bm, bn=bn, bk=bk)
    return _amr_matmul_int8_lut_grouped_jit(
        a, b, table, bm=t.bm, bn=t.bn, bk=t.bk,
        interpret=resolve_interpret(interpret))
