"""Public AMR-matmul op: float matmul under AMR-MUL numerics via Pallas.

Dispatches between the two kernel variants (kernel.py):

  * ``method="lowrank"`` — rank-r SVD factors of the error table, one
    augmented MXU contraction per block; per-product error <= sigma_{r+1}
    of the error table's spectrum (core/lut.py documents the bound);
  * ``method="lut"``     — full 256x256 int32 table lookup, bit-exact AMR
    products with int32 accumulation.

Both source their constants from ``core/lut.py``'s cached accessors — the
factors/table for a ``(border, rank, engine)`` point are built once per
process by the fused multi-border engine and converted to jnp once
(``lut.factor_arrays`` / ``lut.table_array``); no call site rebuilds them.

Tiling (``bm/bn/bk=None``) and execution mode (``interpret=None``) resolve
in THIS non-jitted wrapper — aligned tiles from the shared backend-keyed
autotune table (the jitted kernels pad M and N up to them), interpret from
the backend autodetect with the ``REPRO_PALLAS_INTERPRET`` env override —
then the jitted inner function is keyed on the concrete values.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.core import lut as lut_lib
from repro.kernels.pallas_config import resolve_interpret
from repro.numerics.quant import quantize_int8

from .kernel import (_amr_matmul_int8_jit, _amr_matmul_int8_lut_grouped_jit,
                     _amr_matmul_int8_lut_jit, check_lut_range)
from .tiling import pick_tiles


def lut_factors(
    border: int | None, rank: int, engine: str = "jax"
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Cached low-rank error factors for the kernel (u, v) as jnp arrays.

    Thin alias for ``core.lut.factor_arrays`` — the single process-level
    cache behind every kernel/numerics call site (the source 256x256 table
    comes from the fused multi-border engine build, provenance recorded on
    the underlying LowRankFactors)."""
    return lut_lib.factor_arrays(border, rank, engine)


@partial(jax.jit, static_argnames=("border", "rank", "method", "bm", "bn", "bk",
                                   "interpret"))
def _amr_matmul_jit(a, b, *, border, rank, method, bm, bn, bk, interpret):
    qa, sa = quantize_int8(a, axis=-1)
    qb, sb = quantize_int8(b, axis=0)
    if method == "lut":
        table = lut_lib.table_array(border)
        out = _amr_matmul_int8_lut_jit(qa, qb, table, bm=bm, bn=bn, bk=bk,
                                       interpret=interpret).astype(jnp.float32)
    elif method == "lowrank":
        u, v = lut_factors(border, rank)
        out = _amr_matmul_int8_jit(qa, qb, u, v, bm=bm, bn=bn, bk=bk,
                                   interpret=interpret)
    else:
        raise ValueError(f"method must be 'lowrank' or 'lut', got {method!r}")
    return out * sa * sb


def amr_matmul(a: jnp.ndarray, b: jnp.ndarray, *, border: int | None = 8,
               rank: int = 8, method: str = "lowrank",
               bm: int | None = None, bn: int | None = None, bk: int | None = None,
               interpret: bool | None = None) -> jnp.ndarray:
    """Float (M,K) @ (K,N) with AMR-MUL product semantics
    (quantize -> kernel variant -> rescale)."""
    if method not in ("lowrank", "lut"):
        raise ValueError(f"method must be 'lowrank' or 'lut', got {method!r}")
    if method == "lut":
        check_lut_range(lut_lib.table_max_abs(border))
    tiles = pick_tiles(a.shape[0], b.shape[1], a.shape[1],
                       variant=method, bm=bm, bn=bn, bk=bk)
    return _amr_matmul_jit(a, b, border=border, rank=rank, method=method,
                           bm=tiles.bm, bn=tiles.bn, bk=tiles.bk,
                           interpret=resolve_interpret(interpret))


@partial(jax.jit, static_argnames=("border", "bm", "bn", "bk", "interpret"))
def _amr_matmul_grouped_jit(a, b, *, border, bm, bn, bk, interpret):
    qa, sa = quantize_int8(a, axis=-1)               # per-row scale (G, M, 1)
    qb, sb = quantize_int8(b, axis=-2)               # per-col scale (G, 1, N)
    table = lut_lib.table_array(border)
    out = _amr_matmul_int8_lut_grouped_jit(qa, qb, table, bm=bm, bn=bn, bk=bk,
                                           interpret=interpret)
    return out.astype(jnp.float32) * sa * sb


def amr_matmul_grouped(a: jnp.ndarray, b: jnp.ndarray, *,
                       border: int | None = 8,
                       bm: int | None = None, bn: int | None = None,
                       bk: int | None = None,
                       interpret: bool | None = None) -> jnp.ndarray:
    """Grouped float (G, M, K) @ (G, K, N) under bit-exact full-LUT AMR
    numerics — the activation×activation kernel form (MoE expert capacity
    buffers, attention score/value contractions after the batch·head
    leading dims are flattened to one group axis).

    Quantization follows the seam convention (per-row of A, per-column of
    B), so the output is bit-identical to stacking per-group
    ``amr_matmul(..., method="lut")`` calls.  Tiles come from the shared
    autotune table (variant ``lut_grouped``); the kernel pads M and N.
    """
    if a.ndim != 3 or b.ndim != 3 or a.shape[0] != b.shape[0]:
        raise ValueError(
            f"amr_matmul_grouped takes (G, M, K) @ (G, K, N) with matching "
            f"group counts, got {a.shape} @ {b.shape}")
    check_lut_range(lut_lib.table_max_abs(border))
    tiles = pick_tiles(a.shape[1], b.shape[2], a.shape[2],
                       variant="lut_grouped", bm=bm, bn=bn, bk=bk)
    return _amr_matmul_grouped_jit(a, b, border=border, bm=tiles.bm,
                                   bn=tiles.bn, bk=tiles.bk,
                                   interpret=resolve_interpret(interpret))
