"""Pure-jnp/numpy oracles for the AMR matmul kernel variants.

``ref_lowrank_int8`` mirrors the low-rank kernel's math densely
(A@B + sum_j U_j[A] @ V_j[B]) — agreement with the kernel is to f32
accumulation order.  ``ref_bitexact_int8`` is the ground truth for
BOTH the full-LUT kernel (which must match it bit-for-bit, int64 exact)
and the rank-256 low-rank kernel (which matches to fp32 rounding): it
accumulates per-element products straight from the engine-built 256x256
table, i.e. it *is* the schedule engine's exact replay lifted to a matmul.
The gap between a rank-r kernel and this oracle is bounded by
K * sigma_{r+1} per element (core/lut.py)."""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from repro.core import lut as lut_lib


def ref_lowrank_int8(a: jnp.ndarray, b: jnp.ndarray, u: jnp.ndarray, v: jnp.ndarray):
    """Same math as the kernel, dense jnp: A@B + sum_j U_j[A] @ V_j[B].

    One (M, K) @ (K, N) matmul per factor lane: a (K, N, r) gather would
    put r in the minor dim, which TPU layouts pad to 128 lanes."""
    ia = a.astype(jnp.int32) + 128
    ib = b.astype(jnp.int32) + 128
    out = a.astype(jnp.float32) @ b.astype(jnp.float32)
    for j in range(u.shape[1]):
        out = out + u[:, j][ia] @ v[:, j][ib]
    return out


def ref_bitexact_int8(a: np.ndarray, b: np.ndarray, border: int) -> np.ndarray:
    """Ground truth: per-element products from the bit-accurate LUT."""
    table = lut_lib.build_int8_lut(border).astype(np.int64)
    M, K = a.shape
    N = b.shape[1]
    out = np.zeros((M, N), np.int64)
    for k in range(K):
        out += table[np.asarray(a[:, k], np.int64) + 128][:, np.asarray(b[k], np.int64) + 128]
    return out
