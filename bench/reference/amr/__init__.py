"""The AMR-MUL product table, built by the benchmark itself.

The modules beside this file are frozen copies of the program's bit-level
multiplier model (``repro.core``: ``mrsd``, ``ppgen``, ``cells``,
``reduction`` and ``dse/column``), so that the reference's products do not
move when the program's do.  Only the imports were changed.
"""
from __future__ import annotations

from functools import lru_cache

import numpy as np

from . import mrsd, ppgen, reduction

N_DIGITS = 2  # int8 operands need two radix-16 MRSD digits


@lru_cache(maxsize=8)
def product_table(border: int) -> np.ndarray:
    """(256, 256) int64: ``T[a + 128, b + 128]`` is AMR-MUL(a, b) for int8 a, b."""
    vals = np.arange(-128, 128, dtype=np.int64)
    a, b = np.repeat(vals, 256), np.tile(vals, 256)
    xb = ppgen.flatten_operand_bits(mrsd.encode(a, N_DIGITS))
    yb = ppgen.flatten_operand_bits(mrsd.encode(b, N_DIGITS))
    lo, hi = reduction.evaluate_split(reduction.get_schedule(N_DIGITS, border), xb, yb)
    return (lo + hi * (1 << 32)).reshape(256, 256)


@lru_cache(maxsize=8)
def error_factors(border: int, rank: int) -> tuple[np.ndarray, np.ndarray]:
    """Rank-``rank`` SVD factors (256, rank) f32 of the error table
    ``T - a*b``: the approximation that ``amr_lowrank`` and ``amr_kernel``
    at that rank compute."""
    vals = np.arange(-128, 128, dtype=np.float64)
    err = product_table(border).astype(np.float64) - np.outer(vals, vals)
    u, s, vt = np.linalg.svd(err, full_matrices=False)
    sr = np.sqrt(s[:rank])
    return (u[:, :rank] * sr).astype(np.float32), (vt[:rank].T * sr).astype(np.float32)
