"""Shared tiling policy for the amr_matmul kernel variants.

One autotune table keyed on ``(backend, variant)`` serves the low-rank
and full-table kernels and the injection replay; callers pass
``bm/bn/bk=None`` to take the table entry.

Auto tiles PAD, they never clamp to a divisor: Mosaic accepts a block only
when its last two dims are multiples of the (8, 128) layout tile (32 rows
for int8 operands) or equal the whole array dim, and a divisor of an
awkward shape (decode's handful of rows, vocab 50280) is rarely aligned.
So ``bm``/``bn`` come out aligned, a problem smaller than the preferred
tile gets one block of its own size rounded up to the alignment, and the
op wrappers pad M and N up to whole blocks and slice the result.  K is
never padded (the AMR product of a zero pad need not be 0): ``bk`` is the
largest aligned divisor of K up to the preference, else all of K.

Entries encode where each variant is bound:

  * ``lowrank`` and ``lut`` look up table rows with one-hot MXU dots per
    K row, and the B-side lookups are redone for every row block, so both
    prefer tall (256-row) blocks; ``lowrank`` holds (bk*R, bm) and
    (bk*R, bn) f32 lookups (R = rank + 1, padded to 8) and so takes
    bk=64 to stay inside the 16 MiB of scoped VMEM Mosaic grants a kernel
    on v5e;
  * ``inject_replay`` (kernels/inject_replay) holds the whole bit-sliced
    wire state of a block in VMEM — ~n_wires uint32 words per (m, k) pair
    per 32 output columns — so its M/K tiles are much smaller than the
    LUT variants'; its n dimension is blocked in 32-column lane words.
    Mosaic refuses that kernel (pallas_config.REFUSED_ON_TPU), so its rows
    only shape the interpreter's grid.

Explicit ``bm/bn/bk`` overrides win over the table but must divide the
problem shape exactly, and are taken as given (no alignment): they serve
interpreter tests and benches that pin a grid.
"""
from __future__ import annotations

import dataclasses

from repro.kernels.pallas_config import backend_kind


@dataclasses.dataclass(frozen=True)
class TileConfig:
    bm: int
    bn: int
    bk: int


# (backend, variant) -> preferred tiles, aligned and padded at pick time.
# The gpu rows size VMEM-equivalent footprints for a future Triton
# variant — today GPU runs the interpreter (pallas_config) so they only
# shape the grid.
AUTOTUNE: dict[tuple[str, str], TileConfig] = {
    ("tpu", "lowrank"): TileConfig(256, 256, 64),
    ("tpu", "lut"): TileConfig(256, 256, 128),
    ("tpu", "lut_grouped"): TileConfig(256, 256, 128),
    ("tpu", "inject_replay"): TileConfig(32, 128, 8),
    ("gpu", "lowrank"): TileConfig(64, 128, 64),
    ("gpu", "lut"): TileConfig(64, 128, 32),
    ("gpu", "lut_grouped"): TileConfig(64, 128, 32),
    ("gpu", "inject_replay"): TileConfig(32, 128, 8),
    ("cpu", "lowrank"): TileConfig(128, 128, 128),
    ("cpu", "lut"): TileConfig(128, 128, 128),
    ("cpu", "lut_grouped"): TileConfig(128, 128, 128),
    ("cpu", "inject_replay"): TileConfig(64, 256, 16),
}

VARIANTS = ("lowrank", "lut", "lut_grouped", "inject_replay")

# Mosaic layout tile (sublane, lane) of an f32/int32 block, and the K
# alignment each variant's operand blocks need: int8 blocks tile 32 rows;
# the injection replay's int32 operands are interpreter-only.
SUBLANE, LANE = 8, 128
K_ALIGN = {"lowrank": 32, "lut": 32, "lut_grouped": 32, "inject_replay": 1}

# Fused-attention query-row tiles (kernels/attn_fused), keyed on the
# backend and a HEAD-DIM BUCKET: the kernel holds a whole (bm, T) score
# block plus the (T, D)/(T, P) operand panels in VMEM — larger head dims
# mean proportionally larger panels, so the preferred query tile shrinks
# as head_dim grows.  T/D/P are never tiled (full-T masked softmax).
ATTN_AUTOTUNE: dict[tuple[str, int], int] = {
    ("tpu", 64): 256, ("tpu", 128): 128, ("tpu", 256): 64,
    ("gpu", 64): 128, ("gpu", 128): 64, ("gpu", 256): 32,
    ("cpu", 64): 128, ("cpu", 128): 128, ("cpu", 256): 64,
}


def head_dim_bucket(head_dim: int) -> int:
    """Bucket a head dim to the next power of two in [64, 256] — the key
    granularity of ``ATTN_AUTOTUNE`` (sub-64 head dims share the 64 row)."""
    return min(max(64, 1 << max(head_dim - 1, 1).bit_length()), 256)


def pick_attn_tile(m: int, head_dim: int, *, backend: str | None = None,
                   bm: int | None = None) -> int:
    """Query-row tile for the fused-attention kernel: explicit ``bm`` wins
    (validated as a divisor of the row count), else the head-dim-bucketed
    autotune preference clamped to the largest divisor of ``m``."""
    pref = ATTN_AUTOTUNE[(backend or backend_kind(), head_dim_bucket(head_dim))]
    return _resolve_dim("bm", "m", m, bm, pref)


def _largest_divisor_leq(n: int, cap: int) -> int:
    for d in range(min(n, cap), 0, -1):
        if n % d == 0:
            return d
    return 1


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def _check_override(name: str, dim_name: str, size: int, override: int) -> int:
    if override < 1 or size % override:
        raise ValueError(
            f"{name}={override} does not tile the problem: {dim_name}={size} "
            f"is not a multiple (the grid would miss a partial tile); pass "
            f"None to take the aligned autotune entry (the op pads M and N)")
    return override


def _resolve_dim(name: str, dim_name: str, size: int, override: int | None,
                 pref: int) -> int:
    if override is None:
        return _largest_divisor_leq(size, pref)
    return _check_override(name, dim_name, size, override)


def _aligned_k(k: int, pref: int, align: int) -> int:
    """Largest divisor of k that is <= pref and a multiple of align, else k
    (a block spanning the whole dim is always accepted)."""
    for d in range(min(k, pref) // align * align, 0, -align):
        if k % d == 0:
            return d
    return k


def pick_tiles(
    m: int, n: int, k: int, *, variant: str = "lowrank", backend: str | None = None,
    bm: int | None = None, bn: int | None = None, bk: int | None = None,
) -> TileConfig:
    """Resolve block sizes: explicit overrides win (validated to divide the
    problem shape exactly), else the autotune entry for the (detected)
    backend, aligned as the module docstring says — the op pads M up to a
    multiple of ``bm`` and N up to a multiple of ``bn``; ``bk`` divides k."""
    if variant not in VARIANTS:
        raise ValueError(f"variant must be one of {VARIANTS}, got {variant!r}")
    pref = AUTOTUNE[(backend or backend_kind(), variant)]
    return TileConfig(
        bm=(_check_override("bm", "m", m, bm) if bm is not None
            else min(pref.bm, _round_up(m, SUBLANE))),
        bn=(_check_override("bn", "n", n, bn) if bn is not None
            else min(pref.bn, _round_up(n, LANE))),
        bk=(_check_override("bk", "k", k, bk) if bk is not None
            else _aligned_k(k, pref.bk, K_ALIGN[variant])),
    )
