"""Public injection-replay op: exact AMR integer matmul via the Pallas kernel.

``inject_replay_matmul`` mirrors ``numerics.injection.injected_matmul_int``
(the XLA form of the same outer-product replay) — identical contract,
bit-identical int32 output — but runs the stage loop inside a Pallas
kernel whose tiles come from the shared autotune table
(``amr_matmul/tiling.py``, variant ``inject_replay``).  Dispatch between
the two lives in ``numerics.approx_matmul.matmul_amr_inject`` via the
``AMRNumerics.inject_impl`` policy field, resolved by
``kernels/pallas_config.resolve_inject_impl`` (XLA unless asked for).
The TPU compiler refuses this kernel (``pallas_config.REFUSED_ON_TPU``):
requested compiled, it raises ``KernelRefusedError``.

The n dimension is blocked in WORD units: 32 output columns share one
uint32 lane word, so an explicit ``bn`` override must be a multiple of 32
(as well as dividing the padded column count).  Autotuned tiles pad: rows
up to a multiple of ``bm`` and words up to a multiple of the word tile.
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from repro.core.engine import _LANE_BITS, CompiledInjector
from repro.kernels.amr_matmul.tiling import pick_tiles
from repro.kernels.pallas_config import check_compilable, resolve_interpret

from .kernel import _inject_replay_jit


def inject_replay_matmul(inj: CompiledInjector, ia, ib, *,
                         bm: int | None = None, bn: int | None = None,
                         bk: int | None = None,
                         interpret: bool | None = None,
                         packed_ib=None, schedule: str | None = None):
    """Exact integer AMR matmul on the Pallas replay kernel.

    ``ia``: (..., M, K) and ``ib``: (K, N) int32 operand indices
    (value + 128) -> (..., M, N) int32, bit-identical to
    ``injection.injected_matmul_int`` and the ``amr_lut`` gather oracle.
    Weight packing goes through the shared ``packed_weights`` cache (packed
    once per matmul in-trace; cached across calls for concrete weights) —
    or is bypassed entirely by a precomputed ``packed_ib``.  Raises at
    trace time when K could saturate the int32 accumulator, and
    ``KernelRefusedError`` when asked to run compiled (as on TPU).
    """
    from repro.numerics.injection import (check_accumulation_bound,
                                          packed_weights)

    interpret = resolve_interpret(interpret)
    check_compilable("inject_replay", interpret)
    *lead, m, k = ia.shape
    n = ib.shape[-1]
    check_accumulation_bound(inj, k, schedule=schedule)
    if bn is not None and bn % _LANE_BITS:
        # word-alignment first: clearer than pick_tiles' divisor error
        # against the padded width for a bn that divides the user's N
        raise ValueError(
            f"inject_replay blocks n in 32-column lane words: bn={bn} must "
            f"be a multiple of {_LANE_BITS} (and divide N={n} padded up to "
            f"whole words)")
    rows = int(np.prod(lead, dtype=np.int64)) * m if lead else m
    yw = packed_ib if packed_ib is not None else packed_weights(inj, ib)
    n_words = yw.shape[-1]
    npad = n_words * _LANE_BITS
    # note: bm tiles the FLATTENED row count (lead batch dims * M), bn the
    # padded column count — pick_tiles errors report those quantities
    tiles = pick_tiles(rows, npad, k, variant="inject_replay",
                       bm=bm, bn=bn, bk=bk)
    bnw = max(1, tiles.bn // _LANE_BITS)
    # pad rows with index 128 (operand value 0) and words with zero words;
    # both land in output rows / columns that are sliced off below
    ia2 = jnp.pad(ia.reshape(rows, k), [(0, -rows % tiles.bm), (0, 0)],
                  constant_values=128)
    yw = jnp.pad(yw, [(0, 0), (0, 0), (0, -n_words % bnw)])
    out = _inject_replay_jit(ia2, yw, inj._value_masks,
                             lowered=inj.lowered, bm=tiles.bm, bnw=bnw,
                             bk=tiles.bk, interpret=interpret)
    return out[:rows, :n].reshape(*lead, m, n)
