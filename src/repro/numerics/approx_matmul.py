"""Approximate matmul modes — the AMR-MUL as a NN numerics policy.

Modes (DESIGN.md §2/§3; docs/numerics.md has the full dispatch table):
  exact        — jnp.einsum in the requested dtype (baseline).
  amr_lut      — bit-exact AMR-MUL semantics per scalar product: int8
                 quantize, per-element gather from the 256x256 LUT,
                 accumulate in int32. Paper-faithful; VPU-bound on TPU.
                 The ORACLE the other integer paths are asserted against
                 (small shapes only: it materializes (.., M, K, N)).
  amr_inject   — on-device error injection: the SAME bit-exact products as
                 amr_lut, computed by replaying the reduction circuit
                 (engine.CompiledInjector) on the actual quantized operands
                 inside the jit trace — works for ANY reduction.Schedule,
                 including DSE candidate assignments with no materialized
                 LUT (numerics.schedule_ref), and trains through an STE
                 backward. K-chunked accumulation keeps memory flat.
  amr_lowrank  — beyond-paper MXU form: C = (A@B + U(A)@V(B)) * scales,
                 rank-r SVD factors of the LUT error table. rank=256 is
                 bit-equivalent to amr_lut up to fp32 accumulation.
  amr_noise    — training-scale surrogate: exact matmul + Gaussian error
                 with moments matched to the measured AMR-MUL error table
                 (paper Fig. 6 shows the relative error is ~Gaussian, mu~0).
                 Noise decorrelates across call sites / layers / steps via
                 numerics.context (site labels + the ambient scope).
  amr_kernel   — the production Pallas kernel path (kernels/amr_matmul):
                 low-rank MXU kernel at numerics.rank, or the bit-exact
                 full-table LUT kernel when rank == 0. Compiled on TPU
                 (both compile for v5e: tests/test_tpu_compile.py),
                 interpreter mode on CPU/GPU (REPRO_PALLAS_INTERPRET
                 overrides; kernels/pallas_config).

All functions take A: (..., M, K) and B: (K, N) **or** a batched
B: (..., K, N) whose leading dims broadcast against A's — the weight-matmul
form dense layers consume, and the activation×activation form attention
scores (QK^T), attention-value contraction (PV), the MoE expert grouped
matmul and the SSD scan readout consume.  Quantization is always per-row
of A (axis=-1) and per-column of B (axis=-2 — identical to axis=0 for the
2-D weight form), so a batched call is bit-identical to stacking the
per-group un-batched calls.  jit/pjit-safe; the LUT and factors are
closed-over constants (baked into the executable), pulled from
core/lut.py's process-level caches — never rebuilt per call site.

Dispatch goes through the mode REGISTRY (numerics/registry.py): each
``matmul_amr_*`` registers ``(name, impl, required_params)`` at the bottom
of this module, ``AMRNumerics`` validates mode/params against the registry
at construction, and ``MODES`` is derived from it — external callers never
string-match mode names.
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial

import jax
import jax.numpy as jnp

from repro.core import lut as lut_lib
from . import registry
from .context import current_scope, noise_key
from .quant import quantize_int8, quantize_int8_ste

# A registered mode name — see numerics.registry.mode_names()
Mode = str


def __getattr__(name: str):
    # MODES stays importable (`from repro.numerics import MODES`) but is
    # derived from the registry, so late registrations are never stale.
    if name == "MODES":
        return registry.mode_names()
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


@dataclasses.dataclass(frozen=True)
class AMRNumerics:
    """Policy object threaded through models; hashable/static for jit.

    Construction validates ``mode`` and its required parameters against the
    mode registry — an invalid policy fails HERE with a message naming the
    valid modes, not deep inside a jit trace.
    """

    mode: Mode = "exact"
    border: int = 8          # approximate border column (paper Table I/II)
    rank: int = 8            # low-rank error rank (amr_lowrank/amr_kernel; 0 in
                             # amr_kernel mode selects the full-LUT variant)
    noise_seed: int = 0
    # amr_inject: handle of a registered custom schedule (DSE candidate);
    # None = the paper's default schedule for (n_digits=2, border).  Handles
    # come from numerics.injection.register_schedule (process-level registry
    # — the policy itself must stay hashable for jit).
    schedule_ref: str | None = None
    # amr_inject implementation: "xla" (outer-product replay in the trace),
    # "pallas" (kernels/inject_replay, refused by the TPU compiler), or
    # None = "xla" unless REPRO_INJECT_IMPL says otherwise
    # (kernels/pallas_config).
    inject_impl: str | None = None

    def __post_init__(self):
        registry.validate_policy(self)

    def is_exact(self) -> bool:
        return self.mode == _EXACT_SPEC.name


def _lut_constants(border: int):
    return lut_lib.table_array(border)


def _lowrank_constants(border: int, rank: int):
    return lut_lib.factor_arrays(border, rank)


def _noise_constants(border: int) -> tuple[float, float]:
    s = lut_lib.error_stats(border)
    return s["mean"], s["std"]


def matmul_exact(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    return jnp.matmul(a, b)


def _lut_matmul(a: jnp.ndarray, b: jnp.ndarray, table, max_abs: int,
                what: str, quantizer=quantize_int8) -> jnp.ndarray:
    """Shared LUT-gather matmul core: quantize, gather, int32-accumulate.

    ``quantizer`` selects the int8 front end: ``quantize_int8`` (hard int8,
    the amr_lut mode) or ``quantize_int8_ste`` (float-on-the-int8-grid —
    what the inject path uses; its audit oracle must quantize IDENTICALLY,
    bf16 inputs round differently through the two forms).

    Raises ``ValueError`` at trace time when the contraction length could
    saturate the int32 accumulator (K * max|product| >= 2**31) — the same
    guard ``injection.injected_matmul_int`` applies, so oracle and injected
    path reject exactly the same shapes instead of silently wrapping.
    """
    k = a.shape[-1]
    if k * max_abs >= 2**31:
        raise ValueError(
            f"{what} int32 accumulator can saturate: K={k} with "
            f"max|product|={max_abs} gives K*max|product| = {k * max_abs} "
            f">= 2**31 = {2**31}; keep K <= {(2**31 - 1) // max_abs} "
            f"(or split the contraction before the matmul)")
    qa, sa = quantizer(a, axis=-1)               # per-row scale (..., M, 1)
    qb, sb = quantizer(b, axis=-2)               # per-col scale (..., 1, N)
    ia = jax.lax.stop_gradient(qa).astype(jnp.int32) + 128  # (..., M, K)
    ib = jax.lax.stop_gradient(qb).astype(jnp.int32) + 128  # (..., K, N)
    # the index arrays broadcast their (possibly batched) leading dims
    prods = table[ia[..., :, :, None], ib[..., None, :, :]]  # (..., M, K, N)
    acc = prods.sum(axis=-2).astype(jnp.float32)
    return acc * sa * sb


def matmul_amr_lut(a: jnp.ndarray, b: jnp.ndarray, border: int) -> jnp.ndarray:
    """Bit-exact AMR-MUL matmul via LUT gather (oracle; small shapes only)."""
    return _lut_matmul(a, b, _lut_constants(border),
                       lut_lib.table_max_abs(border),
                       f"amr_lut(border={border})")


@partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def matmul_amr_lowrank(a: jnp.ndarray, b: jnp.ndarray, border: int, rank: int) -> jnp.ndarray:
    """MXU formulation of AMR-MUL semantics (§Perf cell P, iteration 3).

    Forward: one augmented contraction over (lane, k) with lanes
    [exact, err_1..err_r] on BOTH sides — the same sum the low-rank kernel
    (kernels/amr_matmul) runs per block — with bf16 error lanes (int8-grid
    exact lanes are bf16-exact) and f32 accumulation.  The lane axis leads
    each operand, (1+r, ..., M, K) and (1+r, ..., K, N): as a minor dim of
    width 1+r it would be padded to 128 lanes on TPU, 8x the bytes at
    r=16.  No f32 (K,N,r) correction tensor materialises/reshards.

    Backward (custom_vjp): plain full-precision matmul vjp — the explicit
    straight-through surrogate. Guarantees the (1+r)x flops are paid ONLY on
    the forward pass instead of hoping XLA DCEs dead augmented-lane grads.
    """
    return _lowrank_fwd(a, b, border, rank)[0]


def _lowrank_fwd(a, b, border, rank):
    u, v = _lowrank_constants(border, rank)
    qa, sa = quantize_int8_ste(a, axis=-1)
    qb, sb = quantize_int8_ste(b, axis=-2)
    ia = jax.lax.stop_gradient(qa).astype(jnp.int32) + 128
    ib = jax.lax.stop_gradient(qb).astype(jnp.int32) + 128
    a_aug = jnp.concatenate([qa[None].astype(jnp.bfloat16),
                             _lane_lookup(u, ia)])   # (1+r, ..., M, K)
    b_aug = jnp.concatenate([qb[None].astype(jnp.bfloat16),
                             _lane_lookup(v, ib)])   # (1+r, ..., K, N)
    spec = "j...mk,jkn->...mn" if b.ndim == 2 else "j...mk,j...kn->...mn"
    out = jnp.einsum(spec, a_aug, b_aug, preferred_element_type=jnp.float32)
    return out * sa * sb, (a, b)


def _lane_lookup(table, idx):
    """``table[idx]`` with the lane axis leading: (256, r), (...) -> (r, ...)
    bf16, as ``table^T @ one_hot(idx)`` on the MXU.  Exact (one nonzero
    term per output); TPU gathers of this size run far slower, and a
    (256, r) gather emits its r-wide slice as the minor dim."""
    flat = idx.reshape(1, -1)
    one_hot = (jax.lax.broadcasted_iota(jnp.int32, (table.shape[0], flat.shape[1]), 0)
               == flat).astype(jnp.bfloat16)
    out = jnp.matmul(table.T.astype(jnp.bfloat16), one_hot,
                     preferred_element_type=jnp.float32)
    return out.astype(jnp.bfloat16).reshape(table.shape[1], *idx.shape)


def _reduce_to_shape(g: jnp.ndarray, shape: tuple) -> jnp.ndarray:
    """Sum a gradient down to ``shape`` (undo matmul leading-dim broadcast)."""
    if g.shape == tuple(shape):
        return g
    extra = g.ndim - len(shape)
    if extra:
        g = g.sum(axis=tuple(range(extra)))
    keep = tuple(i for i, (gd, sd) in enumerate(zip(g.shape, shape))
                 if gd != sd)
    return g.sum(axis=keep, keepdims=True) if keep else g


def _lowrank_bwd(border, rank, res, g):
    a, b = res
    ga = jnp.matmul(g, jnp.swapaxes(b, -1, -2).astype(g.dtype))
    gb = jnp.matmul(jnp.swapaxes(a, -1, -2).astype(g.dtype), g) \
        if b.ndim > 2 else \
        jnp.matmul(a.reshape(-1, a.shape[-1]).T.astype(g.dtype),
                   g.reshape(-1, g.shape[-1]))
    return (_reduce_to_shape(ga, a.shape).astype(a.dtype),
            _reduce_to_shape(gb, b.shape).astype(b.dtype))


matmul_amr_lowrank.defvjp(_lowrank_fwd, _lowrank_bwd)


@partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def matmul_amr_kernel(a: jnp.ndarray, b: jnp.ndarray, border: int, rank: int) -> jnp.ndarray:
    """Pallas-kernel-backed AMR matmul (the servable hot path).

    Forward: kernels/amr_matmul — low-rank MXU kernel at ``rank``, or the
    bit-exact full-table gather kernel when ``rank == 0``; tiling and
    interpret mode resolve per backend (autotune table + autodetect).
    Backward: the same straight-through full-precision surrogate as
    amr_lowrank, so serving and training share one policy surface.
    """
    return _kernel_fwd(a, b, border, rank)[0]


def _kernel_fwd(a, b, border, rank):
    from repro.kernels.amr_matmul.ops import (amr_matmul,  # lazy: pkg cycle
                                              amr_matmul_grouped)

    if b.ndim == 2:
        a2 = a.reshape(-1, a.shape[-1])
        out = amr_matmul(a2, b, border=border, rank=max(rank, 1),
                         method="lut" if rank == 0 else "lowrank")
        return out.reshape(*a.shape[:-1], b.shape[-1]), (a, b)
    # activation×activation form: B carries leading batch dims.  rank == 0
    # runs the grouped full-LUT Pallas kernel (one grid axis per group —
    # the MoE grouped-matmul variant, docs/kernels.md); rank > 0 falls back
    # to the XLA augmented-K batched matmul, the same math the low-rank
    # kernel implements per block.
    a3, b3, lead = _broadcast_groups(a, b)
    if rank == 0:
        out = amr_matmul_grouped(a3, b3, border=border)
    else:
        out = _lowrank_fwd(a3, b3, border, rank)[0]
    return out.reshape(*lead, a.shape[-2], b.shape[-1]), (a, b)


def _broadcast_groups(a: jnp.ndarray, b: jnp.ndarray):
    """Broadcast A/B leading dims together and flatten them to one group
    axis: (..., M, K), (..., K, N) -> (G, M, K), (G, K, N), lead-shape."""
    lead = jnp.broadcast_shapes(a.shape[:-2], b.shape[:-2])
    a3 = jnp.broadcast_to(a, (*lead, *a.shape[-2:]))
    b3 = jnp.broadcast_to(b, (*lead, *b.shape[-2:]))
    g = math.prod(lead) if lead else 1
    return (a3.reshape(g, *a.shape[-2:]), b3.reshape(g, *b.shape[-2:]), lead)


matmul_amr_kernel.defvjp(_kernel_fwd, _lowrank_bwd)


@partial(jax.custom_vjp, nondiff_argnums=(2,))
def matmul_amr_inject(a: jnp.ndarray, b: jnp.ndarray, numerics: "AMRNumerics") -> jnp.ndarray:
    """On-device error injection: exact per-sample AMR products of the
    actual quantized operands, for ANY schedule (docs/numerics.md).

    Forward: quantize (STE), replay the reduction circuit on-device for the
    operand pairs of this matmul, rescale — bit-identical to the
    ``matmul_amr_lut`` oracle when the schedule matches, but never
    materializes a 256x256 LUT or the (.., M, K, N) product tensor, and
    accepts DSE candidate schedules via ``numerics.schedule_ref``.  The
    replay runs either as XLA ops in the surrounding trace
    (``injection.injected_matmul_int``, row+K-chunked) or as the Pallas
    injection-replay kernel (``kernels/inject_replay``), selected by
    ``numerics.inject_impl`` (None = xla, docs/kernels.md);
    both share the weight-side bit-pack and are bit-identical.

    Backward: the straight-through full-precision surrogate shared with
    amr_lowrank/amr_kernel, so a searched design point can be dropped
    straight into ``train_step`` and its real loss impact measured.
    """
    return _inject_fwd(a, b, numerics)[0]


def _inject_fwd(a, b, numerics):
    from repro.kernels.pallas_config import resolve_inject_impl  # lazy:
    from . import injection  # keeps module import light / breaks pkg cycle

    inj = injection.get_injector(numerics)
    qa, sa = quantize_int8_ste(a, axis=-1)
    qb, sb = quantize_int8_ste(b, axis=-2)
    ia = jax.lax.stop_gradient(qa).astype(jnp.int32) + 128  # (..., M, K)
    ib = jax.lax.stop_gradient(qb).astype(jnp.int32) + 128  # (..., K, N)
    handle = numerics.schedule_ref  # None = default design point (self-labels)
    if ib.ndim > 2:
        # activation×activation form: the B operand is traced and batched,
        # so there is no reusable weight pack — injection's grouped route
        # lane-packs each group on the fly inside the trace (same replay,
        # same int32-saturation guard; injection.injected_matmul_grouped).
        ia3, ib3, lead = _broadcast_groups(ia, ib)
        acc = injection.injected_matmul_grouped(
            inj, ia3, ib3, schedule=handle,
            impl=resolve_inject_impl(numerics.inject_impl))
        acc = acc.reshape(*lead, ia.shape[-2], ib.shape[-1])
    elif resolve_inject_impl(numerics.inject_impl) == "pallas":
        from repro.kernels.inject_replay import inject_replay_matmul

        acc = inject_replay_matmul(inj, ia, ib, schedule=handle)  # int32, exact
    else:
        acc = injection.injected_matmul_int(inj, ia, ib,
                                            schedule=handle)      # int32, exact
    return acc.astype(jnp.float32) * sa * sb, (a, b)


def _inject_bwd(numerics, res, g):
    return _lowrank_bwd(None, None, res, g)  # same STE surrogate


matmul_amr_inject.defvjp(_inject_fwd, _inject_bwd)


# Exported product tables of registered custom schedules, keyed by handle —
# same lifetime/keying as injection's per-handle injector cache.
_ORACLE_TABLES: dict[str, tuple] = {}


def _inject_oracle(a: jnp.ndarray, b: jnp.ndarray, numerics: "AMRNumerics") -> jnp.ndarray:
    """LUT-gather reference of the amr_inject products (the audit oracle).

    Gathers from a product table built INDEPENDENTLY of the on-device
    replay — ``core/lut``'s (2, border) table for the paper-default
    schedule, or ``dse.lut_from_schedule`` for a registered DSE candidate
    (``numerics.schedule_ref``) — so a zero audit diff proves the injector's
    circuit replay bit-identical to the tabulated multiplier, not merely
    self-consistent.  Quantizes with the SAME ``quantize_int8_ste`` front
    end as ``_inject_fwd``: on bf16 activations the hard-int8 form rounds
    in bf16 and would feed the table different operands.
    """
    if numerics.schedule_ref is None:
        table = _lut_constants(numerics.border)
        max_abs = lut_lib.table_max_abs(numerics.border)
        what = f"amr_inject(border={numerics.border}) oracle"
    else:
        table, max_abs = _oracle_table(numerics)
        what = f"amr_inject[{numerics.schedule_ref}] oracle"
    return _lut_matmul(a, b, table, max_abs, what, quantizer=quantize_int8_ste)


def _oracle_table(numerics):
    cached = _ORACLE_TABLES.get(numerics.schedule_ref)
    if cached is None:
        import numpy as np

        from repro.core.dse.export import lut_from_schedule  # lazy: pkg cycle
        from . import injection

        tab = lut_from_schedule(injection.resolve_schedule(numerics))
        with jax.ensure_compile_time_eval():
            cached = (jnp.asarray(tab, jnp.int32), int(np.abs(tab).max()))
        _ORACLE_TABLES[numerics.schedule_ref] = cached
    return cached


def _key_batch(key: jax.Array) -> int | None:
    """Leading batch size of a batched PRNG key array, or None for one key.

    ``noise_key`` returns a BATCH of keys when the ambient scope's step is a
    per-request position vector (slot-batched decode, serve/engine.py): one
    key per request, so each slot's noise stream depends only on ITS OWN
    decode position — batched decode draws the same noise a solo decode of
    that request would.
    """
    try:
        if jnp.issubdtype(key.dtype, jax.dtypes.prng_key):
            return key.shape[0] if key.ndim else None
    except (AttributeError, TypeError):
        pass
    return key.shape[0] if key.ndim > 1 else None  # raw uint32 keys: (B, 2)


def matmul_amr_noise(a: jnp.ndarray, b: jnp.ndarray, border: int, key: jax.Array) -> jnp.ndarray:
    """Surrogate: exact matmul + error noise with AMR-MUL-matched moments.

    Per-element product error has mean mu and std sigma (from the LUT);
    a K-length accumulation contributes N(K*mu, sqrt(K)*sigma) in the int8
    domain, rescaled by the quantization scales.

    ``key`` may be a batch of keys (one per leading-axis group of rows —
    per-request keys in slot-batched decode); each group then draws from
    its own stream, decorrelating noise per request.
    """
    mu, sigma = _noise_constants(border)
    qa, sa = quantize_int8_ste(a, axis=-1)
    qb, sb = quantize_int8_ste(b, axis=-2)
    k = a.shape[-1]
    exact = jnp.matmul(qa, qb)
    nb = _key_batch(key)
    if nb is None:
        draw = jax.random.normal(key, exact.shape)
    else:
        rows = math.prod(exact.shape[:-1])
        if rows % nb:
            raise ValueError(
                f"amr_noise got {nb} per-request keys but {rows} output rows "
                f"({exact.shape}); rows must divide evenly across requests")
        per = rows // nb
        draw = jax.vmap(lambda kk: jax.random.normal(kk, (per, exact.shape[-1])))(key)
        draw = draw.reshape(exact.shape)
    noise = mu * k + jnp.sqrt(float(k)) * sigma * draw
    return (exact + noise) * sa * sb


def approx_matmul(
    a: jnp.ndarray,
    b: jnp.ndarray,
    numerics: "AMRNumerics | None" = None,
    *,
    key: jax.Array | None = None,
    site: str | None = None,
) -> jnp.ndarray:
    """Dispatch a matmul under the given numerics policy (None = exact).

    ``numerics`` may be a single ``AMRNumerics`` or any ``NumericsPolicy``
    resolver (numerics/policy.py) — the latter resolves HERE, at trace
    time, against the static ``site`` label and the ambient scope's
    ``static_layer`` coordinate, so per-layer heterogeneous policies bake
    into the trace with zero run-time dispatch.

    ``site`` is a static call-site label (e.g. ``"mlp.w_gate"``); together
    with the ambient ``numerics_scope`` (step / layer) it decorrelates the
    amr_noise PRNG stream per call site, layer and training step — an
    explicit ``key`` overrides the derivation entirely.

    Dispatch is registry-driven: ``numerics.mode`` selects the impl
    registered in ``numerics.registry`` (modes were validated when the
    policy was constructed).

    When the ambient scope carries an AUDIT channel
    (``numerics_scope(audit=AuditTrace())``), a reference is evaluated
    alongside the impl and the per-site (and, when a layer coordinate is in
    scope, per-(site, layer)) diff recorded at run time via
    ``jax.debug.callback`` — read the trace after ``jax.effects_barrier()``.
    The default ``AuditTrace(compare="oracle")`` diffs against the mode's
    bit-exact ``oracle`` in product-grid steps (the conformance matrix's
    inject-vs-LUT bit-identity proof); ``AuditTrace(compare="exact")``
    diffs against the exact float matmul and accumulates error mass (the
    model-level policy search's sensitivity probe).
    """
    scope = current_scope()
    if numerics is not None and not isinstance(numerics, AMRNumerics):
        numerics = numerics.resolve(site, scope.static_layer)
    if scope.shape_probe is not None:
        # static trace-time record (works under jax.eval_shape): the
        # saturation proof in repro.analysis collects every site's K here
        scope.shape_probe.append({
            "site": site or "<unlabeled>",
            "k": int(a.shape[-1]),
            "mode": "exact" if numerics is None else numerics.mode,
            "schedule": getattr(numerics, "schedule_ref", None),
        })
    if numerics is None or numerics.is_exact():
        return matmul_exact(a, b)
    spec = registry.get_mode(numerics.mode)
    out = spec.impl(a, b, numerics, key=key, site=site)
    audit = scope.audit
    if audit is not None:
        diff = mass = None
        if getattr(audit, "compare", "oracle") == "exact":
            err = jnp.abs(out.astype(jnp.float32)
                          - matmul_exact(a, b).astype(jnp.float32))
            diff, mass = jnp.max(err), jnp.sum(err)
        elif spec.oracle is not None:
            ref = spec.oracle(a, b, numerics)
            diff = _grid_diff(out, ref, a, b)
            mass = diff
        if diff is not None:
            cb = partial(audit.record, site or "<unlabeled>")
            if scope.layer is not None:
                jax.debug.callback(
                    lambda d, m, layer: cb(d, layer=layer, mass=m),
                    diff, mass, scope.layer)
            else:
                jax.debug.callback(lambda d, m: cb(d, mass=m), diff, mass)
    return out


def _grid_diff(out, ref, a, b):
    """Max |out - ref| in integer-product-grid steps (audit metric).

    Audited modes share one quantization convention (per-row scales of A,
    per-column scales of B); impl and oracle outputs are both
    ``float(acc) * sa * sb`` with bitwise-identical scales, so any REAL
    semantic difference is >= 1 step on the int32 accumulator grid.
    Comparing after dividing the scales back out makes the audit immune to
    XLA compiling the two (mathematically identical) rescale chains with
    different FMA contraction — observed ~1-ulp float noise that is not a
    numerics difference.  Sub-quantum float noise rounds to 0.0; a genuine
    product mismatch records >= 1.0.  (The reconstruction is exact while
    |acc| < 2**24, i.e. for oracle-sized shapes — the regime the
    conformance matrix audits.)
    """
    quantum = quantize_int8(a, axis=-1)[1] * quantize_int8(b, axis=-2)[1]
    return jnp.max(jnp.abs(jnp.round(out / quantum) - jnp.round(ref / quantum)))


# --------------------------------------------------------------------------
# mode registration — canonical order; this block IS the MODES list
# --------------------------------------------------------------------------

def _require_border(nm) -> None:
    if not isinstance(nm.border, int) or nm.border < 0:
        raise ValueError(
            f"numerics mode {nm.mode!r} needs a non-negative integer border, "
            f"got {nm.border!r}")


def _validate_rank(nm, *, minimum: int) -> None:
    _require_border(nm)
    if not isinstance(nm.rank, int) or nm.rank < minimum:
        raise ValueError(
            f"numerics mode {nm.mode!r} needs an integer rank >= {minimum}, "
            f"got {nm.rank!r}")


def _validate_inject(nm) -> None:
    _require_border(nm)
    if nm.inject_impl is not None:
        from repro.kernels.pallas_config import INJECT_IMPLS  # lazy: pkg cycle

        if nm.inject_impl not in INJECT_IMPLS:
            raise ValueError(
                f"inject_impl must be one of {INJECT_IMPLS} (or None = "
                f"the default), got {nm.inject_impl!r}")
    if nm.schedule_ref is not None and not isinstance(nm.schedule_ref, str):
        raise ValueError(
            f"schedule_ref must be a registered-schedule handle (str) or "
            f"None, got {nm.schedule_ref!r}")


_EXACT_SPEC = registry.register_mode(
    "exact", lambda a, b, nm, *, key=None, site=None: matmul_exact(a, b),
    description="jnp.einsum in the requested dtype (baseline)", exact=True)

registry.register_mode(
    "amr_lut",
    lambda a, b, nm, *, key=None, site=None: matmul_amr_lut(a, b, nm.border),
    required_params=("border",), validate=_require_border,
    description="bit-exact LUT-gather oracle (small shapes)")

registry.register_mode(
    "amr_inject",
    lambda a, b, nm, *, key=None, site=None: matmul_amr_inject(a, b, nm),
    required_params=("border",), validate=_validate_inject,
    oracle=_inject_oracle,
    accepts_params=("schedule_ref", "inject_impl"),
    description="on-device exact error injection (any schedule)")

registry.register_mode(
    "amr_lowrank",
    lambda a, b, nm, *, key=None, site=None: matmul_amr_lowrank(
        a, b, nm.border, nm.rank),
    required_params=("border", "rank"),
    validate=partial(_validate_rank, minimum=1),
    defaults={"rank": 4},
    description="MXU low-rank error factorization")

registry.register_mode(
    "amr_noise",
    lambda a, b, nm, *, key=None, site=None: matmul_amr_noise(
        a, b, nm.border,
        key if key is not None else noise_key(nm.noise_seed, site)),
    required_params=("border", "noise_seed"), validate=_require_border,
    description="Gaussian surrogate with AMR-matched moments")

registry.register_mode(
    "amr_kernel",
    lambda a, b, nm, *, key=None, site=None: matmul_amr_kernel(
        a, b, nm.border, nm.rank),
    required_params=("border", "rank"),
    validate=partial(_validate_rank, minimum=0),
    defaults={"rank": 0},
    description="Pallas kernel path (rank 0 = full-LUT variant)")
