"""Pallas execution-mode policy: backend autodetection + env override.

Kernels default to ``interpret=None`` and resolve it here at trace time:

  * ``REPRO_PALLAS_INTERPRET`` set to ``1/true/yes/on`` forces interpreter
    mode everywhere (debugging on real hardware), ``0/false/no/off`` forces
    compiled Mosaic/Triton lowering (e.g. to verify a CPU CI job fails fast
    rather than silently interpreting), ``auto``/unset defers to detection;
  * detection: compiled kernels on real TPU backends only. CPU has no
    compiled Pallas lowering, and the amr_matmul kernels use TPU memory
    spaces (``pltpu.VMEM`` scratch) that the Triton/GPU lowering does not
    support — so both fall back to interpreter mode until a Triton variant
    of the kernels lands.

``resolve_interpret`` is called by the NON-jitted public wrappers (see
kernels/amr_matmul/ops.py) so the env var is re-read on every call and a
changed override never collides with a stale jit cache entry keyed on
``interpret=None``.

Kernels the TPU compiler refuses are listed in ``REFUSED_ON_TPU`` with
the refusal; requesting one compiled (``interpret=False``, which is what
the autodetect gives on a TPU) raises ``KernelRefusedError`` naming it,
never a silent fallback to another path or to the interpreter.

The ``amr_inject`` numerics mode carries its own variant policy on top:
``AMRNumerics.inject_impl`` picks between the XLA outer-product replay
(``numerics/injection.py``) and the Pallas injection-replay kernel
(``kernels/inject_replay``); ``None`` means ``xla`` on every backend (see
``default_inject_impl``), with the ``REPRO_INJECT_IMPL`` env var
(``xla``/``pallas``/``auto``) overriding.  ``resolve_inject_impl`` runs at
trace time (the inject matmul only exists inside jitted steps), so a
changed env var takes effect on the next trace, not mid-executable.
"""
from __future__ import annotations

import os

ENV_VAR = "REPRO_PALLAS_INTERPRET"
INJECT_IMPL_ENV = "REPRO_INJECT_IMPL"
INJECT_IMPLS = ("xla", "pallas")
_TRUE = ("1", "true", "yes", "on")
_FALSE = ("0", "false", "no", "off")

# kernel -> why Mosaic (the TPU's Pallas compiler) refuses it, as compiled
# for a v5e at amr-paper-100m widths (tests/test_tpu_compile.py).
REFUSED_ON_TPU = {
    "inject_replay": (
        "with (8, 128)-aligned blocks Mosaic fails on the replay's dynamic "
        "jnp.take gathers over the 4-D wire tensors ('Shape mismatch in "
        "input, indices and output'); its preferred (32, 8) operand block "
        "is refused first, since block dims must be multiples of (8, 128)"),
}


class KernelRefusedError(RuntimeError):
    """A Pallas kernel the TPU compiler refuses was requested compiled."""


def check_compilable(kernel: str, interpret: bool) -> None:
    """Raise ``KernelRefusedError`` when ``kernel`` is requested compiled
    (``interpret=False``) but is listed in ``REFUSED_ON_TPU``."""
    if not interpret and kernel in REFUSED_ON_TPU:
        raise KernelRefusedError(
            f"Pallas kernel {kernel!r} does not compile for TPU: "
            f"{REFUSED_ON_TPU[kernel]}. Run it interpreted on CPU, or use "
            f"the XLA path (AMRNumerics(inject_impl='xla')) on TPU.")


def backend_kind() -> str:
    """Coarse platform for the tiling/interpret tables: 'tpu'|'gpu'|'cpu'."""
    import jax

    plat = jax.default_backend()
    if plat in ("gpu", "cuda", "rocm"):
        return "gpu"
    return plat if plat == "tpu" else "cpu"


def default_interpret() -> bool:
    """Env override if set, else compiled only where the kernels can lower
    (TPU); CPU and GPU run the interpreter (see module docstring)."""
    raw = os.environ.get(ENV_VAR, "").strip().lower()
    if raw in _TRUE:
        return True
    if raw in _FALSE:
        return False
    if raw and raw != "auto":
        raise ValueError(
            f"{ENV_VAR}={raw!r}: expected one of {_TRUE + _FALSE} or 'auto'")
    return backend_kind() != "tpu"


def resolve_interpret(interpret: bool | None) -> bool:
    """None -> autodetected/env-overridden mode; explicit bool wins."""
    return default_interpret() if interpret is None else interpret


def default_inject_impl() -> str:
    """Env override if set, else ``xla`` on every backend — a static
    choice: on TPU the Pallas replay kernel does not compile
    (``REFUSED_ON_TPU``), and elsewhere interpreter-mode Pallas is strictly
    slower than the XLA outer-product replay it mirrors.  Both are
    bit-identical wherever they run."""
    raw = os.environ.get(INJECT_IMPL_ENV, "").strip().lower()
    if raw in INJECT_IMPLS:
        return raw
    if raw and raw != "auto":
        raise ValueError(
            f"{INJECT_IMPL_ENV}={raw!r}: expected one of {INJECT_IMPLS} or 'auto'")
    return "xla"


def resolve_inject_impl(impl: str | None) -> str:
    """None -> autodetected/env-overridden impl; an explicit impl wins."""
    if impl is None:
        return default_inject_impl()
    if impl not in INJECT_IMPLS:
        raise ValueError(
            f"inject_impl must be one of {INJECT_IMPLS} (or None = auto), "
            f"got {impl!r}")
    return impl
