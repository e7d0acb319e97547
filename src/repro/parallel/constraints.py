"""Logical activation sharding constraints (mesh-agnostic ``pin``).

``pin(x, "batch", None, "tp")`` applies jax.lax.with_sharding_constraint
with the ambient mesh's axes: "batch" -> ("pod","data") (whichever exist),
"tp" -> "model". Every dim is divisibility-guarded; with no ambient mesh
(unit tests, single-device examples) it is a no-op.

Why explicit pins: GSPMD propagation through reshape(head-split) + rope +
GQA einsums can drop the batch sharding entirely when head counts don't
divide the model axis (observed: gemma-2b MQA attention replicated to
global batch). Pinning activations at module boundaries keeps the
partitioner honest — this is what production JAX LM stacks do.
"""
from __future__ import annotations

import math

import jax
from jax.sharding import PartitionSpec as P


def _ambient_axes() -> dict[str, int]:
    m = jax.sharding.get_abstract_mesh()
    if m is None or not m.axis_names:
        return {}
    return dict(zip(m.axis_names, m.axis_sizes))


def ambient_axis_size(name: str) -> int:
    """Size of a mesh axis in the ambient mesh (1 when absent/no mesh)."""
    return _ambient_axes().get(name, 1)


def pin(x, *dims):
    """dims entries: None | 'batch' | 'tp' (one per array dim)."""
    axes = _ambient_axes()
    if not axes:
        return x
    dp = tuple(a for a in ("pod", "data") if a in axes)
    dp_size = math.prod(axes[a] for a in dp) if dp else 1
    tp_size = axes.get("model", 1)
    spec = []
    for d, size in zip(dims, x.shape):
        if d == "batch" and dp and size % dp_size == 0:
            spec.append(dp if len(dp) > 1 else dp[0])
        elif d == "tp" and "model" in axes and size % tp_size == 0:
            spec.append("model")
        else:
            spec.append(None)
    return jax.lax.with_sharding_constraint(x, P(*spec))
