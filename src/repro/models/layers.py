"""Common layers: norms, rotary embeddings, MLPs — pure-JAX, param-dict style.

Every matmul routes through the numerics policy (repro.numerics), which is
how the paper's approximate multiplier enters the model. Params are nested
dicts of jnp arrays; init functions mirror apply functions 1:1.
"""
from __future__ import annotations

import contextlib

import jax
import jax.numpy as jnp

from repro.numerics import AMRNumerics, NumericsPolicy, resolve_numerics
from repro.numerics.approx_matmul import approx_matmul
from repro.parallel.constraints import pin

Numerics = AMRNumerics | NumericsPolicy | None


def seam_scope(site: str | None):
    """``jax.named_scope("seam." + site)``: the call site's label in the op
    metadata of everything it lowers to, in every numerics mode (compile-time
    metadata only; no value changes).  No label, no scope."""
    return jax.named_scope("seam." + site) if site else contextlib.nullcontext()


def dense(x: jnp.ndarray, w: jnp.ndarray, numerics: Numerics = None,
          site: str | None = None) -> jnp.ndarray:
    """x: (..., K) @ w: (K, N) under the numerics policy.

    ``site`` is the static call-site label (e.g. ``"mlp.w_gate"``) that,
    with the ambient step/layer scope (repro.numerics.context), decorrelates
    the amr_noise PRNG stream — without it every projection in every layer
    would draw the identical noise tensor.

    ``numerics`` may also be a site-resolved ``NumericsPolicy``; it resolves
    here against ``site`` and the ambient static layer coordinate, so each
    call site of each (statically indexed) layer can run a different
    multiplier design (numerics/policy.py).
    """
    numerics = resolve_numerics(numerics, site)
    with seam_scope(site):
        if numerics is None or numerics.is_exact():
            return jnp.matmul(x, w)
        shape = x.shape
        out = approx_matmul(x.reshape(-1, shape[-1]), w, numerics, site=site)
        return out.reshape(*shape[:-1], w.shape[-1]).astype(x.dtype)


def rms_norm(x: jnp.ndarray, scale: jnp.ndarray, eps: float = 1e-6) -> jnp.ndarray:
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    out = x * jax.lax.rsqrt(var + eps) * (1.0 + scale.astype(jnp.float32))
    return out.astype(dtype)


def init_rms_norm(d: int) -> jnp.ndarray:
    return jnp.zeros((d,), dtype=jnp.float32)


# ----------------------------------------------------------------- rotary
def rope_freqs(head_dim: int, theta: float) -> jnp.ndarray:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float) -> jnp.ndarray:
    """x: (B, S, H, D); positions: (B, S) int32."""
    freqs = rope_freqs(x.shape[-1], theta)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (B, S, D/2)
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# -------------------------------------------------------------------- MLP
def init_mlp(key: jax.Array, d_model: int, d_ff: int, act: str, dtype) -> dict:
    k1, k2, k3 = jax.random.split(key, 3)
    s_in = d_model ** -0.5
    s_ff = d_ff ** -0.5
    return {
        "w_gate": (jax.random.normal(k1, (d_model, d_ff)) * s_in).astype(dtype),
        "w_up": (jax.random.normal(k2, (d_model, d_ff)) * s_in).astype(dtype),
        "w_down": (jax.random.normal(k3, (d_ff, d_model)) * s_ff).astype(dtype),
    }


def mlp(params: dict, x: jnp.ndarray, act: str, numerics: Numerics) -> jnp.ndarray:
    g = pin(dense(x, params["w_gate"], numerics, site="mlp.w_gate"), "batch", None, "tp")
    u = pin(dense(x, params["w_up"], numerics, site="mlp.w_up"), "batch", None, "tp")
    if act == "geglu":
        h = jax.nn.gelu(g) * u
    elif act == "swiglu":
        h = jax.nn.silu(g) * u
    elif act == "gelu":
        h = jax.nn.gelu(g + u)  # degenerate non-gated form keeps param tree uniform
    else:
        raise ValueError(act)
    return pin(dense(h, params["w_down"], numerics, site="mlp.w_down"), "batch", None, None)


# -------------------------------------------------------------- embeddings
def init_embedding(key: jax.Array, vocab: int, d_model: int, dtype) -> jnp.ndarray:
    return (jax.random.normal(key, (vocab, d_model)) * (d_model ** -0.5)).astype(dtype)


def embed(table: jnp.ndarray, tokens: jnp.ndarray) -> jnp.ndarray:
    return jnp.take(table, tokens, axis=0)


def unembed(x: jnp.ndarray, table: jnp.ndarray, numerics: AMRNumerics | None = None) -> jnp.ndarray:
    """Logits; tied embeddings use table.T. Kept exact by default: the LM
    head dominates vocab-scaled error, and the paper's technique targets
    inner matmuls (DESIGN.md §Arch-applicability)."""
    return jnp.matmul(x, table.T.astype(x.dtype))
