"""Prefill -> decode cache handoff: one-shot prefill must agree with both
the full forward pass and subsequent decode steps, for every mixer family."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_reduced_config
from repro.models import decode_step, forward, init_params
from repro.models.model import _encoder_forward, prefill_with_cache

# Single-device consistency checks (no meshes).

FAMILIES = ["gemma-2b", "mamba2-370m", "zamba2-1.2b", "gemma3-1b",
            "whisper-small", "dbrx-132b"]


@pytest.mark.parametrize("arch", FAMILIES)
def test_handoff_matches_forward(arch):
    cfg = get_reduced_config(arch)
    params = init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    S = 16
    toks = jnp.asarray(rng.integers(0, cfg.vocab, (2, S)), jnp.int32)
    extra = None
    enc_out = None
    if cfg.encoder_layers:
        extra = jnp.asarray(rng.normal(size=(2, cfg.encoder_frames, cfg.d_model)),
                            jnp.dtype(cfg.dtype))
        enc_out = _encoder_forward(cfg, params, extra, cfg.numerics)

    ref, _ = forward(cfg, params, toks, extra)
    # prefill S-1 tokens, then decode token S-1: logits must match forward's
    logits_pre, cache = prefill_with_cache(cfg, params, toks[:, : S - 1],
                                           capacity=S, extra_embeddings=extra)
    np.testing.assert_allclose(
        np.asarray(logits_pre[:, 0], np.float32),
        np.asarray(ref[:, S - 2], np.float32), rtol=0.15, atol=0.15)
    lg, cache = decode_step(cfg, params, toks[:, S - 1 : S], cache, enc_out)
    np.testing.assert_allclose(
        np.asarray(lg[:, 0], np.float32),
        np.asarray(ref[:, -1], np.float32), rtol=0.15, atol=0.15)


def test_swa_ring_handoff_long_prompt():
    """Sliding-window cache handoff with prompt longer than the window."""
    cfg = get_reduced_config("gemma3-1b")  # window 8 in reduced config
    params = init_params(cfg, jax.random.PRNGKey(1))
    rng = np.random.default_rng(1)
    S = 24  # > window
    toks = jnp.asarray(rng.integers(0, cfg.vocab, (1, S)), jnp.int32)
    ref, _ = forward(cfg, params, toks)
    _, cache = prefill_with_cache(cfg, params, toks[:, : S - 1], capacity=S)
    lg, _ = decode_step(cfg, params, toks[:, S - 1 : S], cache)
    np.testing.assert_allclose(
        np.asarray(lg[:, 0], np.float32), np.asarray(ref[:, -1], np.float32),
        rtol=0.15, atol=0.15)
