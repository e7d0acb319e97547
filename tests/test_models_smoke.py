"""Per-arch smoke tests: reduced config, one forward + one decode step on CPU.

Asserts output shapes and finiteness (no NaNs) for every assigned arch —
deliverable (f). The FULL configs are exercised abstractly by the dry-run.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_NAMES, get_reduced_config
from repro.models import decode_step, forward, init_cache, init_params

# Single-device smoke only — no meshes/shardings anywhere in these tests
# (pin() is a no-op without an ambient mesh).

ALL = ARCH_NAMES + ["amr-paper-100m"]


def _inputs(cfg, batch=2, seq=32):
    rng = np.random.default_rng(0)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab, (batch, seq)), jnp.int32)
    extra = None
    if cfg.vision_prefix:
        extra = jnp.asarray(rng.normal(size=(batch, cfg.vision_prefix, cfg.d_model)),
                            jnp.dtype(cfg.dtype))
    elif cfg.encoder_layers:
        extra = jnp.asarray(rng.normal(size=(batch, cfg.encoder_frames, cfg.d_model)),
                            jnp.dtype(cfg.dtype))
    return tokens, extra


@pytest.mark.parametrize("arch", ALL)
def test_forward_smoke(arch):
    cfg = get_reduced_config(arch)
    params = init_params(cfg, jax.random.PRNGKey(0))
    tokens, extra = _inputs(cfg)
    logits, aux = forward(cfg, params, tokens, extra)
    assert logits.shape == (2, 32, cfg.vocab)
    assert np.isfinite(np.asarray(logits, dtype=np.float32)).all()
    assert np.isfinite(float(aux))


@pytest.mark.parametrize("arch", ALL)
def test_train_step_smoke(arch):
    """One grad step: loss finite, grads finite and tree-matching params."""
    cfg = get_reduced_config(arch)
    params = init_params(cfg, jax.random.PRNGKey(0))
    tokens, extra = _inputs(cfg)

    def loss_fn(p):
        logits, aux = forward(cfg, p, tokens, extra)
        tgt = jnp.roll(tokens, -1, axis=1)
        ll = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        loss = -jnp.mean(jnp.take_along_axis(ll, tgt[..., None], axis=-1))
        return loss + 0.01 * aux

    loss, grads = jax.value_and_grad(loss_fn)(params)
    assert np.isfinite(float(loss))
    flat = jax.tree.leaves(grads)
    assert len(flat) == len(jax.tree.leaves(params))
    for g in flat:
        assert np.isfinite(np.asarray(g, dtype=np.float32)).all()


@pytest.mark.parametrize("arch", ALL)
def test_decode_smoke(arch):
    cfg = get_reduced_config(arch)
    params = init_params(cfg, jax.random.PRNGKey(0))
    cache = init_cache(cfg, batch=2, capacity=16)
    tok = jnp.zeros((2, 1), jnp.int32)
    enc = None
    if cfg.encoder_layers:
        enc = jnp.zeros((2, cfg.encoder_frames, cfg.d_model), jnp.dtype(cfg.dtype))
    logits, cache = decode_step(cfg, params, tok, cache, enc)
    logits2, cache = decode_step(cfg, params, tok + 1, cache, enc)
    assert logits.shape == (2, 1, cfg.vocab)
    assert np.isfinite(np.asarray(logits2, dtype=np.float32)).all()


def test_decode_matches_prefill_gemma():
    """Sequential decode == full forward on the same tokens (KV-cache sanity)."""
    cfg = get_reduced_config("gemma-2b")
    params = init_params(cfg, jax.random.PRNGKey(1))
    rng = np.random.default_rng(1)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab, (1, 8)), jnp.int32)
    full_logits, _ = forward(cfg, params, tokens)

    cache = init_cache(cfg, batch=1, capacity=8)
    outs = []
    for t in range(8):
        lg, cache = decode_step(cfg, params, tokens[:, t : t + 1], cache)
        outs.append(lg[:, 0])
    dec_logits = jnp.stack(outs, axis=1)
    np.testing.assert_allclose(
        np.asarray(dec_logits, np.float32), np.asarray(full_logits, np.float32),
        rtol=0.15, atol=0.15)  # bf16 accumulation-order tolerance


def test_decode_matches_prefill_mamba():
    cfg = get_reduced_config("mamba2-370m")
    params = init_params(cfg, jax.random.PRNGKey(2))
    rng = np.random.default_rng(2)
    S = 16  # one SSD chunk
    tokens = jnp.asarray(rng.integers(0, cfg.vocab, (1, S)), jnp.int32)
    full_logits, _ = forward(cfg, params, tokens)
    cache = init_cache(cfg, batch=1, capacity=S)
    outs = []
    for t in range(S):
        lg, cache = decode_step(cfg, params, tokens[:, t : t + 1], cache)
        outs.append(lg[:, 0])
    dec_logits = jnp.stack(outs, axis=1)
    np.testing.assert_allclose(
        np.asarray(dec_logits, np.float32), np.asarray(full_logits, np.float32),
        rtol=0.15, atol=0.2)
