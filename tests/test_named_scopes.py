"""Named scopes on the numerics seam, the KV cache and the optimizer.

Every matmul site emits ``jax.named_scope("seam." + site)`` in every
numerics mode, the KV cache writes emit ``kv.write`` (and the decode
step's layer carry and slot merge ``kv.carry`` / ``kv.merge``), the AdamW
update ``optim.update``.  A profiler trace then ties each device op to the
program code it belongs to.  The scopes are compile-time metadata: the
values computed with and without them are identical, bit for bit."""
import contextlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ModelConfig
from repro.models import attention, init_cache, init_params, layers, moe, prefill_with_cache, ssm
from repro.numerics import AMRNumerics
from repro.serve import engine as engine_mod
from repro.train.steps import make_serve_step, make_train_state, make_train_step

MODES = {"exact": AMRNumerics("exact"),
         "amr_lowrank": AMRNumerics("amr_lowrank", border=8, rank=16)}
_SCOPE = re.compile(r"(?:seam|kv|optim)\.[\w.]*\w")


def tiny_cfg(numerics):
    return ModelConfig(name="scope-test", family="dense", vocab=61, d_model=32,
                       n_layers=2, n_heads=4, n_kv_heads=2, head_dim=8, d_ff=64,
                       numerics=numerics)


def scopes_in(lowered) -> set[str]:
    """Program scopes named in the op metadata of a lowered computation."""
    text = lowered.as_text(dialect="hlo", debug_info=True)
    return {m for name in re.findall(r'op_name="([^"]*)"', text)
            for m in _SCOPE.findall(name)}


def _train_args(cfg):
    state = make_train_state(cfg, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0, cfg.vocab)
    return state, {"tokens": tokens, "targets": jnp.roll(tokens, -1, axis=1)}


def _decode_args(cfg):
    params = init_params(cfg, jax.random.PRNGKey(0))
    _, rcache = prefill_with_cache(cfg, params, jnp.array([[3, 5, 7]], jnp.int32), capacity=16)
    cache = engine_mod._insert_request(init_cache(cfg, 2, 16, per_slot=True), rcache,
                                       jnp.int32(1))
    batch = {"token": jnp.array([[4], [9]], jnp.int32), "active": jnp.array([False, True])}
    return params, cache, batch


@pytest.mark.parametrize("mode", sorted(MODES))
def test_train_step_carries_seam_and_optimizer_scopes(mode):
    cfg = tiny_cfg(MODES[mode])
    got = scopes_in(jax.jit(make_train_step(cfg)).lower(*_train_args(cfg)))
    assert {"seam.attn.qk", "seam.attn.pv", "seam.mlp.w_down", "seam.attn.wq",
            "optim.update"} <= got


@pytest.mark.parametrize("mode", sorted(MODES))
def test_decode_step_carries_seam_and_kv_scopes(mode):
    cfg = tiny_cfg(MODES[mode])
    got = scopes_in(jax.jit(make_serve_step(cfg)).lower(*_decode_args(cfg)))
    assert {"seam.attn.qk", "seam.mlp.w_down", "kv.write", "kv.carry", "kv.merge"} <= got


def test_prefill_and_slot_insert_carry_kv_write():
    cfg = tiny_cfg(MODES["exact"])
    params = init_params(cfg, jax.random.PRNGKey(0))
    toks = jnp.array([[3, 5, 7]], jnp.int32)
    low = jax.jit(lambda p, t: prefill_with_cache(cfg, p, t, capacity=16)).lower(params, toks)
    assert {"kv.write", "seam.attn.qk"} <= scopes_in(low)
    _, rcache = prefill_with_cache(cfg, params, toks, capacity=16)
    ins = jax.jit(engine_mod._insert_request).lower(
        init_cache(cfg, 2, 16, per_slot=True), rcache, jnp.int32(0))
    assert scopes_in(ins) == {"kv.write"}


@pytest.fixture
def no_scopes(monkeypatch):
    """The model with every program scope replaced by a null context."""
    null = lambda *a, **k: contextlib.nullcontext()  # noqa: E731
    for mod in (layers, attention, moe, ssm):
        monkeypatch.setattr(mod, "seam_scope", null)
    monkeypatch.setattr(jax, "named_scope", null)


def _with_and_without_scopes(fn, request):
    with_scopes = fn()
    request.getfixturevalue("no_scopes")
    return with_scopes, fn()


@pytest.mark.parametrize("mode", sorted(MODES))
def test_scopes_change_no_value_of_a_train_step(mode, request):
    cfg = tiny_cfg(MODES[mode])

    def step():
        state, metrics = jax.jit(make_train_step(cfg))(*_train_args(cfg))
        return jax.tree.leaves((state, metrics))

    a, b = _with_and_without_scopes(step, request)
    assert not scopes_in(jax.jit(make_train_step(cfg)).lower(*_train_args(cfg)))
    assert len(a) == len(b)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


@pytest.mark.parametrize("mode", sorted(MODES))
def test_scopes_change_no_value_of_a_decode_step(mode, request):
    cfg = tiny_cfg(MODES[mode])

    def step():
        return jax.tree.leaves(jax.jit(make_serve_step(cfg, with_logits=True))(
            *_decode_args(cfg)))

    a, b = _with_and_without_scopes(step, request)
    assert not scopes_in(jax.jit(make_serve_step(cfg)).lower(*_decode_args(cfg)))
    assert len(a) == len(b)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
