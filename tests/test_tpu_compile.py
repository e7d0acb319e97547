"""Compile the TPU-dispatched Pallas kernels for a described TPU v5e.

No chip is needed: the TPU compiler is installed and compiles for a
topology that is described, not attached.  Shapes are amr-paper-100m's
matmuls (q/k/v/o 768->768, MLP 768->3072 and 3072->768, LM head
768->32000; attention QK^T / PV per head) at 256 prefill and 4 decode
rows.  Tiles come from ``pick_tiles(..., backend="tpu")`` and kernels are
asked for compiled (``interpret=False``), steered from here because the
test process's default backend is the CPU.  A compile that passes is not
a chip run: it proves only that Mosaic accepts the kernel.

The topology is described inside a fixture, never at import: only one
process may load the TPU library at a time.  All such compiles live in
this one file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import engine
from repro.kernels import pallas_config
from repro.kernels.amr_matmul.kernel import (_amr_matmul_int8_jit,
                                             _amr_matmul_int8_lut_grouped_jit,
                                             _amr_matmul_int8_lut_jit)
from repro.kernels.amr_matmul.tiling import pick_tiles
from repro.kernels.inject_replay import inject_replay_matmul
from repro.kernels.inject_replay.kernel import _inject_replay_jit

D, FF, VOCAB, HEADS, HEAD_DIM, RANK = 768, 3072, 32000, 12, 64, 16
SHAPES = {"qkvo": (D, D), "mlp_up": (D, FF), "mlp_down": (FF, D),
          "lm_head": (D, VOCAB)}
ROWS = (256, 4)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def _no_persistent_cache():
    """A compile for a described chip is written to the persistent cache but
    cannot be read back without one: keep the cache out of these tests."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("m", ROWS)
@pytest.mark.parametrize("site", sorted(SHAPES))
def test_lowrank_compiles(one_chip, site, m):
    k, n = SHAPES[site]
    t = pick_tiles(m, n, k, variant="lowrank", backend="tpu")
    fn = jax.jit(lambda a, b, u, v: _amr_matmul_int8_jit(
        a, b, u, v, bm=t.bm, bn=t.bn, bk=t.bk, interpret=False))
    compiled = fn.lower(_spec(one_chip, (m, k), jnp.int8),
                        _spec(one_chip, (k, n), jnp.int8),
                        _spec(one_chip, (256, RANK), jnp.float32),
                        _spec(one_chip, (256, RANK), jnp.float32)).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("m", ROWS)
@pytest.mark.parametrize("site", sorted(SHAPES))
def test_lut_compiles(one_chip, site, m):
    k, n = SHAPES[site]
    t = pick_tiles(m, n, k, variant="lut", backend="tpu")
    fn = jax.jit(lambda a, b, table: _amr_matmul_int8_lut_jit(
        a, b, table, bm=t.bm, bn=t.bn, bk=t.bk, interpret=False))
    compiled = fn.lower(_spec(one_chip, (m, k), jnp.int8),
                        _spec(one_chip, (k, n), jnp.int8),
                        _spec(one_chip, (256, 256), jnp.int32)).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("m", ROWS)
@pytest.mark.parametrize("site", ["qk", "pv"])
def test_lut_grouped_compiles(one_chip, site, m):
    k, n = (HEAD_DIM, max(ROWS)) if site == "qk" else (max(ROWS), HEAD_DIM)
    t = pick_tiles(m, n, k, variant="lut_grouped", backend="tpu")
    fn = jax.jit(lambda a, b, table: _amr_matmul_int8_lut_grouped_jit(
        a, b, table, bm=t.bm, bn=t.bn, bk=t.bk, interpret=False))
    compiled = fn.lower(_spec(one_chip, (HEADS, m, k), jnp.int8),
                        _spec(one_chip, (HEADS, k, n), jnp.int8),
                        _spec(one_chip, (256, 256), jnp.int32)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_inject_replay_requested_compiled_raises_named_refusal(one_chip):
    inj = engine.get_injector(2, 8)
    fn = jax.jit(lambda ia, ib: inject_replay_matmul(inj, ia, ib,
                                                     interpret=False))
    with pytest.raises(pallas_config.KernelRefusedError,
                       match="'inject_replay' does not compile for TPU"):
        fn.lower(_spec(one_chip, (ROWS[0], D), jnp.int32),
                 _spec(one_chip, (D, FF), jnp.int32))


def test_inject_replay_kernel_is_refused_by_mosaic(one_chip):
    """The refusal recorded in REFUSED_ON_TPU is the compiler's: with
    (8, 128)-aligned blocks Mosaic still rejects the replay's gathers.
    When this starts to compile, drop the entry and dispatch the kernel."""
    inj = engine.get_injector(2, 8)
    masks = inj._value_masks
    n_words = FF // 32
    fn = jax.jit(lambda ia, yw, mk: _inject_replay_jit(
        ia, yw, mk, lowered=inj.lowered, bm=8, bnw=n_words, bk=128,
        interpret=False))
    with pytest.raises(Exception, match="Shape mismatch in input, indices and output"):
        fn.lower(_spec(one_chip, (ROWS[0], D), jnp.int32),
                 _spec(one_chip, (D, masks.shape[1], n_words), jnp.uint32),
                 _spec(one_chip, masks.shape, jnp.uint32)).compile()
