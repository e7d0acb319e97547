"""Sharding-rule tests: head-gating, divisibility guards, cache specs."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import get_config
from repro.launch import specs as specs_lib
from repro.parallel import sharding as shard_lib


def _mesh_1x1(names=("data", "model")):
    return jax.make_mesh((1,) * len(names), names,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(names))


class _FakeMesh:
    """Shape-only mesh stand-in so rule tests don't need 256 devices."""

    def __init__(self, shape: dict):
        self.shape = shape
        self.axis_names = tuple(shape)


MESH = _FakeMesh({"data": 16, "model": 16})
MESH_POD = _FakeMesh({"pod": 2, "data": 16, "model": 16})


class TestParamRules:
    def test_qwen3_attention_tp(self):
        cfg = get_config("qwen3-32b")  # 64 q heads, 8 kv heads: both % 16 == 0
        params = specs_lib.abstract_params(cfg)
        specs = shard_lib.param_specs(MESH, params, cfg)
        leaf = specs["layers"][0]["attn"]
        assert leaf["wq"] == P(None, "data", "model")  # leading stack axis
        assert leaf["wk"][-1] is None  # kv=8 not divisible by 16 -> replicate
        assert leaf["wo"] == P(None, "model", "data")

    def test_mqa_head_gate_replicates(self):
        cfg = get_config("gemma-2b")  # 8 q heads, 1 kv head on model=16
        params = specs_lib.abstract_params(cfg)
        specs = shard_lib.param_specs(MESH, params, cfg)
        leaf = specs["layers"][0]["attn"]
        assert leaf["wq"][-1] is None   # heads don't divide -> no TP split
        assert leaf["wk"][-1] is None
        # FSDP still shards the d_model dim
        assert leaf["wq"][-2] == "data"

    def test_mlp_col_row(self):
        cfg = get_config("gemma-2b")
        params = specs_lib.abstract_params(cfg)
        specs = shard_lib.param_specs(MESH, params, cfg)
        leaf = specs["layers"][0]["mlp"]
        assert leaf["w_gate"] == P(None, "data", "model")
        assert leaf["w_down"] == P(None, "model", "data")

    def test_vocab_divisibility_guard(self):
        cfg = get_config("mamba2-370m")  # vocab 50280 % 16 != 0
        params = specs_lib.abstract_params(cfg)
        specs = shard_lib.param_specs(MESH, params, cfg)
        assert specs["embed"][0] is None      # vocab replicated
        assert specs["embed"][1] == "data"    # d_model FSDP

    def test_moe_expert_ffn(self):
        cfg = get_config("dbrx-132b")
        params = specs_lib.abstract_params(cfg)
        specs = shard_lib.param_specs(MESH, params, cfg)
        leaf = specs["layers"][0]["moe"]
        assert leaf["w_gate"] == P(None, None, "data", "model")
        assert leaf["w_down"] == P(None, None, "model", "data")

    def test_ssm_projections(self):
        cfg = get_config("mamba2-370m")  # 32 ssm heads % 16 == 0
        params = specs_lib.abstract_params(cfg)
        specs = shard_lib.param_specs(MESH, params, cfg)
        leaf = specs["layers"][0]["ssm"]
        assert leaf["wx"] == P(None, "data", "model")
        assert leaf["out_proj"] == P(None, "model", "data")
        assert leaf["wb"][-1] is None  # small B/C projections replicate on model

    def test_opt_state_mirrors_params(self):
        cfg = get_config("gemma-2b")
        state = specs_lib.abstract_train_state(cfg)
        specs = shard_lib.param_specs(MESH, state, cfg)
        assert (specs.params["layers"][0]["mlp"]["w_gate"]
                == specs.opt.mu["layers"][0]["mlp"]["w_gate"])


class TestBatchAndCache:
    def test_batch_spec_divisible(self):
        assert shard_lib.batch_partition_spec(MESH, 256, 2) == P(("data",), None)
        assert shard_lib.batch_partition_spec(MESH_POD, 256, 2) == P(("pod", "data"), None)

    def test_batch_spec_indivisible_replicates(self):
        assert shard_lib.batch_partition_spec(MESH, 1, 2) == P(None, None)

    def test_cache_specs(self):
        cfg = get_config("qwen3-32b")
        cache, _ = specs_lib.decode_specs(cfg, type("S", (), {
            "global_batch": 128, "seq_len": 1024, "kind": "decode"})())
        specs = shard_lib.cache_specs(MESH, cache, 128)
        kv_spec = jax.tree.leaves(
            specs, is_leaf=lambda x: isinstance(x, P))[0]
        assert kv_spec[1] == "data"  # batch dim


class TestConstraints:
    def test_pin_noop_without_mesh(self):
        from repro.parallel.constraints import pin
        x = jnp.ones((4, 4))
        y = pin(x, "batch", "tp")
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))

    def test_pin_applies_under_mesh(self):
        from repro.parallel.constraints import pin
        mesh = _mesh_1x1()
        with jax.set_mesh(mesh):
            def f(x):
                return pin(x, "batch", "tp")
            out = jax.jit(f)(jnp.ones((4, 4)))
        assert out.shape == (4, 4)


class TestInputSpecs:
    @pytest.mark.parametrize("arch", ["gemma-2b", "dbrx-132b", "whisper-small",
                                      "internvl2-76b", "mamba2-370m"])
    def test_train_specs_shapes(self, arch):
        from repro.configs import SHAPES
        cfg = get_config(arch)
        spec = specs_lib.train_specs(cfg, SHAPES["train_4k"])
        total = spec["tokens"].shape[1] + (cfg.vision_prefix or 0)
        assert total == 4096
        assert spec["tokens"].shape[0] == 256

    def test_param_counts_sane(self):
        # dbrx ~132B total / ~36B active; internvl ~76B; qwen3 ~32B
        assert 1.2e11 < specs_lib.param_count(get_config("dbrx-132b")) < 1.5e11
        a = specs_lib.active_param_count(get_config("dbrx-132b"))
        assert 2.5e10 < a < 4.5e10
        assert 6.5e10 < specs_lib.param_count(get_config("internvl2-76b")) < 8.5e10
        assert 2.8e10 < specs_lib.param_count(get_config("qwen3-32b")) < 3.6e10
