"""kernels/inject_replay: the Pallas bit-sliced injection-replay kernel.

The contract chain under test (docs/kernels.md):
  Pallas replay == CompiledInjector.products accumulation
                == injection.injected_matmul_int (XLA outer-product path)
                == the 256x256 LUT-gather oracle,
bit for bit, for the default design point AND a raw DSE candidate
schedule; plus the inject_impl policy resolution and the weight-side
bit-pack cache (hit / refresh-on-update / GC eviction).

All Pallas calls pin ``interpret=True`` — the kernel contract is identical
under compiled Mosaic lowering on real TPUs.
"""
import gc

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from repro.core import engine, lut  # noqa: E402
from repro.core.dse import lut_from_schedule, materialize, search_assignments  # noqa: E402
from repro.kernels import pallas_config  # noqa: E402
from repro.kernels.inject_replay import inject_replay_matmul  # noqa: E402
from repro.numerics import AMRNumerics, approx_matmul, injection  # noqa: E402
from repro.numerics.approx_matmul import matmul_amr_lut  # noqa: E402


def _oracle(table, ia, ib):
    return table[np.asarray(ia)[..., :, None],
                 np.asarray(ib)[..., None, :, :]].sum(axis=-2)


class TestInjectReplayKernel:
    @pytest.mark.parametrize("m,k,n", [
        (8, 16, 12),     # n smaller than one 32-lane word
        (32, 48, 64),    # multi-word, multi-block
        (4, 13, 45),     # prime K, ragged N: clamped tiles
        (64, 8, 96),
    ])
    def test_bitexact_vs_lut_oracle(self, m, k, n):
        inj = engine.get_injector(2, 8)
        table = lut.build_int8_lut(8).astype(np.int64)
        rng = np.random.default_rng(m + k + n)
        ia = jnp.asarray(rng.integers(0, 256, (m, k)))
        ib = jnp.asarray(rng.integers(0, 256, (k, n)))
        got = np.asarray(inject_replay_matmul(inj, ia, ib, interpret=True))
        np.testing.assert_array_equal(got.astype(np.int64), _oracle(table, ia, ib))

    def test_bitexact_vs_injector_products(self):
        """Kernel == pairwise CompiledInjector.products accumulation."""
        inj = engine.get_injector(2, 6)
        rng = np.random.default_rng(1)
        ia = jnp.asarray(rng.integers(0, 256, (6, 10)))
        ib = jnp.asarray(rng.integers(0, 256, (10, 37)))
        pa = jnp.broadcast_to(ia[:, :, None], (6, 10, 37))
        pb = jnp.broadcast_to(ib[None, :, :], (6, 10, 37))
        want = np.asarray(inj.products(pa, pb)).sum(axis=1)
        got = np.asarray(inject_replay_matmul(inj, ia, ib, interpret=True))
        np.testing.assert_array_equal(got, want)

    def test_bitexact_vs_xla_outer_path(self):
        inj = engine.get_injector(2, 8)
        rng = np.random.default_rng(2)
        ia = jnp.asarray(rng.integers(0, 256, (2, 5, 24)))  # lead batch dim
        ib = jnp.asarray(rng.integers(0, 256, (24, 40)))
        got = np.asarray(inject_replay_matmul(inj, ia, ib, interpret=True))
        want = np.asarray(injection.injected_matmul_int(inj, ia, ib))
        np.testing.assert_array_equal(got, want)

    def test_explicit_tiles_and_word_alignment(self):
        inj = engine.get_injector(2, 8)
        rng = np.random.default_rng(3)
        ia = jnp.asarray(rng.integers(0, 256, (6, 16)))
        ib = jnp.asarray(rng.integers(0, 256, (16, 64)))
        table = lut.build_int8_lut(8).astype(np.int64)
        got = np.asarray(inject_replay_matmul(inj, ia, ib, bm=3, bn=32, bk=4,
                                              interpret=True))
        np.testing.assert_array_equal(got.astype(np.int64), _oracle(table, ia, ib))
        # bn=16 divides the 64-column padded width but is NOT word-aligned
        with pytest.raises(ValueError, match="lane words"):
            inject_replay_matmul(inj, ia, ib, bn=16, interpret=True)

    def test_saturation_guard(self):
        inj = engine.get_injector(2, 8)
        k_bad = 2**31 // inj.max_abs_product + 1
        ia = jnp.zeros((1, k_bad), jnp.int32)
        ib = jnp.zeros((k_bad, 1), jnp.int32)
        with pytest.raises(ValueError, match="saturate") as ei:
            inject_replay_matmul(inj, ia, ib, interpret=True)
        assert str(k_bad) in str(ei.value)                    # names K
        assert str(inj.max_abs_product) in str(ei.value)      # and the bound


class TestInjectReplayDSECandidate:
    def _candidate(self):
        cands = search_assignments(2, 8, k=1, beam_width=8, branch_cap=4,
                                   max_nodes=2000)
        return materialize(cands[0])

    def test_kernel_matches_candidate_lut_export(self):
        sched = self._candidate()
        inj = engine.compile_injector(sched)
        table = lut_from_schedule(sched).astype(np.int64)
        rng = np.random.default_rng(4)
        ia = jnp.asarray(rng.integers(0, 256, (8, 12)))
        ib = jnp.asarray(rng.integers(0, 256, (12, 33)))
        got = np.asarray(inject_replay_matmul(inj, ia, ib, interpret=True))
        np.testing.assert_array_equal(got.astype(np.int64), _oracle(table, ia, ib))

    def test_policy_impls_agree_via_schedule_ref(self):
        """amr_inject through the registry: pallas impl == xla impl, bitwise,
        inside jit — the numerics-level form of the kernel contract."""
        handle = injection.register_schedule(self._candidate(),
                                             name="test:replay-cand")
        a = jax.random.normal(jax.random.PRNGKey(5), (4, 16), jnp.float32)
        b = jax.random.normal(jax.random.PRNGKey(6), (16, 8), jnp.float32)
        outs = {}
        for impl in ("xla", "pallas"):
            nm = AMRNumerics("amr_inject", border=8, schedule_ref=handle,
                             inject_impl=impl)
            outs[impl] = np.asarray(jax.jit(
                lambda a, b, nm=nm: approx_matmul(a, b, nm))(a, b))
        np.testing.assert_array_equal(outs["pallas"], outs["xla"])

    def test_default_schedule_policy_matches_oracle(self):
        # both sides jitted: the bit-identity contract is per execution
        # regime (eager-vs-jit XLA fusion can flip the last rescale ulp on
        # unlucky operands, for the LUT oracle itself too)
        a = jax.random.normal(jax.random.PRNGKey(7), (4, 16), jnp.float32)
        b = jax.random.normal(jax.random.PRNGKey(8), (16, 8), jnp.float32)
        want = np.asarray(jax.jit(lambda a, b: matmul_amr_lut(a, b, 8))(a, b))
        nm = AMRNumerics("amr_inject", border=8, inject_impl="pallas")
        got = np.asarray(jax.jit(lambda a, b: approx_matmul(a, b, nm))(a, b))
        np.testing.assert_array_equal(got, want)


class TestWeightPackCache:
    def test_hit_refresh_and_eviction(self):
        inj = engine.get_injector(2, 8)
        injection.WEIGHT_PACKS.clear()
        rng = np.random.default_rng(9)
        ib1 = jnp.asarray(rng.integers(0, 256, (8, 16)))
        p1 = injection.packed_weights(inj, ib1)
        assert injection.packed_weights(inj, ib1) is p1  # cache hit
        assert len(injection.WEIGHT_PACKS) == 1

        # "weights updated" = a NEW array object (jax arrays are immutable):
        # the pack must be refreshed, never served stale
        ib2 = jnp.asarray(rng.integers(0, 256, (8, 16)))
        p2 = injection.packed_weights(inj, ib2)
        assert p2 is not p1
        np.testing.assert_array_equal(np.asarray(p2),
                                      np.asarray(inj.pack_weights(ib2)))

        # and the matmul result reflects the NEW weights
        table = lut.build_int8_lut(8).astype(np.int64)
        ia = jnp.asarray(rng.integers(0, 256, (4, 8)))
        got = np.asarray(injection.injected_matmul_int(inj, ia, ib2))
        np.testing.assert_array_equal(got.astype(np.int64), _oracle(table, ia, ib2))

        # dead source arrays evict their entries (no stale id aliasing)
        assert len(injection.WEIGHT_PACKS) == 2
        del ib1, ib2, p1, p2
        gc.collect()
        assert len(injection.WEIGHT_PACKS) == 0

    def test_mutable_numpy_weights_never_cached(self):
        """An in-place update of a numpy weight array keeps its identity, so
        caching it would serve a stale pack — numpy operands must repack
        every call and always reflect the current values."""
        inj = engine.get_injector(2, 8)
        injection.WEIGHT_PACKS.clear()
        rng = np.random.default_rng(11)
        table = lut.build_int8_lut(8).astype(np.int64)
        ia = jnp.asarray(rng.integers(0, 256, (4, 8)))
        ib = np.ascontiguousarray(rng.integers(0, 256, (8, 16)))
        before = np.asarray(injection.injected_matmul_int(inj, ia, ib))
        assert len(injection.WEIGHT_PACKS) == 0  # numpy: never cached
        np.testing.assert_array_equal(before.astype(np.int64), _oracle(table, ia, ib))
        ib[:] = rng.integers(0, 256, (8, 16))  # mutate IN PLACE, same object
        after = np.asarray(injection.injected_matmul_int(inj, ia, ib))
        np.testing.assert_array_equal(after.astype(np.int64), _oracle(table, ia, ib))
        assert not np.array_equal(before, after)  # stale pack would reuse it

    def test_kernel_and_xla_share_the_cache(self):
        inj = engine.get_injector(2, 8)
        injection.WEIGHT_PACKS.clear()
        rng = np.random.default_rng(10)
        ia = jnp.asarray(rng.integers(0, 256, (4, 8)))
        ib = jnp.asarray(rng.integers(0, 256, (8, 16)))
        a = np.asarray(injection.injected_matmul_int(inj, ia, ib))
        assert len(injection.WEIGHT_PACKS) == 1
        b = np.asarray(inject_replay_matmul(inj, ia, ib, interpret=True))
        assert len(injection.WEIGHT_PACKS) == 1  # second impl reused the pack
        np.testing.assert_array_equal(a, b)
        injection.WEIGHT_PACKS.clear()


class TestInjectImplPolicy:
    def test_autodetect_per_backend(self, monkeypatch):
        monkeypatch.delenv(pallas_config.INJECT_IMPL_ENV, raising=False)
        # a static choice: the TPU compiler refuses the Pallas replay
        for backend, impl in (("tpu", "xla"), ("gpu", "xla"), ("cpu", "xla")):
            monkeypatch.setattr(pallas_config, "backend_kind", lambda b=backend: b)
            assert pallas_config.default_inject_impl() == impl, backend
            assert pallas_config.resolve_inject_impl(None) == impl, backend

    def test_env_override_wins(self, monkeypatch):
        monkeypatch.setenv(pallas_config.INJECT_IMPL_ENV, "pallas")
        assert pallas_config.default_inject_impl() == "pallas"
        monkeypatch.setenv(pallas_config.INJECT_IMPL_ENV, "xla")
        assert pallas_config.default_inject_impl() == "xla"
        monkeypatch.setenv(pallas_config.INJECT_IMPL_ENV, "bogus")
        with pytest.raises(ValueError):
            pallas_config.default_inject_impl()

    def test_explicit_impl_beats_env(self, monkeypatch):
        monkeypatch.setenv(pallas_config.INJECT_IMPL_ENV, "pallas")
        assert pallas_config.resolve_inject_impl("xla") == "xla"
        with pytest.raises(ValueError, match="inject_impl"):
            pallas_config.resolve_inject_impl("mosaic")

    def test_compiled_request_raises_named_refusal(self):
        """Mosaic refuses the replay kernel: asked for compiled (what a TPU
        resolves to) it names itself and the refusal, never falls back."""
        inj = engine.get_injector(2, 8)
        ia = jnp.full((4, 16), 128, jnp.int32)
        with pytest.raises(pallas_config.KernelRefusedError,
                           match="'inject_replay' does not compile for TPU"):
            inject_replay_matmul(inj, ia, ia.T, interpret=False)

    def test_pallas_policy_on_compiled_backend_raises(self, monkeypatch):
        monkeypatch.setenv(pallas_config.ENV_VAR, "0")  # compiled, as on TPU
        nm = AMRNumerics("amr_inject", border=8, inject_impl="pallas")
        a = jnp.ones((4, 16), jnp.float32)
        with pytest.raises(pallas_config.KernelRefusedError,
                           match="Shape mismatch"):
            jax.jit(lambda a, b: approx_matmul(a, b, nm))(a, a.T)

    def test_policy_field_stays_hashable(self):
        nm = AMRNumerics("amr_inject", border=8, inject_impl="pallas")
        assert hash(nm) != hash(AMRNumerics("amr_inject", border=8))
