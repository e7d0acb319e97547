"""Pallas kernel tests: shape/dtype sweeps vs pure-jnp oracles (interpret mode)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import lut as lut_lib
from repro.kernels import pallas_config
from repro.kernels.amr_matmul.kernel import amr_matmul_int8, amr_matmul_int8_lut
from repro.kernels.amr_matmul.ops import amr_matmul, lut_factors
from repro.kernels.amr_matmul.ref import ref_bitexact_int8, ref_lowrank_int8
from repro.kernels.amr_matmul.tiling import TileConfig, pick_tiles
from repro.kernels.ssd_scan.kernel import ssd_scan
from repro.kernels.ssd_scan.ref import ref_ssd


class TestAMRMatmulKernel:
    @pytest.mark.parametrize("m,n,k,bm,bn,bk", [
        (128, 128, 128, 128, 128, 128),
        (256, 128, 256, 128, 128, 128),
        (128, 256, 384, 128, 128, 128),
        (256, 256, 256, 128, 256, 64),
    ])
    def test_matches_ref_lowrank(self, m, n, k, bm, bn, bk):
        rng = np.random.default_rng(m + n + k)
        a = jnp.asarray(rng.integers(-128, 128, (m, k)), jnp.int8)
        b = jnp.asarray(rng.integers(-128, 128, (k, n)), jnp.int8)
        u, v = lut_factors(border=8, rank=8)
        got = amr_matmul_int8(a, b, u, v, bm=bm, bn=bn, bk=bk, interpret=True)
        want = ref_lowrank_int8(a, b, u, v)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-4, atol=2.0)

    @pytest.mark.parametrize("rank", [2, 16])
    def test_rank_sweep(self, rank):
        rng = np.random.default_rng(rank)
        a = jnp.asarray(rng.integers(-128, 128, (128, 128)), jnp.int8)
        b = jnp.asarray(rng.integers(-128, 128, (128, 128)), jnp.int8)
        u, v = lut_factors(border=8, rank=rank)
        got = amr_matmul_int8(a, b, u, v, interpret=True)
        want = ref_lowrank_int8(a, b, u, v)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-4, atol=2.0)

    def test_rank256_bitexact(self):
        """Full-rank kernel == bit-accurate AMR-MUL LUT accumulation."""
        rng = np.random.default_rng(0)
        a = jnp.asarray(rng.integers(-128, 128, (128, 128)), jnp.int8)
        b = jnp.asarray(rng.integers(-128, 128, (128, 128)), jnp.int8)
        u, v = lut_factors(border=8, rank=256)
        got = np.asarray(amr_matmul_int8(a, b, u, v, interpret=True))
        want = ref_bitexact_int8(np.asarray(a), np.asarray(b), border=8)
        # fp32 accumulation of ~1e4-magnitude products over K=128: tiny rounding
        np.testing.assert_allclose(got, want.astype(np.float64), rtol=1e-5, atol=8.0)

    def test_float_wrapper(self):
        rng = np.random.default_rng(1)
        a = jnp.asarray(rng.normal(size=(128, 128)), jnp.float32)
        b = jnp.asarray(rng.normal(size=(128, 128)), jnp.float32)
        out = amr_matmul(a, b, border=8, rank=8, interpret=True)
        exact = a @ b
        rel = np.abs(np.asarray(out - exact)) / (np.abs(np.asarray(exact)) + 1e-2)
        assert np.median(rel) < 0.25  # border-8 approximate semantics

    def test_exact_border_is_exact_quantized(self):
        """border=None factors encode E=0: kernel == plain int8 matmul."""
        rng = np.random.default_rng(2)
        a = jnp.asarray(rng.integers(-128, 128, (128, 128)), jnp.int8)
        b = jnp.asarray(rng.integers(-128, 128, (128, 128)), jnp.int8)
        u, v = lut_factors(border=None, rank=8)
        got = amr_matmul_int8(a, b, u, v, interpret=True)
        want = a.astype(jnp.float32) @ b.astype(jnp.float32)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1.0)


@pytest.mark.parametrize("m,n,k", [(4, 200, 96), (300, 130, 64), (8, 12, 7)])
def test_auto_tiles_pad_awkward_shapes(m, n, k):
    """Auto tiles pad M and N up to aligned blocks (decode's handful of
    rows, a vocab that no 128-multiple divides) and slice the pad off."""
    rng = np.random.default_rng(m * n + k)
    a = jnp.asarray(rng.integers(-128, 128, (m, k)), jnp.int8)
    b = jnp.asarray(rng.integers(-128, 128, (k, n)), jnp.int8)
    u, v = lut_factors(border=8, rank=4)
    got = amr_matmul_int8(a, b, u, v, interpret=True)
    assert got.shape == (m, n)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(ref_lowrank_int8(a, b, u, v)),
                               rtol=2e-4, atol=2.0)
    got = np.asarray(amr_matmul_int8_lut(a, b, lut_lib.table_array(8),
                                         interpret=True))
    want = ref_bitexact_int8(np.asarray(a), np.asarray(b), border=8)
    np.testing.assert_array_equal(got.astype(np.int64), want)


class TestAMRMatmulLUTKernel:
    """Full-table LUT-gather variant: bit-exact AMR products."""

    @pytest.mark.parametrize("m,n,k,bm,bn,bk", [
        (128, 128, 128, 128, 128, 128),
        (256, 128, 256, 128, 128, 64),
        (128, 256, 384, 64, 128, 128),
    ])
    def test_bitexact_vs_ref(self, m, n, k, bm, bn, bk):
        """int32 kernel output == int64 per-element LUT accumulation, exactly."""
        rng = np.random.default_rng(m + n + k + 1)
        a = jnp.asarray(rng.integers(-128, 128, (m, k)), jnp.int8)
        b = jnp.asarray(rng.integers(-128, 128, (k, n)), jnp.int8)
        table = lut_lib.table_array(8)
        got = np.asarray(amr_matmul_int8_lut(a, b, table, bm=bm, bn=bn, bk=bk,
                                             interpret=True))
        want = ref_bitexact_int8(np.asarray(a), np.asarray(b), border=8)
        np.testing.assert_array_equal(got.astype(np.int64), want)

    def test_bitexact_vs_engine_replay(self):
        """Kernel products == the compiled schedule engine's replay, with the
        per-element products evaluated by the engine directly (not via the
        table), then accumulated host-side."""
        from repro.core.amrmul import AMRMultiplier

        m_, n_, k_ = 8, 8, 64
        rng = np.random.default_rng(5)
        a = rng.integers(-128, 128, (m_, k_))
        b = rng.integers(-128, 128, (k_, n_))
        mult = AMRMultiplier(2, border=8, engine="jax")
        aa = np.repeat(a[:, :, None], n_, axis=2)          # (M, K, N)
        bb = np.repeat(b.T[None, :, :], m_, axis=0).transpose(0, 2, 1)
        prods = mult.multiply_values(aa.reshape(-1), bb.reshape(-1))
        want = prods.reshape(m_, k_, n_).sum(axis=1).astype(np.int64)
        got = np.asarray(amr_matmul_int8_lut(
            jnp.asarray(a, jnp.int8), jnp.asarray(b, jnp.int8),
            lut_lib.table_array(8), bm=8, bn=8, bk=64, interpret=True))
        np.testing.assert_array_equal(got.astype(np.int64), want)

    def test_exact_border_matches_int_matmul(self):
        rng = np.random.default_rng(6)
        a = jnp.asarray(rng.integers(-128, 128, (128, 128)), jnp.int8)
        b = jnp.asarray(rng.integers(-128, 128, (128, 128)), jnp.int8)
        got = np.asarray(amr_matmul_int8_lut(a, b, lut_lib.table_array(None),
                                             interpret=True))
        want = np.asarray(a, np.int64) @ np.asarray(b, np.int64)
        np.testing.assert_array_equal(got.astype(np.int64), want)

    def test_float_wrapper_method_lut(self):
        """method='lut' through the float wrapper == the jnp LUT-gather mode."""
        from repro.numerics.approx_matmul import matmul_amr_lut

        rng = np.random.default_rng(7)
        a = jnp.asarray(rng.normal(size=(128, 128)), jnp.float32)
        b = jnp.asarray(rng.normal(size=(128, 128)), jnp.float32)
        got = np.asarray(amr_matmul(a, b, border=8, method="lut", interpret=True))
        want = np.asarray(matmul_amr_lut(a, b, border=8))
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-4)


class TestPallasPolicy:
    """Interpret autodetection, env override, shared tiling table."""

    def test_cpu_autodetects_interpret(self, monkeypatch):
        if pallas_config.backend_kind() != "cpu":
            pytest.skip("autodetect assertions are for CPU-backed runs")
        monkeypatch.delenv(pallas_config.ENV_VAR, raising=False)
        assert pallas_config.default_interpret() is True
        assert pallas_config.resolve_interpret(None) is True

    def test_only_tpu_compiles_by_default(self, monkeypatch):
        monkeypatch.delenv(pallas_config.ENV_VAR, raising=False)
        for backend, interp in (("tpu", False), ("gpu", True), ("cpu", True)):
            monkeypatch.setattr(pallas_config, "backend_kind", lambda b=backend: b)
            assert pallas_config.default_interpret() is interp, backend

    def test_env_override_wins(self, monkeypatch):
        monkeypatch.setenv(pallas_config.ENV_VAR, "0")
        assert pallas_config.default_interpret() is False
        monkeypatch.setenv(pallas_config.ENV_VAR, "true")
        assert pallas_config.default_interpret() is True
        if pallas_config.backend_kind() == "cpu":
            monkeypatch.setenv(pallas_config.ENV_VAR, "auto")
            assert pallas_config.default_interpret() is True  # cpu fallback
        monkeypatch.setenv(pallas_config.ENV_VAR, "bogus")
        with pytest.raises(ValueError):
            pallas_config.default_interpret()

    def test_explicit_interpret_beats_env(self, monkeypatch):
        monkeypatch.setenv(pallas_config.ENV_VAR, "0")
        assert pallas_config.resolve_interpret(True) is True

    def test_pick_tiles_divides_shapes(self):
        """Auto tiles are (8, 128)-aligned and the op pads M and N up to
        them; K is never padded, so bk divides it."""
        for backend in ("cpu", "tpu"):
            for variant in ("lowrank", "lut", "inject_replay"):
                for (m, n, k) in [(128, 128, 128), (96, 64, 160), (100, 12, 7),
                                  (4, 32000, 768)]:
                    t = pick_tiles(m, n, k, variant=variant, backend=backend)
                    assert t.bm % 8 == 0 and t.bn % 128 == 0, (t, m, n)
                    assert k % t.bk == 0, (t, k)
                    assert t.bm <= max(m + 7, 8) and t.bn <= max(n + 127, 128)

    def test_pick_tiles_overrides_and_backends(self):
        t = pick_tiles(256, 256, 256, variant="lut", backend="tpu")
        assert t == TileConfig(256, 256, 128)  # autotune entry, no clamping
        t = pick_tiles(256, 256, 256, variant="lut", backend="tpu", bk=256)
        assert t.bk == 256  # explicit override wins over the table
        # decode rows pad up to one sublane tile, never clamp below it
        t = pick_tiles(4, 50280, 768, variant="lowrank", backend="tpu")
        assert t == TileConfig(8, 256, 64)
        t = pick_tiles(256, 256, 256, variant="inject_replay", backend="tpu")
        assert t == TileConfig(32, 128, 8)  # third-variant autotune entry

    def test_pick_tiles_rejects_non_divisor_overrides(self):
        """Regression: a bm/bn/bk override that does not divide the problem
        shape produced a grid missing a partial tile; now a clear error."""
        for variant in ("lowrank", "lut", "inject_replay"):
            for kwargs in ({"bm": 96}, {"bn": 100}, {"bk": 5}, {"bm": 0}):
                with pytest.raises(ValueError, match="does not tile"):
                    pick_tiles(128, 128, 128, variant=variant, **kwargs)
        # exact divisors still pass
        t = pick_tiles(128, 128, 128, variant="inject_replay", bm=64, bn=32, bk=2)
        assert t == TileConfig(64, 32, 2)


class TestSSDKernel:
    @pytest.mark.parametrize("B,S,H,P,N,chunk", [
        (1, 128, 2, 64, 64, 64),
        (2, 256, 4, 32, 16, 128),
        (1, 512, 1, 64, 128, 256),
        (2, 128, 8, 16, 32, 32),
    ])
    def test_matches_ref(self, B, S, H, P, N, chunk):
        rng = np.random.default_rng(B * S + H)
        x = jnp.asarray(rng.normal(size=(B, S, H, P)), jnp.float32)
        dt = jnp.asarray(rng.uniform(0.01, 0.2, (B, S, H)), jnp.float32)
        a_log = jnp.asarray(rng.uniform(0.0, 1.5, (H,)), jnp.float32)
        b = jnp.asarray(rng.normal(size=(B, S, H, N)), jnp.float32)
        c = jnp.asarray(rng.normal(size=(B, S, H, N)), jnp.float32)
        got = ssd_scan(x, dt, a_log, b, c, chunk, interpret=True)
        want = ref_ssd(x, dt, a_log, b, c, chunk)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-4)

    def test_dtype_bf16_inputs(self):
        rng = np.random.default_rng(9)
        B, S, H, P, N, chunk = 1, 128, 2, 32, 32, 64
        x = jnp.asarray(rng.normal(size=(B, S, H, P)), jnp.bfloat16)
        dt = jnp.asarray(rng.uniform(0.01, 0.2, (B, S, H)), jnp.float32)
        a_log = jnp.asarray(rng.uniform(0.0, 1.5, (H,)), jnp.float32)
        b = jnp.asarray(rng.normal(size=(B, S, H, N)), jnp.bfloat16)
        c = jnp.asarray(rng.normal(size=(B, S, H, N)), jnp.bfloat16)
        got = ssd_scan(x, dt, a_log, b, c, chunk, interpret=True)
        want = ref_ssd(x.astype(jnp.float32), dt, a_log, b.astype(jnp.float32),
                       c.astype(jnp.float32), chunk)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=0.05, atol=0.05)

    def test_state_carries_across_chunks(self):
        """A single impulse at t=0 must influence outputs in later chunks."""
        B, S, H, P, N, chunk = 1, 256, 1, 8, 8, 64
        x = jnp.zeros((B, S, H, P)).at[0, 0, 0, :].set(1.0)
        dt = jnp.full((B, S, H), 0.05, jnp.float32)
        a_log = jnp.asarray([0.1], jnp.float32)
        b = jnp.ones((B, S, H, N), jnp.float32)
        c = jnp.ones((B, S, H, N), jnp.float32)
        y = np.asarray(ssd_scan(x, dt, a_log, b, c, chunk, interpret=True))
        assert np.abs(y[0, chunk + 5]).sum() > 0  # crossed the chunk boundary
