"""Trace reduction, FLOP counts and the peaks table."""
from __future__ import annotations

import json

import pytest

from _bench_tiny import ROOT
from harness import common, flops, trace


def _synthetic():
    # window 0..10 s; a while op (1..5) holding two kernels, a fusion 6..8
    ops = [("while.3", 1.0, 5.0), ("_amr_matmul_int8_jit.7", 1.5, 2.5),
           ("_amr_matmul_int8_jit.9", 3.0, 4.0), ("fusion.1", 6.0, 8.0),
           ("fusion.2", 11.0, 12.0)]
    spans = [("engine_run", 0.5, 5.5), ("client", 5.5, 6.0), ("wait", 8.0, 10.0)]
    return trace.Trace([ops], spans, (0.0, 10.0))


def test_busy_is_the_union_of_op_intervals_in_the_window():
    assert trace.busy_s(_synthetic()) == pytest.approx(4.0 + 2.0)


def test_self_times_exclude_nested_ops():
    st = trace.self_times(_synthetic().device_ops[0])
    assert st["while.3"] == pytest.approx(2.0)
    assert st["_amr_matmul_int8_jit.7"] == pytest.approx(1.0)


def test_kernel_seconds_by_stable_name():
    t = _synthetic()
    got = trace.op_seconds(t, lambda n: trace.op_family(n) == "_amr_matmul_int8_jit")
    assert got == pytest.approx(2.0)
    assert trace.top_ops(t)[0] == ["_amr_matmul_int8_jit", pytest.approx(2.0)]


def test_idle_gaps_are_named_by_the_host_span_over_them():
    idle = dict(trace.idle_by_span(_synthetic()))
    # idle: 0..1, 5..6, 8..10
    assert idle["engine_run"] == pytest.approx(0.5 + 0.5)
    assert idle["client"] == pytest.approx(0.5)
    assert idle["wait"] == pytest.approx(2.0)
    assert idle["host_other"] == pytest.approx(0.5)
    assert sum(idle.values()) == pytest.approx(10.0 - trace.busy_s(_synthetic()))


def test_load_reads_the_harness_spans_of_a_recorded_trace(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    tracer = common.Tracer(tmp_path / "t")
    tracer.start()
    with common.span("window", tracer):
        with common.span("engine_run", tracer):
            f(x).block_until_ready()
        with common.span("client", tracer):
            pass
    tracer.stop()
    t = trace.load(str(next((tmp_path / "t").glob("plugins/profile/*/*.xplane.pb"))))
    assert t.window_s > 0
    assert {s[0] for s in t.spans} == {"engine_run", "client"}
    assert t.device_ops == []  # no TPU plane on the CPU


def test_amr_paper_train_flops_match_the_hand_count():
    cfg = json.loads((ROOT / "bench/configs/amr-paper-100m.json").read_text())
    # 137.8M matmul parameters x 6 + attention 3 x 4 x 12 x 768 x 1024
    assert flops.matmul_params(cfg) == 12 * (4 * 768 * 768 + 3 * 768 * 3072) + 32000 * 768
    assert flops.train_flops_per_token(cfg, 1024) == pytest.approx(0.94e9, rel=0.01)


def test_peaks_table():
    p = common.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12 and p["int8_ops_per_s"] == 393e12
    assert p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        common.peaks("TPU v9 imaginary")


def test_no_tpu_is_refused():
    with pytest.raises(common.NoChip):
        common.device_info(1)
