"""The program's spans and named scopes in a trace, and the four metrics
that read them: ``seam.device_share.train``, ``decode.kv_write_share``,
``engine.host_idle_share``, ``engine.admit_self_share``."""
from __future__ import annotations

import dataclasses
from types import SimpleNamespace

import pytest

from _bench_tiny import TINY
from harness import common, program_trace, spec, trace

NEW = ("seam.device_share.train", "decode.kv_write_share", "engine.host_idle_share",
       "engine.admit_self_share")
OLD = ("device.idle_share.train", "device.idle_share.serve", "decode.mfu",
       "engine.admit_share", "train.mfu")
ENGINE_SPANS = {"serve.admit", "serve.prefill", "serve.insert", "serve.first_token",
                "serve.decode", "serve.decode.launch", "serve.decode.sync",
                "serve.decode.emit"}


def _synthetic(marks: bool = True) -> program_trace.ProgramTrace:
    # window 0..10 s; a while op (1..5) holding three ops, then two more
    ops = [("while.1", 1.0, 5.0), ("fusion.2", 1.0, 2.0), ("fusion.3", 2.5, 4.0),
           ("copy.4", 4.0, 5.0), ("fusion.5", 6.0, 8.0), ("fusion.6", 8.5, 9.0)]
    scopes = ["(unscoped)", "seam.attn.qk", "kv.write", "kv.carry", "seam.mlp.w_down",
              "optim.update"]
    spans = [("engine_run", 0.5, 5.5), ("client", 5.5, 6.0), ("wait", 8.0, 10.0)]
    prog = [("serve.admit", 0.6, 2.2), ("serve.prefill", 0.6, 0.8),
            ("serve.insert", 0.8, 0.9), ("serve.first_token", 0.9, 2.2),
            ("serve.decode", 2.2, 5.4), ("serve.decode.launch", 2.2, 2.4),
            ("serve.decode.sync", 2.4, 5.0), ("serve.decode.emit", 5.0, 5.4)]
    if not marks:
        scopes, prog = ["(unscoped)"] * len(ops), []
    return program_trace.ProgramTrace([ops], spans, (0.0, 10.0), [scopes], prog)


def _ctx(pt, **kw):
    counters = {"decode_s": 2.0, "decode_flops": 4e12, "engine_s": 4.0, "steps": 3,
                "flops_per_token": 1e9, "tokens": 3000, "window_s": 10.0}
    cell = SimpleNamespace(workload={"peak": "bf16_flops_per_s"})
    return SimpleNamespace(cell=cell, e2e={}, counters=counters, trace=pt,
                           peaks=common.peaks("TPU v5 lite"), chips=1, **kw)


def _read(name, ctx):
    return spec.metric_reader(name)(ctx)


@pytest.mark.parametrize("path,scope", [
    ("jit(train_step)/jvp()/while/body/closed_call/seam.attn.qk/bskgd,btkd->bkgst/dot_general",
     "seam.attn.qk"),
    ("jit(train_step)/transpose(jvp(seam.mlp.w_down))/dot_general", "seam.mlp.w_down"),
    ("checkpoint/rematted_computation/seam.moe.expert.w_gate/dot_general",
     "seam.moe.expert.w_gate"),
    ("jit(serve_step)/while/body/kv.carry/dynamic_update_slice", "kv.carry"),
    ("jit(train_step)/optim.update/sqrt", "optim.update"),
    ("jit(train_step)/jvp()/while/body/closed_call/jit(_where)/select_n", "(unscoped)"),
    ("state.opt.mu['layers'][0]['attn']['wq']", "(unscoped)"),
    ("", "(unscoped)"),
])
def test_scope_of_an_op_is_its_innermost_program_scope(path, scope):
    assert program_trace.scope_of(path) == scope


def test_device_scopes_are_self_times_by_scope():
    pt = _synthetic()
    got = dict(program_trace.device_scopes(pt))
    assert got == pytest.approx({"(unscoped)": 0.5, "seam.attn.qk": 1.0, "kv.write": 1.5,
                                 "kv.carry": 1.0, "seam.mlp.w_down": 2.0,
                                 "optim.update": 0.5})
    assert sum(got.values()) == pytest.approx(trace.busy_s(pt))


def test_idle_gaps_name_the_innermost_span_with_the_same_total():
    pt = _synthetic()
    got = dict(program_trace.idle_by_innermost_span(pt, n=None))
    # idle: 0..1, 5..6, 8..8.5, 9..10
    assert got == pytest.approx({"host_other": 0.5, "engine_run": 0.2, "serve.prefill": 0.2,
                                 "serve.insert": 0.1, "serve.first_token": 0.1,
                                 "serve.decode.emit": 0.4, "client": 0.5, "wait": 1.5})
    before = dict(trace.idle_by_span(pt, n=100))
    assert before == pytest.approx({"engine_run": 1.0, "client": 0.5, "wait": 1.5,
                                    "host_other": 0.5})
    assert sum(got.values()) == pytest.approx(sum(before.values()), rel=1e-12)
    assert sum(got.values()) == pytest.approx(pt.window_s - trace.busy_s(pt))


@pytest.mark.parametrize("name,value", [
    ("seam.device_share.train", 100.0 * 3.0 / 6.5),
    ("decode.kv_write_share", 100.0 * 1.5 / 6.5),
    ("engine.host_idle_share", 100.0 * 0.8 / 10.0),
    ("engine.admit_self_share", 100.0 * 1.6 / 4.8),
])
def test_new_metric_reads_its_hand_computed_value(name, value):
    pt = _synthetic()
    assert _read(name, _ctx(pt, program_trace=pt)) == pytest.approx(value)


@pytest.mark.parametrize("name", OLD)
def test_existing_metrics_read_unchanged_on_a_program_trace(name):
    pt = _synthetic()
    base = trace.Trace(pt.device_ops, pt.spans, pt.window)
    assert _read(name, _ctx(pt, program_trace=pt)) == _read(name, _ctx(base))
    assert trace.top_ops(pt) == trace.top_ops(base)
    assert trace.idle_by_span(pt) == trace.idle_by_span(base)


@pytest.mark.parametrize("name", NEW)
def test_new_metric_finds_nothing_in_a_program_without_marks(name):
    pt = _synthetic(marks=False)
    assert _read(name, _ctx(pt, program_trace=pt)) is None


@pytest.mark.parametrize("name", NEW)
def test_new_metric_finds_nothing_without_a_trace(name):
    assert _read(name, _ctx(None)) is None


def _tiny_engine_trace(tmp_path):
    """2 slots, 3 requests of the tiny model under the profiler."""
    import jax

    from repro.configs.base import ModelConfig
    from repro.models import init_params
    from repro.serve import Request, ServeEngine

    fields = {f.name for f in dataclasses.fields(ModelConfig)}
    cfg = ModelConfig(**{k: v for k, v in TINY.items() if k in fields})
    eng = ServeEngine(cfg, init_params(cfg, jax.random.PRNGKey(0)), n_slots=2, capacity=32)
    prompts = [(5, 9, 2, 7), (3, 11, 4, 1, 8, 6), (13, 2, 4, 4)]
    tracer = common.Tracer(tmp_path / "t")
    tracer.start()
    with common.span("window", tracer):
        with common.span("engine_run", tracer):
            for p, g in zip(prompts, (3, 2, 4)):
                eng.submit(Request(prompt=p, max_new_tokens=g))
            eng.run()
    tracer.stop()
    path = str(next((tmp_path / "t").glob("plugins/profile/*/*.xplane.pb")))
    return eng, prompts, path


def test_recorded_engine_trace_has_every_span_nested(tmp_path):
    eng, prompts, path = _tiny_engine_trace(tmp_path)
    pt = program_trace.load(path)
    names = [s[0] for s in pt.program_spans]
    assert set(names) == ENGINE_SPANS
    assert names.count("serve.admit") == 3
    assert names.count("serve.decode") == eng.counters.decode_steps == 4

    def within(child, parent):
        outer = [s for s in pt.program_spans if s[0] == parent]
        for s in pt.program_spans:
            if s[0] == child:
                assert any(o[1] <= s[1] and s[2] <= o[2] for o in outer), (child, s)

    for c in ("serve.prefill", "serve.insert", "serve.first_token"):
        within(c, "serve.admit")
    for c in ("serve.decode.launch", "serve.decode.sync", "serve.decode.emit"):
        within(c, "serve.decode")
    engine_run = next(s for s in pt.spans if s[0] == "engine_run")
    assert all(engine_run[1] <= s[1] and s[2] <= engine_run[2] for s in pt.program_spans)

    admits = [a for s, a in zip(pt.program_spans, pt.span_args) if s[0] == "serve.admit"]
    assert sorted(a["uid"] for a in admits) == sorted({a["uid"] for a in admits})
    assert sorted(a["prompt_len"] for a in admits) == sorted(len(p) for p in prompts)
    assert {a["slot"] for a in admits} <= {0, 1}
    steps = [a for s, a in zip(pt.program_spans, pt.span_args) if s[0] == "serve.decode"]
    assert [a["step"] for a in steps] == [1, 2, 3, 4]
    assert [a["active"] for a in steps] == [2, 2, 1, 1]
    compiled = [a.get("compiled") for s, a in zip(pt.program_spans, pt.span_args)
                if s[0] == "serve.prefill"]
    assert compiled.count(1) == eng.counters.prefill_compiles == 2

    # the spans and the counters time the same admissions
    assert program_trace.span_seconds(pt, "serve.admit") == pytest.approx(
        eng.counters.admit_seconds, rel=0.25, abs=0.02)
    # the harness's own reading of the same file is what it was
    base = trace.load(path)
    assert (base.device_ops, base.spans, base.window) == (pt.device_ops, pt.spans, pt.window)


def test_a_traced_run_loads_its_trace_once_for_every_reader(tmp_path, monkeypatch, capsys):
    import jax
    import jax.numpy as jnp

    tracer = common.Tracer(tmp_path / "trace")
    tracer.start()
    with common.span("window", tracer):
        with common.span("engine_run", tracer):
            jax.jit(lambda x: x * 2)(jnp.ones(8)).block_until_ready()
    tracer.stop()
    monkeypatch.setattr(program_trace, "TRACE_DIR", tmp_path / "trace")
    ctx = _ctx(trace.Trace([], [], (0.0, 1.0)))
    pt = program_trace.of(ctx)
    assert ctx.program_trace is pt and program_trace.of(ctx) is pt
    assert [s[0] for s in pt.spans] == ["engine_run"] and pt.program_spans == []
    assert capsys.readouterr().err.count("program_breakdown ") == 1
    # a program without spans or scopes: the new metrics find nothing
    assert all(_read(name, ctx) is None for name in NEW)


def _xspace_file(tmp_path):
    """A small TPU trace as the profiler writes one: a device plane whose
    ``XLA Ops`` events name their scope path in the ``tf_op`` stat of the
    event metadata (as a string, or as a reference to an interned one),
    and a host plane with the harness's and the engine's spans."""
    space = program_trace.xspace_class()()
    dev = space.planes.add(id=1, name="/device:TPU:0")
    for k, name in ((1, "tf_op"), (2, "flops"), (3, "jit(step)/seam.attn.qk/dot_general")):
        sm = dev.stat_metadata.add(key=k).value
        sm.id, sm.name = k, name
    ops = [("%fusion.1 = f32[8] fusion(...)", "jit(step)/transpose(jvp(seam.mlp.w_down))/dot", None),
           ("%copy.2 = f32[8] copy(...)", "jit(step)/while/body/copy", None),
           ("%fusion.3 = f32[8] fusion(...)", None, 3),
           ("%fusion.4 = f32[8] fusion(...)", "jit(step)/optim.update/sqrt", None)]
    for i, (name, path, ref) in enumerate(ops, start=1):
        md = dev.event_metadata.add(key=i).value
        md.id, md.name = i, name
        md.stats.add(metadata_id=2, int64_value=100)
        if path is not None:
            md.stats.add(metadata_id=1, str_value=path)
        if ref is not None:
            md.stats.add(metadata_id=1, ref_value=ref)
    line = dev.lines.add(id=1, name="XLA Ops", timestamp_ns=1_000)
    for i, (off_us, dur_us) in enumerate(((0, 200), (300, 100), (500, 300), (900, 50)), start=1):
        line.events.add(metadata_id=i, offset_ps=off_us * 1_000_000, duration_ps=dur_us * 1_000_000)
    host = space.planes.add(id=2, name="/host:CPU")
    names = ["window", "engine_run", "serve.decode", "serve.decode.sync"]
    for i, name in enumerate(names, start=1):
        md = host.event_metadata.add(key=i).value
        md.id, md.name = i, name
    hl = host.lines.add(id=2, name="python", timestamp_ns=1_000)
    for i, (off_us, dur_us) in enumerate(((0, 1000), (100, 900), (150, 800), (200, 700)), start=1):
        hl.events.add(metadata_id=i, offset_ps=off_us * 1_000_000, duration_ps=dur_us * 1_000_000)
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(space.SerializeToString())
    return str(path)


def test_scopes_are_read_from_the_event_metadata_of_a_tpu_trace(tmp_path):
    path = _xspace_file(tmp_path)
    pt = program_trace.load(path)
    assert pt.op_scopes == [["seam.mlp.w_down", "(unscoped)", "seam.attn.qk", "optim.update"]]
    assert [o[0] for o in pt.device_ops[0]] == ["fusion.1", "copy.2", "fusion.3", "fusion.4"]
    base = trace.load(path)
    assert (base.device_ops, base.spans, base.window) == (pt.device_ops, pt.spans, pt.window)
    assert [s[0] for s in pt.program_spans] == ["serve.decode", "serve.decode.sync"]
    got = dict(program_trace.device_scopes(pt))
    assert got == pytest.approx({"seam.mlp.w_down": 200e-6, "(unscoped)": 100e-6,
                                 "seam.attn.qk": 300e-6, "optim.update": 50e-6})
    idle = dict(program_trace.idle_by_innermost_span(pt, n=None))
    # idle in the 1000 us window: 200-300, 400-500 and 800-900 under
    # serve.decode.sync, 950-1000 under engine_run alone
    assert idle == pytest.approx({"serve.decode.sync": 300e-6, "engine_run": 50e-6})
