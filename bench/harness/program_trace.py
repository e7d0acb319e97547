"""The program's own marks in a profiler trace: its host spans and the
named scopes of its device ops.

The program wraps each phase of its serve engine in a host span named
``serve.*`` (``repro.runtime.spans``), nested: ``serve.admit`` holds
``serve.prefill``, ``serve.insert`` and ``serve.first_token``;
``serve.decode`` holds ``serve.decode.launch``, ``.sync`` and ``.emit``.
They sit under the harness's ``engine_run`` span.  It also emits a
``jax.named_scope`` where work is done on the device: ``seam.<site>`` at
each matmul site of the numerics seam, ``kv.write`` / ``kv.carry`` /
``kv.merge`` on the KV cache, ``optim.update`` on the optimizer.  XLA keeps
the scope path in each op's metadata; the TPU trace carries it as the
``tf_op`` stat of the op's event *metadata* (a fusion takes its root op's
path), which ``jax.profiler.ProfileData`` does not show, so the file is
read a second time as a protobuf for it.  An op's scope is the innermost
``seam.``/``kv.``/``optim.`` component of that path, or ``(unscoped)``.

A trace of a program without these marks loads too: it has no program
spans and every op is ``(unscoped)``, and the metrics that read them find
nothing to read.
"""
from __future__ import annotations

import bisect
import dataclasses
import json
import re
import sys

from . import trace
from .spec import ROOT

PROGRAM_SPAN = "serve."
UNSCOPED = "(unscoped)"
SCOPE_STAT = "tf_op"
# the directory bench/run.py traces its window into
TRACE_DIR = ROOT / ".bench_cache" / "trace"

_SCOPE = re.compile(r"(?<![\w.])(?:seam|kv|optim)\.[\w.]*\w")


def scope_of(path: str) -> str:
    """Innermost program scope in an op's scope path, or ``(unscoped)``.

    ``jit(step)/transpose(jvp(seam.mlp.w_down))/dot_general`` -> ``seam.mlp.w_down``."""
    found = _SCOPE.findall(path or "")
    return found[-1] if found else UNSCOPED


@dataclasses.dataclass
class ProgramTrace(trace.Trace):
    """A ``trace.Trace`` with each device op's scope and the program's spans."""

    op_scopes: list[list[str]]                 # per device, parallel to device_ops
    program_spans: list[tuple[str, float, float]]  # ``serve.*``, nested
    span_args: list[dict] = dataclasses.field(default_factory=list)  # parallel to program_spans

    def has_scopes(self) -> bool:
        return any(s != UNSCOPED for per in self.op_scopes for s in per)


def xspace_class():
    """The protobuf class of an ``.xplane.pb`` (an ``XSpace``), with the
    fields read here and their numbers from ``tsl/profiler/protobuf/
    xplane.proto``; map fields as their wire form, repeated key/value
    entries.  Fields not named are skipped by the parser."""
    from google.protobuf import descriptor_pb2, descriptor_pool, message_factory

    F = descriptor_pb2.FieldDescriptorProto
    fp = descriptor_pb2.FileDescriptorProto(name="bench_xplane.proto",
                                            package="bench_xplane", syntax="proto3")
    types = {"i": F.TYPE_INT64, "u": F.TYPE_UINT64, "s": F.TYPE_STRING}
    schema = {
        "XStat": [("metadata_id", 1, "i"), ("int64_value", 4, "i"), ("str_value", 5, "s"),
                  ("ref_value", 7, "u")],
        "XStatMetadata": [("id", 1, "i"), ("name", 2, "s")],
        "XEventMetadata": [("id", 1, "i"), ("name", 2, "s"), ("stats", 5, "*XStat")],
        "XEvent": [("metadata_id", 1, "i"), ("offset_ps", 2, "i"), ("duration_ps", 3, "i"),
                   ("stats", 4, "*XStat")],
        "XLine": [("id", 1, "i"), ("name", 2, "s"), ("timestamp_ns", 3, "i"),
                  ("events", 4, "*XEvent")],
        "EventMetadataEntry": [("key", 1, "i"), ("value", 2, "XEventMetadata")],
        "StatMetadataEntry": [("key", 1, "i"), ("value", 2, "XStatMetadata")],
        "XPlane": [("id", 1, "i"), ("name", 2, "s"), ("lines", 3, "*XLine"),
                   ("event_metadata", 4, "*EventMetadataEntry"),
                   ("stat_metadata", 5, "*StatMetadataEntry")],
        "XSpace": [("planes", 1, "*XPlane")],
    }
    for name, fields in schema.items():
        m = fp.message_type.add(name=name)
        for fname, number, typ in fields:
            f = m.field.add(name=fname, number=number, label=F.LABEL_OPTIONAL)
            if typ in types:
                f.type = types[typ]
            else:
                f.type = F.TYPE_MESSAGE
                f.type_name = ".bench_xplane." + typ.lstrip("*")
                if typ.startswith("*"):
                    f.label = F.LABEL_REPEATED
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fp)
    return message_factory.GetMessageClass(pool.FindMessageTypeByName("bench_xplane.XSpace"))


def op_scopes(path: str) -> list[list[tuple[str, str]]]:
    """Per TPU plane, in event order: (event name, scope) of each op of its
    ``XLA Ops`` line, the scope from the ``tf_op`` stat of the event's
    metadata."""
    space = xspace_class()()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    out = []
    for plane in space.planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        stat_names = {e.key: e.value.name for e in plane.stat_metadata}
        meta = {}
        for e in plane.event_metadata:
            path_ = ""
            for st in e.value.stats:
                if stat_names.get(st.metadata_id) == SCOPE_STAT:
                    path_ = st.str_value or stat_names.get(st.ref_value, "")
            meta[e.key] = (e.value.name, scope_of(path_))
        for line in plane.lines:
            if line.name == "XLA Ops":
                out.append([meta.get(ev.metadata_id, ("", UNSCOPED)) for ev in line.events])
    return out


def load(path: str) -> ProgramTrace:
    """Read an ``.xplane.pb``: what ``trace.load`` reads, plus scopes and
    program spans."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    named = op_scopes(path)
    devices, scopes, spans, prog, args = [], [], [], [], []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops, sc = [], []
                    meta = named[len(devices)]
                    for e, (name, scope) in zip(line.events, meta, strict=True):
                        if name != e.name:
                            raise ValueError(f"{path}: op {e.name!r} read as {name!r}")
                        ops.append((trace.short_name(e.name), e.start_ns * 1e-9,
                                    (e.start_ns + e.duration_ns) * 1e-9))
                        sc.append(scope)
                    devices.append(ops)
                    scopes.append(sc)
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                for e in line.events:
                    iv = (e.name, e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9)
                    if e.name in trace.SPANS:
                        spans.append(iv)
                    elif e.name.startswith(PROGRAM_SPAN):
                        prog.append(iv)
                        args.append(dict(e.stats))
    windows = [s for s in spans if s[0] == "window"]
    if not windows:
        raise ValueError(f"{path}: no 'window' span in the trace")
    return ProgramTrace(devices, [s for s in spans if s[0] != "window"],
                        (windows[0][1], windows[0][2]), scopes, prog, args)


def of(ctx) -> ProgramTrace | None:
    """The run's ``ProgramTrace``: ``ctx.program_trace``, else the trace file
    ``bench/run.py`` wrote for this run, loaded into ``ctx.program_trace``
    for the metrics read after this one.  None in a run without a trace."""
    pt = getattr(ctx, "program_trace", None)
    if pt is None and getattr(ctx, "trace", None) is not None:
        files = sorted(TRACE_DIR.glob("plugins/profile/*/*.xplane.pb"))
        if files:
            pt = ctx.program_trace = load(str(files[-1]))
            # the result line's breakdown is bench/run.py's: print this one beside it
            print("program_breakdown " + json.dumps(breakdown(pt)), file=sys.stderr, flush=True)
    return pt


# ------------------------------------------------------------- device time
def scope_seconds(pt: ProgramTrace) -> dict[str, float]:
    """Device self seconds per scope of the ops inside the window (each op
    without the ops nested in it), averaged over devices."""
    lo, hi = pt.window
    agg: dict[str, float] = {}
    n = max(len(pt.device_ops), 1)
    for ops, scopes in zip(pt.device_ops, pt.op_scopes):
        # self times keyed by the op's index, so that each op keeps its scope
        inside = [(i, s, e) for i, (_, s, e) in enumerate(ops) if s >= lo and e <= hi]
        for i, t in trace.self_times(inside).items():
            agg[scopes[i]] = agg.get(scopes[i], 0.0) + t / n
    return agg


def device_scopes(pt: ProgramTrace, n: int = 10) -> list[list]:
    """[[scope, seconds]] of the scopes that took most device self time."""
    return [[k, v] for k, v in sorted(scope_seconds(pt).items(), key=lambda kv: -kv[1])[:n]]


def scope_share(pt: ProgramTrace, match) -> float | None:
    """Percent of the busy time spent in ops whose scope satisfies
    ``match``; None where no op carries a program scope."""
    busy = trace.busy_s(pt)
    if not pt.has_scopes() or busy <= 0:
        return None
    return 100.0 * sum(t for sc, t in scope_seconds(pt).items() if match(sc)) / busy


# ---------------------------------------------------------------- idle time
def _gaps(ops, lo: float, hi: float) -> list[tuple[float, float]]:
    out, t = [], lo
    for a, b in trace._union(((s, e) for _, s, e in ops), lo, hi):
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if t < hi:
        out.append((t, hi))
    return out


def _innermost_pieces(spans):
    """[(start, end, name)]: the timeline cut where any span starts or
    ends, each piece named by the innermost span over it (the one that
    began last), or None where no span lies."""
    cuts = sorted({t for _, a, b in spans for t in (a, b)})
    by_start = sorted(spans, key=lambda sp: (sp[1], -sp[2]))
    out, open_ = [], []
    j = 0
    for a, b in zip(cuts, cuts[1:]):
        while j < len(by_start) and by_start[j][1] <= a:
            open_.append(by_start[j])
            j += 1
        open_ = [sp for sp in open_ if sp[2] > a]
        out.append((a, b, open_[-1][0] if open_ else None))
    return out


def idle_by_innermost_span(pt: ProgramTrace, n: int | None = 10) -> list[list]:
    """[[span, seconds]] of device idle time in the window, each stretch
    named by the innermost span over it: a program span where one lies
    under the harness span, else the harness span; ``host_other`` where
    none lies.  Averaged over devices; the total is ``trace.idle_by_span``'s."""
    lo, hi = pt.window
    pieces = _innermost_pieces(list(pt.spans) + list(pt.program_spans))
    starts = [p[0] for p in pieces]
    agg: dict[str, float] = {}
    ndev = max(len(pt.device_ops), 1)
    for ops in pt.device_ops:
        for ga, gb in _gaps(ops, lo, hi):
            covered = 0.0
            i = max(bisect.bisect_right(starts, ga) - 1, 0)
            while i < len(pieces) and pieces[i][0] < gb:
                a, b, name = pieces[i]
                ov = min(gb, b) - max(ga, a)
                if ov > 0 and name is not None:
                    agg[name] = agg.get(name, 0.0) + ov / ndev
                    covered += ov
                i += 1
            rest = (gb - ga) - covered
            if rest > 0:
                agg["host_other"] = agg.get("host_other", 0.0) + rest / ndev
    return [[k, v] for k, v in sorted(agg.items(), key=lambda kv: -kv[1])[:n]]


def program_idle_share(pt: ProgramTrace) -> float | None:
    """Percent of the window in which the device is idle under a program
    span (innermost); None where the trace has no program spans."""
    if not pt.program_spans or pt.window_s <= 0:
        return None
    idle = idle_by_innermost_span(pt, n=None)
    return 100.0 * sum(v for k, v in idle if k.startswith(PROGRAM_SPAN)) / pt.window_s


def span_seconds(pt: ProgramTrace, name: str) -> float:
    """Host seconds under the program spans called ``name``."""
    return sum(b - a for n, a, b in pt.program_spans if n == name)


def breakdown(pt: ProgramTrace) -> dict:
    return {"device_scopes": device_scopes(pt), "idle_gaps": idle_by_innermost_span(pt)}
