"""Reduce a profiler trace to the numbers the per-layer metrics read.

A trace is the ``.xplane.pb`` the JAX profiler writes.  Device operations
are the events of the ``XLA Ops`` line of each ``/device:TPU:<n>`` plane;
they nest (a ``while`` holds its body's ops), so busy time is the union of
their intervals and an op's own time excludes the ops inside it.  Host
spans are the harness's own ``TraceAnnotation`` events on the host plane,
named in ``SPANS``; the ``window`` span marks the measured window.  All
events share one clock (nanoseconds from the start of the trace).
"""
from __future__ import annotations

import bisect
import dataclasses
import re

SPANS = ("window", "data", "train_step", "engine_run", "client", "wait")

_NUM_SUFFIX = re.compile(r"\.\d+$")


@dataclasses.dataclass
class Trace:
    """Events of one traced window; times in seconds from the trace start."""

    device_ops: list[list[tuple[str, float, float]]]  # per device: (name, start, end)
    spans: list[tuple[str, float, float]]             # harness host spans
    window: tuple[float, float]

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]


def short_name(hlo_text: str) -> str:
    """``%fusion.12 = f32[...] fusion(...)`` -> ``fusion.12``."""
    return hlo_text.split(" = ", 1)[0].lstrip("%")


def op_family(name: str) -> str:
    """``fusion.12`` -> ``fusion``: an op's name without its instance number."""
    return _NUM_SUFFIX.sub("", name)


def load(path: str) -> Trace:
    """Read an ``.xplane.pb`` written by ``jax.profiler``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, spans = [], []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    devices.append([(short_name(e.name), e.start_ns * 1e-9,
                                     (e.start_ns + e.duration_ns) * 1e-9)
                                    for e in line.events])
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                spans.extend((e.name, e.start_ns * 1e-9,
                              (e.start_ns + e.duration_ns) * 1e-9)
                             for e in line.events if e.name in SPANS)
    windows = [s for s in spans if s[0] == "window"]
    if not windows:
        raise ValueError(f"{path}: no 'window' span in the trace")
    return Trace(devices, [s for s in spans if s[0] != "window"],
                 (windows[0][1], windows[0][2]))


def _union(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    """Disjoint union of intervals clipped to [lo, hi], sorted."""
    out: list[list[float]] = []
    for a, b in sorted((max(a, lo), min(b, hi)) for a, b in intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def busy_s(trace: Trace) -> float:
    """Seconds in the window in which some op ran, averaged over devices."""
    lo, hi = trace.window
    per = [sum(b - a for a, b in _union(((s, e) for _, s, e in ops), lo, hi))
           for ops in trace.device_ops]
    return sum(per) / len(per) if per else 0.0


def self_times(ops) -> dict[str, float]:
    """Seconds of each op name on one device, excluding nested ops."""
    out: dict[str, float] = {}
    stack: list[list] = []  # [name, start, end, child_time]

    def close(item):
        name, s, e, child = item
        out[name] = out.get(name, 0.0) + max(e - s - child, 0.0)
        if stack:
            stack[-1][3] += e - s

    for name, s, e in sorted(ops, key=lambda o: (o[1], -o[2])):
        while stack and stack[-1][2] <= s:
            close(stack.pop())
        stack.append([name, s, e, 0.0])
    while stack:
        close(stack.pop())
    return out


def op_seconds(trace: Trace, match) -> float:
    """Seconds of the ops whose name satisfies ``match`` inside the window,
    summed over devices and averaged per device."""
    lo, hi = trace.window
    per = []
    for ops in trace.device_ops:
        inside = [o for o in ops if o[1] >= lo and o[2] <= hi]
        per.append(sum(t for n, t in self_times(inside).items() if match(n)))
    return sum(per) / len(per) if per else 0.0


def top_ops(trace: Trace, n: int = 10) -> list[list]:
    """[[op family, seconds]] of the ops that took most device time."""
    lo, hi = trace.window
    agg: dict[str, float] = {}
    for ops in trace.device_ops:
        inside = [o for o in ops if o[1] >= lo and o[2] <= hi]
        for name, t in self_times(inside).items():
            agg[op_family(name)] = agg.get(op_family(name), 0.0) + t / len(trace.device_ops)
    return [[k, v] for k, v in sorted(agg.items(), key=lambda kv: -kv[1])[:n]]


def idle_by_span(trace: Trace, n: int = 10) -> list[list]:
    """[[host activity, seconds]] of device idle time in the window, each
    idle stretch split among the harness spans that overlap it; what no
    span covers is ``host_other``.  Averaged over devices."""
    lo, hi = trace.window
    agg: dict[str, float] = {}
    ndev = max(len(trace.device_ops), 1)
    spans = sorted(trace.spans, key=lambda sp: sp[1])
    starts = [sp[1] for sp in spans]
    for ops in trace.device_ops:
        busy = _union(((s, e) for _, s, e in ops), lo, hi)
        gaps, t = [], lo
        for a, b in busy:
            if a > t:
                gaps.append((t, a))
            t = max(t, b)
        if t < hi:
            gaps.append((t, hi))
        for ga, gb in gaps:
            covered = 0.0
            # spans do not overlap one another: start at the last one that
            # begins before the gap
            i = max(bisect.bisect_right(starts, ga) - 1, 0)
            while i < len(spans) and spans[i][1] < gb:
                name, sa, sb = spans[i]
                ov = min(gb, sb) - max(ga, sa)
                if ov > 0:
                    agg[name] = agg.get(name, 0.0) + ov / ndev
                    covered += ov
                i += 1
            rest = (gb - ga) - covered
            if rest > 0:
                agg["host_other"] = agg.get("host_other", 0.0) + rest / ndev
    return [[k, v] for k, v in sorted(agg.items(), key=lambda kv: -kv[1])[:n]]
