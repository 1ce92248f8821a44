"""Reduce a profiler trace to the device numbers the benchmark reports.

Input: the ``.xplane.pb`` the JAX profiler writes, read with
``jax.profiler.ProfileData`` (nothing else).  Every device plane
(``/device:TPU:<n>``) has a line of operations (``XLA Ops``) and a line of
whole programs (``XLA Modules``); the host plane has the benchmark's own
``TraceAnnotation`` spans.  Host and device events share one clock.

Output (:class:`Reduced`), all within the traced window (the host span
named ``window``; events are clipped to it):

* ``busy_s``: the union of the intervals in which an operation ran, per
  device, averaged over the devices;
* ``op_s`` / ``module_s``: summed device time by operation / by program
  name.  An operation is named by its HLO instruction (the trace's event
  name is the instruction's text, ``%fusion.12 = bf16[...] fusion(...)``;
  the name is ``fusion.12``); control flow (``while``, ``conditional``,
  ``call``), whose events enclose their bodies' operations, is left out.
  A program name is its jitted function's, ``jit_<name>``, with the
  ``(<id>)`` suffix dropped;
* ``gaps``: idle intervals between operations, each labelled with the
  innermost host span that covers its midpoint (``host`` when none does).
"""

from __future__ import annotations

import bisect
import dataclasses
import re
from collections import defaultdict
from typing import Dict, List, Sequence, Tuple

WINDOW = "window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
_SUFFIX = re.compile(r"\(\d+\)$")
_INSTR = re.compile(r"^%?([^\s=]+)")
CONTROL_FLOW = ("while", "conditional", "call")


def op_name(event_name: str) -> str:
    """The HLO instruction name of an ``XLA Ops`` event."""
    m = _INSTR.match(event_name)
    return m.group(1) if m else event_name


def _is_control_flow(name: str) -> bool:
    return name.split(".")[0] in CONTROL_FLOW


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


@dataclasses.dataclass
class Trace:
    """Device ops and programs per device plane, and the host spans."""

    ops: Dict[str, List[Event]]
    modules: Dict[str, List[Event]]
    host: List[Event]


@dataclasses.dataclass
class Reduced:
    window_s: float
    busy_s: float
    op_s: Dict[str, float]
    module_s: Dict[str, float]
    gaps: List[Tuple[str, float]]

    def top_ops(self, n: int = 10) -> List[List]:
        return [[k, v] for k, v in sorted(self.op_s.items(),
                                          key=lambda kv: -kv[1])[:n]]

    def idle_by_label(self, n: int = 10) -> List[List]:
        acc: Dict[str, float] = defaultdict(float)
        for label, s in self.gaps:
            acc[label] += s
        return [[k, v] for k, v in sorted(acc.items(),
                                          key=lambda kv: -kv[1])[:n]]

    def ops_matching(self, pattern: str) -> float:
        rx = re.compile(pattern)
        return sum(v for k, v in self.op_s.items() if rx.search(k))

    def modules_matching(self, pattern: str) -> float:
        rx = re.compile(pattern)
        return sum(v for k, v in self.module_s.items() if rx.search(k))


def read_xplane(path: str) -> Trace:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    ops: Dict[str, List[Event]] = {}
    modules: Dict[str, List[Event]] = {}
    host: List[Event] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops.setdefault(plane.name, []).extend(
                        Event(op_name(e.name), e.start_ns, e.duration_ns)
                        for e in line.events)
                elif line.name == MODULES_LINE:
                    modules.setdefault(plane.name, []).extend(
                        Event(e.name, e.start_ns, e.duration_ns)
                        for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend(Event(e.name, e.start_ns, e.duration_ns)
                            for e in line.events)
    return Trace(ops=ops, modules=modules, host=host)


def _union(intervals: Sequence[Tuple[float, float]]) -> List[Tuple[float,
                                                                   float]]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _clip(events: Sequence[Event], lo: float, hi: float):
    for ev in events:
        s, e = max(ev.start_ns, lo), min(ev.end_ns, hi)
        if e > s:
            yield ev.name, s, e


def _label(mid: float, spans: Sequence[Event], starts: Sequence[float],
           reach: int = 16) -> str:
    """The innermost of ``spans`` (sorted by start) that covers ``mid``.
    Spans nest a few deep and follow one another, so the covering ones
    start among the last ``reach`` spans that start before ``mid``."""
    i = bisect.bisect_right(starts, mid)
    for sp in spans[max(0, i - reach):i][::-1]:
        if sp.end_ns >= mid:
            return sp.name
    return "host"


def reduce(trace: Trace, labels: Sequence[str] = ()) -> Reduced:
    """``labels``: the host span names that may label an idle gap."""
    win = [e for e in trace.host if e.name == WINDOW]
    if not win:
        raise ValueError(f"the trace has no host span named {WINDOW!r}")
    lo, hi = win[0].start_ns, win[0].end_ns
    spans = sorted((e for e in trace.host if e.name in set(labels)
                    and e.end_ns > lo and e.start_ns < hi),
                   key=lambda e: e.start_ns)
    starts = [e.start_ns for e in spans]
    op_s: Dict[str, float] = defaultdict(float)
    module_s: Dict[str, float] = defaultdict(float)
    busy, gaps = 0.0, []
    planes = sorted(trace.ops)
    for plane in planes:
        ivs = []
        for name, s, e in _clip(trace.ops[plane], lo, hi):
            if not _is_control_flow(name):
                op_s[name] += (e - s) / len(planes) * 1e-9
            ivs.append((s, e))
        merged = _union(ivs)
        busy += sum(e - s for s, e in merged) / len(planes) * 1e-9
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b > a:
                gaps.append((_label((a + b) / 2, spans, starts),
                             (b - a) / len(planes) * 1e-9))
    for plane, events in trace.modules.items():
        for name, s, e in _clip(events, lo, hi):
            module_s[_SUFFIX.sub("", name)] += (e - s) / max(len(planes),
                                                             1) * 1e-9
    return Reduced(window_s=(hi - lo) * 1e-9, busy_s=busy, op_s=dict(op_s),
                   module_s=dict(module_s), gaps=gaps)
