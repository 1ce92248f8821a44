"""Structured tracing: spans + instants, on one of two sinks.

A :class:`Tracer` records *spans* (name, track, start, duration) and
*instant events* from the hypervisor event loop, the serving executor and
the batcher round loop.  Tracks (one per tenant, plus
``hypervisor``/``batcher``/...) name the row an event belongs to.  Where
the events go depends on the sink:

* **In memory** (the default): events are stamped on an injectable
  ``clock=`` and kept in ``events``; :meth:`Tracer.export` writes them in
  the Chrome trace-event format that ``chrome://tracing`` and
  https://ui.perfetto.dev open directly.  This sink serves simulated
  time: the hypervisor passes its event time as ``ts=`` /
  :meth:`Tracer.complete` stamps, and a shared ``clock=`` lines the
  serving loop up with it.  Events store raw clock *seconds*; export
  normalizes to the earliest timestamp and converts to microseconds, so
  sim time (small floats near 0) and ``time.monotonic`` (large floats)
  both render sensibly — just don't mix the two in one tracer.  Neither
  clock is the device's: these spans cannot be laid against a profiler
  trace.
* **Profiler** (``Tracer(profiler=True)``): each span enters a
  ``jax.profiler.TraceAnnotation`` carrying its track and args, so it
  lands on the profiler's host plane, on the same clock as the device
  trace, whenever a ``jax.profiler`` trace is being taken (and costs one
  annotation object otherwise).  An instant is a zero-length
  annotation.  Nothing is kept in ``events``: the profiler owns the
  record.  Pre-measured stamps (:meth:`Tracer.complete`, ``instant(ts=)``)
  cannot be placed on the profiler's clock and are refused with a
  ``ValueError``.

Design constraints, in order:

* **Zero-cost when disabled.** Every record method checks ``enabled``
  before touching the clock; ``span(...)`` returns a shared no-op context
  manager.  ``NULL_TRACER`` is the canonical disabled instance — layers
  default to it so instrumented code never branches on ``tracer is None``.
  A span's arguments may be added inside the block with
  ``set_metadata(**args)`` (a no-op on the disabled span), so a site can
  name what it found (``joins=3``) once it knows.
* **Bounded memory.** ``max_events`` caps in-memory retention; once full,
  new events are counted in ``dropped`` but not stored, so a runaway run
  can't eat the host (and committed sample traces stay small).
"""

from __future__ import annotations

import json
import time
from typing import Any, Dict, List, Optional


class _NullSpan:
    """Shared no-op context manager for disabled tracers."""

    __slots__ = ()

    def __enter__(self) -> "_NullSpan":
        return self

    def __exit__(self, *exc: object) -> None:
        return None

    def set_metadata(self, **args: Any) -> None:
        return None


_NULL_SPAN = _NullSpan()


class _Span:
    """Live in-memory span: stamps the clock on enter/exit."""

    __slots__ = ("_tracer", "name", "track", "args", "_t0")

    def __init__(self, tracer: "Tracer", name: str, track: str,
                 args: Optional[Dict[str, Any]]) -> None:
        self._tracer = tracer
        self.name = name
        self.track = track
        self.args = args
        self._t0 = 0.0

    def __enter__(self) -> "_Span":
        self._t0 = self._tracer._clock()
        return self

    def __exit__(self, *exc: object) -> None:
        t1 = self._tracer._clock()
        self._tracer._push({"ph": "X", "name": self.name,
                            "track": self.track, "ts": self._t0,
                            "dur": max(t1 - self._t0, 0.0),
                            "args": self.args})

    def set_metadata(self, **args: Any) -> None:
        self.args = {**(self.args or {}), **args}


def _profiler_args(args: Optional[Dict[str, Any]]) -> Dict[str, Any]:
    """Annotation arguments: numbers and strings pass as they are, any
    other value (a list of rids) as its ``str``."""
    return {k: v if isinstance(v, (int, float, str)) else str(v)
            for k, v in (args or {}).items()}


class Tracer:
    """Collects spans/instants in memory on an injectable clock (Chrome
    JSON export), or, with ``profiler=True``, hands them to the JAX
    profiler on the device trace's clock."""

    def __init__(self, *, clock=None, enabled: bool = True,
                 max_events: int = 100_000, profiler: bool = False) -> None:
        self.enabled = enabled
        self.profiler = profiler
        self._clock = clock if clock is not None else time.monotonic
        self.max_events = max_events
        self.events: List[Dict[str, Any]] = []
        self.dropped = 0
        self._annotation = None
        if profiler:
            from jax.profiler import TraceAnnotation

            self._annotation = TraceAnnotation

    # -- recording -------------------------------------------------------
    def _push(self, ev: Dict[str, Any]) -> None:
        if len(self.events) >= self.max_events:
            self.dropped += 1
            return
        self.events.append(ev)

    def _refuse_stamps(self, what: str) -> None:
        raise ValueError(
            f"{what} takes a pre-measured stamp, which the profiler sink "
            f"cannot place on the device trace's clock; record it on an "
            f"in-memory Tracer instead")

    def instant(self, name: str, track: str = "main", *,
                ts: Optional[float] = None,
                args: Optional[Dict[str, Any]] = None) -> None:
        """Point-in-time event.  ``ts`` overrides the clock (sim time;
        in-memory sink only)."""
        if not self.enabled:
            return
        if self.profiler:
            if ts is not None:
                self._refuse_stamps("instant(ts=...)")
            with self._annotation(name, track=track, **_profiler_args(args)):
                pass
            return
        self._push({"ph": "i", "name": name, "track": track,
                    "ts": self._clock() if ts is None else ts,
                    "args": args})

    def complete(self, name: str, track: str, ts: float, dur: float,
                 args: Optional[Dict[str, Any]] = None) -> None:
        """Explicit span from pre-measured stamps (e.g. sim-time ranges);
        in-memory sink only."""
        if not self.enabled:
            return
        if self.profiler:
            self._refuse_stamps("complete()")
        self._push({"ph": "X", "name": name, "track": track,
                    "ts": ts, "dur": max(dur, 0.0), "args": args})

    def span(self, name: str, track: str = "main", *,
             args: Optional[Dict[str, Any]] = None):
        """Context manager measuring the enclosed block; the object it
        yields takes more arguments through ``set_metadata(**args)``."""
        if not self.enabled:
            return _NULL_SPAN
        if self.profiler:
            return self._annotation(name, track=track,
                                    **_profiler_args(args))
        return _Span(self, name, track, args)

    # -- export ----------------------------------------------------------
    def tracks(self) -> List[str]:
        out: List[str] = []
        for ev in self.events:
            if ev["track"] not in out:
                out.append(ev["track"])
        return out

    def to_chrome(self) -> Dict[str, Any]:
        """Chrome trace-event JSON (object form).  Timestamps are shifted
        so the earliest event is t=0 and scaled seconds -> microseconds;
        each track becomes a named tid with a ``thread_name`` metadata
        record so Perfetto labels the rows."""
        t0 = min((ev["ts"] for ev in self.events), default=0.0)
        tids = {track: i for i, track in enumerate(self.tracks())}
        out: List[Dict[str, Any]] = []
        for track, tid in tids.items():
            out.append({"ph": "M", "name": "thread_name", "pid": 1,
                        "tid": tid, "args": {"name": track}})
        for ev in self.events:
            rec: Dict[str, Any] = {
                "ph": ev["ph"], "name": ev["name"], "pid": 1,
                "tid": tids[ev["track"]],
                "ts": (ev["ts"] - t0) * 1e6,
            }
            if ev["ph"] == "X":
                rec["dur"] = ev["dur"] * 1e6
            if ev["ph"] == "i":
                rec["s"] = "t"          # instant scope: thread
            if ev.get("args"):
                rec["args"] = ev["args"]
            out.append(rec)
        return {"traceEvents": out, "displayTimeUnit": "ms"}

    def export(self, path: str) -> str:
        with open(path, "w") as f:
            json.dump(self.to_chrome(), f)
        return path


NULL_TRACER = Tracer(enabled=False, clock=lambda: 0.0, max_events=0)
