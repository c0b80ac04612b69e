"""Per-request tracing across the task graph (DESIGN.md §14).

A :class:`Tracer` records spans — queue wait, service, and the covering
per-hop span — for sampled requests as they move through the
:class:`~repro.runtime.cluster.ClusterRuntime` event loop or the live
gateway.  Export is Chrome-trace / Perfetto JSON (the ``traceEvents``
array of ``ph: "X"`` complete events): load the file at
``chrome://tracing`` or https://ui.perfetto.dev and each app renders as
a process, each request as a track (tid = root id), each task-graph hop
as one span with queue/service sub-phases.

Span timestamps are the runtime's *simulated* seconds (wall seconds for
the live gateway, which runs its clock in sim units scaled by
``time_scale``), converted to the microseconds Chrome-trace expects.

The tracer is bounded: ``max_events`` caps memory, ``sample_every``
traces one in N roots so instrumentation stays off the hot path at high
request rates (the overhead pin in ``BENCH_gateway.json`` is measured
with sampling on).

To lay the spans over a JAX profiler trace, the live gateway anchors the
tracer at its start: ``anchor_ns`` is the profiler's host clock
(``CLOCK_REALTIME``, ``time.time_ns``) at span time 0, and a span at
``t`` seconds began at ``anchor_ns + t * wall_per_unit * 1e9`` on that
clock; the export carries both under ``otherData``.  A profiler file
gives its host events in nanoseconds after the ``profile_start_time``
stat of its ``Task Environment`` plane, on the same clock.
:class:`ProfilerInterval` writes back-to-back annotations onto that
clock from code that never imports jax itself.
"""
from __future__ import annotations

import json
import sys
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

__all__ = ["ProfilerInterval", "Span", "Tracer", "validate_chrome_trace"]


@dataclass
class Span:
    """One complete ("X") trace event."""
    name: str
    cat: str
    start_s: float
    end_s: float
    app: str
    root_id: int
    args: Optional[dict] = None

    def to_event(self, pid: int) -> dict:
        ev = {"name": self.name, "cat": self.cat, "ph": "X",
              "ts": self.start_s * 1e6,
              "dur": max(self.end_s - self.start_s, 0.0) * 1e6,
              "pid": pid, "tid": self.root_id}
        if self.args:
            ev["args"] = self.args
        return ev


@dataclass
class Tracer:
    """Bounded span recorder with 1-in-N root sampling."""

    max_events: int = 100_000
    sample_every: int = 1
    spans: List[Span] = field(default_factory=list)
    dropped: int = 0
    anchor_ns: Optional[int] = None     # profiler clock at span time 0
    wall_per_unit: float = 1.0          # wall seconds per span second

    def enabled_for(self, root_id: int) -> bool:
        if self.sample_every <= 1:
            return True
        return root_id % self.sample_every == 0

    def record(self, name: str, cat: str, start_s: float, end_s: float,
               app: str, root_id: int,
               args: Optional[dict] = None) -> None:
        if len(self.spans) >= self.max_events:
            self.dropped += 1
            return
        self.spans.append(Span(name, cat, start_s, end_s, app, root_id,
                               args))

    def spans_for_root(self, root_id: int,
                       cat: Optional[str] = None) -> List[Span]:
        return [s for s in self.spans
                if s.root_id == root_id and (cat is None or s.cat == cat)]

    def clear(self) -> None:
        self.spans.clear()
        self.dropped = 0

    # -- export --------------------------------------------------------
    def chrome_trace(self) -> dict:
        """The Chrome-trace JSON object: one process per app (metadata-
        named), one ``ph: "X"`` event per span."""
        pids: Dict[str, int] = {}
        events: List[dict] = []
        for s in self.spans:
            pid = pids.setdefault(s.app, len(pids) + 1)
            events.append(s.to_event(pid))
        for app, pid in pids.items():
            events.append({"name": "process_name", "ph": "M", "pid": pid,
                           "tid": 0,
                           "args": {"name": app or "app"}})
        out: dict = {"traceEvents": events, "displayTimeUnit": "ms"}
        if self.anchor_ns is not None:
            out["otherData"] = {"clock": "CLOCK_REALTIME",
                                "anchor_ns": self.anchor_ns,
                                "wall_per_unit": self.wall_per_unit}
        return out

    def export(self, path: str) -> None:
        with open(path, "w") as f:
            json.dump(self.chrome_trace(), f)


class ProfilerInterval:
    """One JAX profiler annotation open at a time, switched at the edges
    of back-to-back intervals (a loop's states), so each interval is one
    event on the profiler's clock.  A no-op until something else has
    imported jax: the SimBackend paths stay jax-free.  An interval that
    began before the profiler started is not recorded."""

    __slots__ = ("_open",)

    def __init__(self) -> None:
        self._open: Any = None

    def switch(self, name: Optional[str]) -> None:
        """Close the open interval and, unless ``name`` is None, open
        ``name``."""
        if self._open is not None:
            self._open.__exit__(None, None, None)
            self._open = None
        jax = sys.modules.get("jax")
        if name is not None and jax is not None:
            ann = jax.profiler.TraceAnnotation(name)
            ann.__enter__()
            self._open = ann


def validate_chrome_trace(obj: dict) -> List[dict]:
    """Assert ``obj`` is a loadable Chrome-trace JSON object; returns the
    complete ("X") events.  Raises ``ValueError`` on malformed traces —
    used by tests and the gateway smoke job."""
    if not isinstance(obj, dict) or "traceEvents" not in obj:
        raise ValueError("chrome trace must be an object with traceEvents")
    events = obj["traceEvents"]
    if not isinstance(events, list):
        raise ValueError("traceEvents must be a list")
    complete: List[dict] = []
    for ev in events:
        for k in ("name", "ph", "pid", "tid"):
            if k not in ev:
                raise ValueError(f"trace event missing {k!r}: {ev}")
        if ev["ph"] == "X":
            if "ts" not in ev or "dur" not in ev:
                raise ValueError(f"complete event missing ts/dur: {ev}")
            if ev["dur"] < 0:
                raise ValueError(f"negative span duration: {ev}")
            complete.append(ev)
    return complete
