"""Lightweight span tracer for the data-plane hot paths.

A span is one timed region — ``with TRACER.span("consumer.fetch",
cat="read"): ...`` — recorded into a bounded ring buffer. The tracer is
**disabled by default** and, when disabled, ``span()`` returns a shared
no-op context manager: the hot paths (commit protocol, ranged reads,
prefetch) pay one attribute load and one call, which keeps the fig12
overhead budget (<5%) honest even with instrumentation compiled in
everywhere.

While enabled, each span

  * starts on the clock of the JAX profiler's host plane (tsl's
    ``GetCurrentTimeNanos``, the realtime clock: ``time.time_ns()``) and
    takes its duration from ``perf_counter_ns``, so a span lines up with
    the device operations of a profiler trace taken at the same time;
  * is mirrored into that trace as a ``jax.profiler.TraceAnnotation`` of
    the same name and arguments, where jax is already imported (a process
    without jax has no device trace to join);
  * records its ``id`` and the ``parent`` id of the innermost span open on
    the same thread, so a span's self time (its duration less its
    children's) can be computed. Work handed to another thread names its
    parent explicitly: ``span(..., parent=TRACER.current())`` taken on the
    thread that hands it over.

Two export surfaces:

  * ``chrome_trace()`` — Chrome-trace-format event list (``ph: "X"``
    complete events, microsecond timestamps on the profiler's clock) that
    loads directly into Perfetto / ``chrome://tracing``.
  * ``stall_report()`` — plain-text attribution: per-name and per-category
    self time, and the headline split the paper's fig5/fig12 arguments turn
    on — how much of the trainer's critical path waited on the data plane
    vs computed.

Span taxonomy (catalog in docs/OBSERVABILITY.md): categories are ``commit``,
``read``, ``prefetch``, ``derive``, ``checkpoint``, ``compute``, ``h2d``;
names are ``<component>.<phase>`` (e.g. ``commit.cput``,
``consumer.footer``). No program span starts with ``bench.``: that prefix
belongs to the chip benchmark's own annotations.
"""
from __future__ import annotations

import itertools
import json
import os
import sys
import threading
import time
from collections import deque
from typing import Dict, List, Optional

from repro.core.stats import percentiles

__all__ = ["Span", "Tracer", "TRACER", "enable_tracing", "disable_tracing",
           "trace_span", "self_times"]

#: default ring-buffer capacity (spans; oldest evicted first)
DEFAULT_CAPACITY = 8192

#: the category the stall report counts as compute
COMPUTE_CAT = "compute"

#: the trainer's critical-path waits on the data plane, counted as
#: data-plane wait by the stall report unless they run under a staging span
CRITICAL_WAITS = ("pipeline.data_wait", "pipeline.h2d", "consumer.wait")

#: the category of the overlapped staging work (``pipeline.stage.fetch``,
#: ``prefetch.fetch``): waits nested in it are off the critical path
STAGING_CAT = "prefetch"


class Span:
    """One completed timed region: ``t0`` in seconds since the epoch on the
    profiler's host clock, ``dur`` in seconds, ``id`` unique in the process,
    ``parent`` the id of the span it ran in (on the same thread, or the one
    named when it was opened), or None."""

    __slots__ = ("name", "cat", "t0", "dur", "tid", "args", "id", "parent")

    def __init__(self, name: str, cat: str, t0: float, dur: float, tid: int,
                 args: Optional[dict], id: int = 0,
                 parent: Optional[int] = None):
        self.name = name
        self.cat = cat
        self.t0 = t0
        self.dur = dur
        self.tid = tid
        self.args = args
        self.id = id
        self.parent = parent

    def __repr__(self) -> str:
        return f"Span({self.name!r}, cat={self.cat!r}, dur={self.dur:.6f})"


class _NullSpan:
    """Shared no-op context manager returned while tracing is disabled."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def note(self, **args) -> None:
        pass


_NULL_SPAN = _NullSpan()


def _annotation(name: str, args: Optional[dict]):
    """A ``jax.profiler.TraceAnnotation`` for the span, or None where jax
    has not been imported (then no profiler can be running)."""
    jax = sys.modules.get("jax")
    profiler = getattr(jax, "profiler", None)
    if profiler is None:
        return None
    return profiler.TraceAnnotation(name, **(args or {}))


class _OpenSpans(threading.local):
    """Per thread: the ids of the spans open on it, innermost last."""

    def __init__(self):
        self.stack: List[int] = []


class _LiveSpan:
    """Context manager that records one span on exit (exceptions included —
    a failed cput is exactly the span you want to see)."""

    __slots__ = ("_tracer", "name", "cat", "args", "t0", "_p0", "_stack",
                 "_mirror", "id", "parent")

    def __init__(self, tracer: "Tracer", name: str, cat: str,
                 args: Optional[dict], parent: Optional[int] = None):
        self._tracer = tracer
        self.name = name
        self.cat = cat
        self.args = args
        self.parent = parent

    def __enter__(self):
        stack = self._stack = self._tracer._open.stack
        if self.parent is None and stack:
            self.parent = stack[-1]
        self.id = next(self._tracer._ids)
        stack.append(self.id)
        self._mirror = _annotation(self.name, self.args)
        if self._mirror is not None:
            self._mirror.__enter__()
        self.t0 = time.time_ns()
        self._p0 = time.perf_counter_ns()
        return self

    def note(self, **args) -> None:
        """Add arguments known only inside the span, such as what it
        fetched; they go to the recorded span, not to the profiler's."""
        self.args = {**(self.args or {}), **args}

    def __exit__(self, exc_type, exc, tb):
        dur = (time.perf_counter_ns() - self._p0) / 1e9
        if self._mirror is not None:
            self._mirror.__exit__(exc_type, exc, tb)
        self._stack.pop()
        self._tracer._record(Span(self.name, self.cat, self.t0 / 1e9, dur, 0,
                                  self.args, self.id, self.parent))
        return False


def self_times(spans: List[Span]) -> Dict[int, float]:
    """Each span's duration less those of its recorded children on its own
    thread, by id (children on other threads may overlap each other)."""
    own = {s.id: s.dur for s in spans}
    tid = {s.id: s.tid for s in spans}
    for s in spans:
        if s.parent in own and tid[s.parent] == s.tid:
            own[s.parent] -= s.dur
    return own


class Tracer:
    """Bounded-ring span recorder with Chrome-trace and stall-report export."""

    def __init__(self, capacity: int = DEFAULT_CAPACITY):
        self.enabled = False
        self._ring: "deque[Span]" = deque(maxlen=capacity)
        self._lock = threading.Lock()
        self._tids: Dict[int, int] = {}  # thread ident -> small stable id
        self._ids = itertools.count(1)    # span ids, unique in the process
        self._open = _OpenSpans()

    # -- recording ---------------------------------------------------------
    def enable(self) -> "Tracer":
        self.enabled = True
        return self

    def disable(self) -> "Tracer":
        self.enabled = False
        return self

    def clear(self) -> None:
        with self._lock:
            self._ring.clear()

    def span(self, name: str, cat: str = "", *,
             parent: Optional[int] = None, **args):
        """Context manager timing one region. Free when disabled. ``parent``
        names the enclosing span's id where that span is open on another
        thread; by default it is the innermost span open on this one."""
        if not self.enabled:
            return _NULL_SPAN
        return _LiveSpan(self, name, cat, args or None, parent)

    def current(self) -> Optional[int]:
        """The id of the innermost span open on this thread, or None."""
        stack = self._open.stack
        return stack[-1] if stack else None

    def _record(self, span: Span) -> None:
        ident = threading.get_ident()
        with self._lock:
            tid = self._tids.get(ident)
            if tid is None:
                tid = self._tids[ident] = len(self._tids)
            span.tid = tid
            self._ring.append(span)

    # -- read surface ------------------------------------------------------
    def spans(self) -> List[Span]:
        with self._lock:
            return list(self._ring)

    def __len__(self) -> int:
        with self._lock:
            return len(self._ring)

    # -- exports -----------------------------------------------------------
    def chrome_trace(self) -> List[dict]:
        """Chrome-trace-format complete events (load in Perfetto), ``ts`` on
        the profiler's host clock so they line up with a ``.xplane.pb``."""
        pid = os.getpid()
        events = []
        for s in self.spans():
            ev = {
                "name": s.name,
                "cat": s.cat or "default",
                "ph": "X",
                "ts": s.t0 * 1e6,      # Chrome trace wants microseconds
                "dur": s.dur * 1e6,
                "pid": pid,
                "tid": s.tid,
            }
            if s.args:
                ev["args"] = s.args
            events.append(ev)
        return events

    def write_chrome_trace(self, path: str) -> int:
        """Write ``{"traceEvents": [...]}`` JSON; returns the event count."""
        events = self.chrome_trace()
        with open(path, "w") as f:
            json.dump({"traceEvents": events,
                       "displayTimeUnit": "ms"}, f)
        return len(events)

    def stall_report(self) -> str:
        """Plain-text attribution report: where did the wall time go?

        Groups spans by name (count, total, self, p50/p95 of the durations)
        and by category (self time), and closes with the trainer's
        critical-path split: its waits on the data plane (``CRITICAL_WAITS``
        outside staging spans) vs compute (the ``compute`` category). Self
        time keeps nested spans from counting twice; spans on different
        threads are summed, not deduplicated — the report attributes
        *work*, not wall-clock occupancy.
        """
        spans = self.spans()
        if not spans:
            return "no spans recorded (is tracing enabled?)\n"
        own = self_times(spans)
        by_id = {s.id: s for s in spans}
        by_name: Dict[str, List[Span]] = {}
        by_cat: Dict[str, float] = {}
        for s in spans:
            by_name.setdefault(s.name, []).append(s)
            cat = s.cat or "default"
            by_cat[cat] = by_cat.get(cat, 0.0) + own[s.id]

        def staged(s: Span) -> bool:
            p = by_id.get(s.parent)
            while p is not None:
                if p.cat == STAGING_CAT:
                    return True
                p = by_id.get(p.parent)
            return False

        lines = [f"{'span':<28} {'count':>7} {'total_ms':>10} "
                 f"{'self_ms':>10} {'p50_ms':>9} {'p95_ms':>9}"]
        for name in sorted(by_name,
                           key=lambda n: -sum(own[s.id] for s in by_name[n])):
            ss = by_name[name]
            ps = percentiles([s.dur for s in ss], (50.0, 95.0))
            lines.append(f"{name:<28} {len(ss):>7} "
                         f"{sum(s.dur for s in ss) * 1e3:>10.2f} "
                         f"{sum(own[s.id] for s in ss) * 1e3:>10.2f} "
                         f"{ps[50.0] * 1e3:>9.3f} {ps[95.0] * 1e3:>9.3f}")
        compute = by_cat.get(COMPUTE_CAT, 0.0)
        data = sum(own[s.id] for s in spans
                   if s.name in CRITICAL_WAITS and not staged(s))
        lines.append("")
        for cat in sorted(by_cat, key=by_cat.get, reverse=True):
            lines.append(f"category {cat:<18} {by_cat[cat] * 1e3:>10.2f} ms")
        total = compute + data
        if total > 0:
            lines.append(f"data-plane wait {data * 1e3:.2f} ms vs compute "
                         f"{compute * 1e3:.2f} ms "
                         f"({100.0 * data / total:.1f}% data-plane)")
        return "\n".join(lines) + "\n"


#: process-wide tracer every instrumented component uses
TRACER = Tracer()


def enable_tracing(capacity: Optional[int] = None) -> Tracer:
    """Turn on the global tracer (optionally resizing its ring)."""
    if capacity is not None:
        with TRACER._lock:
            TRACER._ring = deque(TRACER._ring, maxlen=capacity)
    return TRACER.enable()


def disable_tracing() -> Tracer:
    return TRACER.disable()


def trace_span(name: str, cat: str = "", *, parent: Optional[int] = None,
               **args):
    """Module-level shortcut: ``with trace_span("commit.cput", cat="commit")``."""
    if not TRACER.enabled:
        return _NULL_SPAN
    return _LiveSpan(TRACER, name, cat, args or None, parent)
