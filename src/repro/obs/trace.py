"""Request tracing: a thread-safe, bounded ring-buffer span recorder.

The serving runtime (and the engine's compile pipeline) emit *spans* — named
intervals with attributes — and instant *events* into a :class:`Tracer`.
Design constraints, in order:

  * **near-zero overhead when disabled** — every instrumentation site checks
    ``tracer.enabled`` (one attribute read) before building any attribute
    dict; a disabled tracer records nothing and allocates nothing while
    no profiler session records.
    ``NULL_TRACER`` is the shared disabled instance every un-instrumented
    server uses, so the hot path never branches on ``None``;
  * **bounded memory** — spans live in a ``deque(maxlen=capacity)`` ring:
    a week-long server keeps the *latest* ``capacity`` spans and counts the
    rest in ``dropped`` instead of growing without bound;
  * **injected clock** — spans are timestamped on the same clock the server
    schedules on (``SparseServer(clock=...)``), so deterministic fake-clock
    tests produce deterministic traces;
  * **standard export** — :meth:`Tracer.export` writes either Chrome-trace
    JSON (loadable in ``chrome://tracing`` / `Perfetto <https://ui.perfetto.dev>`_)
    or JSONL (one span object per line, grep/jq-friendly);
  * **profiler capture** — while a ``jax.profiler`` session records, every
    scoped span (``with tracer.span(...)``) of every tracer, the disabled
    ``NULL_TRACER`` included, is also written into the profiler's trace as
    a ``TraceAnnotation`` (the ``/host:CPU`` plane, on the device trace's
    clock), and its duration is added to a process-wide per-name total
    (:func:`span_totals`); :func:`count` adds to a process-wide counter
    (:func:`counter_totals`) in the same sessions only.  The session is
    process-wide, and so are the totals.

Span taxonomy (names, attributes, units) is documented in
``docs/observability.md``.
"""

from __future__ import annotations

import dataclasses
import json
import os
import threading
import time
from collections import deque
from typing import Callable, Dict, List, Optional, Tuple

from jax.profiler import TraceAnnotation

__all__ = ["Span", "Tracer", "NULL_TRACER", "span_totals",
           "reset_span_totals", "count", "counter_totals"]

#: True while a profiler session records host events
_profiling = TraceAnnotation.is_enabled
#: the only span attributes written into the profiler's trace
_PROFILER_ARGS = ("bucket", "rows")

_totals_mu = threading.Lock()
_totals: Dict[str, List] = {}           # name -> [count, seconds]
_counters: Dict[str, int] = {}          # name -> total


def _add_total(name: str, seconds: float) -> None:
    with _totals_mu:
        t = _totals.get(name)
        if t is None:
            _totals[name] = [1, seconds]
        else:
            t[0] += 1
            t[1] += seconds


def span_totals() -> Dict[str, Tuple[int, float]]:
    """``{name: (count, seconds)}`` of the scoped spans that ran while a
    profiler session was recording (since the last
    :func:`reset_span_totals`); seconds are wall time on
    ``time.perf_counter``."""
    with _totals_mu:
        return {k: (n, s) for k, (n, s) in _totals.items()}


def reset_span_totals() -> None:
    """Start the span totals and the counters afresh."""
    with _totals_mu:
        _totals.clear()
        _counters.clear()


def count(name: str, n: int) -> None:
    """Add ``n`` to the process-wide counter ``name`` while a profiler
    session records; otherwise do nothing (one check of the session)."""
    if not _profiling():
        return
    with _totals_mu:
        _counters[name] = _counters.get(name, 0) + int(n)


def counter_totals() -> Dict[str, int]:
    """``{name: total}`` of what :func:`count` added while a profiler
    session was recording (since the last :func:`reset_span_totals`)."""
    with _totals_mu:
        return dict(_counters)


@dataclasses.dataclass
class Span:
    """One recorded interval (``phase="X"``) or instant event (``"i"``).

    Times are seconds on the tracer's clock; ``tid``/``thread`` identify the
    recording thread (Chrome trace rows group by tid)."""

    name: str
    t0: float
    t1: float
    tid: int
    thread: str
    phase: str = "X"                    # "X" complete span | "i" instant
    attrs: Dict[str, object] = dataclasses.field(default_factory=dict)

    @property
    def dur(self) -> float:
        return max(0.0, self.t1 - self.t0)

    def to_chrome(self, pid: int) -> dict:
        """One Chrome-trace event: complete (``X``, microsecond ``ts`` +
        ``dur``) or instant (``i``, thread-scoped)."""
        ev = {
            "name": self.name,
            "cat": self.name.split(".", 1)[0],
            "ph": self.phase,
            "ts": self.t0 * 1e6,
            "pid": pid,
            "tid": self.tid,
            "args": self.attrs,
        }
        if self.phase == "X":
            ev["dur"] = self.dur * 1e6
        else:
            ev["s"] = "t"               # instant events are thread-scoped
        return ev

    def to_dict(self) -> dict:
        return {"name": self.name, "t0": self.t0, "t1": self.t1,
                "dur": self.dur, "phase": self.phase, "tid": self.tid,
                "thread": self.thread, "attrs": self.attrs}


class _NullSpan:
    """The no-op context manager a disabled tracer hands out (shared
    singleton: entering/exiting it does nothing and allocates nothing)."""

    __slots__ = ()

    def __enter__(self) -> "_NullSpan":
        return self

    def __exit__(self, *exc) -> bool:
        return False

    def __setitem__(self, key, value) -> None:
        pass


_NULL_SPAN = _NullSpan()


class _SpanCtx:
    """Context manager timing one span into the sinks that were on when it
    was made: the tracer's ring (recorded on exit, on the tracer's clock)
    and the profiler (a ``TraceAnnotation`` carrying at most ``bucket`` and
    ``rows``, plus the span's :func:`span_totals` entry).  Attributes can
    be added mid-span with ``sp["key"] = value`` (e.g. an outcome only
    known at the end of the interval); they go to the ring only."""

    __slots__ = ("_tracer", "_name", "_attrs", "_t0", "_ann", "_p0")

    def __init__(self, tracer: "Tracer", name: str, attrs: Dict[str, object],
                 profiling: bool):
        self._tracer = tracer if tracer.enabled else None
        self._name = name
        self._attrs = attrs
        self._ann = None
        if profiling:
            self._ann = TraceAnnotation(
                name, **{k: attrs[k] for k in _PROFILER_ARGS if k in attrs})

    def __enter__(self) -> "_SpanCtx":
        if self._ann is not None:
            self._ann.__enter__()
            self._p0 = time.perf_counter()
        if self._tracer is not None:
            self._t0 = self._tracer.clock()
        return self

    def __setitem__(self, key: str, value) -> None:
        self._attrs[key] = value

    def __exit__(self, exc_type, exc, tb) -> bool:
        tracer = self._tracer
        if tracer is not None:
            if exc_type is not None:
                self._attrs.setdefault("error", exc_type.__name__)
            tracer.span_at(self._name, self._t0, tracer.clock(),
                           **self._attrs)
        if self._ann is not None:
            _add_total(self._name, time.perf_counter() - self._p0)
            self._ann.__exit__(exc_type, exc, tb)
        return False


class Tracer:
    """Thread-safe bounded span recorder.

    Args:
      capacity: ring-buffer bound — the newest ``capacity`` spans are kept,
        older ones are evicted and counted in ``dropped``.
      clock: monotonic time source (inject the server's fake clock in
        tests; defaults to ``time.monotonic``).
      enabled: a disabled tracer keeps no ring — ``span_at``/``event``
        return immediately, and ``span`` hands out a shared no-op unless a
        profiler session is recording.  Instrumentation sites should
        additionally guard attribute-dict construction behind
        ``tracer.enabled`` so a disabled tracer costs one attribute read
        per site.
    """

    def __init__(self, capacity: int = 16384,
                 clock: Callable[[], float] = time.monotonic,
                 enabled: bool = True):
        if capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {capacity}")
        self.capacity = capacity
        self.clock = clock
        self.enabled = enabled
        self._mu = threading.Lock()
        self._buf: deque = deque(maxlen=capacity)
        self.recorded = 0               # spans ever recorded
        self.dropped = 0                # spans evicted by the ring bound

    # ------------------------------------------------------------------ #
    # recording
    # ------------------------------------------------------------------ #
    def span(self, name: str, **attrs) -> "_SpanCtx | _NullSpan":
        """Context manager timing one interval: ``with tracer.span("x"): ...``.

        Recorded into the ring when the tracer is enabled, and into the
        profiler's trace and :func:`span_totals` while a profiler session
        records; with neither, the shared no-op."""
        profiling = _profiling()
        if not (self.enabled or profiling):
            return _NULL_SPAN
        return _SpanCtx(self, name, attrs, profiling)

    def span_at(self, name: str, t0: float, t1: float, **attrs) -> None:
        """Record a span whose endpoints were observed elsewhere (e.g. a
        request's queue interval, closed retroactively at batch formation)."""
        if not self.enabled:
            return
        t = threading.current_thread()
        self._record(Span(name=name, t0=t0, t1=t1, tid=t.ident or 0,
                          thread=t.name, attrs=attrs))

    def event(self, name: str, **attrs) -> None:
        """Record an instant event (a state transition, not an interval)."""
        if not self.enabled:
            return
        now = self.clock()
        t = threading.current_thread()
        self._record(Span(name=name, t0=now, t1=now, tid=t.ident or 0,
                          thread=t.name, phase="i", attrs=attrs))

    def _record(self, span: Span) -> None:
        with self._mu:
            if len(self._buf) == self.capacity:
                self.dropped += 1       # deque(maxlen) evicts the oldest
            self._buf.append(span)
            self.recorded += 1

    # ------------------------------------------------------------------ #
    # inspection / export
    # ------------------------------------------------------------------ #
    def spans(self) -> List[Span]:
        """Snapshot of the buffered spans, oldest first."""
        with self._mu:
            return list(self._buf)

    def clear(self) -> None:
        with self._mu:
            self._buf.clear()

    def snapshot(self) -> dict:
        with self._mu:
            return {"buffered": len(self._buf), "recorded": self.recorded,
                    "dropped": self.dropped, "capacity": self.capacity,
                    "enabled": self.enabled}

    def to_chrome(self) -> dict:
        """Chrome-trace/Perfetto-loadable JSON object.  Events are sorted by
        ``ts`` (retroactive spans can be recorded out of order; the sorted
        stream is what viewers — and the format validator in the tests —
        expect)."""
        pid = os.getpid()
        events = [s.to_chrome(pid) for s in self.spans()]
        events.sort(key=lambda e: (e["ts"], e.get("dur", 0.0)))
        return {"traceEvents": events, "displayTimeUnit": "ms"}

    def export_chrome(self, path: str) -> str:
        with open(path, "w") as fh:
            json.dump(self.to_chrome(), fh)
            fh.write("\n")
        return path

    def export_jsonl(self, path: str) -> str:
        with open(path, "w") as fh:
            for s in self.spans():
                fh.write(json.dumps(s.to_dict()) + "\n")
        return path

    def export(self, path: str) -> str:
        """Chrome-trace JSON by default; JSONL when ``path`` ends ``.jsonl``."""
        if path.endswith(".jsonl"):
            return self.export_jsonl(path)
        return self.export_chrome(path)


#: Shared disabled tracer: the default for every un-instrumented server, so
#: hot paths branch on ``tracer.enabled`` instead of ``tracer is None``.  Its
#: ring is off; its scoped spans still reach a recording profiler.
NULL_TRACER = Tracer(capacity=1, enabled=False)
