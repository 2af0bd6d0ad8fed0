"""Structured trace spans: one record, one clock, Chrome/Perfetto export.

This is the one tracing seam every layer threads through; nothing
times a block beside it.

- :func:`span` — context manager (or decorator) around a block. It
  ALWAYS wraps ``jax.profiler.TraceAnnotation``, so the name shows up
  on the host plane of a captured device trace, and ALWAYS folds the
  block's duration into :data:`TOTALS` (per name: count, total and
  self seconds — the counters the metrics registry publishes as source
  ``spans``). With a :class:`TraceRecorder` installed the completed
  span is also recorded with an ``id``, its ``parent`` (the innermost
  span open on the same thread, or the span a worker thread
  :func:`adopt`-ed) and an optional ``cause`` (the id of the span on
  another thread that handed this work over: the sweep's drain names
  its dispatch). The block receives the span's id.
- :func:`add_span` — record a span from explicit ``time.monotonic``
  begin/end stamps, for spans whose start predates the code that
  observes them (queue wait: submit -> dispatch; the sweep's tail).
- :func:`clock_anchor` — a zero-length ``lir/clock_anchor``
  annotation bracketed by two ``time.monotonic`` readings, recorded
  under the same name: a reader of a device trace finds the
  annotation on the profiler's clock and the recorder span on the
  serve clock, and the pair gives the offset between the two. Called
  right after a profiler starts and right before it stops, the second
  pair bounds the drift; every recorder span (set-up included, which
  ran before the profiler) then lies on the device timeline.
- :class:`TraceRecorder` — bounded ring of span events (oldest dropped,
  drops counted; ids stay unique) with
  :meth:`~TraceRecorder.export_chrome` producing the Chrome trace-event
  JSON that chrome://tracing and Perfetto load directly; ``--trace-out``
  on the serve/perturb CLIs writes it at exit.

Naming convention: spans are ``layer/stage`` (``sweep/plan``,
``sweep/drain_wait``, ``engine/compile_load``, ``serve/dispatch``,
``fleet/weight_swap``) with request / dispatch / model identity in
``args`` — the lifecycle of one request is the filter
``args.request_id == X``, of one sweep dispatch ``args.dispatch == N``.
Phases INSIDE a jitted program are not spans but ``jax.named_scope``s
named ``lir.<phase>`` (``lir.prefill``, ``lir.extend``, ``lir.decode``,
``lir.readout``; models/decoder.py and engine/generate.py), read back
per device operation through ``engine/compile_plan.scope_table``.
"""

from __future__ import annotations

import contextlib
import itertools
import json
import threading
import time
from collections import deque
from pathlib import Path
from typing import Dict, Iterator, List, Optional

DEFAULT_CAPACITY = 65536
CLOCK_ANCHOR = "lir/clock_anchor"

# Span ids are process-wide and never reused (a ring that overflowed
# still holds unique ids); next() on a count is atomic under the GIL.
_IDS = itertools.count(1)
# Per thread: ``stack`` of open spans as [id, seconds spent in children,
# args], and ``base``, the parent a worker thread adopted from its caller.
_LOCAL = threading.local()


def _stack() -> list:
    stack = getattr(_LOCAL, "stack", None)
    if stack is None:
        stack = _LOCAL.stack = []
    return stack


def current_span() -> Optional[int]:
    """Id of the innermost span open on this thread (else the adopted
    parent, else None)."""
    stack = _stack()
    return stack[-1][0] if stack else getattr(_LOCAL, "base", None)


def adopt(parent: Optional[int]) -> None:
    """Make ``parent`` the parent of spans this thread opens at top
    level: a worker running a call on behalf of another thread
    (guard/watchdog.watch_call) adopts the caller's open span, so spans
    under a watched dispatch are not orphans."""
    _LOCAL.base = parent


class SpanTotals:
    """Per span name: how many closed, their summed seconds, and their
    summed SELF seconds (duration minus the spans nested inside on the
    same thread). Always on — two clock reads and one locked dict update
    per span — and registered as the metrics source ``spans``, so a
    span is also a counter a per-layer metric can read."""

    def __init__(self):
        self._lock = threading.Lock()
        self._names: Dict[str, List[float]] = {}  # guarded-by: _lock

    def add(self, name: str, seconds: float, self_seconds: float) -> None:
        with self._lock:
            rec = self._names.setdefault(name, [0, 0.0, 0.0])
            rec[0] += 1
            rec[1] += seconds
            rec[2] += self_seconds

    def clear(self) -> None:
        with self._lock:
            self._names.clear()

    def summary(self) -> Dict[str, Dict[str, float]]:
        with self._lock:
            return {name: {"count": int(c), "total_s": t, "self_s": s}
                    for name, (c, t, s) in sorted(self._names.items())}


TOTALS = SpanTotals()


class TraceRecorder:
    """Bounded in-memory span ring. Thread-safe — every serving and
    sweep thread appends concurrently; export snapshots under the lock.

    Timestamps are ``time.monotonic`` seconds (the serve clock domain);
    export rebases them onto the recorder's construction time so traces
    start near zero.
    """

    def __init__(self, capacity: int = DEFAULT_CAPACITY):
        self.capacity = max(int(capacity), 1)
        self._lock = threading.Lock()
        self._events: deque = deque(maxlen=self.capacity)  # guarded-by: _lock
        self._dropped = 0  # guarded-by: _lock
        self._t0 = time.monotonic()

    def add(self, name: str, t0: float, t1: float, cat: str = "host",
            args: Optional[Dict] = None, span_id: Optional[int] = None,
            parent: Optional[int] = None,
            cause: Optional[int] = None) -> int:
        """Record one completed span; returns its id (``span_id`` when
        the opener already drew one, else a fresh one)."""
        if span_id is None:
            span_id = next(_IDS)
        ev = {"name": str(name), "cat": str(cat), "t0": float(t0),
              "t1": float(t1), "id": span_id,
              "thread": threading.current_thread().name}
        if parent is not None:
            ev["parent"] = parent
        if cause is not None:
            ev["cause"] = cause
        if args:
            ev["args"] = args
        with self._lock:
            if len(self._events) == self.capacity:
                self._dropped += 1
            self._events.append(ev)
        return span_id

    def __len__(self) -> int:
        with self._lock:
            return len(self._events)

    @property
    def dropped(self) -> int:
        with self._lock:
            return self._dropped

    def events(self) -> List[Dict]:
        with self._lock:
            return list(self._events)

    def clear(self) -> None:
        with self._lock:
            self._events.clear()
            self._dropped = 0

    def summary(self) -> Dict[str, object]:
        """Registry-facing counters (the recorder is itself a metrics
        source: span volume and ring pressure are operator signals)."""
        with self._lock:
            n = len(self._events)
            names: Dict[str, int] = {}
            for ev in self._events:
                names[ev["name"]] = names.get(ev["name"], 0) + 1
            return {"spans": n, "dropped": self._dropped,
                    "capacity": self.capacity,
                    "per_name": dict(sorted(names.items()))}

    # -- Chrome trace-event export -------------------------------------------

    def export_chrome(self, path: Optional[Path] = None) -> Dict:
        """The Chrome trace-event JSON (``ph: "X"`` complete events, µs
        timestamps, one tid per recording thread with ``thread_name``
        metadata). Loads directly in chrome://tracing and Perfetto;
        device traces captured with ``jax.profiler`` carry the SAME
        span names via TraceAnnotation, so host and device views line
        up by name."""
        events = self.events()
        tids: Dict[str, int] = {}
        trace_events: List[Dict] = []
        for ev in events:
            tid = tids.setdefault(ev["thread"], len(tids) + 1)
            rec = {
                "name": ev["name"], "cat": ev["cat"], "ph": "X",
                "ts": (ev["t0"] - self._t0) * 1e6,
                "dur": max(ev["t1"] - ev["t0"], 0.0) * 1e6,
                "pid": 1, "tid": tid,
            }
            # The causal record rides in args (top-level "id" means an
            # async event to the Chrome format).
            rec["args"] = dict(ev.get("args", {}), span=ev["id"],
                               **{k: ev[k] for k in ("parent", "cause")
                                  if k in ev})
            trace_events.append(rec)
        for name, tid in tids.items():
            trace_events.append({
                "name": "thread_name", "ph": "M", "pid": 1, "tid": tid,
                "args": {"name": name}})
        out = {"traceEvents": trace_events, "displayTimeUnit": "ms",
               "otherData": {"dropped_spans": self.dropped}}
        if path is not None:
            Path(path).write_text(json.dumps(out), encoding="utf-8")
        return out


# Process-wide recorder. None (the default) keeps spans at
# TraceAnnotation-only cost; the CLI installs one under --trace-out,
# the bench's observatory mode and tests install their own.
_RECORDER: Optional[TraceRecorder] = None


def set_recorder(rec: Optional[TraceRecorder]) -> Optional[TraceRecorder]:
    """Install (or clear, with None) the process recorder; returns the
    previous one so tests can restore it."""
    global _RECORDER
    prev, _RECORDER = _RECORDER, rec
    return prev


def get_recorder() -> Optional[TraceRecorder]:
    return _RECORDER


@contextlib.contextmanager
def span(name: str, cat: str = "host", cause: Optional[int] = None,
         **args) -> Iterator[int]:
    """Named span around a block (also usable as a decorator): ALWAYS
    annotated into device traces (``jax.profiler.TraceAnnotation`` —
    effectively free when no profiler is capturing) and folded into
    :data:`TOTALS`; recorded with id / parent / ``cause`` when a
    recorder is installed. Yields the span's id, for a consumer on
    another thread to name as its ``cause``."""
    import jax

    stack = _stack()
    parent = stack[-1][0] if stack else getattr(_LOCAL, "base", None)
    frame = [next(_IDS), 0.0, args]
    with jax.profiler.TraceAnnotation(name):
        stack.append(frame)
        t0 = time.monotonic()
        try:
            yield frame[0]
        finally:
            t1 = time.monotonic()
            stack.pop()
            if stack:
                stack[-1][1] += t1 - t0
            TOTALS.add(name, t1 - t0, t1 - t0 - frame[1])
            rec = _RECORDER
            if rec is not None:
                rec.add(name, t0, t1, cat, frame[2] or None,
                        span_id=frame[0], parent=parent, cause=cause)


def annotate(**args) -> None:
    """Add ``args`` to the innermost span open on this thread: what a
    block learns only while it runs (whether the persistent cache
    served a compile). No-op outside a span."""
    stack = _stack()
    if stack:
        stack[-1][2].update(args)


def add_span(name: str, t0: float, t1: float, cat: str = "host",
             cause: Optional[int] = None, **args) -> None:
    """Record a completed span from explicit ``time.monotonic``
    begin/end stamps (queue-wait spans start at submit time, long
    before the dispatch path observes them). Its parent is the span
    open on the calling thread; it may start before that parent did, so
    it never counts against the parent's self time."""
    TOTALS.add(name, t1 - t0, t1 - t0)
    rec = _RECORDER
    if rec is not None:
        rec.add(name, t0, t1, cat, args or None, parent=current_span(),
                cause=cause)


def clock_anchor() -> None:
    """Tie the recorder's clock to a capturing profiler's: a zero-length
    ``lir/clock_anchor`` annotation between two ``time.monotonic``
    readings, recorded as a span of the same name over those readings.
    The annotation's profiler timestamp lies inside the recorded
    interval, so their midpoints differ by the clocks' offset, to
    within half the interval (microseconds)."""
    import jax

    t0 = time.monotonic()
    with jax.profiler.TraceAnnotation(CLOCK_ANCHOR):
        pass
    t1 = time.monotonic()
    rec = _RECORDER
    if rec is not None:
        rec.add(CLOCK_ANCHOR, t0, t1, "clock")
