"""Span-based request-path tracing with Chrome trace-event export.

A :class:`TraceBuffer` records named spans — either live via the
``span()`` context manager (enter/exit stamps ``time.perf_counter``) or
retroactively via ``add_span(name, t0, t1)`` with timestamps the caller
already holds (the serving engine stamps request arrival/completion
itself).  ``to_chrome()`` renders the buffer as Chrome trace-event JSON
(the ``chrome://tracing`` / Perfetto ``traceEvents`` format), so a
serving run's request lifecycle — arrive → hit / queue → fill → complete
— loads straight into a trace viewer.

``annotate(name)`` writes a host span into the JAX profiler's own trace
(a ``jax.profiler.TraceAnnotation``), which shares the device trace's
clock: a span there lies beside the device ops it launched or left the
chip idle for.  It is a no-op context until jax is loaded, so host-only
consumers never pay a jax import.  The program's hot path is marked with
``rac/...`` annotations (see ``docs/observability.md``), and
``TraceBuffer.span`` enters one too, so a live span recorded for a Chrome
export also lies on the profiler's timeline.  Retroactive ``add_span``
records (times the caller already holds) stay host-only: the profiler
cannot take a span after the fact.

Trace ids are process-monotonic ints from :func:`next_trace_id` —
decisions must never depend on telemetry, so ids come from a counter,
not a random source.
"""
from __future__ import annotations

import contextlib
import itertools
import json
import sys
import threading
import time
from typing import Optional

__all__ = ["TraceBuffer", "annotate", "next_trace_id"]

_trace_ids = itertools.count(1)
_NULL_SPAN = contextlib.nullcontext()       # reusable & reentrant


def next_trace_id() -> int:
    """Monotonic per-process trace/request id (deterministic, not random)."""
    return next(_trace_ids)


def annotate(name: str):
    """A host span ``name`` in the JAX profiler's trace (one clock with the
    device's); a no-op context when jax is not loaded, since a process
    that never imported jax has no profiler trace to write into."""
    jax = sys.modules.get("jax")
    if jax is None:
        return _NULL_SPAN
    return jax.profiler.TraceAnnotation(name)


class TraceBuffer:
    """Bounded in-memory span store with Chrome trace-event export.

    Spans are ``(name, t0, dur, track, tags)`` with times in seconds on
    the ``time.perf_counter`` clock; export converts to the microsecond
    timestamps Chrome expects, relative to the buffer's construction
    origin.  ``max_events`` bounds memory on long runs (oldest spans are
    dropped in blocks; the drop count is reported in the export metadata
    so a truncated trace is never mistaken for a complete one).
    """

    def __init__(self, max_events: int = 100_000):
        self.origin = time.perf_counter()
        self.max_events = int(max_events)
        self.events: list[tuple] = []
        self.dropped = 0
        self._lock = threading.Lock()

    def __len__(self) -> int:
        return len(self.events)

    def add_span(self, name: str, t0: float, t1: float, *, track: int = 0,
                 tags: Optional[dict] = None) -> None:
        """Record one completed span (perf_counter seconds)."""
        with self._lock:
            self.events.append((name, t0, max(0.0, t1 - t0), track, tags))
            if len(self.events) > self.max_events:
                cut = max(1, self.max_events // 10)
                del self.events[:cut]
                self.dropped += cut

    @contextlib.contextmanager
    def span(self, name: str, *, track: int = 0, tags: Optional[dict] = None):
        """Time the enclosed block into the buffer; the block is also an
        :func:`annotate` span, so it shows in a profiler trace as well."""
        with annotate(name):
            t0 = time.perf_counter()
            try:
                yield self
            finally:
                self.add_span(name, t0, time.perf_counter(), track=track,
                              tags=tags)

    # ------------------------------------------------------------- export
    def to_chrome(self) -> dict:
        """Render as a Chrome trace-event JSON object (``traceEvents`` in
        the "X" complete-event form; load via chrome://tracing, Perfetto,
        or ``json.load``)."""
        with self._lock:
            events = list(self.events)
            dropped = self.dropped
        trace_events = [
            {"name": name, "cat": "repro", "ph": "X",
             "ts": (t0 - self.origin) * 1e6, "dur": dur * 1e6,
             "pid": 0, "tid": track, "args": dict(tags) if tags else {}}
            for name, t0, dur, track, tags in events]
        return {"traceEvents": trace_events, "displayTimeUnit": "ms",
                "otherData": {"dropped_spans": dropped}}

    def export_chrome(self, path: str) -> str:
        with open(path, "w") as f:
            json.dump(self.to_chrome(), f)
        return path
