"""The harness's clock: the window, waiting for due times, and the host
spans it writes into the profiler's trace."""
from __future__ import annotations

import contextlib
import time

#: How long past the window's close the harness keeps answering requests
#: that fell due inside it, before it counts the rest as failed.
DRAIN_S = 60.0


def annotate(name: str):
    """A host span in the profiler's trace (no cost when not tracing)."""
    try:
        import jax
    except ImportError:                       # pragma: no cover
        return contextlib.nullcontext()
    return jax.profiler.TraceAnnotation(name)


class Clock:
    """A window of ``seconds`` that opens ``lead`` seconds from now (the
    lead-in brings the open loop to its steady state first).
    ``wait_until`` sleeps, then spins, to a due time, and keeps how late it
    woke (the generator's own lateness, not the system's backlog): the
    most, and each wake later than ``LATE_S`` with its time in the
    window."""

    LATE_S = 5e-3

    def __init__(self, seconds: float, drain_s: float = DRAIN_S,
                 lead: float = 0.0):
        self.t0 = time.perf_counter() + lead
        self.end = self.t0 + seconds
        self.drain_s = drain_s
        self.late_max_s = 0.0
        self.late: list[tuple[float, float]] = []

    def wait_until(self, t: float) -> float:
        now = time.perf_counter()
        if now >= t:
            return now
        with annotate("chipbench/idle_wait"):
            if t - now > 2e-3:
                time.sleep(t - now - 1e-3)
            while (now := time.perf_counter()) < t:
                pass
        self.late_max_s = max(self.late_max_s, now - t)
        if now - t > self.LATE_S:
            self.late.append((t - self.t0, now - t))
        return now


class Phases:
    """Wall seconds of named set-up phases, for the log."""

    def __init__(self):
        self.t = time.perf_counter()
        self.parts: list[tuple[str, float]] = []

    def mark(self, name: str):
        now = time.perf_counter()
        self.parts.append((name, now - self.t))
        self.t = now

    def __str__(self) -> str:
        return ", ".join(f"{n} {s:.2f} s" for n, s in self.parts)
