"""Host spans of the data plane, on the profiler's clock.

``span(name, **meta)`` marks one phase of the per-batch hot path (the
Traffic Orchestrator's classification, the fused dispatch's index algebra
and enqueue). It does two things:

  * it enters ``jax.profiler.TraceAnnotation(name, **meta)``, so while a
    profiler session is active the phase is written into the profiler's own
    trace, on the issuing thread and on the clock of the device's
    operations; with no session active the annotation is a cheap no-op;
  * it adds the phase's host-clock duration (``time.perf_counter_ns``) to a
    process-wide record per name: cumulative ns and calls, and the start
    and length of the most recent ``HISTORY`` calls.

``set_gauge(name, value)`` keeps one process-wide number per name, the last
value set (the regex accelerator's compiled automaton: its states and the
bytes it holds on the device); ``gauges()`` returns them all.

``totals()`` returns the cumulative record as plain dicts. Like the
orchestrator's ``fast_stats`` the totals only go up, so callers read them
by deltas. ``between(name, lo_ns, hi_ns)`` sums the recent calls that
started inside an interval of the same clock (``time.perf_counter`` is
that clock in seconds), for a reader that knows a window's bounds but took
no snapshot of ``totals()`` at its start; it raises once the history no
longer reaches back to the interval's start.

Per-batch data-plane phases do not go into ``obs.trace.DecisionTrace``:
that log is the control plane's causal audit on logical ticks, one event
dict per decision. A batch runs several phases, each a few microseconds
to tens of milliseconds, and what they must be compared with is the
device's timeline, which only the profiler's clock shares.
"""
from __future__ import annotations

import threading
import time
from typing import Dict, Tuple

import jax
import numpy as np

HISTORY = 1 << 14      # recent calls kept per name (start ns, length ns)


class _Record:
    __slots__ = ("ns", "calls", "start", "dur")

    def __init__(self):
        self.ns = 0
        self.calls = 0
        self.start = np.zeros(HISTORY, np.int64)
        self.dur = np.zeros(HISTORY, np.int64)


_RECORDS: Dict[str, _Record] = {}
_GAUGES: Dict[str, float] = {}
_LOCK = threading.Lock()


class span:
    """Context manager: one call of the phase ``name`` (see the module
    docstring); ``meta`` goes to the profiler's event as its arguments."""

    __slots__ = ("_name", "_ann", "_t0")

    def __init__(self, name: str, **meta):
        self._name = name
        self._ann = jax.profiler.TraceAnnotation(name, **meta)

    def __enter__(self) -> "span":
        self._ann.__enter__()
        self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc) -> None:
        t1 = time.perf_counter_ns()
        self._ann.__exit__(*exc)
        ns = t1 - self._t0
        with _LOCK:
            rec = _RECORDS.get(self._name)
            if rec is None:
                rec = _RECORDS[self._name] = _Record()
            i = rec.calls % HISTORY
            rec.start[i] = self._t0
            rec.dur[i] = ns
            rec.ns += ns
            rec.calls += 1


def set_gauge(name: str, value: float) -> None:
    """Set the process-wide gauge ``name`` to ``value``."""
    with _LOCK:
        _GAUGES[name] = float(value)


def gauges() -> Dict[str, float]:
    """``{name: value}`` of every gauge set so far."""
    with _LOCK:
        return dict(_GAUGES)


def totals() -> Dict[str, Dict[str, int]]:
    """``{name: {"ns", "calls"}}`` for every name seen so far."""
    with _LOCK:
        return {n: {"ns": r.ns, "calls": r.calls}
                for n, r in _RECORDS.items()}


def between(name: str, lo_ns: int, hi_ns: int) -> Tuple[int, int]:
    """Calls of ``name`` that started in ``[lo_ns, hi_ns]`` and their summed
    ns; ``(0, 0)`` for a name never entered. Raises ``RuntimeError`` when
    the record no longer reaches back to ``lo_ns`` (more than ``HISTORY``
    calls since)."""
    with _LOCK:
        rec = _RECORDS.get(name)
        if rec is None:
            return 0, 0
        calls = rec.calls
        kept = min(calls, HISTORY)
        idx = (calls - kept + np.arange(kept)) % HISTORY
        start, dur = rec.start[idx], rec.dur[idx]
    if calls > HISTORY and start[0] > lo_ns:
        raise RuntimeError(
            f"span {name!r}: the last {HISTORY} calls start after "
            f"{lo_ns} ns; the interval is no longer held")
    inside = (start >= lo_ns) & (start <= hi_ns)
    return int(inside.sum()), int(dur[inside].sum())
