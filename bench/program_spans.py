"""The program's own phase spans (``repro.obs.spans``) over a run's window.

The program keeps one process-wide record per span name, on the host clock
that the harness's batch records use (``time.perf_counter``). A window's
share is the calls that started between the window's first hand-over to
``process`` and its last return: nothing else drives the plane in that
interval. A program older than its spans has no record and reads nothing;
a window longer than the record's history of one span raises.
"""
from __future__ import annotations

from typing import Optional


def window_ns(run, name: str) -> Optional[int]:
    """Summed ns of ``name``'s calls inside the window's batches; ``None``
    where the program keeps no such record or the window has no batch."""
    if not run.records:
        return None
    try:
        from repro.obs import spans
    except ImportError:
        return None
    lo = min(r.due for r in run.records)
    hi = max(r.returned for r in run.records)
    return spans.between(name, int(lo * 1e9), int(hi * 1e9))[1]


def per_batch_ms(run, name: str) -> Optional[float]:
    """``window_ns`` per window batch, in ms; ``None`` where the span was
    never entered in the window."""
    ns = window_ns(run, name)
    return ns / len(run.records) / 1e6 if ns else None
