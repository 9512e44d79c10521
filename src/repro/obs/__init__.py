"""Pool-wide observability (ISSUE 7): metrics registry + decision-audit trace.

``Obs`` is the bundle every layer shares: a ``MetricsRegistry`` (counters /
gauges / histograms with measured streaming percentiles) and a
``DecisionTrace`` (the causally-ordered decision/fault/span event log). The
controller creates one by default and hands it to its governor; the service
runtime reuses the controller's so all layers write one log. Recording is
always on — events are list appends and histogram observes, cheap enough
that the chaos benchmark's wall-clock budget (<5% overhead) holds — and
export is explicit (``dump``).

``spans`` is apart from ``Obs``: the data plane's per-batch phase spans,
written into the profiler's trace and into one process-wide record.
"""
from __future__ import annotations

import pathlib
from typing import Iterable, Optional

from repro.obs.metrics import (Counter, Gauge, Histogram,  # noqa: F401
                               MetricsRegistry)
from repro.obs.percentiles import P2Quantile, Reservoir    # noqa: F401
from repro.obs import spans                               # noqa: F401
from repro.obs.trace import (DECISION, FAULT, MARK, RECONCILE,  # noqa: F401
                             SPAN, DecisionTrace, Span, TraceEvent)


class Obs:
    """One observability context: metrics + trace, shared across layers."""

    def __init__(self, seed: int = 0, clock=None):
        self.metrics = MetricsRegistry(seed=seed)
        self.trace = (DecisionTrace(clock=clock) if clock is not None
                      else DecisionTrace())

    def set_tick(self, tick: int) -> None:
        self.trace.set_tick(tick)

    # -- data-plane snapshot ---------------------------------------------------
    def snapshot_compile_caches(self, planes: Iterable = ()) -> None:
        """Pull the process-wide compile-cache hit/miss counters
        (core.graph) and per-plane dispatch stats into registry gauges, so
        an exported artifact carries the zero-steady-state-recompile
        evidence beside the latency series."""
        from repro.core import graph
        for cache, stats in graph.compile_cache_stats().items():
            for field, v in stats.items():
                self.metrics.gauge("compile_cache_" + field,
                                   cache=cache).set(v)
        calls = compiles = 0
        for dp in planes:
            calls += dp.dispatch_stats.get("calls", 0)
            compiles += dp.dispatch_stats.get("compiles", 0)
        if calls or compiles:
            self.metrics.gauge("dataplane_dispatch_calls").set(calls)
            self.metrics.gauge("dataplane_dispatch_compiles").set(compiles)

    # -- artifact export -------------------------------------------------------
    def dump(self, out_dir, prefix: str = "") -> dict:
        """Write ``trace.jsonl``, ``metrics.jsonl``, and ``metrics.prom``
        under ``out_dir`` (created if missing); returns the paths."""
        out = pathlib.Path(out_dir)
        out.mkdir(parents=True, exist_ok=True)
        p = (prefix + "." if prefix else "")
        paths = {
            "trace": out / f"{p}trace.jsonl",
            "metrics": out / f"{p}metrics.jsonl",
            "prom": out / f"{p}metrics.prom",
        }
        self.trace.dump_jsonl(paths["trace"])
        self.metrics.dump_jsonl(paths["metrics"])
        paths["prom"].write_text(self.metrics.render_prometheus())
        return {k: str(v) for k, v in paths.items()}


def load_trace(path) -> DecisionTrace:
    """Load a dumped ``trace.jsonl`` artifact back into a queryable trace."""
    return DecisionTrace.load_jsonl(path)


# SLO / alerting / flight-recorder layer (ISSUE 10). Imported last: these
# modules use ``from repro.obs import Obs``, which needs the class above to
# exist during this package's own initialization.
from repro.obs.alerts import (AlertTransition, BurnAlertManager,  # noqa: E402,F401
                              BurnRule, DEFAULT_RULES, FIRING, PAGE,
                              RESOLVED, WARN)
from repro.obs.flight import FlightRecorder, load_bundle  # noqa: E402,F401
from repro.obs.slo import (BurnSample, SLOEngine, SLOPolicy,  # noqa: E402,F401
                           TenantBudget)
