"""The readers of the program's phase spans (``bench/program_spans.py`` and
the ``program_span`` metrics of ``BENCHMARK.json``), on hand-made runs and
on a tiny run on the CPU; and the trace reduction's attribution of idle
time to ``meili.*`` spans nested in the benchmark's."""
import itertools
import sys
import time

import pytest

from bench import harness, spec
from bench import trace as tracemod
from repro.obs import spans

# What each reader reads from the hand-made run: every span 1000 ns a batch,
# ``meili.to.assign`` 11000 ns around its five phases.
WANT = {"to.probe_ms": 1e-3, "to.self_ms": 10e-3, "to.flow_ids_ms": 1e-3,
        "to.miss_loop_ms": 1e-3, "to.commit_ms": 1e-3,
        "to.maintain_ms": 1e-3, "dispatch.index_ms": 1e-3,
        "dispatch.enqueue_ms": 1e-3}
NEW = tuple(WANT)
TO_PHASES = ("meili.to.flow_ids", "meili.to.probe", "meili.to.miss_loop",
             "meili.to.commit", "meili.to.maintain")


def readers():
    entries = {m["name"]: m for m in spec.load_benchmark()["per_layer"]}
    return {n: spec.metric(entries[n]) for n in NEW}


def test_idle_gaps_go_to_program_spans_nested_in_the_benchmarks():
    window = [("bench.window", 0, 100), ("bench.dispatch_host", 1, 90),
              ("bench.classify", 2, 60), ("meili.to.assign", 3, 58),
              ("meili.to.flow_ids", 4, 10), ("meili.to.probe", 10, 40),
              ("meili.to.commit", 45, 58), ("meili.dispatch.index", 61, 70),
              ("meili.dispatch.enqueue", 70, 88)]
    ops = [("jit__lookup_jnp/fusion", 30, 40),
           ("jit_dispatch/fusion.1", 88, 95)]
    red = tracemod.reduce(
        {"devices": {"/device:TPU:0": {"ops": [list(o) for o in ops],
                                       "modules": []}},
         "host": {"/host:CPU/main": [list(s) for s in window]}})
    # idle: [0,30) [40,88) [95,100); bare bench.classify: [2,3) [58,60)
    assert red.gap_s == pytest.approx({
        "host.other": 6e-9, "bench.dispatch_host": 2e-9,
        "bench.classify": 3e-9, "meili.to.assign": 6e-9,
        "meili.to.flow_ids": 6e-9, "meili.to.probe": 20e-9,
        "meili.to.commit": 13e-9, "meili.dispatch.index": 9e-9,
        "meili.dispatch.enqueue": 18e-9})


class FakeClock:
    def __init__(self, start):
        self._next = itertools.count(start, 1000)

    def perf_counter_ns(self):
        return next(self._next)


def hand_made_run(monkeypatch, batches=3):
    """``batches`` window batches, each: ``meili.to.assign`` holding the
    TO's five phases, then the dispatch's index and enqueue; one more
    batch's spans fall after the window and must not count."""
    base = time.perf_counter_ns() + 10**12       # clear of real calls
    monkeypatch.setattr(spans, "time", FakeClock(base))
    records = []
    for k in range(batches + 1):
        due = spans.time.perf_counter_ns()
        with spans.span("meili.to.assign"):
            for name in TO_PHASES:
                with spans.span(name):
                    pass
        for name in ("meili.dispatch.index", "meili.dispatch.enqueue"):
            with spans.span(name):
                pass
        ret = spans.time.perf_counter_ns()
        records.append(harness.BatchRecord(k, 16, due * 1e-9, ret * 1e-9,
                                           ret * 1e-9, 11e-6))
    return harness.Run(None, 1.0, 0.0, 0.0, records[:batches], {})


@pytest.mark.parametrize("name", NEW)
def test_readers_on_a_hand_made_run(monkeypatch, name):
    run = hand_made_run(monkeypatch)
    assert readers()[name].read(run) == pytest.approx(WANT[name])


def test_a_window_longer_than_the_history_is_an_error(monkeypatch):
    monkeypatch.setattr(spans, "HISTORY", 2)
    monkeypatch.setattr(spans, "_RECORDS", {})
    run = hand_made_run(monkeypatch)
    with pytest.raises(RuntimeError, match="no longer held"):
        readers()["to.probe_ms"].read(run)


def test_readers_find_nothing_in_a_program_without_spans(monkeypatch):
    run = hand_made_run(monkeypatch)
    monkeypatch.setitem(sys.modules, "repro.obs.spans", None)
    monkeypatch.delattr(sys.modules["repro.obs"], "spans")
    assert {n: m.read(run) for n, m in readers().items()} == dict.fromkeys(
        NEW)
    assert all(m.read(harness.Run(None, 1.0, 0.0, 0.0, [], {})) is None
               for m in readers().values())


def test_a_tiny_run_splits_the_outside_timings(monkeypatch):
    """On the CPU the numbers are no device's; what holds anywhere is that
    the program's spans lie inside the benchmark's timers."""
    cell = spec.resolve("fw.min64")
    cell.mix.update(batch=128, flows=1500, warmup_batches=2)
    seen = {}
    read = harness.read_metrics
    monkeypatch.setattr(harness, "read_metrics",
                        lambda metrics, run: seen.setdefault("run", run)
                        and read(metrics, run))
    harness.measure(cell, 2**31 + 7, 0.3, False, log=lambda _l: None)
    run = seen["run"]
    got = {n: m.read(run) for n, m in readers().items()}
    outside = {m.name: m.read(run) for m in map(spec.metric, [
        {"name": "to.classify_ms", "unit": "ms"},
        {"name": "dispatch.host_ms", "unit": "ms"}])}
    assert all(v is not None and v > 0 for v in got.values())
    assert got["to.probe_ms"] + got["to.self_ms"] <= outside["to.classify_ms"]
    assert (got["to.flow_ids_ms"] + got["to.miss_loop_ms"]
            + got["to.commit_ms"] + got["to.maintain_ms"]) <= got["to.self_ms"]
    assert (got["dispatch.index_ms"] + got["dispatch.enqueue_ms"]
            <= outside["dispatch.host_ms"])
