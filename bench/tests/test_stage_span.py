"""The reader of the put that starts the ingress batch's copy
(``dispatch.stage_ms``, the program's ``meili.dispatch.stage`` span): on a
hand-made run, on a program without the span, and on a tiny run on the
CPU."""
import itertools
import sys
import time

import pytest

from bench import harness, spec
from repro.obs import spans

PHASES = ("meili.dispatch.stage", "meili.to.assign", "meili.dispatch.index",
          "meili.dispatch.enqueue")


def reader(name="dispatch.stage_ms"):
    entries = {m["name"]: m for m in spec.load_benchmark()["per_layer"]}
    return spec.metric(entries[name])


class FakeClock:
    def __init__(self, start):
        self._next = itertools.count(start, 1000)

    def perf_counter_ns(self):
        return next(self._next)


def hand_made_run(monkeypatch, batches=3):
    """``batches`` window batches of four 1000 ns spans each, the put
    first; one more batch falls after the window and must not count."""
    base = time.perf_counter_ns() + 2 * 10**12   # clear of real calls
    monkeypatch.setattr(spans, "time", FakeClock(base))
    records = []
    for k in range(batches + 1):
        due = spans.time.perf_counter_ns()
        for name in PHASES:
            with spans.span(name):
                pass
        ret = spans.time.perf_counter_ns()
        records.append(harness.BatchRecord(k, 16, due * 1e-9, ret * 1e-9,
                                           ret * 1e-9, 1e-6))
    return harness.Run(None, 1.0, 0.0, 0.0, records[:batches], {})


def test_the_reader_on_a_hand_made_run(monkeypatch):
    assert reader().read(hand_made_run(monkeypatch)) == pytest.approx(1e-3)


def test_the_reader_finds_nothing_in_a_program_without_the_span(monkeypatch):
    run = hand_made_run(monkeypatch)
    monkeypatch.setattr(spans, "_RECORDS", {
        n: r for n, r in spans._RECORDS.items()
        if n != "meili.dispatch.stage"})
    assert reader().read(run) is None
    monkeypatch.setitem(sys.modules, "repro.obs.spans", None)
    monkeypatch.delattr(sys.modules["repro.obs"], "spans")
    assert reader().read(run) is None


def test_a_tiny_run_puts_each_batch_inside_the_dispatchs_host_time(
        monkeypatch):
    """On the CPU the numbers are no device's; what holds anywhere is that
    the put is entered in the window and lies, with the index algebra and
    the enqueue, inside ``process`` outside the TO."""
    cell = spec.resolve("fw.min64")
    cell.mix.update(batch=128, flows=1500, warmup_batches=2)
    seen = {}
    read = harness.read_metrics
    monkeypatch.setattr(harness, "read_metrics",
                        lambda metrics, run: seen.setdefault("run", run)
                        and read(metrics, run))
    harness.measure(cell, 2**31 + 11, 0.3, False, log=lambda _l: None)
    run = seen["run"]
    got = {n: reader(n).read(run) for n in (
        "dispatch.stage_ms", "dispatch.index_ms", "dispatch.enqueue_ms",
        "dispatch.host_ms")}
    assert all(v is not None and v > 0 for v in got.values())
    assert (got["dispatch.stage_ms"] + got["dispatch.index_ms"]
            + got["dispatch.enqueue_ms"]) <= got["dispatch.host_ms"]
