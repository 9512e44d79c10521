"""Phase spans of the data plane (``repro.obs.spans``): the helper's record,
its annotations in a profiler trace, and the nine spans that one
``ParallelDataPlane.process`` call advances."""
import glob
import itertools

import jax
import numpy as np
import pytest

from repro.apps import ALL_APPS, synth_packets
from repro.core.executor import ParallelDataPlane
from repro.obs import spans

PHASES = ("meili.to.assign", "meili.to.flow_ids", "meili.to.probe",
          "meili.to.miss_loop", "meili.to.commit", "meili.to.maintain",
          "meili.dispatch.stage", "meili.dispatch.index",
          "meili.dispatch.enqueue")
# Without a flow cache there is no probe.
SLOW_PHASES = tuple(n for n in PHASES if n != "meili.to.probe")


class FakeClock:
    """``time`` stand-in whose ``perf_counter_ns`` steps by 10 ns a read."""

    def __init__(self, start: int):
        self._next = itertools.count(start, 10)

    def perf_counter_ns(self) -> int:
        return next(self._next)


def calls(name):
    return spans.totals().get(name, {"calls": 0})["calls"]


def test_nested_spans_keep_totals_calls_and_last_call(monkeypatch):
    monkeypatch.setattr(spans, "time", FakeClock(1000))
    before = spans.totals().get("test.outer", {"ns": 0, "calls": 0})
    with spans.span("test.outer", round=3):        # enter at 1000
        with spans.span("test.inner"):             # 1010 .. 1020
            pass
        with spans.span("test.inner"):             # 1030 .. 1040
            pass
    with spans.span("test.outer"):                 # 1060 .. 1070
        pass
    got = spans.totals()
    assert got["test.outer"]["calls"] - before["calls"] == 2
    assert got["test.outer"]["ns"] - before["ns"] == 50 + 10
    assert spans.between("test.outer", 1060, 1060) == (1, 10)   # last call
    assert spans.between("test.inner", 1010, 1030) == (2, 20)
    assert spans.between("test.inner", 1011, 1029) == (0, 0)
    assert spans.between("test.never", 0, 1 << 62) == (0, 0)


def test_a_span_that_raises_still_counts(monkeypatch):
    monkeypatch.setattr(spans, "time", FakeClock(5000))
    n = calls("test.raises")
    with pytest.raises(KeyError):
        with spans.span("test.raises"):
            raise KeyError("x")
    assert calls("test.raises") == n + 1


def test_between_says_when_its_history_no_longer_reaches_back(monkeypatch):
    monkeypatch.setattr(spans, "HISTORY", 4)
    monkeypatch.setattr(spans, "_RECORDS", {})
    monkeypatch.setattr(spans, "time", FakeClock(0))
    for _ in range(6):                 # starts 0, 20, ..., 100
        with spans.span("test.ring"):
            pass
    assert spans.between("test.ring", 40, 100) == (4, 40)
    with pytest.raises(RuntimeError, match="no longer held"):
        spans.between("test.ring", 0, 100)


def test_span_names_and_meta_land_in_a_profiler_trace(tmp_path):
    jax.profiler.start_trace(str(tmp_path))
    try:
        with spans.span("meili.test.outer", round=41):
            with spans.span("meili.test.inner"):
                jax.block_until_ready(jax.numpy.ones(4) + 1)
    finally:
        jax.profiler.stop_trace()
    [path] = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    data = jax.profiler.ProfileData.from_file(path)
    found = {e.name: (e.start_ns, e.start_ns + e.duration_ns, dict(e.stats))
             for plane in data.planes if plane.name.startswith("/host:")
             for line in plane.lines for e in line.events
             if e.name.startswith("meili.test.")}
    assert set(found) == {"meili.test.outer", "meili.test.inner"}
    (os_, oe, meta), (is_, ie, _) = (found["meili.test.outer"],
                                     found["meili.test.inner"])
    assert os_ <= is_ < ie <= oe
    assert meta.get("round") == 41


def host_batch(seed):
    """A packet batch of host (numpy) arrays, as a NIC hands it over."""
    pk = synth_packets(batch=256, num_flows=40, pkt_bytes=128, seed=seed)
    return jax.tree.map(np.asarray, pk)


def plane_over(batches, **kw):
    """Span calls advanced by each of ``batches`` through a fresh FW plane."""
    app = ALL_APPS(impl="ref")["FW"]
    dp = ParallelDataPlane(app, num_pipelines=4, capacity_per_pipeline=1000,
                           **kw)
    steps = []
    for pk in batches:
        first = {n: calls(n) for n in PHASES}
        dp.process(pk)
        steps.append({n: calls(n) - first[n] for n in PHASES})
    return dp, steps


def test_each_batch_advances_every_phase_once():
    dp, steps = plane_over([host_batch(s) for s in (1, 2, 3)])
    assert dp.to.fast_stats["miss_flows"] > 0
    assert steps == [dict.fromkeys(PHASES, 1)] * 3


def test_the_slow_path_commits_and_maintains_once_a_batch():
    dp, steps = plane_over([host_batch(s) for s in (5, 6)], flow_cache=False)
    assert dp.to.flow_cache is None
    assert steps == [{**dict.fromkeys(SLOW_PHASES, 1),
                      "meili.to.probe": 0}] * 2


def test_a_fallback_batch_runs_both_loops_and_maintains_once():
    """A fast-path batch whose hits no longer fit their homes falls back to
    the slow loop: two loops and two commit spans (the failed check, the
    slow record), one maintenance."""
    pk = host_batch(7)
    app = ALL_APPS(impl="ref")["FW"]
    dp = ParallelDataPlane(app, num_pipelines=4, capacity_per_pipeline=1000)
    dp.process(pk)                      # caches the batch's flows
    for p in dp.to.pipelines:
        p.capacity = 40.0               # 256 packets no longer fit at home
    first = {n: calls(n) for n in PHASES}
    dp.process(pk)
    assert dp.to.fast_stats["fallbacks"] == 1
    assert {n: calls(n) - first[n] for n in PHASES} == {
        **dict.fromkeys(PHASES, 1), "meili.to.miss_loop": 2,
        "meili.to.commit": 2}
