"""Megaflow flow cache (ISSUE 9): equivalence, bounding, observability.

The load-bearing property: a TrafficOrchestrator with a flow cache is
BYTE-IDENTICAL to one without — same per-packet assign array, same
flow/spill tables, same per-pipeline loads — across arbitrary interleavings
of churning traffic, migration begin/finish, pipeline halt (failover) and
scale-out, including halted-flow buffering and the saturation regimes where
the fast path falls back. The cache may only change WHEN the answer is
computed, never what it is.
"""
from __future__ import annotations

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.apps.packets import pareto_flow_weights, synth_packets_weighted
from repro.core.flowcache import FlowCache, FlowCacheConfig
from repro.core.orchestrator import TrafficOrchestrator
from repro.kernels import flow_lookup as fl
from repro.obs import spans
from repro.obs.trace import DecisionTrace

NPIPE = 4


def _pair(cap, *, capacity=1 << 10, table_cap=None, trace=None,
          idle_ttl=4096, expire_every=256):
    """(cache-on, cache-off) orchestrators with identical topology."""
    fc = FlowCache(FlowCacheConfig(capacity=capacity, idle_ttl=idle_ttl,
                                   expire_every=expire_every))
    a = TrafficOrchestrator(num_pipelines=NPIPE, capacity_per_pipeline=cap,
                            flow_cache=fc, table_cap=table_cap, trace=trace)
    b = TrafficOrchestrator(num_pipelines=NPIPE, capacity_per_pipeline=cap)
    return a, b


def _batch(t, *, batch=96, num_flows=300, drift=0, seed=7):
    w = pareto_flow_weights(num_flows, 1.2, seed=seed)
    return synth_packets_weighted(batch=batch, num_flows=num_flows,
                                  weights=w, seed=(seed, 0, t), pkt_bytes=64,
                                  flow_base=drift)


def _assert_same(a, b, ctx):
    assert a.flow_table == b.flow_table, ctx
    assert a.spill_table == b.spill_table, ctx
    la = [p.load for p in a.pipelines]
    lb = [p.load for p in b.pipelines]
    assert la == lb, (ctx, la, lb)
    assert sorted(a.halted_flows) == sorted(b.halted_flows), ctx


def _run_script(cap, script, ticks=40, churn=11):
    """Drive both orchestrators through `ticks` rounds of churning traffic,
    applying the event script {tick: (op, ...)} to BOTH; assert equality
    after every round."""
    a, b = _pair(cap)
    mig = []
    for t in range(ticks):
        for op in script.get(t, ()):
            if op == "migrate" and a.flow_table:
                f = sorted(a.flow_table)[len(a.flow_table) // 2]
                a.begin_migration(f), b.begin_migration(f)
                mig.append(f)
            elif op == "finish" and mig:
                f = mig.pop()
                dst = a._round % NPIPE
                a.finish_migration(f, dst), b.finish_migration(f, dst)
            elif op == "halt":
                live = [p.pid for p in a.pipelines if p.active]
                if len(live) > 1:
                    a.halt_pipeline(live[-1]), b.halt_pipeline(live[-1])
            elif op == "add":
                a.add_pipeline(cap), b.add_pipeline(cap)
        batch = _batch(t, drift=churn * t)
        ra = a.partition_assign(batch)
        rb = b.partition_assign(batch)
        np.testing.assert_array_equal(ra, rb, err_msg=f"tick {t}")
        _assert_same(a, b, f"tick {t}")
    return a, b


# -- equivalence ---------------------------------------------------------------

def test_equivalent_under_churn_roomy():
    a, _ = _run_script(256.0, {})
    # Roomy capacity: the fast path must actually engage, not fall back.
    assert a.fast_stats["fast_batches"] > 30
    assert a.fast_stats["fallbacks"] == 0
    assert a.fast_stats["hit_flows"] > 0


def test_equivalent_under_events():
    script = {5: ("migrate",), 9: ("finish",), 12: ("halt",),
              17: ("migrate", "halt"), 20: ("finish",), 24: ("add",),
              30: ("migrate",), 34: ("finish",)}
    a, _ = _run_script(96.0, script, ticks=40)
    assert a.fast_stats["fast_batches"] > 0


def test_equivalent_at_saturation_with_fallbacks():
    # Tight capacity: hits overcommit, the fast path must detect it and
    # defer to a pristine slow run (equality asserted inside _run_script).
    a, _ = _run_script(26.0, {8: ("halt",)}, ticks=30)
    assert a.fast_stats["fallbacks"] > 0


@settings(max_examples=15, deadline=None)
@given(st.integers(0, 2 ** 31 - 1))
def test_equivalence_property_random_scripts(seed):
    rng = np.random.default_rng(seed)
    cap = float(rng.choice([24, 48, 96, 256]))
    script = {}
    for t in sorted(rng.choice(28, size=6, replace=False).tolist()):
        script[t] = tuple(rng.choice(
            ["migrate", "finish", "halt", "add"],
            size=rng.integers(1, 3)).tolist())
    _run_script(cap, script, ticks=28, churn=int(rng.integers(0, 23)))


def test_halted_flow_buffering_identical():
    a, b = _pair(128.0)
    batch = _batch(0)
    a.partition_assign(batch), b.partition_assign(batch)
    f = sorted(a.flow_table)[0]
    a.begin_migration(f), b.begin_migration(f)
    for t in range(1, 4):
        nb = _batch(t)
        ra, rb = a.partition_assign(nb), b.partition_assign(nb)
        np.testing.assert_array_equal(ra, rb)
    ka, kb = a.halted_flows.get(f, []), b.halted_flows.get(f, [])
    assert len(ka) == len(kb)
    for sa, sb in zip(ka, kb):
        np.testing.assert_array_equal(sa.indices, sb.indices)
    a.finish_migration(f, 1), b.finish_migration(f, 1)
    _assert_same(a, b, "post-finish")


# -- state bounding (satellite a) ---------------------------------------------

def test_flow_table_bounded_under_churn():
    fc = FlowCache(FlowCacheConfig(capacity=1 << 8, idle_ttl=16,
                                   expire_every=8))
    to = TrafficOrchestrator(num_pipelines=NPIPE, capacity_per_pipeline=256.0,
                            flow_cache=fc, table_cap=200)
    for t in range(60):
        to.partition_assign(_batch(t, drift=40 * t, num_flows=120))
        assert len(to.flow_table) <= 200, t
    assert to.fast_stats["pruned"] > 0
    assert fc.occupancy() <= fc.capacity


def test_idle_expiry_clears_departed_flows():
    # No table_cap: idle expiry alone (not pruning) must clear entries for
    # flows that churned out of the window.
    fc = FlowCache(FlowCacheConfig(capacity=1 << 9, idle_ttl=8,
                                   expire_every=4))
    to = TrafficOrchestrator(num_pipelines=NPIPE, capacity_per_pipeline=256.0,
                            flow_cache=fc)
    for t in range(40):
        to.partition_assign(_batch(t, drift=60 * t, num_flows=80))
    assert to.fast_stats["expired"] > 0
    assert fc.stats["expirations"] > 0


def test_expired_flow_returning_replaces_correctly():
    fc = FlowCache(FlowCacheConfig(capacity=1 << 8, idle_ttl=4,
                                   expire_every=2))
    to = TrafficOrchestrator(num_pipelines=NPIPE, capacity_per_pipeline=256.0,
                            flow_cache=fc, table_cap=64)
    ref = TrafficOrchestrator(num_pipelines=NPIPE,
                              capacity_per_pipeline=256.0)
    b0 = _batch(0, num_flows=40)
    to.partition_assign(b0), ref.partition_assign(b0)
    # Long absence: idle expiry + table pruning forget the early flows.
    for t in range(1, 30):
        to.partition_assign(_batch(t, drift=500 + 40 * t, num_flows=40))
    # The returning batch re-places from scratch — placement must follow
    # the current (empty-for-these-flows) tables, identically to a fresh
    # orchestrator in the same load state.
    for p_to, p_ref in zip(to.pipelines, ref.pipelines):
        p_to.load = p_ref.load = 0.0
    ref.flow_table.clear(), ref.spill_table.clear()
    to.flow_table.clear(), to.spill_table.clear()
    back = _batch(0, num_flows=40)
    np.testing.assert_array_equal(to.partition_assign(back),
                                  ref.partition_assign(back))


# -- observability (satellite b) ----------------------------------------------

def test_trace_explains_placements_and_cache_batches():
    trace = DecisionTrace()
    fc = FlowCache(FlowCacheConfig(capacity=1 << 9))
    to = TrafficOrchestrator(num_pipelines=NPIPE, capacity_per_pipeline=256.0,
                            flow_cache=fc, trace=trace)
    for t in range(3):
        to.partition_assign(_batch(t, drift=10 * t), tenant="t-cdn")
    names = [e.name for e in trace.events]
    assert "slow_path_place" in names
    assert "flow_cache_batch" in names
    place = next(e for e in trace.events if e.name == "slow_path_place")
    assert place.detail["reason"] in ("new_flow", "cache_evicted",
                                      "stale_epoch", "inactive_home")
    assert place.detail["pipeline"] >= 0
    assert place.tenant == "t-cdn"


def test_invalidation_reasons_counted():
    a, _ = _pair(128.0)
    a.partition_assign(_batch(0))
    fc = a.flow_cache
    e0 = fc.epoch
    f = sorted(a.flow_table)[0]
    a.begin_migration(f)
    a.finish_migration(f, 2)
    live = [p.pid for p in a.pipelines if p.active]
    a.halt_pipeline(live[-1])
    assert fc.epoch == e0 + 3          # begin + finish + halt each bump
    assert fc.stats["invalidations"] == 3


def _host_lookup(fc, fids):
    lo, hi = fl.split_fids(fids)
    return fl.lookup_numpy(fc.key_lo, fc.key_hi, fc.pid, fc.ep, lo, hi,
                           fc.epoch, fc.window)


def test_lookup_probes_host_table_without_device_work():
    fc = FlowCache(FlowCacheConfig(capacity=1 << 8))
    rng = np.random.default_rng(0)
    fids = rng.choice(1 << 40, size=150, replace=False).astype(np.int64)
    traces = fl.trace_counts()

    def check(round_):
        got = fc.lookup(fids)
        for g, w in zip(got, _host_lookup(fc, fids)):
            np.testing.assert_array_equal(g, w, err_msg=f"round {round_}")
        return got

    fc.record(fids, rng.integers(0, NPIPE, 150).astype(np.int64), 1)
    slot, _, fresh = check(1)
    assert (slot >= 0).any() and fresh.any()
    fc.delete(fids[:50])
    assert (check(2)[0][:50] == -1).all()
    fc.invalidate("test")
    assert not check(3)[2].any()               # every entry stale
    fc.record(fids[50:100], np.ones(50, np.int64), 4)
    fc.insert(fids[:10], np.zeros(10, np.int64), 4)
    check(4)
    assert fc.expire_idle(4 + fc.cfg.idle_ttl + 1) > 0
    assert (check(5)[0] == -1).all()           # everything expired
    assert fl.trace_counts() == traces


def test_lookup_is_one_probe_span_per_nonempty_batch():
    fc = FlowCache(FlowCacheConfig(capacity=1 << 8))
    fids = np.arange(1, 41, dtype=np.int64) << 33
    fc.record(fids, np.arange(40) % NPIPE, 1)

    def probes():
        return spans.totals().get("meili.to.probe", {"calls": 0})["calls"]

    n = probes()
    fc.lookup(fids)
    assert probes() == n + 1
    fc.lookup(np.zeros(0, np.int64))
    assert probes() == n + 1


# -- benchmark smoke (satellite e) --------------------------------------------

def test_bench_megaflow_fast_smoke():
    from benchmarks import bench_megaflow
    rows = bench_megaflow.run(emit=lambda *_: None, fast=True)
    assert rows and rows[0]["fast"]
    r = rows[0]
    assert r["hit_rate_pkts"] > 0.5
    assert r["fallbacks"] == 0
    assert r["cache_us_per_call"] > 0 and r["slow_us_per_call"] > 0
