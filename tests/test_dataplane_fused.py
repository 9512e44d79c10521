"""Fused data plane: semantics oracle, compile-cache behavior, stacked rings."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.apps import ALL_APPS, synth_packets
from repro.core.executor import (MIN_BUCKET, ParallelDataPlane, PipelineRunner,
                                 _bucket)
from repro.core.graph import chain_runner, run_pipeline, stage_runner
from repro.core.orchestrator import flow_ids
from repro.core.ringbuffer import make_rings, pop_many, push_many

PKTS = synth_packets(batch=96, num_flows=12, pkt_bytes=128, seed=7)


def assert_batches_equal(a, b):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# -- semantics oracle ---------------------------------------------------------

@pytest.mark.parametrize("name", ["ID", "FW", "FM"])
def test_fused_equals_oracle_with_spill(name):
    """capacity 8 << 96 packets: every flow spills; oracle must still hold."""
    app = ALL_APPS(impl="ref")[name]
    dp = ParallelDataPlane(app, num_pipelines=4, capacity_per_pipeline=8)
    oracle = run_pipeline(app, PKTS)
    for _ in range(3):                      # state carries across rounds
        assert_batches_equal(dp.process(PKTS), oracle)


def test_fused_equals_unfused_reference_path():
    app = ALL_APPS(impl="ref")["FW"]
    dp = ParallelDataPlane(app, num_pipelines=3, capacity_per_pipeline=16)
    assert_batches_equal(dp.process(PKTS), dp.process_unfused(PKTS))


def test_fused_oracle_with_migration_active():
    """Packets behind a migrating flow are buffered; the processed remainder
    equals the oracle rows of the non-halted packets, in original order."""
    app = ALL_APPS(impl="ref")["FW"]
    dp = ParallelDataPlane(app, num_pipelines=3, capacity_per_pipeline=64)
    dp.process(PKTS)                        # populate the flow table
    f = next(iter(dp.to.flow_table))
    dp.to.begin_migration(f)
    out = dp.process(PKTS)
    keep = np.nonzero(flow_ids(PKTS) != f)[0]
    assert out.batch == keep.size < PKTS.batch
    oracle = run_pipeline(app, PKTS)
    assert_batches_equal(out, jax.tree.map(lambda a: a[jnp.asarray(keep)],
                                           oracle))
    # released buffers re-enter through the normal path after migration
    buffered = dp.to.finish_migration(f, dst_pid=1)
    assert sum(s.indices.size for s in buffered) + keep.size == PKTS.batch
    assert_batches_equal(dp.process(PKTS), oracle)


# -- the ingress batch's early copy -------------------------------------------

def spy_on_dispatch(dp):
    """Wrap ``dp``'s dispatch program; returns the batches it is given."""
    seen, dispatch = [], dp._dispatch

    def spy(rings, batch, *index):
        seen.append(batch)
        return dispatch(rings, batch, *index)

    spy._cache_size = dispatch._cache_size
    dp._dispatch = spy
    return seen


@pytest.mark.parametrize("size", [256, 300])     # 300 pads to 512 rows
def test_a_host_batch_reaches_the_dispatch_on_the_device(size):
    pk = synth_packets(batch=size, num_flows=24, pkt_bytes=128, seed=size)
    host = jax.tree.map(np.asarray, pk)
    dp = ParallelDataPlane(ALL_APPS(impl="ref")["FW"], num_pipelines=3,
                           capacity_per_pipeline=128)
    seen = spy_on_dispatch(dp)
    assert dp.process(host).batch == size
    [staged] = seen
    leaves = jax.tree.leaves(staged)
    assert leaves and all(isinstance(a, jax.Array) for a in leaves)
    assert staged.batch == _bucket(size)


@pytest.mark.parametrize("size", [256, 300])
@pytest.mark.parametrize("name", ["ID", "FW"])
def test_a_host_batch_equals_the_batch_put_first_and_the_unfused_path(
        name, size):
    app = ALL_APPS(impl="ref")[name]
    pk = synth_packets(batch=size, num_flows=24, pkt_bytes=128, seed=size)
    host = jax.tree.map(np.asarray, pk)

    def plane():
        return ParallelDataPlane(app, num_pipelines=3,
                                 capacity_per_pipeline=64)

    got = plane().process(host)
    assert got.batch == size
    assert_batches_equal(got, plane().process(jax.device_put(host)))
    assert_batches_equal(got, plane().process_unfused(host))
    assert_batches_equal(got, run_pipeline(app, pk))
    # Leaves already on the device are used as they are, never donated.
    mixed = dataclasses.replace(host, payload=jax.device_put(host.payload))
    assert_batches_equal(got, plane().process(mixed))
    assert not mixed.payload.is_deleted()


# -- compile-cache behavior ---------------------------------------------------

def test_zero_steady_state_recompiles():
    app = ALL_APPS(impl="ref")["FW"]
    dp = ParallelDataPlane(app, num_pipelines=4, capacity_per_pipeline=32)
    for _ in range(5):
        dp.process(PKTS)
    assert dp.dispatch_stats["calls"] == 5
    assert dp.dispatch_stats["compiles"] == 1


def test_no_recompiles_after_warmup_via_cache_counters():
    """ISSUE 7: the process-wide compile-cache counters make zero-steady-
    state-recompiles an asserted observable — after warmup, further fused
    dispatches must produce cache HITS only (any miss == a fresh jit)."""
    from repro.core import graph

    app = ALL_APPS(impl="ref")["ID"]
    dp = ParallelDataPlane(app, num_pipelines=2, capacity_per_pipeline=32)
    dp.process(PKTS)                         # warmup compile
    warm_compiles = dp.dispatch_stats["compiles"]
    graph.reset_compile_cache_stats()
    for _ in range(4):
        dp.process(PKTS)
    assert dp.dispatch_stats["compiles"] == warm_compiles
    stats = graph.compile_cache_stats()
    assert stats["dispatch"]["miss"] == 0, (
        f"fused dispatch recompiled after warmup: {stats}")
    assert stats["dispatch"]["hit"] >= 4


def test_dataplane_metrics_and_stage_profile():
    """With a metrics registry attached, dispatch calls/compiles and
    per-stage device timings land as labeled series; each dispatch is one
    ``meili.dispatch.stage``, one ``meili.dispatch.index`` and one
    ``meili.dispatch.enqueue`` span."""
    from repro.obs import Obs, spans

    obs = Obs()
    app = ALL_APPS(impl="ref")["FW"]
    dp = ParallelDataPlane(app, num_pipelines=2, capacity_per_pipeline=32,
                           metrics=obs.metrics)
    before = {n: spans.totals().get(n, {"calls": 0})["calls"]
              for n in ("meili.dispatch.stage", "meili.dispatch.index",
                        "meili.dispatch.enqueue")}
    for _ in range(3):
        dp.process(PKTS)
    calls = obs.metrics.get("dataplane_dispatch_calls_total", app=app.name)
    assert calls is not None and calls.value == 3
    assert {n: spans.totals()[n]["calls"] - c
            for n, c in before.items()} == dict.fromkeys(before, 3)
    timings = dp.profile_stages(PKTS)
    assert set(timings) == set(app.stage_names())
    for s in app.stage_names():
        h = obs.metrics.get("dataplane_stage_us", app=app.name, stage=s)
        assert h is not None and h.count >= 1


def test_bucketing_bounds_shapes():
    assert _bucket(1) == MIN_BUCKET
    assert _bucket(16) == 16
    assert _bucket(17) == 32
    assert _bucket(1000) == 1024
    app = ALL_APPS(impl="ref")["FW"]
    dp = ParallelDataPlane(app, num_pipelines=2, capacity_per_pipeline=1000)
    # distinct pow-2 buckets compile at most once each...
    for b in (64, 64, 96, 96, 64):
        dp.process(synth_packets(batch=b, num_flows=4, pkt_bytes=64))
    assert dp.dispatch_stats["compiles"] == 2
    # ...and batch-size drift WITHIN a bucket shares one compiled program
    # (every jit-facing shape — B, egress length, M — is bucketed).
    dp2 = ParallelDataPlane(app, num_pipelines=2, capacity_per_pipeline=1000)
    dp2.process(synth_packets(batch=100, num_flows=4, pkt_bytes=64))
    base = dp2.dispatch_stats["compiles"]
    for b in (120, 100, 97):
        out = dp2.process(synth_packets(batch=b, num_flows=4, pkt_bytes=64))
        assert out.batch == b
    assert dp2.dispatch_stats["compiles"] == base


def test_replicas_share_compiled_programs():
    app = ALL_APPS(impl="ref")["FW"]
    runners = [PipelineRunner(app) for _ in range(4)]
    assert len({id(r._chain) for r in runners}) == 1
    for stage_idx in range(len(app.stages)):
        assert len({id(r.executors[stage_idx].run) for r in runners}) == 1
    assert chain_runner(app) is runners[0]._chain
    assert stage_runner(app.stages[0]) is runners[0].executors[0].run


def test_multi_deployment_shared_stage_identity_no_double_compile():
    """Two *deployments* whose apps are built from the same Function objects
    (same stage identities) must share the process-wide compiled programs:
    the second data plane's dispatch_stats must show zero fresh compiles for
    shapes the first one already ran (multi-tenant service case)."""
    from repro.core.graph import MeiliApp

    app1 = ALL_APPS(impl="ref")["FW"]
    app2 = MeiliApp("fw-tenant-b")          # a second deployment of the same
    app2.stages = list(app1.stages)         # stage chain (shared identities)

    dp1 = ParallelDataPlane(app1, num_pipelines=3, capacity_per_pipeline=64)
    dp1.process(PKTS, tenant="tenant-a")
    assert dp1.dispatch_stats["compiles"] == 1

    dp2 = ParallelDataPlane(app2, num_pipelines=3, capacity_per_pipeline=64)
    # identical stage identities -> the SAME fused dispatch program object
    assert dp2._dispatch is dp1._dispatch
    assert chain_runner(app2) is chain_runner(app1)
    dp2.process(PKTS, tenant="tenant-b")
    assert dp2.dispatch_stats["calls"] == 1
    assert dp2.dispatch_stats["compiles"] == 0      # no double-compile
    # per-tenant attribution stays per-plane and per-tenant
    assert dp1.dispatch_stats["by_tenant"] == {
        "tenant-a": {"calls": 1, "packets": PKTS.batch}}
    assert dp2.dispatch_stats["by_tenant"] == {
        "tenant-b": {"calls": 1, "packets": PKTS.batch}}

    # a *different* stage identity (fresh UCF closures) does NOT collide
    app3 = ALL_APPS(impl="ref")["FW"]
    dp3 = ParallelDataPlane(app3, num_pipelines=3, capacity_per_pipeline=64)
    assert dp3._dispatch is not dp1._dispatch


# -- stacked multi-lane rings -------------------------------------------------

def test_push_pop_many_fifo_and_wraparound():
    proto = {"x": jnp.zeros((2,), jnp.int32)}
    ring = make_rings(proto, cap=8, lanes=3)
    for wave in range(5):                    # 5 waves of up to 5 rows > cap
        n = jnp.asarray([5, 3, 0], jnp.int32)
        rows = {"x": (jnp.arange(30) + 1000 * wave).reshape(3, 5, 2)}
        ring = push_many(ring, rows, n)
        np.testing.assert_array_equal(np.asarray(ring.occupancy), [5, 3, 0])
        ring, out, valid = pop_many(ring, 5)
        np.testing.assert_array_equal(
            np.asarray(valid),
            [[True] * 5, [True, True, True, False, False], [False] * 5])
        for lane, k in ((0, 5), (1, 3)):
            np.testing.assert_array_equal(np.asarray(out["x"][lane, :k]),
                                          np.asarray(rows["x"][lane, :k]))
    np.testing.assert_array_equal(np.asarray(ring.occupancy), [0, 0, 0])


def test_push_pop_many_is_jittable():
    proto = {"x": jnp.zeros((), jnp.float32)}
    ring = make_rings(proto, cap=16, lanes=2)

    @jax.jit
    def roundtrip(ring, rows, n):
        ring = push_many(ring, rows, n)
        return pop_many(ring, 4)

    rows = {"x": jnp.arange(8.0).reshape(2, 4)}
    ring, out, valid = roundtrip(ring, rows, jnp.asarray([4, 2], jnp.int32))
    np.testing.assert_array_equal(np.asarray(out["x"][0]), [0, 1, 2, 3])
    np.testing.assert_array_equal(np.asarray(valid[1]),
                                  [True, True, False, False])
