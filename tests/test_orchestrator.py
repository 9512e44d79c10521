"""Traffic Orchestrator + ring buffers — data-plane invariants."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.apps.packets import synth_packets
from repro.core.orchestrator import SubBatch, TrafficOrchestrator, flow_ids
from repro.core.ringbuffer import make_ring, peek, pop, push


# -- ring buffer ---------------------------------------------------------------

def test_ring_fifo_and_wraparound():
    proto = {"x": jnp.zeros((3,), jnp.int32)}
    ring = make_ring(proto, cap=8)
    for wave in range(5):                       # 5 waves of 5 > cap wraps
        rows = {"x": (jnp.arange(15) + 100 * wave).reshape(5, 3)}
        assert int(ring.space) >= 5
        ring = push(ring, rows)
        ring, out, valid = pop(ring, 5)
        assert bool(valid.all())
        np.testing.assert_array_equal(np.asarray(out["x"]),
                                      np.asarray(rows["x"]))
    assert int(ring.occupancy) == 0


def test_ring_partial_pop_masks_garbage():
    ring = make_ring({"x": jnp.zeros((), jnp.int32)}, cap=4)
    ring = push(ring, {"x": jnp.asarray([7, 8])})
    ring, out, valid = pop(ring, 4)
    assert valid.tolist() == [True, True, False, False]
    assert out["x"][:2].tolist() == [7, 8]


def test_ring_occupancy_monotonic_cursors():
    ring = make_ring({"x": jnp.zeros((), jnp.int32)}, cap=4)
    ring = push(ring, {"x": jnp.asarray([1, 2, 3])})
    assert int(ring.occupancy) == 3
    ring, _, _ = pop(ring, 2)
    assert int(ring.occupancy) == 1
    assert int(ring.head) == 2 and int(ring.tail) == 3  # monotonic (mod cap)


def test_ring_peek_does_not_consume():
    ring = make_ring({"x": jnp.zeros((), jnp.int32)}, cap=4)
    ring = push(ring, {"x": jnp.asarray([5])})
    rows, valid = peek(ring, 1)
    assert int(rows["x"][0]) == 5
    assert int(ring.occupancy) == 1


# -- partition / aggregation ------------------------------------------------------

def test_partition_aggregate_identity():
    pkts = synth_packets(batch=64, num_flows=10, pkt_bytes=64)
    to = TrafficOrchestrator(num_pipelines=4, capacity_per_pipeline=8)
    subs = to.partition(pkts)
    out = to.aggregate(subs, total=64)
    for a, b in zip(jax.tree.leaves(pkts), jax.tree.leaves(out)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_flow_stickiness_under_capacity():
    pkts = synth_packets(batch=32, num_flows=4, pkt_bytes=64)
    to = TrafficOrchestrator(num_pipelines=4, capacity_per_pipeline=1000)
    to.partition(pkts)
    first = dict(to.flow_table)
    to.partition(pkts)                          # same flows again
    assert to.flow_table == first


def test_heavy_flow_spills_only_at_capacity():
    """Paper §5.1.2: a flow splits across pipelines only when its pipeline
    hits the capacity limit."""
    pkts = synth_packets(batch=40, num_flows=1, pkt_bytes=64)
    to = TrafficOrchestrator(num_pipelines=4, capacity_per_pipeline=16)
    subs = to.partition(pkts)
    sizes = sorted((len(s.indices) for s in subs), reverse=True)
    assert sum(sizes) == 40
    assert sizes[0] == 16                      # home pipeline filled first
    assert len(sizes) == 3                     # spill uses minimum pipelines


def test_light_flows_stay_single_pipeline():
    pkts = synth_packets(batch=8, num_flows=1, pkt_bytes=64)
    to = TrafficOrchestrator(num_pipelines=4, capacity_per_pipeline=16)
    subs = to.partition(pkts)
    assert len(subs) == 1


def test_migration_buffers_and_releases():
    pkts = synth_packets(batch=16, num_flows=2, pkt_bytes=64)
    to = TrafficOrchestrator(num_pipelines=2, capacity_per_pipeline=100)
    to.partition(pkts)
    f = next(iter(to.flow_table))
    to.begin_migration(f)
    subs = to.partition(pkts)                   # packets of f get buffered
    assert all((flow_ids(s.data) != f).all() for s in subs)
    buffered = to.finish_migration(f, dst_pid=1)
    assert to.flow_table[f] == 1
    assert sum(len(b.indices) for b in buffered) > 0


def test_halt_pipeline_reroutes():
    pkts = synth_packets(batch=16, num_flows=4, pkt_bytes=64)
    to = TrafficOrchestrator(num_pipelines=2, capacity_per_pipeline=100)
    to.partition(pkts)
    flows = to.halt_pipeline(0)
    subs = to.partition(pkts)
    assert all(s.pid != 0 for s in subs)


@given(batch=st.integers(1, 64), flows=st.integers(1, 16),
       pipes=st.integers(1, 6), cap=st.integers(1, 64))
@settings(max_examples=40, deadline=None)
def test_property_partition_is_a_partition(batch, flows, pipes, cap):
    pkts = synth_packets(batch=batch, num_flows=flows, pkt_bytes=32)
    to = TrafficOrchestrator(num_pipelines=pipes, capacity_per_pipeline=cap)
    subs = to.partition(pkts)
    idx = np.concatenate([s.indices for s in subs]) if subs else np.array([])
    assert sorted(idx.tolist()) == list(range(batch))   # exactly once each
    seqs = [s.seq for s in subs]
    assert len(set(seqs)) == len(seqs)                   # unique seq numbers
