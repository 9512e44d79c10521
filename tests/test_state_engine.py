"""State engine: operators, access patterns, bounded-inconsistency sync."""
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.state_engine import (FULL_ACCESS, NON_EXTERNAL_WRITE,
                                     LinkedHashTable, StateService,
                                     bounded_sync)


def make_service(n=3):
    return StateService([f"nic{i}" for i in range(n)], buckets=64)


def test_full_access_visible_everywhere():
    svc = make_service()
    svc.declare("ctr", FULL_ACCESS)
    svc.fstate_set("ctr", 42)
    for nic in svc.engines:
        assert svc.get("ctr", local=nic) == 42
    svc.fstate_remove("ctr")
    assert svc.get("ctr", local="nic0") is None


def test_non_external_write_local_write_global_read():
    svc = make_service()
    svc.declare("x", NON_EXTERNAL_WRITE)
    svc.ne_set("x", 7, local="nic1")
    # GET falls back to a remote read from nic1 (paper §4.3)
    r0 = svc.transport.reads
    assert svc.get("x", local="nic0") == 7
    assert svc.transport.reads == r0 + 1
    # local read does not touch the transport
    r1 = svc.transport.reads
    assert svc.get("x", local="nic1") == 7
    assert svc.transport.reads == r1


def test_traverse_pulls_tables_once():
    svc = make_service(n=4)
    for i, nic in enumerate(svc.engines):
        svc.ne_set(f"k{i}", i, local=nic)
    r0 = svc.transport.reads
    entries = svc.traverse(local="nic0")
    assert {e.s_name for e in entries} == {"k0", "k1", "k2", "k3"}
    # one batched read per remote engine, not per key
    assert svc.transport.reads == r0 + 3


def test_compute_ships_instruction():
    svc = make_service()
    svc.fstate_set("v", 5)
    out = svc.compute("v", ucf=lambda vals: sum(vals), combine=sum)
    assert out == 15                           # 5 on each of 3 engines


def test_expiry_lifespan():
    t = LinkedHashTable(buckets=8)
    t.put("a", 1, now=0.0)
    t.put("b", 2, now=400.0)
    assert t.expire(now=600.0, lifespan=500.0) == 1
    assert t.get("a") is None and t.get("b") is not None


def test_hash_collisions_still_correct():
    t = LinkedHashTable(buckets=1)             # force every key to collide
    for i in range(50):
        t.put(f"key{i}", i)
    assert all(t.get(f"key{i}").value == i for i in range(50))
    assert t.remove("key25") and t.get("key25") is None
    assert t.size == 49


def test_bounded_sync_counters_converge():
    """Paper §5.1.2: after the T-periodic merge, every replica holds the
    global value of a sum-like state."""
    values = np.array([[5.0], [3.0], [0.0]])
    snaps = np.zeros_like(values)
    merged, snaps = bounded_sync(values, snaps)
    np.testing.assert_allclose(merged, [[8.0]] * 3)
    # second epoch of local updates
    merged[0] += 2
    merged2, _ = bounded_sync(merged, snaps)
    np.testing.assert_allclose(merged2, [[10.0]] * 3)


@given(st.lists(st.lists(st.floats(-100, 100), min_size=2, max_size=5),
                min_size=1, max_size=6))
@settings(max_examples=100, deadline=None)
def test_property_bounded_sync_sum_preserving(updates_per_round):
    """Over any update sequence, post-sync replicas agree and equal the total
    of all deltas ever applied (counter semantics)."""
    P = len(updates_per_round[0])
    values = np.zeros((P, 1))
    snaps = np.zeros((P, 1))
    total = 0.0
    for round_updates in [updates_per_round[0]]:
        for i, d in enumerate(round_updates[:P]):
            values[i] += d
            total += d
    values, snaps = bounded_sync(values, snaps)
    np.testing.assert_allclose(values, total, atol=1e-6)


def test_bounded_sync_device_form():
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh
    from repro.core.state_engine import bounded_sync_deltas

    devs = jax.devices()
    if len(devs) < 1:
        pytest.skip("no devices")
    # single-device shard_map over a size-1 axis still exercises the psum path
    from jax.experimental.shard_map import shard_map
    from jax.sharding import PartitionSpec as P
    mesh = jax.make_mesh((1,), ("p",))
    f = shard_map(lambda v, s: bounded_sync_deltas(v, s, "p"), mesh=mesh,
                  in_specs=(P("p"), P("p")), out_specs=(P("p"), P("p")))
    v = jnp.asarray([[4.0]])
    s = jnp.asarray([[1.0]])
    merged, snap = f(v, s)
    assert float(merged[0, 0]) == 4.0
