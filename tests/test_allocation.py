"""Algorithm 2/3 (locality-aware allocation) — unit + property tests."""
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import pool as pool_mod
from repro.core.allocation import commit, release, resource_alloc
from repro.core.pool import CPU, CRYPTO, REGEX, NicSpec, Pool, paper_cluster


def simple_pool(n=3, cores=8, bw=100.0):
    return Pool([NicSpec(f"n{i}", "x", cores, {}, bw) for i in range(n)])


def test_locality_consolidates_consecutive_stages():
    pool = simple_pool(n=3, cores=8)
    S = ["s1", "s2"]
    alloc = resource_alloc(S, {"s1": 2, "s2": 2}, {"s1": 5.0, "s2": 5.0},
                           pool, {s: CPU for s in S})
    assert alloc.satisfied()
    # both stages fit one NIC -> locality keeps them together
    assert alloc.num_nics_used() == 1
    assert alloc.nics_for("s1") == alloc.nics_for("s2")


def test_spill_when_nic_full():
    pool = simple_pool(n=2, cores=4)
    S = ["s1", "s2"]
    alloc = resource_alloc(S, {"s1": 4, "s2": 3}, {"s1": 1.0, "s2": 1.0},
                           pool, {s: CPU for s in S})
    assert alloc.satisfied()
    assert alloc.num_nics_used() == 2


def test_heterogeneous_isg_needs_pooling():
    """Paper Fig 5: IPsec Gateway is deployable only by pooling BF-2 (regex)
    with Pensando (AES)."""
    pool = paper_cluster(n_bf2=1, n_bf1=0, n_pensando=1)
    S = ["cpu1", "regex", "aes"]
    need = {"cpu1": CPU, "regex": REGEX, "aes": CRYPTO}
    alloc = resource_alloc(S, {s: 1 for s in S}, {s: 5.0 for s in S}, pool,
                           need)
    assert alloc.satisfied()
    assert alloc.nics_for("regex") == ["bf2-0"]
    assert alloc.nics_for("aes") == ["pensando-0"]


def test_bandwidth_cap_limits_allocation():
    """A NIC with tiny bandwidth cannot host high-throughput units
    (Algorithm 3 allocate_on_bw)."""
    pool = Pool([NicSpec("small", "x", 8, {}, bandwidth_gbps=10.0)])
    alloc = resource_alloc(["s1"], {"s1": 8}, {"s1": 5.0}, pool, {"s1": CPU})
    # only floor(10/5)=2 units fit the link
    assert alloc.units("s1") == 2
    assert alloc.unmet["s1"] == 6


def test_colocated_stage_shares_bandwidth():
    """Algorithm 3 lines 10-12: s colocating with s+ re-uses its bandwidth."""
    pool = Pool([NicSpec("n0", "x", 8, {}, bandwidth_gbps=10.0)])
    S = ["s1", "s2"]
    alloc = resource_alloc(S, {"s1": 2, "s2": 2}, {"s1": 5.0, "s2": 5.0},
                           pool, {s: CPU for s in S})
    # s1 consumes the full 10 Gbps; s2 colocates and reclaims the credit.
    assert alloc.units("s1") == 2
    assert alloc.units("s2") == 2


def test_best_effort_on_exhaustion():
    pool = simple_pool(n=1, cores=2)
    alloc = resource_alloc(["s1"], {"s1": 5}, {"s1": 1.0}, pool, {"s1": CPU})
    assert not alloc.satisfied()
    assert alloc.units("s1") == 2
    assert alloc.unmet["s1"] == 3


def test_commit_and_release_roundtrip():
    pool = simple_pool(n=2, cores=4)
    S = ["s1"]
    need = {"s1": CPU}
    t_s = {"s1": 2.0}
    before_free = pool.free_total(CPU)
    before_bw = pool["n0"].free_bw_gbps
    alloc = resource_alloc(S, {"s1": 3}, t_s, pool, need)
    commit(pool, alloc, need)
    assert pool.free_total(CPU) == before_free - 3
    release(pool, alloc, need, t_s)
    assert pool.free_total(CPU) == before_free
    assert pool["n0"].free_bw_gbps == pytest.approx(before_bw)


@given(
    n_nics=st.integers(1, 6), cores=st.integers(1, 16),
    demand=st.integers(0, 64),
    thr=st.floats(0.5, 20.0), bw=st.floats(10.0, 200.0))
@settings(max_examples=150, deadline=None)
def test_property_never_overallocates(n_nics, cores, demand, thr, bw):
    pool = Pool([NicSpec(f"n{i}", "x", cores, {}, bw) for i in range(n_nics)])
    alloc = resource_alloc(["s"], {"s": demand}, {"s": thr}, pool,
                           {"s": CPU})
    placed = alloc.units("s")
    assert placed + alloc.unmet.get("s", 0) == demand
    for n, row in alloc.A.items():
        assert row.get("s", 0) <= cores                   # capacity respected
        assert row.get("s", 0) * thr <= bw + thr          # bw cap (quantized)
    assert all(v >= -1e-9 for v in alloc.bw_after.values())


@given(st.integers(1, 5), st.integers(1, 4))
@settings(max_examples=50, deadline=None)
def test_property_two_stage_locality(n_nics, units):
    """When one NIC can host both stages entirely, Algorithm 2 uses one NIC."""
    pool = Pool([NicSpec(f"n{i}", "x", 2 * units, {}, 1000.0)
                 for i in range(n_nics)])
    S = ["a", "b"]
    alloc = resource_alloc(S, {"a": units, "b": units},
                           {"a": 1.0, "b": 1.0}, pool, {s: CPU for s in S})
    assert alloc.satisfied()
    assert alloc.num_nics_used() == 1
