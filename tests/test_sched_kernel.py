"""Kernel-vs-oracle property tests (ISSUE 8): every kernel in
``core.sched_kernel`` is checked against the pinned scalar reference in
``core.qos`` / ``service.telemetry`` over randomized inputs.

Each property runs both as a ``@given`` hypothesis test and as a
seeded-random loop. f32 kernel vs f64 scalar means comparisons are
tolerance-based, never bit-exact — the tolerance is the contract.
"""
from __future__ import annotations

import random
import time

import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import sched_kernel as sk
from repro.core.qos import ResourceGovernor, TenantQuota

# Relative tolerance for f32 kernel vs f64 scalar on O(1e4)-byte budgets.
RTOL = 5e-4
ATOL = 1e-2


def _mk_gov(weights, **quota_kw):
    gov = ResourceGovernor()
    for t, w in weights.items():
        gov.register(t, TenantQuota(weight=w, **quota_kw))
    return gov


def _rand_case(rng, n):
    names = [f"t{i:02d}" for i in range(n)]
    weights = {t: rng.choice([0.5, 1.0, 1.0, 2.0, 3.0, 5.0]) for t in names}
    queues = {t: rng.uniform(0.0, 20000.0) for t in names}
    caps = {t: rng.choice([rng.uniform(100.0, 15000.0), float("inf")])
            for t in names}
    return names, weights, queues, caps


def _assert_equivalent(order_s, served_s, order_k, served_k, budget,
                       weights, check_order=True):
    assert set(order_s) == set(order_k)
    # f32 kernel vs f64 scalar: where the budget truncates the final round
    # can land one visit position apart, redistributing at most ~one round's
    # deficit earn (quantum * weight) between adjacent rows — the natural
    # service granularity of DWRR. Totals conserve either way (asserted by
    # the caller); per-tenant service agrees to that granularity.
    total_w = sum(weights.values()) or 1.0
    quantum = budget / (8.0 * total_w)
    for t in served_s:
        tol = max(ATOL, 1.05 * quantum * weights[t] + RTOL * served_s[t])
        assert abs(served_k[t] - served_s[t]) <= tol, (
            t, served_s[t], served_k[t], tol)
    # Dispatch order (stamped at each row's FIRST take, early rounds where
    # drift is negligible) must agree for substantively-served tenants.
    # Only asserted from fresh ring state: once an f32-vs-f64 budget
    # boundary shifts the tail-round count by one, the two rings rotate out
    # of phase and orders legitimately differ (both remain valid DWRR
    # rotations; service equivalence above still holds).
    if not check_order:
        return
    floor = max(ATOL, 1e-3 * budget)
    sub_s = [t for t in order_s if served_s[t] > floor]
    sub_k = [t for t in order_k if served_s[t] > floor]
    assert sub_s == sub_k


# -- capped DWRR ---------------------------------------------------------------

def test_dwrr_capped_matches_scalar_seeded():
    rng = random.Random(42)
    for case in range(25):
        n = rng.randint(1, 24)
        names, weights, queues, caps = _rand_case(rng, n)
        budget = rng.uniform(100.0, 50000.0)

        scalar = _mk_gov(weights)
        o_s, s_s = scalar.dwrr_schedule(dict(queues), dict(caps),
                                        capacity_bytes=budget)
        kern = _mk_gov(weights)
        kern.attach_kernel(sk.VectorizedScheduler())
        o_k, s_k = kern.dwrr_schedule(dict(queues), dict(caps),
                                      capacity_bytes=budget)
        _assert_equivalent(o_s, s_s, o_k, s_k, budget, weights)
        # Conservation: never serve more than budget or demand.
        assert sum(s_k.values()) <= budget * (1 + RTOL) + ATOL
        for t in names:
            assert s_k[t] <= queues[t] * (1 + RTOL) + ATOL
            assert s_k[t] <= caps[t] * (1 + RTOL) + ATOL


def test_dwrr_capped_multi_tick_static_membership():
    """Deficits and the ring offset persist across ticks: a multi-tick
    sequence with static membership stays equivalent, not just tick one."""
    rng = random.Random(7)
    names, weights, _, _ = _rand_case(rng, 9)
    scalar = _mk_gov(weights)
    kern = _mk_gov(weights)
    kern.attach_kernel(sk.VectorizedScheduler())
    for tick in range(12):
        queues = {t: rng.uniform(0.0, 8000.0) for t in names}
        caps = {t: rng.uniform(500.0, 6000.0) for t in names}
        budget = rng.uniform(2000.0, 20000.0)
        o_s, s_s = scalar.dwrr_schedule(dict(queues), dict(caps),
                                        capacity_bytes=budget)
        o_k, s_k = kern.dwrr_schedule(dict(queues), dict(caps),
                                      capacity_bytes=budget)
        _assert_equivalent(o_s, s_s, o_k, s_k, budget, weights,
                           check_order=(tick == 0))


def test_dwrr_weights_shape_longrun_share():
    """Weights 2:1:1 converge to ~2:1:1 served bytes under saturation —
    the classic DRR property, on the kernel path."""
    weights = {"a": 2.0, "b": 1.0, "c": 1.0}
    gov = _mk_gov(weights)
    gov.attach_kernel(sk.VectorizedScheduler())
    tot = {t: 0.0 for t in weights}
    for _ in range(50):
        _, served = gov.dwrr_schedule(
            {t: 1e6 for t in weights}, None, capacity_bytes=4000.0)
        for t, v in served.items():
            tot[t] += v
    assert tot["a"] / tot["b"] == pytest.approx(2.0, rel=0.05)
    assert tot["b"] / tot["c"] == pytest.approx(1.0, rel=0.05)


@settings(max_examples=30, deadline=None)
@given(st.integers(min_value=1, max_value=16),
       st.integers(min_value=0, max_value=2**31 - 1))
def test_dwrr_capped_matches_scalar_hypothesis(n, seed):
    rng = random.Random(seed)
    names, weights, queues, caps = _rand_case(rng, n)
    budget = rng.uniform(100.0, 50000.0)
    scalar = _mk_gov(weights)
    o_s, s_s = scalar.dwrr_schedule(dict(queues), dict(caps),
                                    capacity_bytes=budget)
    kern = _mk_gov(weights)
    kern.attach_kernel(sk.VectorizedScheduler())
    o_k, s_k = kern.dwrr_schedule(dict(queues), dict(caps),
                                  capacity_bytes=budget)
    _assert_equivalent(o_s, s_s, o_k, s_k, budget, weights)


# -- uncapped (order-only) mode ------------------------------------------------

def test_dwrr_uncapped_matches_scalar_seeded():
    rng = random.Random(11)
    for _ in range(20):
        n = rng.randint(1, 20)
        names, weights, queues, caps = _rand_case(rng, n)
        scalar = _mk_gov(weights)
        o_s, s_s = scalar.dwrr_schedule(dict(queues), dict(caps),
                                        capacity_bytes=None)
        kern = _mk_gov(weights)
        kern.attach_kernel(sk.VectorizedScheduler())
        o_k, s_k = kern.dwrr_schedule(dict(queues), dict(caps),
                                      capacity_bytes=None)
        # Order-only mode has no sequential budget: order is an exact sort,
        # so it must match the scalar exactly (ties break by name).
        assert o_s == o_k
        for t in names:
            assert s_k[t] == pytest.approx(s_s[t], rel=RTOL, abs=ATOL)


def test_dwrr_uncapped_tie_break_by_name():
    weights = {"z": 1.0, "a": 1.0, "m": 1.0}
    gov = _mk_gov(weights)
    gov.attach_kernel(sk.VectorizedScheduler())
    order, served = gov.dwrr_schedule({t: 100.0 for t in weights},
                                      {t: 50.0 for t in weights},
                                      capacity_bytes=None)
    assert order == ["a", "m", "z"]
    assert served == {t: pytest.approx(50.0) for t in weights}


# -- scale_decisions vs scale_verdict ------------------------------------------

def _scale_case(rng, brownout):
    n = rng.randint(1, 12)
    names = [f"s{i:02d}" for i in range(n)]
    weights = {t: rng.choice([1.0, 2.0, 4.0]) for t in names}
    quota = {t: rng.choice([None, rng.uniform(5.0, 30.0)]) for t in names}
    burst = {t: rng.choice([0.0, rng.uniform(1.0, 8.0)]) for t in names}
    gov = ResourceGovernor()
    for t in names:
        gov.register(t, TenantQuota(weight=weights[t], max_gbps=quota[t],
                                    burst_gbps=burst[t]))
    if brownout:
        gov.set_brownout(rng.uniform(0.2, 0.8))
    gov.begin_tick(active=names)
    rows = {t: dict(est_gbps=rng.uniform(0.0, 40.0),
                    offered_gbps=rng.uniform(0.0, 40.0),
                    contract_gbps=rng.uniform(5.0, 25.0),
                    current_gbps=rng.uniform(0.0, 30.0),
                    achievable_gbps=rng.uniform(1.0, 30.0))
            for t in names}
    return gov, names, rows


def _run_scale_both(gov, names, rows):
    # Kernel inputs snapshot BEFORE the scalar calls mutate credits.
    creds = np.array([gov.credits.get(t, 0.0) for t in names],
                     dtype=np.float32)
    quota = np.array([gov.quota(t).max_gbps
                      if gov.quota(t).max_gbps is not None else np.inf
                      for t in names], dtype=np.float32)
    w = np.array([gov.weight(t) for t in names], dtype=np.float32)
    wmax = max((q.weight for q in gov.quotas.values()), default=1.0)
    blevel = gov._brownout if gov._brownout is not None else 1.0
    cols = {k: np.array([rows[t][k] for t in names], dtype=np.float32)
            for k in ("est_gbps", "offered_gbps", "contract_gbps",
                      "current_gbps", "achievable_gbps")}
    granted, rescale, pressure, browned, _ = sk.scale_decisions(
        jnp.asarray(cols["est_gbps"]), jnp.asarray(cols["offered_gbps"]),
        jnp.asarray(cols["contract_gbps"]), jnp.asarray(cols["current_gbps"]),
        jnp.asarray(cols["achievable_gbps"]), jnp.asarray(quota),
        jnp.asarray(creds), jnp.asarray(w), jnp.float32(blevel),
        jnp.float32(wmax), jnp.float32(1.15), jnp.float32(0.2),
        jnp.float32(gov.pressure_frac), jnp.float32(0.1))
    verdicts = [gov.scale_verdict(t, **rows[t]) for t in names]
    return (np.asarray(granted), np.asarray(rescale), np.asarray(pressure),
            np.asarray(browned), verdicts)


@pytest.mark.parametrize("brownout", [False, True])
def test_scale_decisions_matches_scale_verdict(brownout):
    rng = random.Random(97 + brownout)
    for case in range(20):
        gov, names, rows = _scale_case(rng, brownout)
        granted, rescale, pressure, browned, verdicts = _run_scale_both(
            gov, names, rows)
        for i, (t, v) in enumerate(zip(names, verdicts)):
            assert float(granted[i]) == pytest.approx(
                v.target_gbps, rel=1e-4, abs=1e-4), (case, t)
            assert bool(rescale[i]) == v.rescale, (case, t)
            assert bool(pressure[i]) == v.pressure, (case, t)
            assert bool(browned[i]) == v.brownout, (case, t)


@settings(max_examples=25, deadline=None)
@given(st.integers(min_value=0, max_value=2**31 - 1),
       st.booleans())
def test_scale_decisions_matches_scale_verdict_hypothesis(seed, brownout):
    rng = random.Random(seed)
    gov, names, rows = _scale_case(rng, brownout)
    granted, rescale, pressure, browned, verdicts = _run_scale_both(
        gov, names, rows)
    for i, v in enumerate(verdicts):
        assert float(granted[i]) == pytest.approx(
            v.target_gbps, rel=1e-4, abs=1e-4)
        assert bool(rescale[i]) == v.rescale


# -- burst refill / queue drain ------------------------------------------------

def test_refill_credits_matches_begin_tick():
    rng = random.Random(3)
    names = [f"b{i}" for i in range(16)]
    depth = {t: rng.choice([0.0, rng.uniform(1.0, 10.0)]) for t in names}
    refill = {t: rng.uniform(0.1, 3.0) for t in names}
    gov = ResourceGovernor()
    for t in names:
        gov.register(t, TenantQuota(burst_gbps=depth[t],
                                    burst_refill_gbps=refill[t]))
        gov.credits[t] = rng.uniform(0.0, depth[t]) if depth[t] else 0.0
    before = np.array([gov.credits[t] for t in names], dtype=np.float32)
    out = sk.refill_credits(
        jnp.asarray(before),
        jnp.asarray(np.array([depth[t] for t in names], dtype=np.float32)),
        jnp.asarray(np.array([refill[t] for t in names], dtype=np.float32)))
    gov.begin_tick(active=names)
    for i, t in enumerate(names):
        assert float(out[i]) == pytest.approx(gov.credits[t],
                                              rel=1e-6, abs=1e-6)


def test_queue_drain_matches_measure_math():
    """queue_drain reproduces measure_tenant_tick's arrival/serve/carry
    arithmetic (lines it was lifted from) for random loads."""
    rng = random.Random(5)
    for _ in range(40):
        off = rng.uniform(0.0, 2e6)
        back = rng.uniform(0.0, 5e4)
        cap = rng.uniform(0.0, 2e6)
        grant = rng.choice([np.inf, rng.uniform(0.0, 1e5)])
        dt = 0.1
        arriving = off * dt + back
        served_ref = min(arriving, cap * dt, grant)
        served, new_back, ach = sk.queue_drain(
            jnp.float32(off), jnp.float32(back), jnp.float32(cap),
            jnp.float32(grant), jnp.float32(dt))
        assert float(served) == pytest.approx(served_ref, rel=1e-5, abs=1e-2)
        assert float(new_back) == pytest.approx(arriving - served_ref,
                                                rel=1e-4, abs=0.5)
        assert float(ach) == pytest.approx(served_ref / dt, rel=1e-5,
                                           abs=1e-1)


# -- telemetry reduction -------------------------------------------------------

def test_telemetry_reduce_matches_dict_loop():
    rng = random.Random(13)
    tenants = ["a", "b", "c", "d"]
    recs = [(rng.choice(tenants), rng.uniform(0, 10), rng.uniform(0, 5))
            for _ in range(200)]
    idx = np.array([tenants.index(t) for t, _, _ in recs])
    off = np.array([o for _, o, _ in recs])
    p99 = np.array([p for _, _, p in recs])
    counts, means, maxes = sk.telemetry_reduce_np(
        idx, len(tenants), {"off": off}, {"p99": p99})
    for i, t in enumerate(tenants):
        mine = [(o, p) for tt, o, p in recs if tt == t]
        assert counts[i] == len(mine)
        assert means["off"][i] == pytest.approx(
            sum(o for o, _ in mine) / len(mine))
        assert maxes["p99"][i] == pytest.approx(max(p for _, p in mine))


def test_telemetry_reduce_handles_absent_tenant():
    counts, means, maxes = sk.telemetry_reduce_np(
        np.array([0, 0]), 2, {"x": np.array([1.0, 3.0])},
        {"y": np.array([2.0, 4.0])})
    assert counts[1] == 0 and means["x"][1] == 0.0
    assert maxes["y"][1] == -np.inf


# -- padding / recompile discipline --------------------------------------------

def test_pad_rows_pow2():
    assert sk.pad_rows(1) == 8
    assert sk.pad_rows(8) == 8
    assert sk.pad_rows(9) == 16
    assert sk.pad_rows(100) == 128


def test_churn_repads_without_retracing():
    """Tenant churn inside one pow-2 bucket must not retrace dwrr_step;
    crossing a bucket boundary traces exactly once more."""
    # max_rounds is a static jit arg: an unusual value gives this test its
    # own compile-cache entries, isolating it from shapes other tests (or
    # the same process's earlier ticks) already compiled.
    sched = sk.VectorizedScheduler(max_rounds=997)

    def tick(names):
        w = {t: 1.0 for t in names}
        sched.schedule({t: 100.0 for t in names}, None, 1000.0, weights=w)

    names = [f"c{i:02d}" for i in range(5)]
    tick(names)
    sk.reset_trace_counts()
    tick(names[:4])          # churn within the 8-row bucket
    tick(names)              # and back
    assert sk.trace_counts().get("dwrr_step", 0) == 0
    tick([f"c{i:02d}" for i in range(9)])   # 8 -> 16 rows: one retrace
    assert sk.trace_counts().get("dwrr_step", 0) == 1


def test_fast_smoke_200_tenants_tick_budget_and_zero_recompiles():
    """Tier-1 smoke (ISSUE 8): a 200-tenant tick on the vectorized path
    stays under a generous host-time budget with zero steady-state
    recompiles."""
    n = 200
    weights = {f"m{i:03d}": float(1 + i % 4) for i in range(n)}
    gov = _mk_gov(weights)
    gov.attach_kernel(sk.VectorizedScheduler())
    rng = random.Random(0)

    def one_tick():
        q = {t: rng.uniform(0.0, 1e5) for t in weights}
        caps = {t: 5e4 for t in weights}
        gov.dwrr_schedule(q, caps, capacity_bytes=2e6)

    one_tick()                      # warmup: compile
    sk.reset_trace_counts()
    t0 = time.perf_counter()
    ticks = 30
    for _ in range(ticks):
        one_tick()
    per_tick = (time.perf_counter() - t0) / ticks
    assert sk.trace_counts() == {}, "steady-state recompile detected"
    assert per_tick < 0.05, f"tick cost {per_tick*1e3:.1f} ms over budget"
