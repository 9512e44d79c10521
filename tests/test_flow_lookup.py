"""Megaflow lookup kernel parity (ISSUE 9, satellite f).

Pins the two implementations of the bounded-window exact-match probe —
numpy oracle and the jitted jnp device probe — against each other AND
against a plain dict oracle, across load factors, forced
bucket collisions, epoch bumps, and query padding. Also pins the
incremental device-scatter maintenance path (device planes must equal the
host planes after any update sequence) and the trace-time compile counters
the zero-steady-state-recompile gate reads.
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import flow_lookup as fl

CAP = 1 << 10
W = 8


def _fill(rng, n, cap=CAP, npipe=8, epoch_mix=None):
    """Build host planes holding n random entries inserted window-style
    (first empty slot in the probe window; overflowing keys dropped), plus
    the dict oracle {fid: (pid, epoch)}."""
    key_lo = np.zeros(cap, np.uint32)
    key_hi = np.zeros(cap, np.uint32)
    pid = np.full(cap, -1, np.int32)
    ep = np.zeros(cap, np.int32)
    oracle = {}
    fids = rng.choice(np.int64(1) << 40, size=n, replace=False).astype(np.int64)
    fids[n // 2:] = -fids[n // 2:]          # negative fids must round-trip
    lo, hi = fl.split_fids(fids)
    base = fl.bucket_hash(lo, hi) & np.uint32(cap - 1)
    for i in range(n):
        e = int(rng.integers(0, 3)) if epoch_mix else 0
        p = int(rng.integers(0, npipe))
        for w in range(W):
            s = (int(base[i]) + w) & (cap - 1)
            if pid[s] < 0:
                key_lo[s], key_hi[s] = lo[i], hi[i]
                pid[s], ep[s] = p, e
                oracle[int(fids[i])] = (p, e)
                break
    return (key_lo, key_hi, pid, ep), fids, oracle


def _oracle_lookup(oracle, q, cur_epoch):
    pids, fresh = [], []
    for f in q.tolist():
        p, e = oracle.get(int(f), (-1, -1))
        hit = p >= 0 and e == cur_epoch
        pids.append(p if hit else -1)
        fresh.append(hit)
    return np.array(pids, np.int32), np.array(fresh, bool)


def _queries(rng, fids, extra=64):
    """Half present keys, half absent (never-inserted) keys, shuffled."""
    absent = rng.choice(np.int64(1) << 40, size=extra).astype(np.int64) | (
        np.int64(1) << 41)                  # disjoint id space
    q = np.concatenate([rng.choice(fids, size=min(len(fids), 192)), absent])
    rng.shuffle(q)
    # pow-2 pad, as FlowCache.lookup pads every query batch
    F = 1 << (len(q) - 1).bit_length()
    return np.concatenate([q, np.zeros(F - len(q), np.int64)])


@pytest.mark.parametrize("load", [0.25, 0.60, 0.90])
@pytest.mark.parametrize("cur_epoch", [0, 1])
def test_three_way_parity(load, cur_epoch):
    rng = np.random.default_rng(load.__hash__() % 1000 + cur_epoch)
    planes, fids, oracle = _fill(rng, int(CAP * load), epoch_mix=True)
    q = _queries(rng, fids)
    lo, hi = fl.split_fids(q)

    s_np, p_np, f_np = fl.lookup_numpy(*planes, lo, hi, cur_epoch, W)
    jp = [jnp.asarray(a) for a in planes]
    s_j, p_j, f_j = fl.lookup_jnp(*jp, jnp.asarray(lo), jnp.asarray(hi),
                                  cur_epoch, W)
    np.testing.assert_array_equal(s_np, np.asarray(s_j))
    np.testing.assert_array_equal(p_np, np.asarray(p_j))
    np.testing.assert_array_equal(f_np, np.asarray(f_j))

    p_o, f_o = _oracle_lookup(oracle, q, cur_epoch)
    np.testing.assert_array_equal(p_np, p_o)
    np.testing.assert_array_equal(f_np, f_o)
    # slot is the revalidation handle: any-epoch key match.
    for i, f in enumerate(q.tolist()):
        assert (s_np[i] >= 0) == (int(f) in oracle)
        if s_np[i] >= 0:
            assert int(planes[2][s_np[i]]) == oracle[int(f)][0]


def test_forced_collisions_share_window():
    """Keys engineered into the SAME bucket must all resolve (window scan,
    not just the home slot)."""
    rng = np.random.default_rng(7)
    cand = rng.choice(np.int64(1) << 40, size=20000, replace=False)
    lo, hi = fl.split_fids(cand)
    bucket = fl.bucket_hash(lo, hi) & np.uint32(CAP - 1)
    tgt = bucket[0]
    same = cand[bucket == tgt][:W]          # window-many colliders
    assert len(same) >= 3, "need a few colliding keys"
    key_lo = np.zeros(CAP, np.uint32)
    key_hi = np.zeros(CAP, np.uint32)
    pid = np.full(CAP, -1, np.int32)
    ep = np.zeros(CAP, np.int32)
    slo, shi = fl.split_fids(same)
    for i in range(len(same)):
        s = (int(tgt) + i) & (CAP - 1)
        key_lo[s], key_hi[s], pid[s] = slo[i], shi[i], i
    q = np.concatenate([same, np.zeros(16 - len(same), np.int64)])
    qlo, qhi = fl.split_fids(q)
    s_np, p_np, f_np = fl.lookup_numpy(key_lo, key_hi, pid, ep, qlo, qhi, 0, W)
    assert (p_np[:len(same)] == np.arange(len(same))).all()
    jp = [jnp.asarray(a) for a in (key_lo, key_hi, pid, ep)]
    s_j, p_j, f_j = fl.lookup_jnp(*jp, jnp.asarray(qlo), jnp.asarray(qhi),
                                  0, W)
    np.testing.assert_array_equal(p_np, np.asarray(p_j))
    np.testing.assert_array_equal(s_np, np.asarray(s_j))
    np.testing.assert_array_equal(f_np, np.asarray(f_j))


def test_epoch_bump_stales_everything_but_keeps_slots():
    rng = np.random.default_rng(3)
    planes, fids, oracle = _fill(rng, 200)
    q = _queries(rng, fids, extra=0)
    lo, hi = fl.split_fids(q)
    s0, p0, f0 = fl.lookup_numpy(*planes, lo, hi, 0, W)
    s1, p1, f1 = fl.lookup_numpy(*planes, lo, hi, 1, W)   # epoch bumped
    np.testing.assert_array_equal(s0, s1)   # slot: any-epoch match survives
    assert not f1.any()
    assert (p1 == -1).all()
    assert f0.sum() > 0


def test_apply_updates_matches_host():
    """Random incremental scatters: device planes == host planes after each
    flush, including sentinel-padded (dropped) slots."""
    rng = np.random.default_rng(11)
    host = [np.zeros(CAP, np.uint32), np.zeros(CAP, np.uint32),
            np.full(CAP, -1, np.int32), np.zeros(CAP, np.int32)]
    dev = tuple(jnp.asarray(a) for a in host)
    for _ in range(5):
        n = int(rng.integers(1, 50))
        slots = rng.integers(0, CAP, size=n)
        pad = np.full(8, CAP, np.int64)     # sentinels: must be dropped
        u_lo = rng.integers(0, 1 << 32, size=n, dtype=np.uint32)
        u_hi = rng.integers(0, 1 << 32, size=n, dtype=np.uint32)
        u_pid = rng.integers(-1, 8, size=n, dtype=np.int32)
        u_ep = rng.integers(0, 4, size=n, dtype=np.int32)
        host[0][slots], host[1][slots] = u_lo, u_hi
        host[2][slots], host[3][slots] = u_pid, u_ep
        dev = fl.apply_updates(
            dev, np.concatenate([slots, pad]),
            np.concatenate([u_lo, np.zeros(8, np.uint32)]),
            np.concatenate([u_hi, np.zeros(8, np.uint32)]),
            np.concatenate([u_pid, np.zeros(8, np.int32)]),
            np.concatenate([u_ep, np.zeros(8, np.int32)]))
        for d, h in zip(dev, host):
            np.testing.assert_array_equal(np.asarray(d), h)


def test_trace_counts_stable_across_repeat_calls():
    """The compile counters must not grow on warm shapes — the invariant
    the bench's zero-steady-state-recompile gate reads."""
    rng = np.random.default_rng(5)
    planes, fids, _ = _fill(rng, 100)
    jp = [jnp.asarray(a) for a in planes]
    q = _queries(rng, fids, extra=0)
    lo, hi = fl.split_fids(q)
    fl.lookup_jnp(*jp, jnp.asarray(lo), jnp.asarray(hi), 0, W)
    base = sum(fl.trace_counts().values())
    for e in range(4):                      # epoch is traced, not static
        fl.lookup_jnp(*jp, jnp.asarray(lo), jnp.asarray(hi), e, W)
    assert sum(fl.trace_counts().values()) == base
