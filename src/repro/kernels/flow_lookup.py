"""Exact-match flow-classification lookup — the megaflow fast-path kernel.

The flow cache (``core.flowcache``) keeps fid -> (pipeline, epoch) in an
open-addressed table with a BOUNDED probe window: a key may only live in the
``window`` consecutive slots starting at its hash bucket. That makes lookup
branch-free vector code — gather the window, compare keys, take the first
live match — and makes deletion trivial (no tombstones: absence means "not
in the window", never "probe until an empty slot").

Two implementations of the same probe, pinned bit-identical against each
other and a dict oracle in ``tests/test_flow_lookup.py``:

  * ``lookup_numpy`` — host-side oracle; also what the cache's mutation
                       path (insert/evict/expire) uses to find slots;
  * ``lookup_jnp``   — one jitted XLA gather program over the HBM-resident
                       table planes: the device probe on every backend. A
                       2^17-slot table is four 512 KiB planes, which a
                       VMEM-resident kernel cannot hold once each (cap, 1)
                       plane pads to 128 lanes.

Keys are int64 flow ids split into two uint32 planes (lo, hi) so no path
needs x64 mode; the bucket hash is the same wraparound uint32 mix in both.
A slot is live iff its pid plane is >= 0. Outputs per query:

  slot  — table slot holding the key (any epoch), or -1 if absent;
  pid   — cached pipeline id if the entry is live AND epoch-fresh, else -1;
  fresh — bool, live key match with entry epoch == current epoch.

``slot`` without ``fresh`` is the revalidation handle: after an epoch bump
the entry is refreshed in place instead of re-inserted. Compilations are
counted at trace time (``trace_counts``) so benchmarks can assert zero
steady-state recompiles.
"""
from __future__ import annotations

import functools
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# Trace-time compile counters (idiom shared with core.sched_kernel): the
# Python body of a jitted function runs once per specialization, so steady
# state leaves these untouched.
_TRACE_COUNTS: Dict[str, int] = {}


def _count_trace(name: str) -> None:
    _TRACE_COUNTS[name] = _TRACE_COUNTS.get(name, 0) + 1


def trace_counts() -> Dict[str, int]:
    return dict(_TRACE_COUNTS)


def reset_trace_counts() -> None:
    _TRACE_COUNTS.clear()


# -- key splitting + bucket hash ---------------------------------------------

_M1 = np.uint32(0x9E3779B1)      # golden-ratio odd constants; wraparound
_M2 = np.uint32(0x85EBCA77)      # uint32 multiplies are identical in
_M3 = np.uint32(0xC2B2AE3D)      # numpy, XLA and Mosaic.


def split_fids(fids: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """int64 flow ids -> (lo, hi) uint32 planes (bit-exact round trip)."""
    u = np.asarray(fids, dtype=np.int64).view(np.uint64)
    lo = (u & np.uint64(0xFFFFFFFF)).astype(np.uint32)
    hi = (u >> np.uint64(32)).astype(np.uint32)
    return lo, hi


def bucket_hash(lo, hi):
    """uint32 mix of the two key words — same code path for numpy and jnp
    arrays (both wrap uint32 arithmetic)."""
    h = (lo * _M1) ^ (hi * _M2)
    h = (h ^ (h >> 15)) * _M3
    return h ^ (h >> 13)


# -- numpy oracle -------------------------------------------------------------

def lookup_numpy(key_lo: np.ndarray, key_hi: np.ndarray, pid: np.ndarray,
                 epoch: np.ndarray, q_lo: np.ndarray, q_hi: np.ndarray,
                 cur_epoch: int, window: int
                 ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    cap = key_lo.shape[0]
    base = bucket_hash(q_lo, q_hi) & np.uint32(cap - 1)
    idx = ((base[:, None] + np.arange(window, dtype=np.uint32))
           & np.uint32(cap - 1)).astype(np.int64)              # (F, W)
    match = ((key_lo[idx] == q_lo[:, None])
             & (key_hi[idx] == q_hi[:, None]) & (pid[idx] >= 0))
    found = match.any(axis=1)
    first = match.argmax(axis=1)
    rows = np.arange(idx.shape[0])
    slot = np.where(found, idx[rows, first], -1).astype(np.int64)
    safe = np.where(slot >= 0, slot, 0)
    fresh = found & (epoch[safe] == np.int32(cur_epoch))
    out_pid = np.where(fresh, pid[safe], -1).astype(np.int32)
    return slot, out_pid, fresh


# -- jitted device probe -------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("window",))
def _lookup_jnp(key_lo, key_hi, pid, epoch, q_lo, q_hi, cur_epoch, *, window):
    _count_trace("flow_lookup_jnp")
    cap = key_lo.shape[0]
    base = bucket_hash(q_lo, q_hi) & np.uint32(cap - 1)
    offs = jnp.arange(window, dtype=jnp.uint32)
    idx = ((base[:, None] + offs[None, :])
           & np.uint32(cap - 1)).astype(jnp.int32)             # (F, W)
    match = ((key_lo[idx] == q_lo[:, None])
             & (key_hi[idx] == q_hi[:, None]) & (pid[idx] >= 0))
    found = match.any(axis=1)
    first = jnp.argmax(match, axis=1)
    slot_w = jnp.take_along_axis(idx, first[:, None], axis=1)[:, 0]
    slot = jnp.where(found, slot_w, -1)
    safe = jnp.where(slot >= 0, slot, 0)
    fresh = found & (epoch[safe] == cur_epoch)
    out_pid = jnp.where(fresh, pid[safe], -1).astype(jnp.int32)
    return slot, out_pid, fresh


def lookup_jnp(key_lo, key_hi, pid, epoch, q_lo, q_hi, cur_epoch: int,
               window: int):
    return _lookup_jnp(key_lo, key_hi, pid, epoch, q_lo, q_hi,
                       jnp.int32(cur_epoch), window=window)


# -- incremental device-table maintenance -------------------------------------

@jax.jit
def _apply_updates(key_lo, key_hi, pid, epoch, slots, u_lo, u_hi, u_pid,
                   u_epoch):
    _count_trace("flow_table_update")
    # slots padded with out-of-range sentinels; mode="drop" ignores them, so
    # one compiled program serves every (pow-2 bucketed) update size.
    return (key_lo.at[slots].set(u_lo, mode="drop"),
            key_hi.at[slots].set(u_hi, mode="drop"),
            pid.at[slots].set(u_pid, mode="drop"),
            epoch.at[slots].set(u_epoch, mode="drop"))


def apply_updates(planes, slots, u_lo, u_hi, u_pid, u_epoch):
    """Scatter host-side table mutations into the device-resident planes.

    ``planes`` is the (key_lo, key_hi, pid, epoch) tuple of device arrays;
    returns the updated tuple. Pad ``slots`` with values >= capacity to hit
    a cached specialization (dropped by the scatter).
    """
    return _apply_updates(*planes, jnp.asarray(slots), jnp.asarray(u_lo),
                          jnp.asarray(u_hi), jnp.asarray(u_pid),
                          jnp.asarray(u_epoch))
