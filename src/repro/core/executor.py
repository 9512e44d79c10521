"""Executors + the fused parallel data plane (paper §3, §5; ISSUE 1).

An Executor is the isolated runtime for one stage (paper: a container; here:
one jit-compiled program, shared process-wide by every replica of the stage).
A PipelineRunner chains executors; the ParallelDataPlane couples a
TrafficOrchestrator with N pipeline replicas and per-pipeline ring buffers,
implementing partition -> process -> aggregate.

Steady-state per-batch cost is ONE vectorized host pass (the TO's per-flow
partition, numpy) plus ONE cached fused device program that does everything
else:

  gather+pad packets into (N, M) lanes -> push/pop the persistent stacked
  ingress rings -> run the full stage chain once over all lanes -> gather
  the egress back to original packet order.

The ingress batch's copy to the device is issued at hand-over, before the
host pass, and runs while the TO classifies.

``M`` is the per-pipeline sub-batch slot count, padded up to a power-of-two
bucket so the set of compiled shapes stays small and bounded (recompiles are
counted in ``dispatch_stats`` — zero in steady state). Rings are allocated
once per data plane (one stacked device buffer for all N pipelines) instead
of per call. Aggregation is a single device-side gather with a
host-precomputed index, replacing the host concat + inverse-permutation of
the unfused design. See DESIGN.md ("Fused data plane").

Semantics contract (tested): ParallelDataPlane(app, R).process(batch) ==
graph.run_pipeline(app, batch) up to packet order — i.e. replication and
traffic partitioning never change application semantics. With migration
active, packets of halted flows are buffered by the TO and the processed
remainder is returned in original relative order.

That contract presumes UCFs are **per-packet (elementwise)**: splitting a
batch across pipeline replicas — fused or not — already changes which rows
a cross-row reduction would see, so a UCF that aggregates across its batch
has no well-defined parallel semantics. The fused dispatch additionally
runs the chain over all lanes at once, including pad slots whose content is
stale ring data; pad outputs are never referenced by the egress gather, but
a non-elementwise UCF would observe them. All paper apps (apps/nf.py) are
elementwise per the Table 2 paradigm ops.
"""
from __future__ import annotations

import time
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.graph import (MeiliApp, PacketBatch, _cache_stats,
                              apply_stage, cache_put, chain_key,
                              chain_runner, stage_runner)
from repro.core.orchestrator import SubBatch, TrafficOrchestrator
from repro.core.ringbuffer import Ring, make_rings, pop_many, push_many
from repro.core import replication as repl
from repro.obs.spans import span

MIN_BUCKET = 16


def _bucket(n: int) -> int:
    """Round a sub-batch size up to the next power-of-two slot count."""
    return max(MIN_BUCKET, 1 << (max(1, n) - 1).bit_length())


class Executor:
    """One stage's runtime (compiled once, shared by all its replicas —
    replicas differ in placement/timing, not in program)."""

    def __init__(self, fn):
        self.fn = fn
        self.run = stage_runner(fn)          # process-wide cached program


class PipelineRunner:
    def __init__(self, app: MeiliApp):
        self.executors = [Executor(f) for f in app.stages]
        self._chain = chain_runner(app)      # one fused program per chain

    def process(self, batch: PacketBatch) -> PacketBatch:
        return self._chain(batch)


# One fused dispatch program per stage chain, shared by every data plane in
# the process (jax.jit caches per-shape specializations underneath).
_DISPATCH_PROGRAMS: Dict[Any, Callable] = {}

# Host arrays go to the device through a jitted call's C++ argument path,
# which issues each copy asynchronously for tens of microseconds of host
# time; jax.device_put's Python path costs ~0.8 ms a batch on a v5e host.
# The argument is donated, so the output aliases the fresh device buffer
# and the program does no work on the device.
_to_device = jax.jit(lambda arrays: arrays, donate_argnums=0)


def _stage(batch: PacketBatch) -> PacketBatch:
    """``batch`` with its host leaves put on the device (copies issued, not
    awaited); leaves already on the device are kept, never donated."""
    leaves, tree = jax.tree.flatten(batch)
    host = [i for i, a in enumerate(leaves) if not isinstance(a, jax.Array)]
    if host:
        for i, a in zip(host, _to_device([leaves[i] for i in host])):
            leaves[i] = a
    return jax.tree.unflatten(tree, leaves)


def _dispatch_program(app: MeiliApp) -> Callable:
    # NOTE: the "dispatch" hit/miss counters are NOT bumped here — this
    # lookup happens once per plane at construction. They are counted per
    # *call* in ParallelDataPlane.process(), where a miss means jax.jit
    # actually traced+compiled a fresh shape specialization (the event the
    # zero-steady-state-recompile invariant is about).
    key = chain_key(app)
    stats = _cache_stats("dispatch")
    prog = _DISPATCH_PROGRAMS.get(key)
    if prog is not None:
        return prog
    if prog is None:
        stages = tuple(app.stages)

        def dispatch(rings: Ring, batch: PacketBatch, perm: jnp.ndarray,
                     counts: jnp.ndarray, out_idx: jnp.ndarray
                     ) -> Tuple[Ring, PacketBatch]:
            # perm: (N, M) source index per lane slot; counts: (N,) valid
            # slots per lane; out_idx: (B,) flat lane*M+slot per egress row.
            stacked = jax.tree.map(lambda a: a[perm], batch)       # (N, M, ...)
            rings = push_many(rings, stacked, counts)              # ingress
            rings, rows, _valid = pop_many(rings, perm.shape[1])
            flat = jax.tree.map(
                lambda a: a.reshape((-1,) + a.shape[2:]), rows)    # (N*M, ...)
            for fn in stages:
                flat = apply_stage(fn, flat)
            out = jax.tree.map(lambda a: a[out_idx], flat)         # egress
            return rings, out

        # Donate the ring: the caller replaces self._rings with the returned
        # one, so XLA may update the (lanes x cap x pkt) allocation in place
        # instead of copying it every batch.
        prog = cache_put(_DISPATCH_PROGRAMS, key,
                         jax.jit(dispatch, donate_argnums=(0,)),
                         stats=stats)
    return prog


class ParallelDataPlane:
    """N replicated pipelines + TO + persistent per-pipeline ring buffers."""

    def __init__(self, app: MeiliApp, num_pipelines: Optional[int] = None,
                 R: Optional[Dict[str, int]] = None,
                 latencies: Optional[Dict[str, float]] = None,
                 capacity_per_pipeline: float = 256.0,
                 ring_capacity: int = 4096,
                 metrics=None,
                 flow_cache: bool = True, flow_cache_config=None,
                 table_cap: Optional[int] = None, trace=None):
        if num_pipelines is None:
            if R is None:
                assert latencies is not None, "need num_pipelines, R or latencies"
                R = repl.num_replication(app.stage_names(), latencies)
            num_pipelines = repl.num_pipelines(R)
        self.app = app
        self.R = R
        # Megaflow fast path (ISSUE 9): classification served from the
        # host-resident exact-match cache; the TO's slow loop runs only on
        # misses. `flow_cache=False` restores the pure slow path (the bench
        # baseline arm); semantics are byte-identical either way.
        fc = None
        if flow_cache:
            from repro.core.flowcache import FlowCache, FlowCacheConfig
            fc = FlowCache(flow_cache_config or FlowCacheConfig())
        self.to = TrafficOrchestrator(num_pipelines, capacity_per_pipeline,
                                      flow_cache=fc, table_cap=table_cap,
                                      trace=trace)
        self._cache_metric_base: Dict[str, int] = {}
        self.pipelines = [PipelineRunner(app) for _ in range(num_pipelines)]
        self.ring_capacity = ring_capacity
        self._dispatch = _dispatch_program(app)
        self._rings: Optional[Ring] = None
        self._ring_cap = 0
        self._ring_lanes = 0
        self._ring_proto_key = None
        # compiles = real XLA specializations of the shared dispatch program,
        # read off jax.jit's own cache. Steady state must show zero growth.
        # by_tenant: per-tenant call/packet attribution when the caller (the
        # service runtime) tags batches with the submitting tenant.
        self.dispatch_stats: Dict[str, Any] = {
            "calls": 0, "compiles": 0, "by_tenant": {}}
        # An optional MetricsRegistry sink for call/compile counters.
        self.metrics = metrics

    def _tag_tenant(self, tenant: Optional[str], packets: int) -> None:
        if tenant is None:
            return
        per = self.dispatch_stats["by_tenant"].setdefault(
            tenant, {"calls": 0, "packets": 0})
        per["calls"] += 1
        per["packets"] += int(packets)

    def _empty_result(self, batch: PacketBatch) -> PacketBatch:
        """A zero-packet batch with the same pytree structure a processed
        round returns (UCF-added meta keys included): the chain runs on a
        MIN_BUCKET dummy — not on zero rows, which some kernel impls reject —
        and the result is sliced empty."""
        dummy = jax.tree.map(
            lambda a: jnp.zeros((MIN_BUCKET,) + a.shape[1:], a.dtype), batch)
        return jax.tree.map(lambda a: a[:0], chain_runner(self.app)(dummy))

    # -- persistent stacked rings ---------------------------------------------
    def _ensure_rings(self, batch: PacketBatch, M: int) -> None:
        # The row layout comes from shapes and dtypes alone: the batch is on
        # the device, and slicing a row out of it would launch an op a leaf.
        proto_key = tuple((tuple(a.shape[1:]), str(a.dtype))
                          for a in jax.tree.leaves(batch))
        lanes = len(self.to.pipelines)
        if (self._rings is None or M > self._ring_cap
                or lanes != self._ring_lanes
                or proto_key != self._ring_proto_key):
            # Power-of-two cap: cursors are monotonic int32 indexed mod cap,
            # and slot indices survive the two's-complement wrap only when
            # cap divides 2^32.
            self._ring_cap = _bucket(max(self.ring_capacity, M))
            self._ring_lanes = lanes
            proto = jax.tree.map(
                lambda a: jax.ShapeDtypeStruct(a.shape[1:], a.dtype), batch)
            self._rings = make_rings(proto, self._ring_cap, lanes)
            self._ring_proto_key = proto_key

    def _sync_cache_metrics(self) -> None:
        """Publish flow-cache counter deltas into the metrics registry
        (counters only go up, so we ship increments from a local base)."""
        fc = self.to.flow_cache
        if fc is None or self.metrics is None:
            return
        snap = {"hits": fc.stats["hits"], "misses": fc.stats["misses"],
                "evictions": fc.stats["evictions"],
                "invalidations": fc.stats["invalidations"]}
        for k, v in snap.items():
            d = v - self._cache_metric_base.get(k, 0)
            if d > 0:
                self.metrics.counter(f"flow_cache_{k}_total",
                                     app=self.app.name).inc(d)
        self._cache_metric_base = snap

    def flow_cache_stats(self) -> Dict[str, Any]:
        """Fast-path counters for bench records: TO batch classification
        plus the cache's own stats (empty dict when the cache is off)."""
        fc = self.to.flow_cache
        if fc is None:
            return {}
        return dict(self.to.fast_stats, **fc.stats_snapshot())

    # -- partition -> fused dispatch -> aggregate ------------------------------
    def process(self, batch: PacketBatch,
                tenant: Optional[str] = None) -> PacketBatch:
        # Start the batch's host->device copy at hand-over: the dispatch
        # gathers its lanes on the device, so the copy needs nothing the TO
        # decides and crosses while the TO classifies. The TO keeps the host
        # batch (it reads the 5-tuple on the host, where a device array
        # would force a sync).
        with span("meili.dispatch.stage"):
            staged = _stage(batch)
        assign = self.to.partition_assign(batch, tenant=tenant)
        proc = np.nonzero(assign >= 0)[0]      # halted-flow packets buffered
        self._tag_tenant(tenant, proc.size)
        if proc.size == 0:
            return self._empty_result(batch)
        with span("meili.dispatch.index"):
            padded, *index = self._index(staged, assign, proc)
        self.dispatch_stats["calls"] += 1
        before = self._dispatch._cache_size()
        with span("meili.dispatch.enqueue"):
            try:
                self._rings, out = self._dispatch(self._rings, padded, *index)
            except BaseException:
                # The ring was donated to the failed call and may already be
                # invalidated; drop it so the next round reallocates instead
                # of dying on deleted buffers forever.
                self._rings = None
                raise

        grew = self._dispatch._cache_size() - before
        self.dispatch_stats["compiles"] += grew
        compiled = grew > 0
        # Process-wide compile-cache counters: one fused dispatch
        # call == one cache event. miss == jax.jit compiled a fresh shape
        # specialization; hit == warm reuse. Tests assert miss stays 0 after
        # warmup (zero steady-state recompiles, now an observable).
        dstats = _cache_stats("dispatch")
        dstats["miss" if compiled else "hit"] += 1
        if self.metrics is not None:
            self._sync_cache_metrics()
            self.metrics.counter("dataplane_dispatch_calls_total",
                                 app=self.app.name).inc()
            if self.dispatch_stats["compiles"] > 0:
                self.metrics.gauge("dataplane_dispatch_compiles",
                                   app=self.app.name).set(
                                       self.dispatch_stats["compiles"])
        P = proc.size
        if _bucket(P) != P:
            out = jax.tree.map(lambda a: a[:P], out)
        return out

    def _index(self, batch: PacketBatch, assign: np.ndarray,
               proc: np.ndarray) -> Tuple:
        """The dispatch's arguments after the rings: the device-resident
        ingress batch padded to its bucket, then ``perm``, ``counts`` and
        ``out_idx`` (host arrays, copied to the device by the dispatch
        call)."""
        lanes_of = assign[proc]
        N = len(self.to.pipelines)
        counts = np.bincount(lanes_of, minlength=N).astype(np.int32)
        M = _bucket(int(counts.max()))

        # Host-side index algebra (numpy, O(B)): lane slot per packet and the
        # egress gather index that undoes the lane layout. Lane ids take only
        # N values, so a counting sort (one flatnonzero pass per lane) beats
        # a comparison argsort and is equally stable.
        order = np.concatenate(
            [np.flatnonzero(lanes_of == i) for i in range(N)])
        starts = np.concatenate([[0], np.cumsum(counts)[:-1]])
        lanes_sorted = lanes_of[order]
        ranks = np.arange(proc.size) - starts[lanes_sorted]
        perm = np.zeros((N, M), np.int32)      # pad slots gather row 0 (masked)
        perm[lanes_sorted, ranks] = proc[order]
        out_idx = np.empty(proc.size, np.int32)
        out_idx[order] = lanes_sorted * M + ranks

        # Every jit-facing shape is bucketed — M above, and here the ingress
        # batch and egress index — so variable-size traffic (B drifting round
        # to round) recompiles at most once per pow-2 bucket, not per size.
        B = batch.batch
        B_pad = _bucket(B)
        if B_pad != B:
            batch = jax.tree.map(
                lambda a: jnp.concatenate(
                    [a, jnp.zeros((B_pad - B,) + a.shape[1:], a.dtype)], 0),
                batch)
        P = proc.size
        P_pad = _bucket(P)
        if P_pad != P:
            out_idx = np.concatenate([out_idx, np.zeros(P_pad - P, np.int32)])
        self._ensure_rings(batch, M)
        return batch, perm, counts, out_idx

    # -- per-stage device profiling (ISSUE 7) ----------------------------------
    def profile_stages(self, batch: PacketBatch,
                       iters: int = 1) -> Dict[str, float]:
        """Time each stage's jitted program to completion on ``batch`` and
        return mean µs per stage. Runs OUTSIDE the fused dispatch (stage
        programs are the same process-wide cached jits the unfused path
        uses), so a profile never perturbs steady-state compile counters of
        the fused program. Timings land in the attached registry as
        ``dataplane_stage_us{app=...,stage=...}`` histograms."""
        out: Dict[str, float] = {}
        cur = batch
        for fn in self.app.stages:
            run = stage_runner(fn)
            jax.block_until_ready(run(cur))          # warm: exclude compile
            t0 = time.perf_counter()
            for _ in range(max(1, iters)):
                nxt = run(cur)
                jax.block_until_ready(nxt)
            us = (time.perf_counter() - t0) * 1e6 / max(1, iters)
            out[fn.name] = us
            if self.metrics is not None:
                self.metrics.histogram("dataplane_stage_us",
                                       app=self.app.name,
                                       stage=fn.name).observe(us)
            cur = nxt
        return out

    # -- unfused reference path (kept as the dispatch-layer oracle) ------------
    def process_unfused(self, batch: PacketBatch,
                        tenant: Optional[str] = None) -> PacketBatch:
        """Per-sub-batch dispatch through PipelineRunner, then sequence-number
        aggregation — the pre-fusion data path, retained for A/B tests and
        benchmarks."""
        subs = self.to.partition(batch)
        self._tag_tenant(tenant, sum(s.indices.size for s in subs))
        if not subs:                       # empty batch or every flow halted
            return self._empty_result(batch)
        done: List[SubBatch] = []
        for sub in subs:
            out = self.pipelines[sub.pid].process(sub.data)
            done.append(SubBatch(pid=sub.pid, seq=sub.seq,
                                 indices=sub.indices, data=out))
        # With migration active the survivors are a subset of the batch:
        # remap original positions to ranks among survivors so aggregate
        # reorders within the processed subset.
        survivors = np.sort(np.concatenate([s.indices for s in done]))
        if survivors.size < batch.batch:
            done = [SubBatch(pid=s.pid, seq=s.seq,
                             indices=np.searchsorted(survivors, s.indices),
                             data=s.data) for s in done]
        return self.to.aggregate(done, total=survivors.size)
