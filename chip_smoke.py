#!/usr/bin/env python3
"""Bring-up check on one TPU chip, through the entry points a user calls.

    python chip_smoke.py [--seed 0]

Phase A, the data plane: each of the six paper apps (``apps/nf.ALL_APPS()``,
default kernels) runs on a ``ParallelDataPlane`` of 8 pipelines with the
flow cache on, over 4 batches of 16384 packets of 1500 B drawn from 10^5
flows. Every output must equal the ``ref`` oracle chain
(``graph.run_pipeline`` over ``ALL_APPS(impl="ref")``) on the same packets
bit for bit, batches 2-4 must compile no dispatch program, and the fused
dispatch of ID and ISG must hold Pallas kernels (``tpu_custom_call``).

Phase B, the served path: ``ServiceRuntime`` on the paper cluster with the
six-tenant mix runs 5 ticks with the data plane on every tick; every
tenant's data plane must have been called.

Wall times are a bring-up record, not a benchmark. Any failure raises. The
last line of output is one JSON object naming the device. Without a TPU the
script exits non-zero before either phase.
"""
from __future__ import annotations

import argparse
import functools
import json
import pathlib
import sys
import time

import jax
import numpy as np

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

BATCH = 16384
FLOWS = 100_000
BATCHES = 4
PIPELINES = 8
TICKS = 5
PALLAS_APPS = ("ID", "ISG")


def require_tpu():
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU; JAX's first device is "
                         f"{dev.platform!r}")
    return dev


class LastCall:
    """Stands in for a plane's dispatch program and keeps the argument
    shapes of its last call, so that the program can be lowered again and
    its text inspected."""

    def __init__(self, prog):
        self.prog = prog
        self.args = None

    def __call__(self, *args):
        self.args = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), args)
        return self.prog(*args)

    def _cache_size(self) -> int:
        return self.prog._cache_size()

    def text(self) -> str:
        return self.prog.lower(*self.args).as_text()


def mismatches(got, want) -> list:
    """Fields of two PacketBatches that differ in any bit."""
    bad = [f for f in ("payload", "length", "five_tuple", "mask")
           if not np.array_equal(np.asarray(getattr(got, f)),
                                 np.asarray(getattr(want, f)))]
    if set(got.meta) != set(want.meta):
        bad.append(f"meta keys {sorted(got.meta)} != {sorted(want.meta)}")
    bad += [f"meta[{k}]" for k in sorted(set(got.meta) & set(want.meta))
            if not np.array_equal(np.asarray(got.meta[k]),
                                  np.asarray(want.meta[k]))]
    return bad


def phase_a(seed: int, batch: int = BATCH, flows: int = FLOWS,
            batches: int = BATCHES, pipelines: int = PIPELINES) -> dict:
    """Run every app over the same batches; returns, per app, its fused
    dispatch text."""
    from repro.apps.nf import ALL_APPS
    from repro.apps.packets import synth_packets
    from repro.core.executor import ParallelDataPlane
    from repro.core.graph import run_pipeline

    t0 = time.perf_counter()
    traffic = [synth_packets(batch=batch, num_flows=flows, seed=(seed, i))
               for i in range(batches)]
    jax.block_until_ready(traffic)
    print(f"phase A: {batches} batches of {batch} packets x "
          f"{traffic[0].payload.shape[1]} B over {flows} flows, made in "
          f"{time.perf_counter() - t0:.3f} s", flush=True)
    refs = ALL_APPS(impl="ref")
    texts = {}
    for name, app in ALL_APPS().items():
        dp = ParallelDataPlane(app, num_pipelines=pipelines,
                               capacity_per_pipeline=batch / pipelines)
        dp._dispatch = last = LastCall(dp._dispatch)
        dp.to.flow_cache.prewarm(max_queries=batch, max_updates=batch)
        outs, secs = [], []
        for i, pkts in enumerate(traffic):
            t = time.perf_counter()
            outs.append(jax.block_until_ready(dp.process(pkts)))
            secs.append(time.perf_counter() - t)
            if i == 0:
                first = dp.dispatch_stats["compiles"]
        recompiles = dp.dispatch_stats["compiles"] - first
        oracle = jax.jit(functools.partial(run_pipeline, refs[name]))
        for i, (got, pkts) in enumerate(zip(outs, traffic)):
            bad = mismatches(got, oracle(pkts))
            if bad:
                raise AssertionError(f"{name} batch {i + 1} differs from the "
                                     f"ref oracle in {bad}")
        if recompiles:
            raise AssertionError(f"{name}: {recompiles} dispatch compiles "
                                 f"after batch 1")
        texts[name] = last.text()
        print(f"phase A {name:4s} batches 2-{batches}: {sum(secs[1:]):.6f} s "
              f"(batch 1 with compile: {secs[0]:.3f} s)  dispatch compiles "
              f"after batch 1: {recompiles}  flow-cache hits: "
              f"{dp.to.flow_cache.stats['hits']}  == ref oracle", flush=True)
    return texts


def phase_b(seed: int, ticks: int = TICKS) -> dict:
    """The served path; returns per-tenant data-plane call counts."""
    from repro.core.controller import MeiliController
    from repro.core.pool import paper_cluster
    from repro.service.runtime import RuntimeConfig, ServiceRuntime
    from repro.service.tenants import (TenantRegistry, contracts,
                                       default_tenant_mix)
    from repro.service.workload import make_scenario

    mix = default_tenant_mix()
    ctrl = MeiliController(paper_cluster())
    registry = TenantRegistry(ctrl)
    for spec in mix:
        registry.register(spec)
    rt = ServiceRuntime(ctrl, registry,
                        make_scenario("steady", contracts(mix), seed=seed),
                        RuntimeConfig(dataplane_every=1))
    registry.admit_all()
    t0 = time.perf_counter()
    rt.run(ticks)
    stats = rt.dataplane_stats()
    idle = [s.name for s in mix if stats.get(s.name, {}).get("calls", 0) < 1]
    if idle:
        raise AssertionError(f"served path: no data-plane call for {idle}")
    print(f"phase B: {ticks} ticks, {len(mix)} tenants in "
          f"{time.perf_counter() - t0:.3f} s (compiles included); "
          f"data-plane calls/packets per tenant: "
          + ", ".join(f"{t} {v['calls']}/{v['packets']}"
                      for t, v in sorted(stats.items())), flush=True)
    return stats


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    dev = require_tpu()
    from repro.compile_cache import enable_compile_cache
    print(f"device: {dev.platform} {dev.device_kind} x{len(jax.devices())}; "
          f"compile cache: {enable_compile_cache()}", flush=True)

    texts = phase_a(args.seed)
    no_kernel = [n for n in PALLAS_APPS if "tpu_custom_call" not in texts[n]]
    if no_kernel:
        raise AssertionError(f"no Pallas kernel in the dispatch of {no_kernel}")
    print(f"phase A: Pallas kernels in the dispatch of {list(PALLAS_APPS)}; "
          f"peak_bytes_in_use: {dev.memory_stats()['peak_bytes_in_use']}",
          flush=True)
    phase_b(args.seed)
    print(f"phase B: peak_bytes_in_use: "
          f"{dev.memory_stats()['peak_bytes_in_use']}", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()
