"""Application profiler (paper §6.1).

Meili decides single-pipeline performance by *offline profiling*: run each
CPU stage with one resource unit (1 core + 4 GB) and accelerator stages on
their engines, and record per-stage latency `l_s` / throughput `t_s` and
whole-pipeline `l_p` / `t_p`.

``measure_app`` wall-clocks each jitted stage on this host (used by the
runnable examples/benchmarks; the host plays the role of the NIC's ARM
core); ``synthetic_profile`` builds a profile from known stage latencies.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, Dict, Optional

import jax

from repro.core.graph import MeiliApp, PacketBatch, apply_stage, stage_runner


@dataclasses.dataclass
class AppProfile:
    stages: list
    l_s: Dict[str, float]        # per-sequence(-batch) stage latency, seconds
    t_s: Dict[str, float]        # per-unit stage throughput, Gbps
    l_p: float                   # single-pipeline latency, seconds
    t_p: float                   # single-pipeline throughput, Gbps

    def batch_bits(self) -> float:
        return self._bits

    def __post_init__(self):
        self._bits = 0.0


def _time_call(fn: Callable, *args, iters: int = 5, warmup: int = 2) -> float:
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(iters):
        jax.block_until_ready(fn(*args))
    return (time.perf_counter() - t0) / iters


def measure_app(app: MeiliApp, batch: PacketBatch, iters: int = 5) -> AppProfile:
    """Wall-clock profile of every stage with one resource unit.

    l_p is the end-to-end pipeline latency (sum of stage latencies — the
    minimum app latency reported to users, §6.1); t_p is the *streaming*
    single-pipeline throughput, set by the slowest stage.
    """
    bits = float(batch.length.sum()) * 8.0
    l_s: Dict[str, float] = {}
    cur = batch
    for fn in app.stages:
        runner = stage_runner(fn)
        l_s[fn.name] = _time_call(runner, cur, iters=iters)
        cur = runner(cur)
    l_p = sum(l_s.values())
    t_s = {n: bits / l / 1e9 for n, l in l_s.items()}
    t_p = bits / max(l_s.values()) / 1e9
    prof = AppProfile(stages=app.stage_names(), l_s=l_s, t_s=t_s, l_p=l_p, t_p=t_p)
    prof._bits = bits
    return prof


def synthetic_profile(stages, l_s: Dict[str, float], batch_bits: float) -> AppProfile:
    """Build a profile from known stage latencies (cost-model / paper tables)."""
    l_p = sum(l_s[s] for s in stages)
    t_s = {s: batch_bits / l_s[s] / 1e9 for s in stages}
    t_p = batch_bits / max(l_s[s] for s in stages) / 1e9
    prof = AppProfile(stages=list(stages), l_s=dict(l_s), t_s=t_s, l_p=l_p, t_p=t_p)
    prof._bits = batch_bits
    return prof
