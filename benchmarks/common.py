"""Shared benchmark utilities + the calibrated paper-cluster cost model.

Without SmartNIC hardware, testbed figures are reproduced on a discrete-time
cost model (core/sim.py) whose per-stage latencies are calibrated so that
single-pipeline app throughputs land in the ranges the paper reports
(Fig 9: ~4-9 Gbps per pipeline at 1500 B). Each benchmark prints CSV rows
``name,us_per_call,derived`` where `derived` carries the figure's headline
quantity; EXPERIMENTS.md tags every number measured-here vs paper-reported.
"""
from __future__ import annotations

import time
from typing import Callable

import jax

# The calibrated cost model now lives in src (repro.apps.profiles) so the
# service runtime can use it without importing benchmarks/; these names are
# re-exported for the existing figure benchmarks.
from repro.apps.profiles import (APP_STAGE_LATENCY_US,  # noqa: F401
                                 APP_STAGE_RESOURCE, HOP_US, PKT_BITS,
                                 unit_gbps)
from repro.compile_cache import enable_compile_cache

# Every benchmark imports this module before its first compile.
enable_compile_cache()


def timeit(fn: Callable, *args, iters: int = 10, warmup: int = 3) -> float:
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(iters):
        jax.block_until_ready(fn(*args))
    return (time.perf_counter() - t0) / iters


def row(name: str, us_per_call: float, derived: str) -> str:
    return f"{name},{us_per_call:.3f},{derived}"
