"""One run of one cell: set up the plane, warm it, drive it for a window,
then check what the window produced against the plain reference.

Load is a closed loop with ``inflight`` batches outstanding, a NIC at
saturation: the issuing thread hands batch k+1 to
``ParallelDataPlane.process`` as soon as the call for batch k returns, and
waits only while ``inflight`` batches are unfinished. A waiter thread
blocks on each egress in order and stamps its completion. A batch's latency
runs from its hand-over to ``process`` (when it was due) to its egress
being ready on the device. Batches go in as host (numpy) arrays, so the
copy to the device is inside the timed path and the program decides when
to make it.

The spans the trace carries are the benchmark's own, around calls into the
program: ``bench.ingress`` (making the batch), ``bench.dispatch_host``
(``process``) with ``bench.classify`` (the Traffic Orchestrator's
``partition_assign``) inside it, ``bench.egress_wait`` (the issuer waiting
for a free slot) and ``bench.waiter`` (the waiter blocking on an egress).
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import importlib
import queue
import tempfile
import threading
import time
from typing import Callable, Dict, List, Optional

import numpy as np

from bench import check, generator
from bench import trace as tracemod

TRACE_SECONDS = 2.0     # the profiler covers this much of the window's start
CHECK_BATCHES = 6       # window batches the correctness check samples
COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",
                  "/jax/compilation_cache/cache_retrieval_time_sec")


class CompileCounter:
    """Counts programs compiled or read from the persistent cache, through
    JAX's monitoring events (one listener per process)."""

    _instance: Optional["CompileCounter"] = None

    def __init__(self):
        self.count = 0

    @classmethod
    def get(cls) -> "CompileCounter":
        if cls._instance is None:
            import jax
            cls._instance = cls()

            def on(event, _secs, **_kw):
                if event in COMPILE_EVENTS:
                    cls._instance.count += 1

            jax.monitoring.register_event_duration_secs_listener(on)
        return cls._instance


def annotate(on: bool):
    if on:
        import jax
        return jax.profiler.TraceAnnotation
    return lambda _name: contextlib.nullcontext()


def build_plane(config: Dict, batch: int):
    from repro.core.executor import ParallelDataPlane
    from repro.core.flowcache import FlowCacheConfig
    module, factory = config["app"].split(":")
    app = getattr(importlib.import_module(module), factory)(
        **config.get("app_args", {}))
    lanes = int(config["pipelines"])
    return ParallelDataPlane(
        app, num_pipelines=lanes,
        capacity_per_pipeline=float(config["headroom"]) * batch / lanes,
        ring_capacity=int(config["ring_capacity"]),
        flow_cache_config=FlowCacheConfig(
            capacity=int(config["flow_cache_slots"]),
            window=int(config["flow_cache_window"])))


def packets(arrays: Dict):
    from repro.core.graph import PacketBatch
    return PacketBatch(payload=arrays["payload"], length=arrays["length"],
                       five_tuple=arrays["five_tuple"], mask=arrays["mask"],
                       meta={})


class Tracer:
    """The profiler over the first ``seconds`` of the window, the stretch
    marked by a ``bench.window`` span on the issuing thread. Python calls
    are not traced: only the program's own events and the benchmark's
    spans."""

    def __init__(self, seconds: float):
        self.seconds = seconds
        self.dir = tempfile.TemporaryDirectory(prefix="bench-trace-")
        self.until = None
        self._span = None

    def start(self) -> None:
        import jax
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.dir.name, profiler_options=opts)
        self._span = jax.profiler.TraceAnnotation("bench.window")
        self._span.__enter__()
        self.until = time.perf_counter() + self.seconds

    def poll(self) -> None:
        if self._span is not None and time.perf_counter() >= self.until:
            self.stop()

    def stop(self) -> None:
        if self._span is not None:
            import jax
            self._span.__exit__(None, None, None)
            self._span = None
            jax.profiler.stop_trace()

    def reduce(self) -> tracemod.Reduced:
        try:
            return tracemod.reduce(tracemod.load(self.dir.name))
        finally:
            self.dir.cleanup()


class GcTimer:
    """Python's garbage collections while it is installed: generation and
    seconds of each (``gc.callbacks``)."""

    def __init__(self):
        self.spans: List = []
        self._t = None

    def __call__(self, phase, info) -> None:
        if phase == "start":
            self._t = time.perf_counter()
        elif self._t is not None:
            self.spans.append((info["generation"],
                               time.perf_counter() - self._t))
            self._t = None

    def __enter__(self):
        gc.callbacks.append(self)
        return self

    def __exit__(self, *exc) -> None:
        gc.callbacks.remove(self)

    def summary(self) -> str:
        counts = [sum(g == gen for g, _ in self.spans) for gen in range(3)]
        g, secs = max(self.spans, key=lambda s: s[1], default=(0, 0.0))
        return (f"python gc in the window: {counts[0]}/{counts[1]}/"
                f"{counts[2]} collections of generation 0/1/2, longest "
                f"{secs * 1e3:.3f} ms (generation {g})")


class Sample:
    """A reservoir of ``size`` window batches' egress, drawn from the seed
    (Algorithm R over the batches in the order they finish)."""

    def __init__(self, size: int, seed: int):
        self.size = size
        self.rng = np.random.default_rng([generator.seed_words(seed), 2])
        self.seen = 0
        self.kept: Dict[int, object] = {}
        self._slot: List[int] = []

    def offer(self, k: int, out) -> None:
        if self.seen < self.size:
            self._slot.append(k)
            self.kept[k] = out
        else:
            j = int(self.rng.integers(0, self.seen + 1))
            if j < self.size:
                del self.kept[self._slot[j]]
                self._slot[j] = k
                self.kept[k] = out
        self.seen += 1


@dataclasses.dataclass
class BatchRecord:
    k: int
    packets: int
    due: float          # handed to process
    returned: float     # process returned
    done: float         # egress ready on the device
    classify_s: float   # inside partition_assign


class ClosedLoop:
    """The closed loop over one plane; ``run`` drives it for a count of
    batches or until a time and returns their records."""

    def __init__(self, plane, traffic: generator.Traffic, inflight: int,
                 spans: bool):
        self.plane, self.traffic = plane, traffic
        self.inflight = inflight
        self.ann = annotate(spans)
        self._classify = 0.0
        assign = plane.to.partition_assign
        ann = self.ann

        def timed(batch, tenant=None):
            t = time.perf_counter()
            try:
                with ann("bench.classify"):
                    return assign(batch, tenant=tenant)
            finally:
                self._classify = time.perf_counter() - t

        plane.to.partition_assign = timed

    def run(self, k0: int, *, count: Optional[int] = None,
            until: Optional[float] = None,
            sample: Optional[Sample] = None,
            poll: Callable[[], None] = lambda: None) -> List[BatchRecord]:
        import jax
        ann = self.ann
        slots = threading.Semaphore(self.inflight)
        todo: "queue.Queue" = queue.Queue()
        records: List[BatchRecord] = []
        failure: List[BaseException] = []

        def waiter():
            while True:
                item = todo.get()
                if item is None:
                    return
                k, n, due, ret, cls, out = item
                try:
                    with ann("bench.waiter"):
                        jax.block_until_ready(out)
                    records.append(BatchRecord(k, n, due, ret,
                                               time.perf_counter(), cls))
                    if sample is not None:
                        sample.offer(k, out)
                except BaseException as e:      # re-raised by the issuer
                    failure.append(e)
                del out, item
                slots.release()

        th = threading.Thread(target=waiter, name="bench-waiter", daemon=True)
        th.start()
        k = k0
        try:
            while not failure:
                with ann("bench.egress_wait"):
                    slots.acquire()
                if (count is not None and k - k0 >= count) or (
                        until is not None and time.perf_counter() >= until):
                    slots.release()
                    break
                with ann("bench.ingress"):
                    pk = packets(self.traffic.batch(k))
                due = time.perf_counter()
                with ann("bench.dispatch_host"):
                    out = self.plane.process(pk)
                ret = time.perf_counter()
                todo.put((k, pk.batch, due, ret, self._classify, out))
                del out, pk
                k += 1
                poll()
        finally:
            todo.put(None)
            th.join()
        if failure:
            raise failure[0]
        return records


@dataclasses.dataclass
class Run:
    """What the metric readers read (``bench/metrics/<name>.py``)."""
    cell: object
    seconds: float
    end: float
    setup_s: float
    records: List[BatchRecord]
    counters: Dict[str, float]
    reduced: Optional[tracemod.Reduced] = None
    peaks: Optional[Dict] = None

    @property
    def completed(self) -> List[BatchRecord]:
        """Batches whose egress was ready before the window closed."""
        return [r for r in self.records if r.done <= self.end]


def read_metrics(metrics, run: Run) -> Dict[str, Dict]:
    out = {}
    for m in metrics:
        v = m.read(run)
        if v is not None:
            out[m.name] = {"value": float(v), "unit": m.unit}
    return out


def measure(cell, seed: int, seconds: float, trace: bool, *,
            device=None, setup_origin: Optional[float] = None,
            log: Callable[[str], None] = print,
            peaks: Optional[Dict] = None) -> Dict:
    """One run of ``cell``; returns the result line's fields."""
    import jax
    t0 = time.perf_counter() if setup_origin is None else setup_origin
    split = {"jax_init": time.perf_counter() - t0}
    compiles = CompileCounter.get()
    mix, config = cell.mix, cell.config

    t = time.perf_counter()
    traffic = generator.Traffic(mix, seed)
    split["traffic"] = time.perf_counter() - t

    t = time.perf_counter()
    plane = build_plane(config, traffic.B)
    plane.to.flow_cache.prewarm(max_queries=traffic.B, max_updates=traffic.B)
    loop = ClosedLoop(plane, traffic, int(mix["inflight"]), spans=trace)
    for arrays in traffic.lane_shapes(int(config["pipelines"])):
        jax.block_until_ready(plane.process(packets(arrays)))
    split["compile"] = time.perf_counter() - t

    t = time.perf_counter()
    k = int(mix["warmup_batches"])
    loop.run(0, count=k)
    split["warmup"] = time.perf_counter() - t

    from repro.kernels import flow_lookup
    before = {"compiles": compiles.count,
              "dispatch": plane.dispatch_stats["compiles"],
              "lookup": sum(flow_lookup.trace_counts().values()),
              **{f"fast.{n}": v for n, v in plane.to.fast_stats.items()}}
    sample = Sample(CHECK_BATCHES, seed)
    tracer = Tracer(min(TRACE_SECONDS, seconds)) if trace else None
    if tracer:
        tracer.start()
    start = time.perf_counter()
    setup_s = start - t0
    end = start + seconds
    try:
        with GcTimer() as gc_timer:
            records = loop.run(k, until=end, sample=sample,
                               poll=tracer.poll if tracer else (lambda: None))
    finally:
        if tracer:
            tracer.stop()

    window_counters = {
        "compiles": compiles.count - before["compiles"],
        "dispatch": plane.dispatch_stats["compiles"] - before["dispatch"],
        "lookup": sum(flow_lookup.trace_counts().values()) - before["lookup"],
        **{f"fast.{n}": v - before[f"fast.{n}"]
           for n, v in plane.to.fast_stats.items()},
        "packets": sum(r.packets for r in records),
        "batches": len(records)}
    log(f"set-up {setup_s:.6f} s: " + ", ".join(
        f"{n} {v:.6f} s" for n, v in split.items()))
    log(f"window: {len(records)} batches issued, "
        f"{sum(r.done <= end for r in records)} completed inside; compiles "
        f"in the window: {window_counters['compiles']} (dispatch "
        f"{window_counters['dispatch']}, flow lookup "
        f"{window_counters['lookup']})")

    dev = device or jax.devices()[0]
    stats = dev.memory_stats() or {}
    memory_peak = int(stats.get("peak_bytes_in_use", 0))

    reduced = tracer.reduce() if tracer else None

    # The check runs after the window, with the plane's state freed first.
    got = {k: check.to_host(out) for k, out in sample.kept.items()}
    del sample, loop, plane
    gc.collect()
    t = time.perf_counter()
    numbers, failed = check.check_batches(got, traffic, cell.reference)
    log(f"check: {len(got)} sampled window batches against the reference "
        f"in {time.perf_counter() - t:.3f} s")

    run = Run(cell, seconds, end, setup_s, records, window_counters,
              reduced, peaks)
    metrics = read_metrics(cell.per_layer if trace else cell.end_to_end, run)
    lat = [r.done - r.due for r in run.completed]
    dues = [r.due for r in records]
    gaps = [b - a for a, b in zip(dues, dues[1:])]
    log(f"latency sample: {len(lat)} batches completed in the window; "
        f"longest latency {max(lat, default=0) * 1e3:.3f} ms, longest gap "
        f"between hand-overs {max(gaps, default=0) * 1e3:.3f} ms")
    if records:
        slow = max(records, key=lambda r: r.done - r.due)
        log(f"slowest batch {slow.k}: latency "
            f"{(slow.done - slow.due) * 1e3:.3f} ms, in process "
            f"{(slow.returned - slow.due) * 1e3:.3f} ms, of which classify "
            f"{slow.classify_s * 1e3:.3f} ms; {gc_timer.summary()}")
    result = {
        "correct": bool(failed == 0 and all(
            v["value"] <= v["limit"] for v in numbers.values())),
        "attempted": len(records),
        "failed": failed,
        "metrics": metrics,
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(jax.devices()),
                   "memory_peak_bytes": memory_peak},
    }
    if reduced is not None:
        result["device"].update(busy_s=reduced.busy_s,
                                window_s=reduced.window_s)
        result["breakdown"] = tracemod.breakdown(reduced)
    result["check"] = numbers
    return result
