"""Reduction of a profiler trace to the numbers the per-layer metrics read.

``load`` turns the ``.xplane.pb`` that ``jax.profiler`` writes into plain
events: per device, its operations (named ``<program>/<instruction>``) and
its program (module) executions; per host thread, the benchmark's own
``bench.*`` spans. ``reduce`` then
works on those events alone, so the test can feed it a small recorded
trace:

  window    the ``bench.window`` span, which the harness opens around the
            traced stretch; every interval is clipped to it;
  busy      the union of the device's operation intervals (averaged over
            devices), and the idle share 1 - busy / window;
  ops       device time per operation name, and per program name with its
            count of executions;
  gaps      the device's idle intervals, each part of one charged to the
            innermost ``bench.*`` span open on the issuing thread (the one
            that holds ``bench.window``) at that time, or ``host.other``.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import pathlib
from typing import Dict, List, Tuple

Interval = Tuple[float, float]
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW = "bench.window"
UNCOVERED = "host.other"


def op_name(hlo: str) -> str:
    """``%fusion.12 = u8[...] fusion(...)`` -> ``fusion.12``."""
    return hlo.split(" = ", 1)[0].lstrip("%")


def program_name(module: str) -> str:
    """``jit_dispatch(8142177517731192836)`` -> ``jit_dispatch``."""
    return module.split("(", 1)[0]


def qualify(ops: List, modules: List) -> List:
    """Name each operation ``<program>/<instruction>`` by the program
    execution that holds its start (instruction names repeat across
    programs)."""
    mods = sorted((s, e, program_name(n)) for n, s, e in modules)
    starts = [m[0] for m in mods]
    out = []
    for name, s, e in ops:
        i = bisect.bisect_right(starts, s) - 1
        prog = mods[i][2] if i >= 0 and s < mods[i][1] else "?"
        out.append([f"{prog}/{op_name(name)}", s, e])
    return out


def load(log_dir: str) -> Dict:
    """Plain events from the one trace under ``log_dir``."""
    import jax
    files = glob.glob(str(pathlib.Path(log_dir) / "**" / "*.xplane.pb"),
                      recursive=True)
    if len(files) != 1:
        raise RuntimeError(f"expected one trace under {log_dir}, got {files}")
    data = jax.profiler.ProfileData.from_file(files[0])
    devices, host = {}, {}
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            lines = {line.name: [[e.name, e.start_ns, e.end_ns]
                                 for e in line.events]
                     for line in plane.lines
                     if line.name in (OPS_LINE, MODULES_LINE)}
            modules = lines.get(MODULES_LINE, [])
            ops = qualify(lines.get(OPS_LINE, []), modules)
            if ops or modules:
                devices[plane.name] = {"ops": ops, "modules": modules}
        elif plane.name.startswith("/host:"):
            # Threads can share a line name: key them by position too.
            for i, line in enumerate(plane.lines):
                spans = [[e.name, e.start_ns, e.end_ns] for e in line.events
                         if e.name.startswith("bench.")]
                if spans:
                    host[f"{plane.name}/{i}/{line.name}"] = spans
    return {"devices": devices, "host": host}


def merge(intervals: List[Interval], lo: float, hi: float) -> List[Interval]:
    """Union of intervals, clipped to [lo, hi], sorted and disjoint."""
    out: List[List[float]] = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def complement(busy: List[Interval], lo: float, hi: float) -> List[Interval]:
    gaps, t = [], lo
    for s, e in busy:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if t < hi:
        gaps.append((t, hi))
    return gaps


def innermost_segments(spans: List[Tuple[str, float, float]], lo: float,
                       hi: float) -> List[Tuple[str, float, float]]:
    """Cut [lo, hi] into pieces labelled by the innermost span covering
    each (spans of one thread nest or are disjoint); bare pieces are
    ``host.other``."""
    cuts = sorted({lo, hi, *(t for _, s, e in spans for t in (s, e)
                             if lo < t < hi)})
    ordered = sorted(spans, key=lambda x: (x[1], -x[2]))
    out, open_, i = [], [], 0
    for a, b in zip(cuts, cuts[1:]):
        while i < len(ordered) and ordered[i][1] <= a:
            open_.append(ordered[i])
            i += 1
        open_ = [sp for sp in open_ if sp[2] > a]
        label = max(open_, key=lambda sp: sp[1])[0] if open_ else UNCOVERED
        out.append((label, a, b))
    return out


def attribute(gaps: List[Interval], segments) -> Dict[str, float]:
    """Seconds of gap time under each label (two-pointer overlap)."""
    total: Dict[str, float] = {}
    j = 0
    for gs, ge in gaps:
        while j < len(segments) and segments[j][2] <= gs:
            j += 1
        k = j
        while k < len(segments) and segments[k][1] < ge:
            label, s, e = segments[k]
            ov = min(e, ge) - max(s, gs)
            if ov > 0:
                total[label] = total.get(label, 0.0) + ov * 1e-9
            k += 1
    return total


@dataclasses.dataclass
class Reduced:
    window_s: float
    busy_s: float                           # averaged over devices
    op_s: Dict[str, float]                  # per op name, averaged over devices
    op_n: Dict[str, float]                  # events per op name, likewise
    module_s: Dict[str, float]              # per program name
    module_n: Dict[str, int]                # executions per program name
    gap_s: Dict[str, float]                 # idle seconds per host span label
    devices: int

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def op_time(self, kernel: str) -> Tuple[float, float]:
        """Seconds and events of a kernel's calls, in any program: the
        operations named ``<program>/<kernel>`` or ``<program>/<kernel>.N``
        (a Pallas call is named for its jitted wrapper)."""
        names = [k for k in self.op_s
                 if k.split("/", 1)[-1].rsplit(".", 1)[0] == kernel
                 or k.split("/", 1)[-1] == kernel]
        return (sum(self.op_s[k] for k in names),
                sum(self.op_n[k] for k in names))


def reduce(events: Dict) -> Reduced:
    windows = [(s, e, thread) for thread, spans in events["host"].items()
               for name, s, e in spans if name == WINDOW]
    if len(windows) != 1:
        raise ValueError(f"expected one {WINDOW} span, found {len(windows)}")
    lo, hi, issuer = windows[0]
    devices = events["devices"]
    if not devices:
        raise ValueError("no device operations in the trace")
    spans = [(n, s, e) for n, s, e in events["host"][issuer] if n != WINDOW]
    segments = innermost_segments(spans, lo, hi)
    n = len(devices)
    busy, ops, ops_n, mod_s, mod_n, gaps = 0.0, {}, {}, {}, {}, {}
    for dev in devices.values():
        merged = merge([(s, e) for _, s, e in dev["ops"]], lo, hi)
        busy += sum(e - s for s, e in merged) * 1e-9 / n
        for name, s, e in dev["ops"]:
            d = min(e, hi) - max(s, lo)
            if d > 0:
                ops[name] = ops.get(name, 0.0) + d * 1e-9 / n
                ops_n[name] = ops_n.get(name, 0.0) + 1.0 / n
        for name, s, e in dev["modules"]:
            d = min(e, hi) - max(s, lo)
            if d > 0:
                mod_s[name] = mod_s.get(name, 0.0) + d * 1e-9 / n
                mod_n[name] = mod_n.get(name, 0) + 1
        for label, sec in attribute(complement(merged, lo, hi),
                                    segments).items():
            gaps[label] = gaps.get(label, 0.0) + sec / n
    return Reduced((hi - lo) * 1e-9, busy, ops, ops_n, mod_s, mod_n, gaps, n)


def breakdown(red: Reduced, top: int = 10) -> Dict[str, List]:
    """The device operations that took most time, and the device's idle
    time by what the issuing thread was doing, largest first."""
    ops = sorted(red.op_s.items(), key=lambda kv: -kv[1])[:top]
    gaps = sorted(red.gap_s.items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[k, v] for k, v in ops],
            "idle_gaps": [[k, v] for k, v in gaps]}
