"""Resolve a cell of ``BENCHMARK.json`` to its files, by name alone.

  configuration  ``configs[].file`` (JSON), its plain reference beside it
                 (the same stem, ``.py``, exporting ``reference``); the
                 file's ``app_args`` go to the program's app factory and to
                 the reference alike;
  traffic mix    ``bench/traffic/<traffic>.json``, read by ``generator``;
  metric         ``bench/metrics/<name>.py``, exporting ``read(run)``.

Adding a cell, a configuration, a mix or a metric is adding files and
entries: nothing here names one.
"""
from __future__ import annotations

import dataclasses
import functools
import importlib.util
import json
import pathlib
from typing import Callable, Dict, List

from bench import generator

ROOT = pathlib.Path(__file__).resolve().parents[1]
BENCH = pathlib.Path(__file__).resolve().parent


def _load_module(path: pathlib.Path, name: str):
    if not path.is_file():
        raise FileNotFoundError(f"benchmark file missing: {path}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Metric:
    name: str
    unit: str
    read: Callable
    moves: str = ""


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: Dict
    reference: Callable
    mix: Dict
    end_to_end: List[Metric]
    per_layer: List[Metric]


def load_benchmark(root: pathlib.Path = ROOT) -> Dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def metric(entry: Dict, bench: pathlib.Path = BENCH) -> Metric:
    mod = _load_module(bench / "metrics" / f"{entry['name']}.py",
                       f"bench_metric_{entry['name'].replace('.', '_')}")
    return Metric(entry["name"], entry["unit"], mod.read,
                  entry.get("moves", ""))


def resolve(workload: str, root: pathlib.Path = ROOT) -> Cell:
    spec = load_benchmark(root)
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json; "
                       f"cells: {sorted(cells)}")
    w = cells[workload]
    cfg_entry = {c["name"]: c for c in spec["configs"]}[w["config"]]
    cfg_path = root / cfg_entry["file"]
    config = json.loads(cfg_path.read_text())
    ref = _load_module(cfg_path.with_suffix(".py"),
                       f"bench_reference_{w['config']}").reference
    ref = functools.partial(ref, **config.get("app_args", {}))
    bench = root / "bench"
    mix = generator.load_mix(bench / "traffic" / f"{w['traffic']}.json")

    def applies(m: Dict, reported: set) -> bool:
        if "workloads" in m:
            return workload in m["workloads"]
        return m.get("moves") in reported if "moves" in m else True

    e2e = [metric(m, bench) for m in spec["end_to_end"] if applies(m, set())]
    names = {m.name for m in e2e}
    per_layer = [metric(m, bench) for m in spec["per_layer"]
                 if applies(m, names)]
    return Cell(workload, int(w["chips"]), config, ref, mix, e2e, per_layer)
