"""BENCHMARK.json and the files it names: every cell resolves by name, a
new cell, configuration, mix or metric is found from new files and entries
alone, the peaks table refuses an unknown device, and without a TPU the
command exits non-zero and prints no result."""
import json
import os
import pathlib
import re
import shutil
import subprocess
import sys

import pytest

from bench import spec
from bench.run import load_peaks

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_every_workload_resolves_its_files_by_name():
    b = spec.load_benchmark()
    for w in b["workloads"]:
        cell = spec.resolve(w["name"])
        assert cell.chips == w["chips"] == 1
        assert callable(cell.reference)
        assert cell.mix["batch"] > 0
        e2e = {m.name for m in cell.end_to_end}
        assert "setup_s" in e2e and len(e2e) >= 2
        assert cell.per_layer
        assert all(callable(m.read) for m in cell.end_to_end + cell.per_layer)
        assert {m.moves for m in cell.per_layer} <= e2e


def test_benchmark_json_keeps_to_its_shape():
    b = spec.load_benchmark()
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert b["paths"] == ["bench"] and 1 <= b["run_seconds"] <= 51
    names = [c["name"] for c in b["configs"]] + [w["name"] for w in b["workloads"]]
    metrics = b["end_to_end"] + b["per_layer"]
    names += [m["name"] for m in metrics]
    assert all(NAME.match(n) for n in names) and len(set(names)) == len(names)
    cells = {w["name"] for w in b["workloads"]}
    for m in b["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    assert {m["name"]: m["bound"] for m in b["end_to_end"]}["setup_s"] == 0.25
    for m in b["per_layer"]:
        assert set(m["workloads"]) <= cells and m["layer"]
        assert m["moves"] in {e["name"] for e in b["end_to_end"]}
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"
    for entry in b["configs"] + b["workloads"]:
        assert 1 <= len(entry["why"]) <= 200
    used = {w["config"] for w in b["workloads"]}
    assert used == {c["name"] for c in b["configs"]}
    for c in b["configs"]:
        assert (spec.ROOT / c["file"]).is_file()
        assert c["file"].startswith("bench/configs/")


def test_new_cells_configs_mixes_and_metrics_are_found_by_name(tmp_path):
    """Adding files and entries is enough: the harness's code is untouched."""
    shutil.copytree(spec.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    b = spec.load_benchmark()
    cfg = json.loads((spec.BENCH / "configs" / "fw.json").read_text())
    (tmp_path / "bench" / "configs" / "fw2.json").write_text(json.dumps(cfg))
    shutil.copy(spec.BENCH / "configs" / "fw.py",
                tmp_path / "bench" / "configs" / "fw2.py")
    (tmp_path / "bench" / "traffic" / "min64-wide.json").write_text(
        json.dumps({"batch": 1024, "pkt_bytes": 64, "flows": 7}))
    (tmp_path / "bench" / "metrics" / "batches_in_window.py").write_text(
        "def read(run):\n    return len(run.records)\n")
    b["configs"].append({"name": "fw2", "source": "https://example.org/fw2",
                         "file": "bench/configs/fw2.json", "reduced": [],
                         "why": "a test"})
    b["workloads"].append({"name": "fw2.wide", "config": "fw2",
                           "traffic": "min64-wide", "chips": 1, "why": "a test"})
    b["per_layer"].append({"name": "batches_in_window", "unit": "batches",
                           "better": "higher", "source": "host_clock",
                           "layer": "Benchmark", "moves": "throughput_mpps",
                           "workloads": ["fw2.wide"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(b))
    cell = spec.resolve("fw2.wide", tmp_path)
    assert cell.mix["flows"] == 7 and cell.mix["batch"] == 1024
    assert [m.name for m in cell.per_layer] == ["batches_in_window"]

    class Run:
        records = [1, 2, 3]
    assert cell.per_layer[0].read(Run) == 3
    # The old cells still resolve against the extended file.
    assert spec.resolve("fw.min64", tmp_path).mix["flows"] == 100_000
    with pytest.raises(KeyError):
        spec.resolve("no.such.cell", tmp_path)


def test_peaks_are_keyed_by_device_kind_and_unknown_is_an_error():
    v5e = load_peaks("TPU v5 lite")
    assert v5e["hbm_bytes_per_s"] == 819e9 and v5e["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError, match="TPU v4"):
        load_peaks("TPU v4")
    with pytest.raises(KeyError):
        load_peaks("cpu")


def _bench_env():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return env


def test_without_a_tpu_the_command_exits_nonzero_and_prints_no_result():
    b = spec.load_benchmark()
    cmd = b["command"][:]
    cmd[0] = sys.executable
    out = subprocess.run(cmd + ["--workload", "fw.min64", "--seed", "3",
                                "--seconds", "1", "--trace", "0"],
                         cwd=spec.ROOT, env=_bench_env(), capture_output=True,
                         text=True, timeout=300)
    assert out.returncode != 0
    assert "{" not in out.stdout
    assert "needs 1 TPU" in out.stderr


def test_benchmark_files_alone_do_not_run(tmp_path):
    shutil.copy(spec.ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(spec.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    cmd = spec.load_benchmark()["command"][:]
    cmd[0] = sys.executable
    out = subprocess.run(cmd + ["--workload", "isg.mtu1500", "--seed", "1",
                                "--seconds", "1"],
                         cwd=tmp_path, env=_bench_env(), capture_output=True,
                         text=True, timeout=300)
    assert out.returncode != 0 and "{" not in out.stdout
    assert not pathlib.Path(tmp_path / ".jax_cache").exists()
