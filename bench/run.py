"""Run one cell of the benchmark once, on the chip this process finds.

    python3 -m bench.run --workload isg.mtu1500 --seed 7 --seconds 20 --trace 0

From the root of a checkout. The cell, its configuration, its traffic mix
and its metrics are found by name from ``BENCHMARK.json``. Set-up (JAX,
traffic, plane, compiles, warm-up) is timed from the start of the process;
then the plane is driven for ``--seconds``. With ``--trace 0`` the result
holds the cell's end-to-end metrics, with ``--trace 1`` its per-layer
metrics, read from the benchmark's spans, the program's counters and a
profiler trace of the window.

Earlier lines of standard output give the set-up split, the compiles in
the window (there should be none) and the latency sample's size; the last
line is one JSON object. The numbers compared with the reference, each
beside its limit, are the last lines of standard error and the result's
last key. Without a TPU, or with fewer chips than the cell asks for, the
run exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
PEAKS = pathlib.Path(__file__).resolve().parent / "peaks.json"


def process_age() -> float:
    """Seconds since this process started, from /proc (0 where absent)."""
    try:
        start_ticks = int(pathlib.Path("/proc/self/stat").read_text()
                          .rsplit(")", 1)[1].split()[19])
        uptime = float(pathlib.Path("/proc/uptime").read_text().split()[0])
        return max(0.0, uptime - start_ticks / os.sysconf("SC_CLK_TCK"))
    except (OSError, ValueError, IndexError):
        return 0.0


AGE_AT_START = process_age()


def load_peaks(device_kind: str, path: pathlib.Path = PEAKS) -> dict:
    """The published peaks of a device kind; an unknown kind is an error."""
    table = json.loads(path.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in {path}; "
                       f"known: {sorted(table)}")
    return table[device_kind]


def enable_compile_cache() -> str:
    """JAX's persistent cache at a fixed path inside the checkout (or where
    JAX_COMPILATION_CACHE_DIR says), keeping every program."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seconds <= 0:
        ap.error("--seconds must be positive")

    sys.path.insert(0, str(ROOT / "src"))
    from bench import harness, spec
    cell = spec.resolve(args.workload, ROOT)

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"bench: {args.workload} needs {cell.chips} TPU chip(s); JAX "
              f"finds {len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 3
    peaks = load_peaks(devices[0].device_kind)
    cache = enable_compile_cache()

    def log(line: str) -> None:
        print(line, flush=True)

    log(f"{args.workload} seed {args.seed}: {len(devices)} x "
        f"{devices[0].device_kind}; compile cache {cache}")
    result = harness.measure(cell, args.seed, args.seconds, bool(args.trace),
                             device=devices[0],
                             setup_origin=T_START - AGE_AT_START, log=log,
                             peaks=peaks)
    for name, v in result["check"].items():
        print(f"check {name}: {v['value']} (limit {v['limit']})",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
