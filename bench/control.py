"""The control of the correctness check: it must come out not correct.

The data plane runs no model and states no precision; its outputs are
integers and booleans, promised bit for bit. So the control is the plain
reference put in the program's place with one guarantee of the
configuration broken, the one a faster plane would be tempted to drop:
packets come back in the order they were handed in. The control returns
the reference's egress lane by lane (each flow on lane ``src ip mod
pipelines``, packets in order within a lane), as the fused dispatch would
without its egress gather.

    python3 -m bench.control --workload isg.mtu1500 --seeds 11,12,13

For each seed it draws the batches a run would sample from the window,
compares the control's egress with the reference exactly as a run does,
and prints the numbers beside their limits. It needs no chip: the control
is host code, and what it reads does not depend on the load.
"""
from __future__ import annotations

import argparse
import json
import sys
from typing import Dict

import numpy as np

from bench import check, generator, harness, spec

WINDOW_BATCHES = 200        # a window's worth of batches to sample from


def lane_order(arrays: Dict, lanes: int) -> np.ndarray:
    """Packet order of the egress left in lane order."""
    lane = arrays["five_tuple"][:, 0].astype(np.int64) % lanes
    return np.argsort(lane, kind="stable")


def control_egress(cell, arrays: Dict) -> Dict:
    want = cell.reference(arrays)
    order = lane_order(arrays, int(cell.config["pipelines"]))
    return {**{f: want[f][order] for f in check.FIELDS},
            "meta": {k: v[order] for k, v in want["meta"].items()}}


def sampled(mix: Dict, seed: int) -> list:
    rng = np.random.default_rng([generator.seed_words(seed), 3])
    first = int(mix["warmup_batches"])
    picks = rng.choice(WINDOW_BATCHES, size=harness.CHECK_BATCHES,
                       replace=False)
    return sorted(first + int(p) for p in picks)


def read(cell, seed: int) -> Dict:
    traffic = generator.Traffic(cell.mix, seed)
    got = {k: control_egress(cell, traffic.batch(k))
           for k in sampled(cell.mix, seed)}
    numbers, failed = check.check_batches(got, traffic, cell.reference)
    return {"correct": failed == 0 and all(
        v["value"] <= v["limit"] for v in numbers.values()),
        "failed": failed, "check": numbers}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated whole numbers")
    args = ap.parse_args(argv)
    cell = spec.resolve(args.workload)
    for seed in (int(s) for s in args.seeds.split(",")):
        out = read(cell, seed)
        print(json.dumps({"workload": args.workload, "seed": seed, **out}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
