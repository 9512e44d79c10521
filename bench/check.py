"""The comparison that decides ``correct``.

Every output of the data plane is integer or boolean and the
configurations promise it bit for bit, so the comparison is exact: for
each field of the egress (payload, length, five-tuple, verdict mask, each
meta key) it counts the packets whose value differs from the plain
reference's, over the window batches the run sampled; ``missing`` counts
packets the plane did not return, or returned beyond the batch. Each count
has the limit 0.
"""
from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor
from typing import Callable, Dict, Tuple

import numpy as np

FIELDS = ("payload", "length", "five_tuple", "mask")
LIMIT = 0


def to_host(batch) -> Dict:
    """A PacketBatch (device or host arrays) as plain numpy arrays."""
    return {**{f: np.asarray(getattr(batch, f)) for f in FIELDS},
            "meta": {k: np.asarray(v) for k, v in batch.meta.items()}}


def _rows_differ(a: np.ndarray, b: np.ndarray) -> int:
    n = min(a.shape[0], b.shape[0])
    if a.shape[1:] != b.shape[1:]:
        return max(a.shape[0], b.shape[0])
    diff = (a[:n] != b[:n]).reshape(n, -1).any(axis=1)
    return int(diff.sum())


def mismatches(got: Dict, want: Dict) -> Dict[str, int]:
    """Packets that differ, per field, between two host batches."""
    out = {f: _rows_differ(got[f], want[f]) for f in FIELDS}
    n_want = want["payload"].shape[0]
    for key in sorted(set(got["meta"]) | set(want["meta"])):
        if key in got["meta"] and key in want["meta"]:
            out[f"meta.{key}"] = _rows_differ(got["meta"][key],
                                              want["meta"][key])
        else:
            out[f"meta.{key}"] = n_want
    out["missing"] = abs(got["payload"].shape[0] - n_want)
    return out


def check_batches(got: Dict[int, Dict], traffic, reference: Callable
                  ) -> Tuple[Dict[str, Dict], int]:
    """Compare each sampled batch with the reference over the same input
    (the references run side by side in threads; numpy lets go of the GIL).
    Returns the summed counts, each with its limit, and the number of
    batches that differ in anything (a run that sampled none counts one)."""
    total: Dict[str, int] = {}
    failed = 0 if got else 1
    keys = sorted(got)
    with ThreadPoolExecutor(max_workers=max(1, len(keys))) as pool:
        wants = list(pool.map(lambda k: reference(traffic.batch(k)), keys))
    for k, want in zip(keys, wants):
        counts = mismatches(got[k], want)
        failed += any(counts.values())
        for name, v in counts.items():
            total[name] = total.get(name, 0) + v
    return {name: {"value": v, "limit": LIMIT}
            for name, v in total.items()}, failed
