"""The one traffic generator: every mix in ``bench/traffic/<name>.json`` is
parameters for it, so a new mix is a new data file and no new code.

A mix fixes the batch (packets per ``process`` call), the packet size, the
flow population and how far it slides per batch, the share of payloads that
carry a rule pattern, the share of flood payloads (one byte repeated, the
low-entropy traffic a DDoS check drops), the flows a firewall blocks, the
warm-up and the load (batches in flight). Everything is drawn from
``--seed``: the same seed gives the same batches, and every seed gives the
same sizes.

Payloads and five-tuples follow ``apps/packets.py`` and the sliding flow
window follows ``service/workload.megaflow``; they are copied here so that
no later change to the program moves the yardstick. The generator returns
plain numpy arrays and imports nothing of the program.
"""
from __future__ import annotations

import json
import pathlib
from typing import Dict

import numpy as np

DEFAULTS = {
    "pkt_bytes": 1500,          # payload bytes per packet (all packets full)
    "flows": 100_000,           # flow ids drawn uniformly from a window this wide
    "slide_per_batch": 0,       # the window's start moves this far per batch
    "embed_frac": 0.1,          # share of payloads that carry a rule pattern
    "flood_frac": 0.0,          # share of payloads that repeat one byte
    "telnet_flow_every": 0,     # flow ids f % n == 0 go to port 23 (0: none)
    "net192_flow_every": 0,     # flow ids f % n == n // 2 come from 192/8
    "warmup_batches": 24,       # batches before the window
    "inflight": 2,              # batches outstanding at once (closed loop)
}
REQUIRED = ("batch",)
EMBED_PATTERNS = ("attack", "GET /admin")   # apps/packets.py's defaults
PAYLOAD_RING = 8                # payload batches made at set-up, reused in turn
WARM_FLOW_BASE = 1 << 29        # flow ids of the shape warm-up, beyond any window


def load_mix(path: pathlib.Path) -> Dict:
    mix = json.loads(pathlib.Path(path).read_text())
    unknown = set(mix) - set(DEFAULTS) - set(REQUIRED)
    if unknown:
        raise ValueError(f"{path}: unknown traffic keys {sorted(unknown)}")
    missing = [k for k in REQUIRED if k not in mix]
    if missing:
        raise ValueError(f"{path}: missing traffic keys {missing}")
    return {**DEFAULTS, **mix}


def seed_words(seed: int) -> int:
    """Any whole number, negative or past 64 bits, as a seed numpy takes."""
    return int(seed) & ((1 << 64) - 1)


def five_tuple(flows: np.ndarray, telnet_every: int = 0,
               net192_every: int = 0) -> np.ndarray:
    """(B, 5) int32 sip dip sport dport proto for per-packet flow ids."""
    f = flows.astype(np.int64)
    five = np.empty((f.shape[0], 5), dtype=np.int32)
    sip = 0x0A000000 + f                         # src ip per flow, in 10/8
    if net192_every:
        sel = f % net192_every == net192_every // 2
        sip[sel] = 0xC0000000 + (f[sel] & 0xFFFFFF)
    five[:, 0] = sip.astype(np.uint32).view(np.int32)
    five[:, 1] = 0x0A800000 + (f // 4)           # dst ip
    five[:, 2] = 1024 + (f % 60000)              # sport
    five[:, 3] = 443                             # dport
    if telnet_every:
        five[f % telnet_every == 0, 3] = 23
    five[:, 4] = 6                               # TCP
    return five


def payloads(rng: np.random.Generator, batch: int, pkt_bytes: int,
             embed_frac: float, flood_frac: float = 0.0,
             patterns=EMBED_PATTERNS) -> np.ndarray:
    """Random bytes; the first ``embed_frac`` of rows carry one pattern each
    (patterns in turn) at a random offset, and the last ``flood_frac`` of
    rows repeat one byte drawn per row."""
    n_embed, n_flood = int(batch * embed_frac), int(batch * flood_frac)
    if n_embed + n_flood > batch:
        raise ValueError("embed_frac + flood_frac exceeds the batch")
    pay = rng.integers(0, 256, size=(batch, pkt_bytes), dtype=np.uint8)
    rows = np.arange(n_embed)
    for j, pat in enumerate(patterns):
        code = np.frombuffer(pat.encode(), dtype=np.uint8)
        sel = rows[rows % len(patterns) == j]
        pos = rng.integers(0, pkt_bytes - code.size, size=sel.size)
        pay[sel[:, None], pos[:, None] + np.arange(code.size)] = code
    if n_flood:
        pay[batch - n_flood:] = rng.integers(0, 256, size=(n_flood, 1),
                                             dtype=np.uint8)
    return pay


class Traffic:
    """Batches of one mix under one seed. ``batch(k)`` is batch k of the
    run: warm-up batches first, then the window's, one numbering."""

    def __init__(self, mix: Dict, seed: int):
        self.mix = mix
        self.B = int(mix["batch"])
        self.L = int(mix["pkt_bytes"])
        self._seed = seed_words(seed)
        self.ring = [payloads(np.random.default_rng([self._seed, 0, r]),
                              self.B, self.L, mix["embed_frac"],
                              mix["flood_frac"])
                     for r in range(PAYLOAD_RING)]
        self._length = np.full(self.B, self.L, np.int32)
        self._mask = np.ones(self.B, bool)

    def flows(self, k: int) -> np.ndarray:
        rng = np.random.default_rng([self._seed, 1, k])
        start = int(self.mix["slide_per_batch"]) * k
        return start + rng.integers(0, int(self.mix["flows"]), size=self.B)

    def _arrays(self, payload: np.ndarray, flows: np.ndarray) -> Dict:
        return {"payload": payload, "length": self._length,
                "five_tuple": five_tuple(flows, self.mix["telnet_flow_every"],
                                         self.mix["net192_flow_every"]),
                "mask": self._mask}

    def batch(self, k: int) -> Dict:
        return self._arrays(self.ring[k % len(self.ring)], self.flows(k))

    def lane_shapes(self, lanes: int):
        """Batches that fill ``lanes`` pipelines evenly (one flow of B/lanes
        packets each) and unevenly (one flow of B/lanes + B/lanes/8 packets
        among small ones): a plane with that many pipelines and 15% capacity
        headroom sees on them the lane buckets its window can see. Their
        flow ids lie beyond every window the mix can reach."""
        per = self.B // lanes
        even = np.repeat(np.arange(lanes), per)
        heavy = per + per // 8
        rest = self.B - heavy
        uneven = np.concatenate([np.zeros(heavy, np.int64),
                                 1 + np.arange(rest) % (8 * lanes)])
        out = []
        for i, f in enumerate((even, uneven)):
            f = np.resize(f, self.B) + WARM_FLOW_BASE + i * 1024
            out.append(self._arrays(self.ring[0], f))
        return out
