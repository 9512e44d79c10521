"""Plain reference of the IPsec gateway (Meili, arXiv:2312.11871, Listing 1
and Appendix F, Table 3), in numpy, for the ``isg`` configuration.

One pipeline, no orchestrator, no rings, no kernels: every stage of the
chain applied in turn to the whole batch, packets in their order.

  ddos_check  keep a packet unless the entropy of its two halves' high-nibble
              histograms, summed, exceeds that of their mean by under 1.2;
  url_check   count occurrences of each rule (overlaps too) that end inside
              the packet's length -> meta match_num (no verdict of its own);
  ipsec       proto := 50 (ESP); meta spi = 0x1001, orig_len = length;
  sha         keyed fold digest of the payload's little-endian words;
  aes         8-round ARX permutation of the payload's words, in place.

The entropy is taken in float64; a random payload's margin lies near 4 and
a flood payload's (one byte repeated) is 0, both far from the threshold,
so the verdict does not hang on the last bit. Everything else is integer arithmetic and is compared exactly.
"""
from __future__ import annotations

from typing import Dict, Sequence

import numpy as np

DDOS_THRESHOLD = 1.2
SHA_KEY = (7, 11, 13, 17)
AES_KEY = (1, 2, 3, 4)
ROUNDS = 8
GOLDEN = np.uint32(0x9E3779B9)


def _entropy(p: np.ndarray) -> np.ndarray:
    return -(p * np.log2(np.maximum(p, 1e-12))).sum(axis=-1)


def _nibble_hist(payload: np.ndarray) -> np.ndarray:
    B = payload.shape[0]
    idx = (payload >> 4).astype(np.int32) + 16 * np.arange(B, dtype=np.int32)[:, None]
    h = np.bincount(idx.ravel(), minlength=16 * B).reshape(B, 16)
    h = h.astype(np.float64)
    return h / np.maximum(h.sum(axis=1, keepdims=True), 1.0)


def ddos_keep(payload: np.ndarray) -> np.ndarray:
    h1 = _nibble_hist(payload[:, :750])
    h2 = _nibble_hist(payload[:, 750:])
    margin = _entropy(h1) + _entropy(h2) - _entropy((h1 + h2) / 2.0)
    return ~(margin < DDOS_THRESHOLD)


def rule_matches(payload: np.ndarray, length: np.ndarray,
                 rules) -> np.ndarray:
    """Occurrences of every rule ending before each packet's length: each
    position where the rule's first byte sits, kept if the rest follows."""
    B, L = payload.shape
    total = np.zeros(B, np.int32)
    for rule in rules:
        code = np.frombuffer(rule.encode(), dtype=np.uint8)
        m = code.size
        if m > L:
            continue
        rows, cols = np.nonzero(payload[:, :L - m + 1] == code[0])
        for j in range(1, m):
            keep = payload[rows, cols + j] == code[j]
            rows, cols = rows[keep], cols[keep]
        inside = cols + m <= length[rows]
        total += np.bincount(rows[inside], minlength=B).astype(np.int32)
    return total


def words(payload: np.ndarray) -> np.ndarray:
    B, L = payload.shape
    Lw = (L // 4) * 4
    return np.ascontiguousarray(payload[:, :Lw]).view("<u4").reshape(B, Lw // 4)


def _rotl(x: np.ndarray, k: int) -> np.ndarray:
    return (x << np.uint32(k)) | (x >> np.uint32(32 - k))


def keyed_hash(w: np.ndarray, key=SHA_KEY) -> np.ndarray:
    h = [np.full(w.shape[0], k, np.uint32) for k in key]
    for col in w.T:
        h0 = h[0] + col
        h1 = h[1] ^ _rotl(h0, 11)
        h2 = h[2] + _rotl(h1, 7)
        h3 = h[3] ^ (h2 + GOLDEN)
        h = [h1, h2, h3, h0]
    return np.stack(h, axis=1)


def arx_cipher(w: np.ndarray, key=AES_KEY) -> np.ndarray:
    x = w.astype(np.uint32)
    lanes = np.arange(x.shape[1], dtype=np.uint32)
    t = np.empty_like(x)
    for r in range(ROUNDS):
        x += np.uint32(key[r % 4] + r * int(GOLDEN) & 0xFFFFFFFF)
        np.add(x, lanes, out=t)                     # x + lanes
        x = _rotl(x, 5)
        x ^= t
        t = _rotl(x, 13)
        t ^= x
        t += _rotl(x, 7)
        x, t = t, x
    return x


def reference(pkts: Dict[str, np.ndarray],
              rules: Sequence[str]) -> Dict[str, np.ndarray]:
    """The chain over one batch, with the configuration's ``rules``."""
    payload, length = pkts["payload"], pkts["length"]
    mask = pkts["mask"] & ddos_keep(payload)
    match_num = rule_matches(payload, length, rules)
    five = pkts["five_tuple"].copy()
    five[:, 4] = 50
    w = words(payload)
    digest = keyed_hash(w)
    enc = arx_cipher(w).view(np.uint8).reshape(payload.shape[0], -1)
    out_payload = payload.copy()
    out_payload[:, :enc.shape[1]] = enc
    return {"payload": out_payload, "length": length.copy(),
            "five_tuple": five, "mask": mask,
            "meta": {"match_num": match_num,
                     "spi": np.full(length.shape, 0x1001, np.int32),
                     "orig_len": length.copy(),
                     "digest": digest}}
