"""The bytes each kernel's algorithm needs, from shapes alone.

A roofline share is the least time the chip could take over the time the
kernel took. For these kernels the least time is set by HBM traffic; they
need no multiplications, so the FLOP bound is never the larger. What is
counted is the work the algorithm needs for the packets handed in, not the
work this implementation issues (the DFA's one-hot matmuls, the int32 copy
of the payload, pad rows of the lanes): a rewrite changes the time, never
the yardstick.
"""
from __future__ import annotations

WORD = 4


def dfa_scan_bytes(packets: int, pkt_bytes: int, states: int) -> int:
    """A multi-pattern DFA scan: every payload byte read once, each
    packet's length read and its match count written (int32), and the
    transition table (states x 256, int32) and its per-state counts read
    once per call."""
    return (packets * pkt_bytes + packets * 2 * WORD
            + states * 256 * WORD + states * WORD)


def cipher_bytes(packets: int, pkt_bytes: int) -> int:
    """The ARX cipher over a payload's whole words: read and write each."""
    return 2 * packets * (pkt_bytes // WORD) * WORD + 4 * WORD


def digest_bytes(packets: int, pkt_bytes: int) -> int:
    """The keyed fold digest: read each whole word, write 4 words."""
    return packets * (pkt_bytes // WORD) * WORD + packets * 4 * WORD + 4 * WORD


def least_seconds(nbytes: float, peaks: dict) -> float:
    return nbytes / float(peaks["hbm_bytes_per_s"])


def trie_states(rules) -> int:
    """States of the Aho-Corasick automaton of literal rules: the root and
    one per distinct non-empty prefix."""
    return 1 + len({r.encode()[:i] for r in rules
                    for i in range(1, len(r.encode()) + 1)})
