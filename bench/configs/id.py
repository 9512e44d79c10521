"""Plain reference of the Intrusion Detection app (Meili, arXiv:2312.11871,
Appendix F, Table 3: flow extraction, DPI regex, verdict), in numpy, for
the ``id`` configuration.

One pipeline, no orchestrator, no rings, no kernels: the chain applied in
turn to the whole batch, packets in their order.

  flow_ext   meta flow_key = source address ^ source port (int32);
  dpi_regex  the Aho-Corasick automaton of the rules (a trie, breadth-first
             failure links, a dense table of 256 bytes a state), stepped
             one byte at a time over each packet's first ``length`` bytes:
             every occurrence of every rule, overlaps and duplicate rules
             included -> meta match_num;
  verdict    keep a packet only if it matched no rule.

Payload, length and five-tuple leave unchanged. The automaton is built
once per rule set and kept. Everything is integer arithmetic and is
compared exactly.

``make_rules`` produced the configuration's ``app_args.rules``.
"""
from __future__ import annotations

import collections
import functools
from typing import Dict, Sequence, Tuple

import numpy as np

SNORT_RULES = ("attack", "GET /admin", "cmd.exe", "/etc/passwd", "SELECT *")


def make_rules(seed: int, count: int = 2048, lengths=(4, 32),
               alphabet=(0x20, 0x7E), first: Sequence[str] = SNORT_RULES):
    """``first``, then ``count - len(first)`` strings drawn from ``seed``:
    lengths uniform over ``lengths`` (both ends included), bytes uniform
    over ``alphabet`` (both ends included)."""
    rng = np.random.default_rng(seed)
    n = count - len(first)
    if n <= 0:
        return list(first)[:count]
    sizes = rng.integers(lengths[0], lengths[1] + 1, size=n)
    codes = rng.integers(alphabet[0], alphabet[1] + 1, size=int(sizes.sum()))
    cuts = np.cumsum(sizes)[:-1]
    return list(first) + ["".join(map(chr, c)) for c in np.split(codes, cuts)]


@functools.lru_cache(maxsize=4)
def automaton(rules: Tuple[str, ...]) -> Tuple[np.ndarray, np.ndarray]:
    """(table, hits): table[s * 256 + b] is the state after byte b in state
    s, hits[s] the rule occurrences that end on entering s."""
    children = [{}]
    ends = [0]
    for rule in rules:
        s = 0
        for b in rule.encode():
            if b not in children[s]:
                children[s][b] = len(children)
                children.append({})
                ends.append(0)
            s = children[s][b]
        ends[s] += 1
    table = np.zeros((len(children), 256), np.int64)
    fail = np.zeros(len(children), np.int64)
    hits = np.asarray(ends, np.int64)
    order = collections.deque()
    for b, t in children[0].items():
        table[0, b] = t
        order.append(t)
    while order:
        s = order.popleft()
        hits[s] += hits[fail[s]]
        table[s] = table[fail[s]]
        for b, t in children[s].items():
            fail[t] = table[fail[s], b]
            table[s, b] = t
            order.append(t)
    return table.ravel(), hits


def rule_matches(payload: np.ndarray, length: np.ndarray,
                 rules: Sequence[str]) -> np.ndarray:
    """Occurrences of the rules ending inside each packet's length."""
    table, hits = automaton(tuple(rules))
    B, L = payload.shape
    state = np.zeros(B, np.int64)
    total = np.zeros(B, np.int64)
    for j in range(int(length.max(initial=0))):
        live = j < length
        nxt = table[state * 256 + payload[:, j]]
        state = np.where(live, nxt, state)
        total += np.where(live, hits[state], 0)
    return total.astype(np.int32)


def reference(pkts: Dict[str, np.ndarray],
              rules: Sequence[str]) -> Dict[str, np.ndarray]:
    """The chain over one batch, with the configuration's ``rules``."""
    five = pkts["five_tuple"]
    match_num = rule_matches(pkts["payload"], pkts["length"], rules)
    return {"payload": pkts["payload"].copy(),
            "length": pkts["length"].copy(),
            "five_tuple": five.copy(),
            "mask": pkts["mask"] & (match_num == 0),
            "meta": {"flow_key": five[:, 0] ^ five[:, 2],
                     "match_num": match_num}}
