"""Plain reference of the Firewall (Meili, arXiv:2312.11871, Appendix F,
Table 3), in numpy, for the ``fw`` configuration.

One pipeline, no orchestrator, no rings: the chain applied in turn to the
whole batch, packets in their order.

  rule_match  drop a packet to port 23 (telnet) or from 192.0.0.0/8;
  conn_track  meta conn_pkts = 1 for a packet still live, else 0.

The payload passes through untouched. All of it is integer arithmetic and
is compared exactly.
"""
from __future__ import annotations

from typing import Dict

import numpy as np


def reference(pkts: Dict[str, np.ndarray]) -> Dict[str, np.ndarray]:
    five = pkts["five_tuple"]
    blocked = (five[:, 3] == 23) | (((five[:, 0] >> 24) & 0xFF) == 0xC0)
    mask = pkts["mask"] & ~blocked
    return {"payload": pkts["payload"].copy(),
            "length": pkts["length"].copy(),
            "five_tuple": five.copy(), "mask": mask,
            "meta": {"conn_pkts": mask.astype(np.int32)}}
