"""Accelerator Function APIs (paper §4.2).

``Meili.regex / Meili.AES / Meili.sha / Meili.compression`` — uniform
invocation over heterogeneous accelerator implementations. Users pass only
the shared parameters (data pointer + rules / key / ratio); Meili binds the
hardware-specific settings (here: kernel impl selection, block shapes,
device placement by the allocator). Each API returns a `Function` stage whose
`resource` field is the accelerator kind Algorithm 2 allocates.

Payload word-packing (uint8 -> uint32) happens once per stage boundary, the
TPU analog of the DMA alignment the NIC SDKs perform.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Sequence

import jax.numpy as jnp
import numpy as np

from repro.core import pool
from repro.core.graph import Function, PacketBatch
from repro.kernels import ops
from repro.obs import spans


def _payload_words(batch: PacketBatch) -> jnp.ndarray:
    pay = batch.payload
    B, L = pay.shape
    Lw = (L // 4) * 4
    w = pay[:, :Lw].reshape(B, Lw // 4, 4).astype(jnp.uint32)
    return (w[..., 0] | (w[..., 1] << 8) | (w[..., 2] << 16) | (w[..., 3] << 24))


def _words_to_payload(words: jnp.ndarray, orig: jnp.ndarray) -> jnp.ndarray:
    B, W = words.shape
    out = jnp.stack([(words >> s) & 0xFF for s in (0, 8, 16, 24)], axis=-1)
    out = out.reshape(B, W * 4).astype(jnp.uint8)
    L = orig.shape[1]
    return jnp.concatenate([out, orig[:, W * 4:]], axis=1) if W * 4 < L else out[:, :L]


def regex(rules: Sequence[str], *, impl: Optional[str] = None,
          name: str = "regex") -> Function:
    """Multi-pattern matching; match count lands in meta['match_num'].

    The rules are compiled once, here, inside the ``meili.regex.compile``
    span; the gauges ``meili.regex.states`` and ``meili.regex.device_bytes``
    then hold the automaton's states and what the compiled rules keep on the
    device."""
    with spans.span("meili.regex.compile", rules=len(rules)):
        match, states, device_bytes = ops.regex_matcher(rules, impl=impl)
    spans.set_gauge("meili.regex.states", states)
    spans.set_gauge("meili.regex.device_bytes", device_bytes)

    def ucf(batch: PacketBatch) -> PacketBatch:
        return batch.with_meta(match_num=match(batch.payload, batch.length))

    return Function(name, "accel", ucf, resource=pool.REGEX,
                    params={"rules": list(rules)})


def AES(key: np.ndarray | Sequence[int], *, impl: Optional[str] = None,
        name: str = "aes") -> Function:
    """Payload encryption in place (ARX analog; see DESIGN.md §2)."""
    key_j = jnp.asarray(np.asarray(key, dtype=np.uint32)[:4])

    def ucf(batch: PacketBatch) -> PacketBatch:
        words = _payload_words(batch)
        enc = ops.cipher(words, key_j, impl=impl)
        return dataclasses.replace(batch,
                                   payload=_words_to_payload(enc, batch.payload))

    return Function(name, "accel", ucf, resource=pool.CRYPTO)


def sha(key: np.ndarray | Sequence[int] = (1, 2, 3, 4), *,
        impl: Optional[str] = None, name: str = "sha") -> Function:
    """Keyed digest into meta['digest'] (B, 4) uint32 (HMAC stand-in)."""
    key_j = jnp.asarray(np.asarray(key, dtype=np.uint32)[:4])

    def ucf(batch: PacketBatch) -> PacketBatch:
        words = _payload_words(batch)
        return batch.with_meta(digest=ops.digest(words, key_j, impl=impl))

    return Function(name, "accel", ucf, resource=pool.CRYPTO)


def compression(rt: float = 0.5, *, name: str = "compression") -> Function:
    """Compression accelerator analog: RLE cost model — computes the
    compressed length into meta['comp_len'] (the NIC engine is an opaque
    throughput box; Meili only needs its latency/throughput shape)."""

    def ucf(batch: PacketBatch) -> PacketBatch:
        pay = batch.payload
        runs = (pay[:, 1:] != pay[:, :-1]).astype(jnp.int32).sum(axis=1) + 1
        est = jnp.minimum(runs * 2, (batch.length * rt).astype(jnp.int32))
        return batch.with_meta(comp_len=est)

    return Function(name, "accel", ucf, resource=pool.COMPRESSION)
