"""ARX cipher / keyed-hash rounds — AES & SHA accelerator analogs (Pallas TPU).

BlueField/Pensando crypto engines are opaque fixed-function blocks; what
matters for Meili is their *throughput shape*: a fixed number of rounds of
cheap word ops over every payload byte. We reproduce that shape with an
8-round ARX permutation (add-rotate-xor, VPU-native — TPUs have no AES-NI
analogue so ARX is the idiomatic substitute) and a keyed fold digest.

Payloads are pre-packed to uint32 words outside the kernel. The cipher
streams blocks of (block_b, W) words through VMEM. The digest is a serial
fold over words, so it lays words out word-major, (W, B/128, 128): each
loop step reads one full (8, 128) tile — 1024 packets' word w — and the
four state rows stay in vector registers. Not cryptographically secure — see
DESIGN.md §2 (structural analog only).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import ref as _ref

_LANES = 128
_SUBLANES = 8


def _cipher_kernel(words_ref, key_ref, out_ref):
    out_ref[...] = _ref.arx_cipher(words_ref[...], key_ref[0])


@functools.partial(jax.jit, static_argnames=("block_b", "interpret"))
def arx_cipher(words: jnp.ndarray, key: jnp.ndarray, *, block_b: int = 256,
               interpret: bool = False) -> jnp.ndarray:
    """words: (B, W) uint32, key: (4,) uint32 -> (B, W) uint32."""
    B, W = words.shape
    block_b = min(block_b, -(-B // _SUBLANES) * _SUBLANES)
    Bp = -(-B // block_b) * block_b
    out = pl.pallas_call(
        _cipher_kernel,
        grid=(Bp // block_b,),
        in_specs=[
            pl.BlockSpec((block_b, W), lambda i: (i, 0)),
            pl.BlockSpec((1, 4), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block_b, W), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((Bp, W), jnp.uint32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(jnp.pad(words.astype(jnp.uint32), ((0, Bp - B), (0, 0))),
      key.astype(jnp.uint32)[None, :])
    return out[:B]


def _hash_kernel(words_ref, h0_ref, out_ref):
    # words_ref: (W, 8, 128) uint32, one packet per (sublane, lane);
    # h0_ref/out_ref: (4, 8, 128) state rows.
    def step(w, h):
        h0, h1, h2, h3 = h
        n0 = h0 + words_ref[w]
        n1 = h1 ^ _ref._rotl(n0, 11)
        n2 = h2 + _ref._rotl(n1, 7)
        n3 = h3 ^ (n2 + _ref._GOLDEN)
        return n1, n2, n3, n0

    h = jax.lax.fori_loop(0, words_ref.shape[0], step,
                          tuple(h0_ref[k] for k in range(4)))
    for k in range(4):
        out_ref[k] = h[k]


@functools.partial(jax.jit, static_argnames=("interpret",))
def keyed_hash(words: jnp.ndarray, key: jnp.ndarray, *,
               interpret: bool = False) -> jnp.ndarray:
    """words: (B, W) uint32, key: (>=4,) uint32 -> (B, 4) uint32 digest
    (the fold of ``ref.keyed_hash``)."""
    B, W = words.shape
    tile = _SUBLANES * _LANES
    G = -(-B // tile)
    x = jnp.pad(words.astype(jnp.uint32), ((0, G * tile - B), (0, 0)))
    x = x.T.reshape(W, G * _SUBLANES, _LANES)
    h0 = jnp.broadcast_to(key[:4].astype(jnp.uint32)[:, None, None],
                          (4, _SUBLANES, _LANES))
    out = pl.pallas_call(
        _hash_kernel,
        grid=(G,),
        in_specs=[
            pl.BlockSpec((W, _SUBLANES, _LANES), lambda i: (0, i, 0)),
            pl.BlockSpec((4, _SUBLANES, _LANES), lambda i: (0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((4, _SUBLANES, _LANES), lambda i: (0, i, 0)),
        out_shape=jax.ShapeDtypeStruct((4, G * _SUBLANES, _LANES), jnp.uint32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(x, h0)
    return out.reshape(4, G * tile).T[:B]
