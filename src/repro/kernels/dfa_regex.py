"""Multi-pattern DFA scan (Aho-Corasick) — the paper's regex accelerator, TPU-native.

BlueField-2's RXP regex engine is a fixed-function block; the TPU analogue is
a vectorized DFA scan. GPU ports step one packet per thread; the TPU-native
rethink (DESIGN.md §2) instead keeps a *vector of packet states* — packets on
the 128 lanes — and turns the per-byte transition into MXU + VPU work with no
gather (Mosaic lowers no vector-indexed gather):

  cols[s, p]     = table[s, byte[p]]  = (tableT-planes @ onehot(byte))[s, p]
  next_state[p]  = sum_s onehot(state[p])[s] * cols[s, p]

The byte one-hot (256, block_b) is an exact bf16 operand, so the table is
split into 8-bit planes (values < 256 are exact in bf16) and recombined in
f32 — exact for any state count below 2^24. Payload bytes are laid out
position-major, (L/8, 8, B): the loop reads one aligned (8, block_b) tile of
8 byte positions per iteration and steps them with static row slices.

Match semantics: out_count[s] occurrences are credited when entering state s
(Aho-Corasick with counted outputs). Validated against ref.dfa_scan.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _dfa_kernel(payload_ref, length_ref, planes_ref, out_count_ref, match_ref,
                *, num_chunks: int):
    # payload_ref: (L/8, 8, BB) int32 bytes; length_ref: (1, BB) int32;
    # planes_ref: (P, S, 256) bf16 8-bit table planes (table = sum 256^k P_k);
    # out_count_ref: (S, BB) int32 (lane-broadcast).
    num_planes, S, _ = planes_ref.shape
    BB = length_ref.shape[1]
    length = length_ref[...]
    out_count = out_count_ref[...]
    byte_ids = jax.lax.broadcasted_iota(jnp.int32, (256, BB), 0)
    state_ids = jax.lax.broadcasted_iota(jnp.int32, (S, BB), 0)

    def step(j, byte, carry):
        state, matches = carry                      # (1, BB), (1, BB)
        onehot_b = (byte_ids == byte).astype(jnp.bfloat16)          # (256, BB)
        cols = jnp.zeros((S, BB), jnp.float32)
        for k in range(num_planes):
            cols = cols + float(256 ** k) * jnp.dot(
                planes_ref[k], onehot_b, preferred_element_type=jnp.float32)
        onehot_s = state_ids == state                               # (S, BB)
        nxt = jnp.sum(jnp.where(onehot_s, cols, 0.0), axis=0,
                      keepdims=True).astype(jnp.int32)
        valid = j < length
        state = jnp.where(valid, nxt, state)
        hits = jnp.sum(jnp.where(state_ids == state, out_count, 0), axis=0,
                       keepdims=True)
        return state, matches + jnp.where(valid, hits, 0)

    def chunk(c, carry):
        rows = payload_ref[c]                       # (8, BB): positions 8c..8c+7
        for r in range(8):
            carry = step(c * 8 + r, rows[r:r + 1, :], carry)
        return carry

    init = (jnp.zeros((1, BB), jnp.int32), jnp.zeros((1, BB), jnp.int32))
    _, matches = jax.lax.fori_loop(0, num_chunks, chunk, init)
    match_ref[...] = matches


def _table_planes(table: jnp.ndarray) -> jnp.ndarray:
    """(S, 256) int32 transitions -> (P, S_pad, 256) bf16 8-bit planes."""
    S = table.shape[0]
    num_planes = max(1, (max(S - 1, 1).bit_length() + 7) // 8)
    s_pad = -(-S // 16) * 16                        # bf16 sublane tile
    t = jnp.pad(table.astype(jnp.int32), ((0, s_pad - S), (0, 0)))
    return jnp.stack([((t >> (8 * k)) & 0xFF).astype(jnp.bfloat16)
                      for k in range(num_planes)])


@functools.partial(jax.jit, static_argnames=("block_b", "interpret"))
def dfa_regex(payload: jnp.ndarray, length: jnp.ndarray, table: jnp.ndarray,
              out_count: jnp.ndarray, *, block_b: int = 128,
              interpret: bool = False) -> jnp.ndarray:
    """payload: (B, L) uint8, length: (B,), table: (S, 256) int32,
    out_count: (S,) int32. Returns per-packet match counts (B,) int32.

    ``block_b`` packets (a multiple of 128) share one grid step; B is padded
    up to a multiple of it and L to a multiple of 8 (pad bytes lie past every
    packet's length, so they never step the DFA)."""
    B, L = payload.shape
    Bp = -(-B // block_b) * block_b
    Lp = -(-L // 8) * 8
    planes = _table_planes(table)
    S = planes.shape[1]
    pay = jnp.pad(payload, ((0, Bp - B), (0, Lp - L))).astype(jnp.int32)
    pay = pay.T.reshape(Lp // 8, 8, Bp)
    length2 = jnp.pad(length.astype(jnp.int32), (0, Bp - B))[None, :]
    oc = jnp.pad(out_count.astype(jnp.int32), (0, S - out_count.shape[0]))
    oc = jnp.broadcast_to(oc[:, None], (S, block_b))

    kernel = functools.partial(_dfa_kernel, num_chunks=Lp // 8)
    out = pl.pallas_call(
        kernel,
        grid=(Bp // block_b,),
        in_specs=[
            pl.BlockSpec((Lp // 8, 8, block_b), lambda i: (0, 0, i)),
            pl.BlockSpec((1, block_b), lambda i: (0, i)),
            pl.BlockSpec(planes.shape, lambda i: (0, 0, 0)),
            pl.BlockSpec((S, block_b), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_b), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, Bp), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(pay, length2, planes, oc)
    return out[0, :B]
