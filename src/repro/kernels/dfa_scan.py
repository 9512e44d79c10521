"""The regex accelerator's multi-pattern scan: a prefix filter over every
byte position, then exact verification of the few candidates.

A scan that steps the whole automaton each byte, with its table in VMEM,
grows in both with the state count, and thousands of literal rules give
tens of thousands of states. This scan's work per byte and its VMEM blocks
do not grow with the states:

  filter  (Pallas, the call named ``dfa_scan``) each block of 128 packets
          is read as its payload bytes and transposed in VMEM, so that
          packets lie on lanes and byte p of each on row p. Each
          position's window of ``width`` bytes (the shortest rule's length,
          at most 4) is put together from the rows after it, hashed
          ``hashes`` times, and each hash looks up one bit of its own
          4096-bit table, held as one row of 128 int32 lanes, by a lane
          gather. A position is a candidate when every bit is set: a
          partitioned Bloom filter over the rules' prefixes, so no
          occurrence is ever missed. ``hashes`` follows from the number of
          distinct prefixes, so that a random window passes with a
          probability below ``FP_TARGET``. Candidates come out as a bitmap,
          one int32 word per 32 positions of a packet, and the payload as
          little-endian int32 words, packet-major, for the verification.
  verify  (XLA) the bitmap's nonzero words are compacted, up to one word
          for every eighth packet at a time, by a running count of them per
          packet (no scatter); each set bit of those words is a candidate,
          checked one bit a word per round. A candidate's window (the
          longest rule's length) is selected from its packet's row of
          words, and its prefix's hash bucket (every rule with a prefix
          that hashes there, with its length and multiplicity) read as one
          row; each rule of the bucket that equals the window and ends
          inside the packet's length is counted. Every read is a gather of
          whole rows: the TPU issues those at a fraction of a microsecond,
          and gathers of byte slices or compactions by scatter (what
          ``jnp.nonzero`` does) take milliseconds at this size.

The counts equal the Aho-Corasick automaton's (``ref.dfa_scan``): every
occurrence of every rule, overlaps and duplicate rules included, that ends
inside the packet's length. Verification costs one round per set bit of the
word that has most among those compacted together (at most 32), and one
compaction per ``Bp / 8`` candidate words; the filter's cost does not
depend on the traffic or the state count, only on ``hashes``. So the scan's
cost is bounded by the candidates, not fixed: a rule of 1 or 2 bytes
shortens every window, and payloads built of a rule's prefix pass
everywhere, and either makes most positions candidates.
"""
from __future__ import annotations

import collections
import dataclasses
import functools
import math
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

MAX_WIDTH = 4          # bytes of a rule's prefix the filter hashes
FIELD_BITS = 12        # one hash: 7 bits of lane, 5 bits of word
TABLE_BITS = 1 << FIELD_BITS
FP_TARGET = 1e-6       # a random window's chance to pass the filter
MAX_HASHES = 32
ROWS = 8               # word rows in one chunk (one int32 tile's sublanes)
CHUNK = 4 * ROWS       # positions a chunk, and a bitmap word, holds
LANES = 128
_GOLDEN = 0x9E3779B9


@dataclasses.dataclass
class Literals:
    """Literal rules compiled for the scan (host arrays).

    ``bloom`` (hashes, 8, 128) int32: hash t's 4096-bit table, bit ``f >> 7``
    of lane ``f & 127`` for field value f, repeated down the 8 sublanes of a
    tile. ``buckets`` (T, slots * (2 + words)) int32: per bucket up to
    ``slots`` distinct rules as [length, multiplicity, bytes as
    little-endian words, zero-padded], length 0 for an empty slot."""
    width: int
    hashes: int
    lmax: int
    slots: int
    empty: int                 # empty rules: each counts once per byte
    bloom: np.ndarray
    buckets: np.ndarray

    @property
    def words(self) -> int:
        """Words that hold the longest rule."""
        return -(-self.lmax // 4)

    @property
    def nbytes(self) -> int:
        return int(self.bloom.nbytes + self.buckets.nbytes)


def _seed(i: int) -> int:
    return (_GOLDEN * (i + 1)) & 0xFFFFFFFF


def _mix_np(key: np.ndarray, seed: int) -> np.ndarray:
    """murmur3's 32-bit finaliser of ``key ^ seed`` (uint32)."""
    h = key.astype(np.uint32) ^ np.uint32(seed)
    h ^= h >> np.uint32(16)
    h *= np.uint32(0x85EBCA6B)
    h ^= h >> np.uint32(13)
    h *= np.uint32(0xC2B2AE35)
    h ^= h >> np.uint32(16)
    return h


def _i32(v: int):
    return jnp.int32(np.uint32(v).view(np.int32))


def _mix(key: jnp.ndarray, seed: int) -> jnp.ndarray:
    """``_mix_np`` on int32 bits (wrapping products, logical shifts)."""
    srl = jax.lax.shift_right_logical
    h = key ^ _i32(seed)
    h = h ^ srl(h, 16)
    h = h * _i32(0x85EBCA6B)
    h = h ^ srl(h, 13)
    h = h * _i32(0xC2B2AE35)
    return h ^ srl(h, 16)


def _field(h, t: int):
    """Hash t's 12-bit field of a mixed word (two fields a word)."""
    return (h >> (FIELD_BITS * (t % 2))) & (TABLE_BITS - 1)


def num_hashes(prefixes: int) -> int:
    """Bloom hashes for ``prefixes`` distinct keys: the fewest whose
    product of per-table occupancies is below ``FP_TARGET``."""
    occupied = 1.0 - (1.0 - 1.0 / TABLE_BITS) ** max(prefixes, 1)
    return int(min(MAX_HASHES, max(1, math.ceil(math.log(FP_TARGET)
                                                / math.log(occupied)))))


def compile_literals(patterns: Sequence) -> Literals:
    """Filter tables and verification buckets of literal byte rules."""
    pats = [p.encode() if isinstance(p, str) else bytes(p) for p in patterns]
    mult = collections.Counter(p for p in pats if p)
    empty = sum(1 for p in pats if not p)
    rules = sorted(mult)
    width = min([MAX_WIDTH] + [len(p) for p in rules])
    lmax = max([len(p) for p in rules], default=1)
    keys = np.asarray([int.from_bytes(p[:width], "little") for p in rules],
                      np.uint32)
    distinct = np.unique(keys)
    hashes = num_hashes(distinct.size)
    bloom = np.zeros((hashes, LANES), np.uint32)
    for t in range(hashes):
        f = _field(_mix_np(distinct, _seed(t // 2)), t)
        np.bitwise_or.at(bloom[t], (f & (LANES - 1)).astype(np.int64),
                         np.uint32(1) << (f >> np.uint32(7)))
    bloom = np.ascontiguousarray(np.broadcast_to(
        bloom.view(np.int32)[:, None, :], (hashes, ROWS, LANES)))
    nb = 1 << max(0, (2 * distinct.size - 1).bit_length())
    bucket = (_mix_np(keys, _seed(MAX_HASHES)) & np.uint32(nb - 1)).astype(
        np.int64)
    per = np.bincount(bucket, minlength=nb) if rules else np.zeros(nb, int)
    slots, words = max(1, int(per.max())), -(-lmax // 4)
    table = np.zeros((nb, slots, 2 + words), np.int32)
    fill = np.zeros(nb, np.int64)
    for p, b in zip(rules, bucket):
        row = table[b, fill[b]]
        row[0], row[1] = len(p), mult[p]
        row[2:] = np.frombuffer(p.ljust(4 * words, b"\0"), "<i4")
        fill[b] += 1
    return Literals(width, hashes, lmax, slots, empty, bloom,
                    table.reshape(nb, -1))


def _filter_kernel(pay_ref, lim_ref, bloom_ref, out_ref, words_ref, row_ref,
                   pos_ref, *, hashes: int, width: int):
    # pay_ref: (BB, L) uint8 payload rows; lim_ref: (1, BB) positions below
    # which a rule can start; bloom_ref: (hashes, 8, 128); out_ref:
    # (chunks, BB) int32 candidate bitmap, bit b of word c for position
    # 32c + b; words_ref: (BB, Lp / 4) int32, word w of a packet holding its
    # bytes 4w..4w+3, little-endian; row_ref: (BB, Lp) scratch, the bytes
    # as int32, zero past L; pos_ref: (Lp, BB) scratch, the same transposed.
    BB, L = pay_ref.shape
    Lp = pos_ref.shape[0]
    tail = L - L % LANES
    if tail < Lp:
        row_ref[:, pl.ds(tail, Lp - tail)] = jnp.zeros((BB, Lp - tail),
                                                       jnp.int32)
    row_ref[:, pl.ds(0, L)] = pay_ref[...].astype(jnp.int32)
    pos_ref[...] = row_ref[...].T
    nw = Lp // 4
    word = pos_ref[pl.ds(0, nw, stride=4), :]
    for k in range(1, 4):
        word = word | (pos_ref[pl.ds(k, nw, stride=4), :] << (8 * k))
    words_ref[...] = word.T
    rows = jax.lax.broadcasted_iota(jnp.int32, (ROWS, LANES), 0)

    def chunk(c, carry):
        for j in range(BB // LANES):
            lanes = pl.ds(j * LANES, LANES)
            lim = lim_ref[:, lanes]
            acc = jnp.zeros((ROWS, LANES), jnp.int32)
            for s in range(CHUNK // ROWS):
                base = pl.multiple_of(c * CHUNK + s * ROWS, ROWS)
                key = pos_ref[pl.ds(base, ROWS), lanes]
                for k in range(1, width):
                    key = key | (pos_ref[pl.ds(base + k, ROWS), lanes]
                                 << (8 * k))
                at = s * ROWS + rows
                hit = at + c * CHUNK < lim
                for t in range(hashes):
                    if t % 2 == 0:
                        h = _mix(key, _seed(t // 2))
                    f = _field(h, t)
                    bits = jnp.take_along_axis(bloom_ref[t], f & (LANES - 1),
                                               axis=1,
                                               mode="promise_in_bounds")
                    hit = hit & ((jax.lax.shift_right_logical(bits, f >> 7)
                                  & 1) != 0)
                acc = acc | jnp.where(hit, jnp.left_shift(1, at), 0)
            # The rows' bits are disjoint, so their sum is their union.
            out_ref[pl.ds(c, 1), lanes] = jnp.sum(acc, axis=0, keepdims=True)
        return carry

    jax.lax.fori_loop(0, out_ref.shape[0], chunk, 0)


def padded_bytes(L: int, chunks: int) -> int:
    """Byte rows the filter holds a packet in: every window of the
    ``chunks`` chunks, whole lane tiles."""
    need = max(L, CHUNK * chunks + MAX_WIDTH - 1)
    return -(-need // LANES) * LANES


@functools.partial(jax.jit, static_argnames=("chunks", "hashes", "width",
                                             "block_b", "interpret"))
def dfa_scan(payload: jnp.ndarray, lim: jnp.ndarray, bloom: jnp.ndarray, *,
             chunks: int, hashes: int, width: int, block_b: int = LANES,
             interpret: bool = False):
    """The filter: payload (Bp, L) uint8, Bp a multiple of ``block_b``;
    lim (1, Bp) int32. Returns the (chunks, Bp) int32 candidate bitmap and
    the payload as (Bp, Lp / 4) int32 words, zero past L."""
    Bp, L = payload.shape
    Lp = padded_bytes(L, chunks)
    kernel = functools.partial(_filter_kernel, hashes=hashes, width=width)
    return pl.pallas_call(
        kernel,
        grid=(Bp // block_b,),
        in_specs=[
            pl.BlockSpec((block_b, L), lambda i: (i, 0)),
            pl.BlockSpec((1, block_b), lambda i: (0, i)),
            pl.BlockSpec(bloom.shape, lambda i: (0, 0, 0)),
        ],
        out_specs=[pl.BlockSpec((chunks, block_b), lambda i: (0, i)),
                   pl.BlockSpec((block_b, Lp // 4), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((chunks, Bp), jnp.int32),
                   jax.ShapeDtypeStruct((Bp, Lp // 4), jnp.int32)],
        scratch_shapes=[pltpu.VMEM((block_b, Lp), jnp.int32),
                        pltpu.VMEM((Lp, block_b), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(payload, lim, bloom)


def _verify(row, n_bytes, pos, buckets, lit: Literals):
    """Occurrences of the rules that start at byte ``pos`` of K packets, of
    ``n_bytes`` bytes each, whose words are ``row`` (K, rows): the window
    is selected from the row, the bucket read as one row of ``buckets``."""
    q, sh = pos // 4, 8 * (pos % 4)
    at = jnp.arange(row.shape[1])
    w = [jnp.sum(jnp.where(at == (q + j)[:, None], row, 0), axis=1)
         for j in range(lit.words + 1)]
    srl = jax.lax.shift_right_logical
    win = jnp.stack([jnp.where(sh == 0, w[j], srl(w[j], sh)
                               | (w[j + 1] << (32 - sh)))
                     for j in range(lit.words)], axis=-1)     # (K, words)
    key = win[:, 0]
    if lit.width < 4:
        key = key & ((1 << (8 * lit.width)) - 1)
    h = _mix(key, _seed(MAX_HASHES)) & (buckets.shape[0] - 1)
    slot = buckets[h].reshape(-1, lit.slots, 2 + lit.words)
    n, mult, code = slot[..., 0], slot[..., 1], slot[..., 2:]
    left = n[..., None] - 4 * jnp.arange(lit.words)           # bytes left
    mask = jnp.where(left >= 4, -1,
                     jnp.left_shift(1, 8 * jnp.clip(left, 0, 3)) - 1)
    same = ((win[:, None, :] & mask) == code).all(axis=-1)
    ok = same & (n > 0) & (pos[:, None] + n <= n_bytes[:, None])
    return jnp.sum(jnp.where(ok, mult, 0), axis=1)


def scan(payload: jnp.ndarray, length: jnp.ndarray, lit: Literals,
         bloom: jnp.ndarray, buckets: jnp.ndarray, *,
         interpret: bool = False) -> jnp.ndarray:
    """Per-packet match counts (B,) int32 of ``lit``'s rules; ``bloom`` and
    ``buckets`` are its tables on the device."""
    B, L = payload.shape
    length = length.astype(jnp.int32)
    Bp = -(-B // LANES) * LANES
    chunks = -(-max(L - lit.width + 1, 1) // CHUNK)
    n_bytes = jnp.pad(length, (0, Bp - B))
    bits, words = dfa_scan(jnp.pad(payload, ((0, Bp - B), (0, 0))),
                           (n_bytes - lit.width + 1)[None, :], bloom,
                           chunks=chunks, hashes=lit.hashes, width=lit.width,
                           interpret=interpret)
    bits = bits.T                                             # (Bp, chunks)

    # Candidate word k (in packet order, then word order) lies in the first
    # packet whose running count of nonzero words passes k.
    nonzero = bits != 0
    ends = jnp.cumsum(jnp.sum(nonzero, axis=1))
    cap = min(Bp * chunks, max(LANES, Bp // 8))
    slot = jnp.arange(cap)

    def verify_words(carry):
        k0, total = carry
        k = k0 + slot
        p = jnp.minimum(jnp.searchsorted(ends, k, side="right",
                                         method="compare_all"), Bp - 1)
        nz, row = nonzero[p], bits[p]
        rank = jnp.cumsum(nz, axis=1) - 1
        first = jnp.where(p > 0, ends[p - 1], 0)
        sel = nz & (rank == (k - first)[:, None]) & (k < ends[-1])[:, None]
        word = jnp.sum(jnp.where(sel, row, 0), axis=1)
        base = jnp.argmax(sel, axis=1) * CHUNK
        pkt, pkt_bytes = words[p], n_bytes[p]

        def one_bit(c):
            word, got = c
            pos = base + jax.lax.population_count((word & -word) - 1)
            hit = _verify(pkt, pkt_bytes, pos, buckets, lit)
            return word & (word - 1), got + jnp.where(word != 0, hit, 0)

        _, got = jax.lax.while_loop(lambda c: (c[0] != 0).any(), one_bit,
                                    (word, jnp.zeros_like(word)))
        return k0 + cap, total.at[p].add(got)

    _, total = jax.lax.while_loop(lambda c: c[0] < ends[-1], verify_words,
                                  (jnp.int32(0), jnp.zeros((Bp,), jnp.int32)))
    return total[:B] + lit.empty * length
