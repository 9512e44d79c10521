"""Pure-jnp oracles for every Pallas kernel (correctness references).

These are deliberately naive (O(S^2) attention, per-step SSM recurrence,
per-byte DFA stepping) — they define semantics; kernels and the blocked
production paths in ops.py are tested against them.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


# ---------------------------------------------------------------------------
# Multi-pattern DFA (Aho-Corasick) — the paper's regex accelerator.
# ---------------------------------------------------------------------------

def trie(patterns) -> tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
    """The trie of literal byte patterns, states in insertion order (0 is
    the root): each state's parent, the byte on its edge, its depth, and
    how many patterns end at it."""
    patterns = [p.encode() if isinstance(p, str) else bytes(p) for p in patterns]
    goto = [{}]
    out = [0]
    parent, edge, depth = [0], [0], [0]
    for pat in patterns:
        s = 0
        for ch in pat:
            nxt = goto[s].get(ch)
            if nxt is None:
                nxt = goto[s][ch] = len(goto)
                goto.append({})
                out.append(0)
                parent.append(s)
                edge.append(ch)
                depth.append(depth[s] + 1)
            s = nxt
        out[s] += 1
    return (np.asarray(parent, np.int64), np.asarray(edge, np.int64),
            np.asarray(depth, np.int64), np.asarray(out, np.int64))


def build_aho_corasick(patterns) -> tuple[np.ndarray, np.ndarray]:
    """Compile literal byte patterns into a dense DFA.

    Returns (table, out_count): table[s, b] = next state, out_count[s] = number
    of pattern occurrences ending when entering state s. Offline rule
    compilation — mirrors loading Snort rules into the regex accelerator.

    States are the trie's. The dense fill goes one depth at a time: a
    state's failure row (complete, as it lies shallower) is copied, then
    its own trie edges are set over it.
    """
    parent, edge, depth, out = trie(patterns)
    n = parent.size
    fail = np.zeros(n, np.int64)
    table = np.zeros((n, 256), dtype=np.int32)
    by_depth = np.argsort(depth, kind="stable")
    bounds = np.searchsorted(depth[by_depth], np.arange(depth.max() + 3))
    for d in range(depth.max() + 1):
        level = by_depth[bounds[d]:bounds[d + 1]]
        if d >= 2:
            fail[level] = table[fail[parent[level]], edge[level]]
        if d >= 1:
            out[level] += out[fail[level]]
            table[level] = table[fail[level]]
        kids = by_depth[bounds[d + 1]:bounds[d + 2]]
        table[parent[kids], edge[kids]] = kids
    return table, out.astype(np.int32)


def dfa_scan(payload: jnp.ndarray, length: jnp.ndarray, table: jnp.ndarray,
             out_count: jnp.ndarray) -> jnp.ndarray:
    """Per-packet match counts by serial per-byte DFA stepping.

    payload: (B, L) uint8; length: (B,) valid bytes; table: (S, 256) int32.
    Returns (B,) int32 total pattern occurrences within the valid prefix.
    """
    B, L = payload.shape

    def step(carry, j):
        state, matches = carry
        byte = payload[:, j].astype(jnp.int32)
        nxt = table[state, byte]
        valid = j < length
        state = jnp.where(valid, nxt, state)
        matches = matches + jnp.where(valid, out_count[state], 0)
        return (state, matches), None

    init = (jnp.zeros((B,), jnp.int32), jnp.zeros((B,), jnp.int32))
    (state, matches), _ = jax.lax.scan(step, init, jnp.arange(L))
    return matches


# ---------------------------------------------------------------------------
# ARX cipher + keyed hash — AES / SHA accelerator analogs (structural).
# ---------------------------------------------------------------------------

_ROUNDS = 8
_GOLDEN = np.uint32(0x9E3779B9)


def _rotl(x: jnp.ndarray, k: int) -> jnp.ndarray:
    return ((x << k) | (x >> (32 - k))).astype(jnp.uint32)


def arx_cipher(words: jnp.ndarray, key: jnp.ndarray) -> jnp.ndarray:
    """8-round ARX permutation over uint32 words. words: (..., W) uint32,
    key: (4,) uint32. Same data-movement shape as an AES-CTR pass."""
    x = words.astype(jnp.uint32)
    W = x.shape[-1]
    lanes = jnp.arange(W, dtype=jnp.uint32)
    for r in range(_ROUNDS):
        rk = (key[r % 4] + jnp.uint32(r) * _GOLDEN).astype(jnp.uint32)
        x = (x + rk).astype(jnp.uint32)
        x = _rotl(x, 5) ^ (x + lanes).astype(jnp.uint32)
        x = (x ^ _rotl(x, 13)) + _rotl(x, 7)
        x = x.astype(jnp.uint32)
    return x


def keyed_hash(words: jnp.ndarray, key: jnp.ndarray) -> jnp.ndarray:
    """Keyed fold digest (SHA stand-in). words: (B, W) uint32 -> (B, 4)."""
    x = words.astype(jnp.uint32)
    h = jnp.tile(key[None, :4], (x.shape[0], 1)).astype(jnp.uint32)

    def step(h, w):
        # w: (B,) one word column
        h0 = (h[:, 0] + w).astype(jnp.uint32)
        h1 = h[:, 1] ^ _rotl(h0, 11)
        h2 = (h[:, 2] + _rotl(h1, 7)).astype(jnp.uint32)
        h3 = h[:, 3] ^ (h2 + _GOLDEN).astype(jnp.uint32)
        return jnp.stack([h1, h2, h3, h0], axis=1), None

    h, _ = jax.lax.scan(step, h, x.T)
    return h


# ---------------------------------------------------------------------------
# Attention oracles.
# ---------------------------------------------------------------------------

def mha_ref(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *, causal: bool = True,
            window: int | None = None, scale: float | None = None) -> jnp.ndarray:
    """Naive softmax attention with GQA. q: (B, Sq, Hq, D), k/v: (B, Sk, Hkv, D).

    window: sliding-window size (attend to keys within `window` positions
    back, inclusive of self) — Gemma-3 local layers.
    """
    B, Sq, Hq, D = q.shape
    _, Sk, Hkv, _ = k.shape
    assert Hq % Hkv == 0
    G = Hq // Hkv
    scale = scale if scale is not None else D ** -0.5
    qf = q.astype(jnp.float32) * scale
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    qf = qf.reshape(B, Sq, Hkv, G, D)
    logits = jnp.einsum("bqhgd,bkhd->bhgqk", qf, kf)
    # Positions: queries occupy the last Sq slots of the Sk timeline.
    qpos = jnp.arange(Sq) + (Sk - Sq)
    kpos = jnp.arange(Sk)
    mask = jnp.ones((Sq, Sk), bool)
    if causal:
        mask &= kpos[None, :] <= qpos[:, None]
    if window is not None:
        mask &= kpos[None, :] > qpos[:, None] - window
    logits = jnp.where(mask[None, None, None], logits, -jnp.inf)
    p = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", p, vf)
    return out.reshape(B, Sq, Hq, D).astype(q.dtype)


def decode_ref(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
               kv_len: jnp.ndarray, *, scale: float | None = None) -> jnp.ndarray:
    """Single-token decode attention. q: (B, Hq, D), k/v: (B, S, Hkv, D),
    kv_len: (B,) valid cache length. Returns (B, Hq, D)."""
    B, Hq, D = q.shape
    _, S, Hkv, _ = k.shape
    G = Hq // Hkv
    scale = scale if scale is not None else D ** -0.5
    qf = q.astype(jnp.float32).reshape(B, Hkv, G, D) * scale
    logits = jnp.einsum("bhgd,bshd->bhgs", qf, k.astype(jnp.float32))
    valid = jnp.arange(S)[None] < kv_len[:, None]
    logits = jnp.where(valid[:, None, None], logits, -jnp.inf)
    p = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhgs,bshd->bhgd", p, v.astype(jnp.float32))
    return out.reshape(B, Hq, D).astype(q.dtype)


# ---------------------------------------------------------------------------
# Mamba-2 SSD oracle (scalar-decay SSM, per-step recurrence).
# ---------------------------------------------------------------------------

def ssd_ref(x: jnp.ndarray, a: jnp.ndarray, b: jnp.ndarray, c: jnp.ndarray,
            h0: jnp.ndarray | None = None) -> tuple[jnp.ndarray, jnp.ndarray]:
    """State-space duality reference.

    x: (B, S, H, P)  inputs (P = head channel dim)
    a: (B, S, H)     per-step decay in (0, 1]
    b: (B, S, H, N)  input projections (N = state dim)
    c: (B, S, H, N)  output projections
    h0: (B, H, N, P) initial state.
    Returns (y: (B, S, H, P), h_final: (B, H, N, P)).

    Recurrence: h_t = a_t * h_{t-1} + b_t ⊗ x_t ; y_t = c_t · h_t.
    """
    B, S, H, P = x.shape
    N = b.shape[-1]
    if h0 is None:
        h0 = jnp.zeros((B, H, N, P), jnp.float32)

    def step(h, t):
        at = a[:, t].astype(jnp.float32)                      # (B, H)
        bt = b[:, t].astype(jnp.float32)                      # (B, H, N)
        ct = c[:, t].astype(jnp.float32)                      # (B, H, N)
        xt = x[:, t].astype(jnp.float32)                      # (B, H, P)
        h = at[..., None, None] * h + bt[..., :, None] * xt[..., None, :]
        yt = jnp.einsum("bhn,bhnp->bhp", ct, h)
        return h, yt

    h, ys = jax.lax.scan(step, h0.astype(jnp.float32), jnp.arange(S))
    y = jnp.moveaxis(ys, 0, 1)                                # (B, S, H, P)
    return y.astype(x.dtype), h
