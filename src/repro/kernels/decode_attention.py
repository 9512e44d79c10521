"""Flash-decode — single-token attention over a blocked KV cache (Pallas TPU).

One new query token attends to a seq_len-deep KV cache. The cache is streamed
through VMEM in block_k tiles with a running (max, sum, acc) carried in
scratch, so VMEM holds O(block_k * D) regardless of cache depth — this is
what makes `decode_32k` / `long_500k` KV depths feasible per-chip.

Validity is passed as a precomputed (B, S) bool mask (avoids SMEM scalar
plumbing and composes with paged/ragged caches). GQA: q is reshaped to
(B, Hkv, G, D) and each grid step processes one kv-head's G query heads, so
the QK^T tile is (G, block_k) — MXU-friendly when G*ceil align, and the same
kernel serves MHA (G = Hq) and MQA (Hkv = 1).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
LANES = 128


def _decode_kernel(q_ref, k_ref, v_ref, valid_ref, o_ref, acc_ref, m_ref, l_ref, *,
                   num_kv_blocks: int, scale: float):
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0, 0, :, :].astype(jnp.float32) * scale           # (G, d)
    k = k_ref[0, :, 0, :].astype(jnp.float32)                   # (bk, d)
    v = v_ref[0, :, 0, :].astype(jnp.float32)                   # (bk, d)
    valid = valid_ref[0, :]                                     # (bk,) bool

    logits = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)  # (G, bk)
    logits = jnp.where(valid[None, :], logits, NEG_INF)

    m_prev = m_ref[:, 0]
    m_next = jnp.maximum(m_prev, jnp.max(logits, axis=1))
    p = jnp.exp(logits - m_next[:, None]) * valid[None, :].astype(jnp.float32)
    alpha = jnp.exp(m_prev - m_next)
    l_ref[...] = jnp.broadcast_to(
        (alpha * l_ref[:, 0] + jnp.sum(p, axis=1))[:, None], l_ref.shape)
    acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_ref[...] = jnp.broadcast_to(m_next[:, None], m_ref.shape)

    @pl.when(ik == num_kv_blocks - 1)
    def _finish():
        l = l_ref[:, 0]
        safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0, :, :] = (acc_ref[...] / safe[:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "block_k", "interpret"))
def decode_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                     kv_len: jnp.ndarray, *, scale: float | None = None,
                     block_k: int = 512, interpret: bool = False) -> jnp.ndarray:
    """q: (B, Hq, D); k, v: (B, S, Hkv, D); kv_len: (B,) -> out (B, Hq, D)."""
    B, Hq, D = q.shape
    _, S, Hkv, _ = k.shape
    assert Hq % Hkv == 0
    G = Hq // Hkv
    block_k = min(block_k, S)
    assert S % block_k == 0, (S, block_k)
    nk = S // block_k
    scale_v = scale if scale is not None else D ** -0.5
    qg = q.reshape(B, Hkv, G, D)
    valid = jnp.arange(S)[None, :] < kv_len[:, None]            # (B, S)

    kernel = functools.partial(_decode_kernel, num_kv_blocks=nk, scale=scale_v)
    out = pl.pallas_call(
        kernel,
        grid=(B, Hkv, nk),
        in_specs=[
            pl.BlockSpec((1, 1, G, D), lambda b, h, ik: (b, h, 0, 0)),
            pl.BlockSpec((1, block_k, 1, D), lambda b, h, ik: (b, ik, h, 0)),
            pl.BlockSpec((1, block_k, 1, D), lambda b, h, ik: (b, ik, h, 0)),
            pl.BlockSpec((1, block_k), lambda b, h, ik: (b, ik)),
        ],
        out_specs=pl.BlockSpec((1, 1, G, D), lambda b, h, ik: (b, h, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((G, D), jnp.float32),
            pltpu.VMEM((G, LANES), jnp.float32),
            pltpu.VMEM((G, LANES), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(qg, k, v, valid)
    return out.reshape(B, Hq, D)
