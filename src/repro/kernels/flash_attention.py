"""Blocked causal/sliding-window GQA attention — Pallas TPU kernel.

TPU-native flash attention: the KV sequence is streamed through VMEM in
(block_k)-sized tiles while a running (max, sum, acc) triple lives in VMEM
scratch; QK^T and PV tiles hit the MXU. Grid = (batch*q_heads, q_blocks,
kv_blocks) with the KV axis innermost ("arbitrary" dimension semantics:
sequential, so scratch carries across kv steps).

Masking: causal and optional sliding window (Gemma-3 local layers). Fully
masked tiles are handled by multiplying probabilities with the mask (never
relying on exp(-inf)).

This kernel is the TPU *target*; it is validated on CPU via interpret=True
against `ref.mha_ref` (tests/test_kernels.py) and selected at runtime by
`ops.attention(..., impl="pallas")`.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
LANES = 128


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                  block_q: int, block_k: int, seq_q: int, seq_k: int,
                  num_kv_blocks: int, causal: bool, window: int | None,
                  scale: float):
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0, :, 0, :].astype(jnp.float32) * scale          # (bq, d)
    k = k_ref[0, :, 0, :].astype(jnp.float32)                  # (bk, d)
    v = v_ref[0, :, 0, :].astype(jnp.float32)                  # (bk, d)

    logits = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)  # (bq, bk)

    iq = pl.program_id(1)
    qpos = iq * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0) \
        + (seq_k - seq_q)
    kpos = ik * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
    mask = jnp.ones((block_q, block_k), jnp.bool_)
    if causal:
        mask &= kpos <= qpos
    if window is not None:
        mask &= kpos > qpos - window
    logits = jnp.where(mask, logits, NEG_INF)

    m_prev = m_ref[:, 0]                                        # (bq,)
    m_cur = jnp.max(logits, axis=1)
    m_next = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(logits - m_next[:, None]) * mask.astype(jnp.float32)
    alpha = jnp.exp(m_prev - m_next)
    l_next = alpha * l_ref[:, 0] + jnp.sum(p, axis=1)
    acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_ref[...] = jnp.broadcast_to(m_next[:, None], m_ref.shape)
    l_ref[...] = jnp.broadcast_to(l_next[:, None], l_ref.shape)

    @pl.when(ik == num_kv_blocks - 1)
    def _finish():
        l = l_ref[:, 0]
        safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, :, 0, :] = (acc_ref[...] / safe[:, None]).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "scale", "block_q", "block_k", "interpret"))
def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                    causal: bool = True, window: int | None = None,
                    scale: float | None = None, block_q: int = 128,
                    block_k: int = 128, interpret: bool = False) -> jnp.ndarray:
    """q: (B, Sq, Hq, D); k, v: (B, Sk, Hkv, D) with Hq % Hkv == 0."""
    B, Sq, Hq, D = q.shape
    _, Sk, Hkv, _ = k.shape
    assert Hq % Hkv == 0, (Hq, Hkv)
    G = Hq // Hkv
    block_q = min(block_q, Sq)
    block_k = min(block_k, Sk)
    assert Sq % block_q == 0 and Sk % block_k == 0, (Sq, Sk, block_q, block_k)
    scale_v = scale if scale is not None else D ** -0.5
    nq, nk = Sq // block_q, Sk // block_k
    grid = (B * Hq, nq, nk)

    kernel = functools.partial(
        _flash_kernel, block_q=block_q, block_k=block_k, seq_q=Sq, seq_k=Sk,
        num_kv_blocks=nk, causal=causal, window=window, scale=scale_v)

    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, 1, D),
                         lambda bh, iq, ik: (bh // Hq, iq, bh % Hq, 0)),
            pl.BlockSpec((1, block_k, 1, D),
                         lambda bh, iq, ik: (bh // Hq, ik, (bh % Hq) // G, 0)),
            pl.BlockSpec((1, block_k, 1, D),
                         lambda bh, iq, ik: (bh // Hq, ik, (bh % Hq) // G, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, 1, D),
                               lambda bh, iq, ik: (bh // Hq, iq, bh % Hq, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, D), jnp.float32),
            pltpu.VMEM((block_q, LANES), jnp.float32),
            pltpu.VMEM((block_q, LANES), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v)
