"""Mamba-2 SSD (state-space duality) chunked scan — Pallas TPU kernel.

The SSD recurrence h_t = a_t h_{t-1} + b_t ⊗ x_t, y_t = c_t · h_t is computed
chunk-by-chunk: within a T-sized chunk the quadratic form
Y = (mask ⊙ exp(cl_t - cl_s) ⊙ (C Bᵀ)) X runs on the MXU ((T,N)x(N,T),
(T,T)x(T,P) matmuls — T = N = 128 aligns with the systolic array), while the
cross-chunk state (N, P) is carried in VMEM scratch through the sequential
chunk grid axis. This is the TPU-native re-blocking of Mamba-2's algorithm:
instead of the paper's warp-level GPU tiling we choose chunk = 128 so every
matmul is MXU-shaped and the carried state never leaves VMEM.

Requires a_t > 0 (true for Mamba-2's exp(-softplus)·dt parameterization).
Validated against `ref.ssd_ref` with interpret=True.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(x_ref, la_ref, b_ref, c_ref, y_ref, hout_ref, h_ref, *,
                num_chunks: int):
    ic = pl.program_id(1)

    @pl.when(ic == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    x = x_ref[0, :, 0, :].astype(jnp.float32)      # (T, P)
    la = la_ref[0, :, 0].astype(jnp.float32)       # (T,)  log a_t
    b = b_ref[0, :, 0, :].astype(jnp.float32)      # (T, N)
    c = c_ref[0, :, 0, :].astype(jnp.float32)      # (T, N)
    h = h_ref[...]                                 # (N, P) carried state

    cl = jnp.cumsum(la)                            # (T,) cl[t] = sum_{i<=t} log a_i
    T = x.shape[0]
    t_idx = jax.lax.broadcasted_iota(jnp.int32, (T, T), 0)
    s_idx = jax.lax.broadcasted_iota(jnp.int32, (T, T), 1)
    # decay[t, s] = prod_{i=s+1..t} a_i  for s <= t
    decay = jnp.exp(cl[:, None] - cl[None, :])
    lmask = (s_idx <= t_idx).astype(jnp.float32)
    cb = jax.lax.dot_general(c, b, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)   # (T, T)
    y_intra = jax.lax.dot_general(cb * decay * lmask, x,
                                  (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)  # (T, P)
    # Contribution of the carried state: y_state[t] = exp(cl[t]) * (c_t · h).
    ch = jax.lax.dot_general(c, h, (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)   # (T, P)
    y = y_intra + jnp.exp(cl)[:, None] * ch
    y_ref[0, :, 0, :] = y.astype(y_ref.dtype)

    # State update: h' = exp(cl[T-1]) h + sum_s exp(cl[T-1] - cl[s]) b_s ⊗ x_s.
    w = jnp.exp(cl[T - 1] - cl)                     # (T,)
    bw = b * w[:, None]                             # (T, N)
    h_next = jnp.exp(cl[T - 1]) * h + jax.lax.dot_general(
        bw, x, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    h_ref[...] = h_next

    @pl.when(ic == num_chunks - 1)
    def _finish():
        hout_ref[0, 0, :, :] = h_next.astype(hout_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(x: jnp.ndarray, a: jnp.ndarray, b: jnp.ndarray, c: jnp.ndarray, *,
             chunk: int = 128, interpret: bool = False):
    """x: (B,S,H,P), a: (B,S,H) decays in (0,1], b/c: (B,S,H,N).

    Returns (y: (B,S,H,P), h_final: (B,H,N,P)). Zero initial state (prefill
    semantics; decode carries state through `serving.ssm_state`).
    """
    B, S, H, P = x.shape
    N = b.shape[-1]
    chunk = min(chunk, S)
    assert S % chunk == 0, (S, chunk)
    nc = S // chunk
    la = jnp.log(a.astype(jnp.float32))

    kernel = functools.partial(_ssd_kernel, num_chunks=nc)
    y, h_fin = pl.pallas_call(
        kernel,
        grid=(B * H, nc),
        in_specs=[
            pl.BlockSpec((1, chunk, 1, P), lambda bh, ic: (bh // H, ic, bh % H, 0)),
            pl.BlockSpec((1, chunk, 1), lambda bh, ic: (bh // H, ic, bh % H)),
            pl.BlockSpec((1, chunk, 1, N), lambda bh, ic: (bh // H, ic, bh % H, 0)),
            pl.BlockSpec((1, chunk, 1, N), lambda bh, ic: (bh // H, ic, bh % H, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, 1, P), lambda bh, ic: (bh // H, ic, bh % H, 0)),
            pl.BlockSpec((1, 1, N, P), lambda bh, ic: (bh // H, bh % H, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, S, H, P), x.dtype),
            jax.ShapeDtypeStruct((B, H, N, P), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((N, P), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(x, la, b, c)
    return y, h_fin
