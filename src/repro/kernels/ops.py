"""Public kernel API with backend dispatch.

Three implementations per op:
  * ``pallas``    — the TPU kernel (pl.pallas_call, BlockSpec VMEM tiling);
  * ``interpret`` — same kernel body executed in Pallas interpret mode
                    (CPU correctness path, used by tests);
  * ``blocked``   — pure-jnp *flash-style* blocked algorithm: identical math,
                    O(block) memory, differentiable (custom VJP with a blocked
                    backward). XLA-compilable on any backend — this is what
                    the multi-pod dry-run lowers, so the compiled HLO reflects
                    flash memory behaviour rather than naive O(S²) attention;
  * ``ref``       — the naive oracle (kernels/ref.py), tests only.

``default_impl()`` picks ``pallas`` on TPU and ``blocked`` elsewhere.
"""
from __future__ import annotations

import functools
import os
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import ref as _ref
from repro.kernels import flash_attention as _fa
from repro.kernels import decode_attention as _da
from repro.kernels import ssd_scan as _ssd
from repro.kernels import dfa_scan as _scan
from repro.kernels import crypto as _crypto


def default_impl() -> str:
    return "pallas" if jax.default_backend() == "tpu" else "blocked"


# When True, blocked-algorithm scans are fully unrolled so XLA cost analysis
# counts every iteration (it counts while bodies ONCE). Used by the roofline
# decomposition (launch/decompose.py); never in production steps.
_UNROLL_SCANS = bool(int(os.environ.get("REPRO_UNROLL_SCANS", "0")))


def set_unroll_scans(v: bool) -> None:
    global _UNROLL_SCANS
    _UNROLL_SCANS = v


def _unroll(n: int) -> int:
    return n if _UNROLL_SCANS else 1


# ---------------------------------------------------------------------------
# Attention (train/prefill).
# ---------------------------------------------------------------------------

def _mask_block(qpos, kpos, causal: bool, window: Optional[int]):
    m = jnp.ones((qpos.shape[0], kpos.shape[0]), jnp.bool_)
    if causal:
        m &= kpos[None, :] <= qpos[:, None]
    if window is not None:
        m &= kpos[None, :] > qpos[:, None] - window
    return m


def _bias_block(qpos, kpos, causal: bool, window: Optional[int]):
    """Additive f32 mask bias (Sq, bk): 0 attendable / NEG_INF masked.

    Masking by arithmetic instead of rank-5 boolean `where` operands: XLA
    was materializing the broadcast pred tensors stacked across the KV-scan
    iterations (nk x B x Sq x Hkv x G x bk bools — tens of GB at 4k/32k
    sequence); an f32 bias folds into the logits add and the per-row
    emptiness guard comes from the running max itself (see fwd)."""
    return jnp.where(_mask_block(qpos, kpos, causal, window), 0.0,
                     _fa.NEG_INF).astype(jnp.float32)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _attention_blocked(q, k, v, causal, window, scale, block_k):
    out, _ = _attention_blocked_fwd(q, k, v, causal, window, scale, block_k)
    return out


def _attention_blocked_fwd(q, k, v, causal, window, scale, block_k):
    B, Sq, Hq, D = q.shape
    _, Sk, Hkv, _ = k.shape
    G = Hq // Hkv
    bk = min(block_k, Sk)
    assert Sk % bk == 0
    nk = Sk // bk
    qf = q.astype(jnp.float32).reshape(B, Sq, Hkv, G, D) * scale
    qpos = jnp.arange(Sq) + (Sk - Sq)

    def step(carry, ik):
        acc, m, l = carry
        kb = jax.lax.dynamic_slice_in_dim(k, ik * bk, bk, 1).astype(jnp.float32)
        vb = jax.lax.dynamic_slice_in_dim(v, ik * bk, bk, 1).astype(jnp.float32)
        logits = jnp.einsum("bqhgd,bkhd->bqhgk", qf, kb)
        kpos = ik * bk + jnp.arange(bk)
        bias = _bias_block(qpos, kpos, causal, window)          # (Sq, bk) f32
        logits = logits + bias[None, :, None, None, :]
        m_new = jnp.maximum(m, logits.max(axis=-1))
        # rows with no valid key so far have m_new == NEG_INF: zero their p
        # (otherwise exp(NEG_INF - NEG_INF) == 1 corrupts l); once a real
        # key appears, masked entries decay to exp(~NEG_INF) == 0 naturally.
        live = (m_new > 0.5 * _fa.NEG_INF).astype(jnp.float32)
        p = jnp.exp(logits - m_new[..., None]) * live[..., None]
        alpha = jnp.exp(m - m_new)
        l = alpha * l + p.sum(axis=-1)
        acc = acc * alpha[..., None] + jnp.einsum("bqhgk,bkhd->bqhgd", p, vb)
        return (acc, m_new, l), None

    acc0 = jnp.zeros((B, Sq, Hkv, G, D), jnp.float32)
    m0 = jnp.full((B, Sq, Hkv, G), _fa.NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, Sq, Hkv, G), jnp.float32)
    (acc, m, l), _ = jax.lax.scan(step, (acc0, m0, l0), jnp.arange(nk),
                                  unroll=_unroll(nk))
    safe_l = jnp.where(l == 0.0, 1.0, l)
    out = (acc / safe_l[..., None]).reshape(B, Sq, Hq, D).astype(q.dtype)
    lse = jnp.where(l > 0.0, m + jnp.log(safe_l), jnp.float32(1e30))
    return out, (q, k, v, out, lse)


def _attention_blocked_bwd(causal, window, scale, block_k, res, dout):
    q, k, v, out, lse = res
    B, Sq, Hq, D = q.shape
    _, Sk, Hkv, _ = k.shape
    G = Hq // Hkv
    bk = min(block_k, Sk)
    nk = Sk // bk
    qf = q.astype(jnp.float32).reshape(B, Sq, Hkv, G, D)
    do = dout.astype(jnp.float32).reshape(B, Sq, Hkv, G, D)
    of = out.astype(jnp.float32).reshape(B, Sq, Hkv, G, D)
    delta = (do * of).sum(-1)                                   # (B,Sq,Hkv,G)
    qpos = jnp.arange(Sq) + (Sk - Sq)

    def step(dq, ik):
        kb = jax.lax.dynamic_slice_in_dim(k, ik * bk, bk, 1).astype(jnp.float32)
        vb = jax.lax.dynamic_slice_in_dim(v, ik * bk, bk, 1).astype(jnp.float32)
        logits = jnp.einsum("bqhgd,bkhd->bqhgk", qf * scale, kb)
        kpos = ik * bk + jnp.arange(bk)
        bias = _bias_block(qpos, kpos, causal, window)
        # lse from fwd is +1e30 for rows with no valid keys -> p == 0 there;
        # masked entries carry bias NEG_INF -> p == 0 (no boolean operands).
        p = jnp.exp(logits + bias[None, :, None, None, :] - lse[..., None])
        dv = jnp.einsum("bqhgk,bqhgd->bkhd", p, do)
        dp = jnp.einsum("bqhgd,bkhd->bqhgk", do, vb)
        ds = p * (dp - delta[..., None]) * scale
        dq = dq + jnp.einsum("bqhgk,bkhd->bqhgd", ds, kb)
        dk = jnp.einsum("bqhgk,bqhgd->bkhd", ds, qf)
        return dq, (dk, dv)

    dq0 = jnp.zeros((B, Sq, Hkv, G, D), jnp.float32)
    dq, (dks, dvs) = jax.lax.scan(step, dq0, jnp.arange(nk),
                                  unroll=_unroll(nk))
    dk = jnp.moveaxis(dks, 0, 1).reshape(B, Sk, Hkv, D)
    dv = jnp.moveaxis(dvs, 0, 1).reshape(B, Sk, Hkv, D)
    return (dq.reshape(B, Sq, Hq, D).astype(q.dtype), dk.astype(k.dtype),
            dv.astype(v.dtype))


_attention_blocked.defvjp(lambda q, k, v, causal, window, scale, block_k:
                          _attention_blocked_fwd(q, k, v, causal, window, scale,
                                                 block_k),
                          _attention_blocked_bwd)


def attention(q, k, v, *, causal: bool = True, window: Optional[int] = None,
              scale: Optional[float] = None, impl: Optional[str] = None,
              block_k: int = 256):
    """Flash attention. q: (B,Sq,Hq,D); k,v: (B,Sk,Hkv,D)."""
    impl = impl or default_impl()
    scale_v = float(scale) if scale is not None else q.shape[-1] ** -0.5
    if impl == "ref":
        return _ref.mha_ref(q, k, v, causal=causal, window=window, scale=scale_v)
    if impl in ("pallas", "interpret"):
        return _fa.flash_attention(q, k, v, causal=causal, window=window,
                                   scale=scale_v, interpret=(impl == "interpret"))
    if impl == "blocked":
        return _attention_blocked(q, k, v, causal, window, scale_v, block_k)
    raise ValueError(f"unknown impl {impl}")


# ---------------------------------------------------------------------------
# Decode attention (one token vs deep KV cache).
# ---------------------------------------------------------------------------

def decode_attention(q, k, v, kv_len, *, scale: Optional[float] = None,
                     impl: Optional[str] = None, block_k: int = 512):
    """q: (B,Hq,D); k,v: (B,S,Hkv,D); kv_len: (B,)."""
    impl = impl or default_impl()
    scale_v = float(scale) if scale is not None else q.shape[-1] ** -0.5
    if impl == "ref":
        return _ref.decode_ref(q, k, v, kv_len, scale=scale_v)
    if impl in ("pallas", "interpret"):
        return _da.decode_attention(q, k, v, kv_len, scale=scale_v,
                                    block_k=block_k,
                                    interpret=(impl == "interpret"))
    if impl == "blocked":
        # One query token: O(S) logits is already flash-equivalent memory.
        return _ref.decode_ref(q, k, v, kv_len, scale=scale_v)
    raise ValueError(f"unknown impl {impl}")


# ---------------------------------------------------------------------------
# Mamba-2 SSD.
# ---------------------------------------------------------------------------

def _ssd_blocked(x, a, b, c, chunk: int):
    """Chunked SSD in pure jnp: same math as the kernel, scan over chunks."""
    B, S, H, P = x.shape
    N = b.shape[-1]
    ck = min(chunk, S)
    assert S % ck == 0
    nc = S // ck
    la_full = jnp.log(a.astype(jnp.float32))
    t_idx = jnp.arange(ck)

    def step(h, ic):
        sl = lambda arr: jax.lax.dynamic_slice_in_dim(arr, ic * ck, ck, 1)
        xc = sl(x).astype(jnp.float32)               # (B,T,H,P)
        lac = sl(la_full)                            # (B,T,H)
        bc = sl(b).astype(jnp.float32)               # (B,T,H,N)
        cc = sl(c).astype(jnp.float32)               # (B,T,H,N)
        cl = jnp.cumsum(lac, axis=1)                 # (B,T,H)
        decay = jnp.exp(cl[:, :, None] - cl[:, None, :])          # (B,T,S,H)... axes: (B,t,s,H)
        lmask = (t_idx[:, None] >= t_idx[None, :]).astype(jnp.float32)
        cb = jnp.einsum("bthn,bshn->btsh", cc, bc)
        y_intra = jnp.einsum("btsh,bshp->bthp", cb * decay * lmask[None, :, :, None], xc)
        ch = jnp.einsum("bthn,bhnp->bthp", cc, h)
        y = y_intra + jnp.exp(cl)[..., None] * ch
        w = jnp.exp(cl[:, -1:, :] - cl)              # (B,T,H)
        h_next = jnp.exp(cl[:, -1])[..., None, None] * h + jnp.einsum(
            "bthn,bthp->bhnp", bc * w[..., None], xc)
        return h_next, y

    h0 = jnp.zeros((B, H, N, P), jnp.float32)
    h_fin, ys = jax.lax.scan(step, h0, jnp.arange(nc), unroll=_unroll(nc))
    y = jnp.moveaxis(ys, 0, 1).reshape(B, S, H, P)
    return y.astype(x.dtype), h_fin


def ssd(x, a, b, c, *, chunk: int = 128, impl: Optional[str] = None):
    """Mamba-2 SSD. x: (B,S,H,P), a: (B,S,H) in (0,1], b/c: (B,S,H,N)."""
    impl = impl or default_impl()
    if impl == "ref":
        return _ref.ssd_ref(x, a, b, c)
    if impl in ("pallas", "interpret"):
        return _ssd.ssd_scan(x, a, b, c, chunk=chunk,
                             interpret=(impl == "interpret"))
    if impl == "blocked":
        return _ssd_blocked(x, a, b, c, chunk)
    raise ValueError(f"unknown impl {impl}")


# ---------------------------------------------------------------------------
# NIC accelerator ops (regex / crypto / hash).
# ---------------------------------------------------------------------------

def regex_matcher(rules, *, impl: Optional[str] = None):
    """Compile literal rules for the regex accelerator. Returns ``(match,
    states, device_bytes)``: ``match(payload, length)`` gives per-packet
    occurrence counts, ``states`` is the size of the rules' automaton, and
    ``device_bytes`` is what the compiled rules hold on the device. The
    device path is the filter-and-verify scan (``kernels/dfa_scan.py``),
    whose work per byte and VMEM blocks do not grow with the states; ``ref``
    and ``blocked`` step the dense automaton (``ref.dfa_scan``)."""
    impl = impl or default_impl()
    states = int(_ref.trie(rules)[0].size)
    if impl in ("ref", "blocked"):
        table, out_count = _ref.build_aho_corasick(rules)
        table_j, out_j = jnp.asarray(table), jnp.asarray(out_count)

        def match(payload, length):
            return _ref.dfa_scan(payload, length, table_j, out_j)

        return match, states, int(table.nbytes + out_count.nbytes)
    lit = _scan.compile_literals(rules)
    bloom, buckets = jnp.asarray(lit.bloom), jnp.asarray(lit.buckets)

    def match(payload, length):
        return _scan.scan(payload, length, lit, bloom, buckets,
                          interpret=(impl == "interpret"))

    return match, states, lit.nbytes


def cipher(words, key, *, impl: Optional[str] = None, block_b: int = 256):
    impl = impl or default_impl()
    if impl in ("ref", "blocked"):
        return _ref.arx_cipher(words, key)
    return _crypto.arx_cipher(words, key, block_b=block_b,
                              interpret=(impl == "interpret"))


def digest(words, key, *, impl: Optional[str] = None):
    impl = impl or default_impl()
    if impl in ("ref", "blocked"):
        return _ref.keyed_hash(words, key)
    return _crypto.keyed_hash(words, key, interpret=(impl == "interpret"))
