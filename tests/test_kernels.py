"""Per-kernel validation: interpret-mode Pallas vs pure-jnp oracle, swept
over shapes/dtypes; blocked production paths; gradients."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

RNG = np.random.default_rng(0)


def _rand(shape, dtype=jnp.float32, scale=1.0):
    return jnp.asarray(RNG.normal(size=shape) * scale, dtype)


# -- flash attention -------------------------------------------------------------

ATTN_SHAPES = [
    # B, Sq, Sk, Hq, Hkv, D
    (1, 64, 64, 1, 1, 32),
    (2, 128, 128, 4, 2, 64),
    (1, 128, 128, 8, 1, 64),      # MQA
    (2, 64, 128, 4, 4, 32),       # cross-length (q suffix)
]


@pytest.mark.parametrize("shape", ATTN_SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("window", [None, 32])
def test_flash_attention_vs_ref(shape, dtype, window):
    B, Sq, Sk, Hq, Hkv, D = shape
    q = _rand((B, Sq, Hq, D), dtype)
    k = _rand((B, Sk, Hkv, D), dtype)
    v = _rand((B, Sk, Hkv, D), dtype)
    want = ref.mha_ref(q, k, v, causal=True, window=window)
    got = ops.attention(q, k, v, causal=True, window=window, impl="interpret")
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=tol,
                               rtol=tol)


@pytest.mark.parametrize("block_k", [32, 64, 128])
def test_blocked_attention_matches_ref(block_k):
    q = _rand((2, 128, 4, 32))
    k = _rand((2, 128, 2, 32))
    v = _rand((2, 128, 2, 32))
    want = ref.mha_ref(q, k, v, causal=True)
    got = ops.attention(q, k, v, causal=True, impl="blocked", block_k=block_k)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5,
                               rtol=2e-5)


def test_blocked_attention_grads_match_ref():
    q = _rand((1, 64, 2, 16))
    k = _rand((1, 64, 1, 16))
    v = _rand((1, 64, 1, 16))

    def loss_blocked(q, k, v):
        return (ops.attention(q, k, v, impl="blocked", block_k=16) ** 2).sum()

    def loss_ref(q, k, v):
        return (ref.mha_ref(q, k, v) ** 2).sum()

    g1 = jax.grad(loss_blocked, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4,
                                   rtol=1e-4)


def test_windowed_blocked_grads():
    q = _rand((1, 64, 2, 16))
    k = _rand((1, 64, 2, 16))
    v = _rand((1, 64, 2, 16))
    g1 = jax.grad(lambda q: (ops.attention(q, k, v, impl="blocked", window=16,
                                           block_k=32) ** 2).sum())(q)
    g2 = jax.grad(lambda q: (ref.mha_ref(q, k, v, window=16) ** 2).sum())(q)
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g2), atol=1e-4,
                               rtol=1e-4)


# -- decode attention -------------------------------------------------------------

@pytest.mark.parametrize("B,S,Hq,Hkv,D", [
    (2, 128, 4, 2, 32), (1, 256, 8, 1, 64), (4, 64, 2, 2, 16)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_attention_vs_ref(B, S, Hq, Hkv, D, dtype):
    q = _rand((B, Hq, D), dtype)
    k = _rand((B, S, Hkv, D), dtype)
    v = _rand((B, S, Hkv, D), dtype)
    kv_len = jnp.asarray(RNG.integers(1, S + 1, size=(B,)), jnp.int32)
    want = ref.decode_ref(q, k, v, kv_len)
    got = ops.decode_attention(q, k, v, kv_len, impl="interpret", block_k=32)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=tol,
                               rtol=tol)


# -- SSD scan -----------------------------------------------------------------------

@pytest.mark.parametrize("B,S,H,P,N,chunk", [
    (1, 128, 2, 16, 32, 32), (2, 256, 3, 8, 16, 64), (1, 64, 1, 32, 64, 64)])
def test_ssd_vs_ref(B, S, H, P, N, chunk):
    x = _rand((B, S, H, P), scale=0.5)
    a = jnp.asarray(RNG.uniform(0.5, 0.999, size=(B, S, H)), jnp.float32)
    b = _rand((B, S, H, N), scale=0.3)
    c = _rand((B, S, H, N), scale=0.3)
    y0, h0 = ref.ssd_ref(x, a, b, c)
    for impl in ("interpret", "blocked"):
        y1, h1 = ops.ssd(x, a, b, c, impl=impl, chunk=chunk)
        np.testing.assert_allclose(np.asarray(y1), np.asarray(y0), atol=1e-4,
                                   rtol=1e-3)
        np.testing.assert_allclose(np.asarray(h1), np.asarray(h0), atol=1e-4,
                                   rtol=1e-3)


def test_ssd_blocked_grads_finite():
    x = _rand((1, 64, 2, 8), scale=0.3)
    a = jnp.asarray(RNG.uniform(0.6, 0.99, size=(1, 64, 2)), jnp.float32)
    b = _rand((1, 64, 2, 16), scale=0.3)
    c = _rand((1, 64, 2, 16), scale=0.3)
    g = jax.grad(lambda x: ops.ssd(x, a, b, c, impl="blocked",
                                   chunk=32)[0].sum())(x)
    assert bool(jnp.isfinite(g).all())


# -- DFA regex ------------------------------------------------------------------------

def test_aho_corasick_counts():
    table, out = ref.build_aho_corasick(["he", "she", "his", "hers"])
    text = b"ushers"
    pay = jnp.asarray(np.frombuffer(text, np.uint8)[None])
    n = ref.dfa_scan(pay, jnp.asarray([len(text)]), jnp.asarray(table),
                     jnp.asarray(out))
    assert int(n[0]) == 3                       # she, he, hers


@pytest.mark.parametrize("B,L", [(4, 64), (8, 96), (2, 128)])
def test_dfa_kernel_vs_ref(B, L):
    rules = ["abc", "cab", "bbb"]
    table, out = ref.build_aho_corasick(rules)
    pay = jnp.asarray(RNG.integers(97, 100, size=(B, L)).astype(np.uint8))
    length = jnp.asarray(RNG.integers(1, L + 1, size=(B,)), jnp.int32)
    want = ref.dfa_scan(pay, length, jnp.asarray(table), jnp.asarray(out))
    match, _, _ = ops.regex_matcher(rules, impl="interpret")
    np.testing.assert_array_equal(np.asarray(match(pay, length)),
                                  np.asarray(want))


def test_dfa_kernel_table_over_256_states():
    pats = ["".join(chr(97 + int(c)) for c in RNG.integers(0, 4, size=6))
            for _ in range(80)]
    table, out = ref.build_aho_corasick(pats)
    assert table.shape[0] > 256
    pay = jnp.asarray(RNG.integers(97, 101, size=(16, 200)).astype(np.uint8))
    length = jnp.asarray(RNG.integers(1, 201, size=(16,)), jnp.int32)
    want = ref.dfa_scan(pay, length, jnp.asarray(table), jnp.asarray(out))
    match, _, _ = ops.regex_matcher(pats, impl="interpret")
    np.testing.assert_array_equal(np.asarray(match(pay, length)),
                                  np.asarray(want))
    assert int(want.sum()) > 0


def test_dfa_respects_length():
    table, out = ref.build_aho_corasick(["xy"])
    pay = jnp.asarray(np.frombuffer(b"xyxyxy", np.uint8)[None])
    for L, expect in [(6, 3), (4, 2), (1, 0)]:
        n = ref.dfa_scan(pay, jnp.asarray([L]), jnp.asarray(table),
                         jnp.asarray(out))
        assert int(n[0]) == expect


# -- crypto ------------------------------------------------------------------------------

def test_cipher_kernel_matches_and_changes_data():
    w = jnp.asarray(RNG.integers(0, 2 ** 32, size=(8, 16),
                                 dtype=np.uint64).astype(np.uint32))
    key = jnp.asarray([1, 2, 3, 4], jnp.uint32)
    want = ref.arx_cipher(w, key)
    got = ops.cipher(w, key, impl="interpret", block_b=4)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert not np.array_equal(np.asarray(got), np.asarray(w))


def test_cipher_key_sensitivity():
    w = jnp.asarray(RNG.integers(0, 2 ** 32, size=(2, 8),
                                 dtype=np.uint64).astype(np.uint32))
    c1 = ref.arx_cipher(w, jnp.asarray([1, 2, 3, 4], jnp.uint32))
    c2 = ref.arx_cipher(w, jnp.asarray([1, 2, 3, 5], jnp.uint32))
    assert not np.array_equal(np.asarray(c1), np.asarray(c2))


@pytest.mark.parametrize("B,W", [(8, 32), (2100, 5)])
def test_hash_kernel_matches(B, W):
    w = jnp.asarray(RNG.integers(0, 2 ** 32, size=(B, W),
                                 dtype=np.uint64).astype(np.uint32))
    key = jnp.asarray([9, 8, 7, 6], jnp.uint32)
    np.testing.assert_array_equal(
        np.asarray(ops.digest(w, key, impl="interpret")),
        np.asarray(ref.keyed_hash(w, key)))
