"""Kernel micro-benchmarks: µs/call of the production (blocked) paths on this
host + interpret-mode spot checks. Roofline-model time on the TPU target is
derived per-shape for context."""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from benchmarks.common import row, timeit
from repro import hw
from repro.kernels import ops

RNG = np.random.default_rng(0)


def run(emit=print) -> dict:
    out = {}
    # flash attention
    B, S, Hq, Hkv, D = 1, 512, 8, 2, 64
    q = jnp.asarray(RNG.normal(size=(B, S, Hq, D)), jnp.float32)
    k = jnp.asarray(RNG.normal(size=(B, S, Hkv, D)), jnp.float32)
    v = jnp.asarray(RNG.normal(size=(B, S, Hkv, D)), jnp.float32)
    us = timeit(lambda: ops.attention(q, k, v, impl="blocked"), iters=5) * 1e6
    flops = 4 * B * Hq * S * S * D / 2
    tpu_us = flops / hw.PEAK_FLOPS_BF16 * 1e6
    out["attention"] = us
    emit(row("kernel_attention_b512", us, f"tpu_roofline={tpu_us:.1f}us"))
    # decode attention
    qd = jnp.asarray(RNG.normal(size=(8, Hq, D)), jnp.float32)
    kd = jnp.asarray(RNG.normal(size=(8, 4096, Hkv, D)), jnp.float32)
    kv_len = jnp.full((8,), 4096, jnp.int32)
    us = timeit(lambda: ops.decode_attention(qd, kd, kd, kv_len,
                                             impl="blocked"), iters=5) * 1e6
    emit(row("kernel_decode_4k", us,
             f"bytes={2 * kd.size * 4}"))
    # ssd
    x = jnp.asarray(RNG.normal(size=(2, 512, 4, 32)) * 0.3, jnp.float32)
    a = jnp.asarray(RNG.uniform(0.7, 0.99, size=(2, 512, 4)), jnp.float32)
    bmat = jnp.asarray(RNG.normal(size=(2, 512, 4, 32)) * 0.3, jnp.float32)
    us = timeit(lambda: ops.ssd(x, a, bmat, bmat, impl="blocked")[0],
                iters=5) * 1e6
    emit(row("kernel_ssd_b512", us, "chunked"))
    # dfa regex
    match, _, _ = ops.regex_matcher(["attack", "GET /admin", "cmd.exe"],
                                    impl="blocked")
    pay = jnp.asarray(RNG.integers(0, 256, size=(256, 1500)).astype(np.uint8))
    length = jnp.full((256,), 1500, jnp.int32)
    us = timeit(lambda: match(pay, length), iters=3) * 1e6
    gbps = 256 * 1500 * 8 / (us * 1e-6) / 1e9
    emit(row("kernel_dfa_regex_256x1500B", us, f"{gbps:.2f}Gbps"))
    # crypto
    w = jnp.asarray(RNG.integers(0, 2 ** 32, size=(256, 375),
                                 dtype=np.uint64).astype(np.uint32))
    key = jnp.asarray([1, 2, 3, 4], jnp.uint32)
    us = timeit(lambda: ops.cipher(w, key, impl="blocked"), iters=5) * 1e6
    gbps = 256 * 1500 * 8 / (us * 1e-6) / 1e9
    emit(row("kernel_arx_cipher_256x1500B", us, f"{gbps:.2f}Gbps"))
    return out


def main() -> None:
    run()


if __name__ == "__main__":
    main()
