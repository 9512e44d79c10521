"""Main-path programs compiled for a described TPU v5e chip, at the sizes
``chip_smoke.py`` runs: 16384 packets of 1500 B, a 2^17-slot flow table, and
at the benchmark's 2048-rule Intrusion Detection set.

Nothing runs: the TPU compiler refuses what Mosaic cannot lower or what does
not fit VMEM or HBM, which interpret mode never shows. The topology is
described inside a fixture, never at import, so every test worker collects
the same tests and only the worker that runs this file loads the TPU
library.
"""
import json
import os
import pathlib

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.apps.nf import SNORT_RULES, intrusion_detection, ipsec_gateway
from repro.core import executor
from repro.core.flowcache import FlowCacheConfig
from repro.core.graph import PKT_BYTES, PacketBatch
from repro.core.ringbuffer import make_rings
from repro.kernels import crypto, dfa_scan
from repro.kernels import flow_lookup as fl

B = 16384                      # packets per batch
W = PKT_BYTES // 4             # payload words per packet
N, M = 8, 2048                 # pipelines x slots per pipeline (N * M == B)
ID_CONFIG = pathlib.Path(__file__).resolve().parents[1] / "bench/configs/id.json"


def _id_rules():
    return json.loads(ID_CONFIG.read_text())["app_args"]["rules"]


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip is written to the persistent cache but
    # cannot be read back without the chip: keep the cache out of it.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def S(one_chip):
    """S(shape, dtype): an argument shape placed on the described chip."""
    return lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)


def _scan_compiles(S, rules, rows):
    lit = dfa_scan.compile_literals(rules)
    return jax.jit(lambda p, n, bloom, buckets: dfa_scan.scan(
        p, n, lit, bloom, buckets)).lower(
        S((rows, PKT_BYTES), jnp.uint8), S((rows,), jnp.int32),
        S(lit.bloom.shape, jnp.int32), S(lit.buckets.shape, jnp.int32)
    ).compile()


def test_dfa_regex_compiles(S):
    """The regex accelerator's scan at ISG's 5 rules."""
    assert "tpu_custom_call" in _scan_compiles(S, SNORT_RULES, B).as_text()


@pytest.mark.parametrize("kernel", [crypto.arx_cipher, crypto.keyed_hash],
                         ids=["arx_cipher", "keyed_hash"])
def test_crypto_compiles(S, kernel):
    c = kernel.lower(S((B, W), jnp.uint32), S((4,), jnp.uint32)).compile()
    assert "tpu_custom_call" in c.as_text()


def test_flow_probe_compiles(S):
    cap = FlowCacheConfig().capacity
    c = fl._lookup_jnp.lower(
        S((cap,), jnp.uint32), S((cap,), jnp.uint32), S((cap,), jnp.int32),
        S((cap,), jnp.int32), S((B,), jnp.uint32), S((B,), jnp.uint32),
        S((), jnp.int32), window=FlowCacheConfig().window).compile()
    assert c.memory_analysis() is not None


def test_dfa_scan_compiles_at_2048_rules(S):
    """The filter-and-verify scan over the lanes of a full dispatch (32768
    rows) with the benchmark's 2048 rules: no VMEM block holds the
    automaton."""
    c = _scan_compiles(S, _id_rules(), 2 * B)
    assert "tpu_custom_call" in c.as_text()


def _dispatch(S, app):
    batch = PacketBatch(payload=S((B, PKT_BYTES), jnp.uint8),
                        length=S((B,), jnp.int32),
                        five_tuple=S((B, 5), jnp.int32),
                        mask=S((B,), jnp.bool_), meta={})
    proto = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape[1:], a.dtype),
                         batch)
    cap = executor._bucket(max(4096, M))
    rings = jax.eval_shape(lambda: make_rings(proto, cap, N))
    rings = jax.tree.map(lambda a: S(a.shape, a.dtype), rings)
    prog = executor._dispatch_program(app)
    return prog.lower(rings, batch, S((N, M), jnp.int32), S((N,), jnp.int32),
                      S((B,), jnp.int32)).compile()


def test_isg_dispatch_compiles(S):
    """The fused ISG dispatch: ring push/pop, ddos_check, regex, encap, digest
    and cipher in one program, with its three Pallas kernels inside it."""
    c = _dispatch(S, ipsec_gateway(impl="pallas"))
    assert c.as_text().count("tpu_custom_call") == 3    # regex, sha, aes


def test_id_dispatch_compiles_at_2048_rules(S):
    """The fused Intrusion Detection dispatch at the benchmark's rule set:
    flow extraction, the filter-and-verify scan and the verdict."""
    c = _dispatch(S, intrusion_detection(rules=_id_rules(), impl="pallas"))
    assert c.as_text().count("tpu_custom_call") == 1    # the scan's filter
