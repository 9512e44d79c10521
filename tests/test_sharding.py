"""Logical-axis sharding resolver properties."""
import jax
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st
from jax.sharding import PartitionSpec

from repro.parallel.sharding import default_rules, spec_for


def mesh_2d():
    return jax.make_mesh((1, 1), ("data", "model"))


def mesh_like(shape_by_axis):
    """A fake mesh-shaped object is not enough — build real 1-device meshes
    and only exercise divisibility logic via axis sizes of 1? Instead use
    the actual device mesh with logical sizes by monkeypatching shape."""
    return None


class _FakeMesh:
    """Minimal mesh stand-in so divisibility logic is testable without
    actually creating hundreds of devices."""

    def __init__(self, sizes):
        self.axis_names = tuple(sizes)
        self.shape = dict(sizes)


def test_heads_take_model_axis_when_divisible():
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    spec = spec_for(("embed", "heads", "head_dim"), (512, 16, 64),
                    default_rules(), mesh)
    assert spec == PartitionSpec("data", "model", None)


def test_no_head_dim_fallback_by_default():
    # contraction-dim TP is disabled by default (see sharding.py note):
    # indivisible heads leave attention unsharded on the model axis.
    mesh = _FakeMesh({"data": 16, "model": 16})
    rules = default_rules()
    spec = spec_for(("embed", "heads", "head_dim"), (512, 36, 64), rules,
                    mesh)
    assert spec[1] is None and spec[2] is None


def test_batch_uses_pod_and_data_jointly():
    mesh = jax.make_mesh((1, 1, 1), ("pod", "data", "model"))
    spec = spec_for(("batch", "seq"), (256, 4096), default_rules(), mesh)
    assert spec == PartitionSpec(("pod", "data"), None)


def test_kv_heads_priority_over_kv_seq():
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    spec = spec_for(("layers", "batch", "kv_seq", "kv_heads", "head_dim"),
                    (16, 128, 32768, 16, 128), default_rules(), mesh)
    # kv_heads (priority 1) wins the model axis; kv_seq stays unsharded
    assert spec[3] == "model"
    assert spec[2] is None


def test_unknown_axis_replicates():
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    spec = spec_for(("mystery", None), (7, 3), default_rules(), mesh)
    assert spec == PartitionSpec(None, None)


def test_no_fsdp_rules():
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    spec = spec_for(("vocab", "embed"), (50304, 2048), default_rules(False),
                    mesh)
    assert spec == PartitionSpec("model", None)


def test_divisibility_respected_fake_mesh():
    mesh = _FakeMesh({"data": 16, "model": 16})
    rules = default_rules()
    # 36 heads % 16 != 0 and no head_dim fallback -> attention unsharded
    spec = spec_for(("embed", "heads", "head_dim"), (2304, 36, 64), rules,
                    mesh)
    assert spec == PartitionSpec("data", None, None)
    # vocab 256206 % 16 != 0 -> replicated; embed gets data (fsdp)
    spec = spec_for(("vocab", "embed"), (256206, 1024), rules, mesh)
    assert spec == PartitionSpec(None, "data")


def test_batch_fallback_to_data_only_fake_mesh():
    mesh = _FakeMesh({"pod": 2, "data": 16, "model": 16})
    rules = default_rules()
    # batch 16 % (2*16) != 0 -> falls back to data alone
    spec = spec_for(("batch", "seq"), (16, 128), rules, mesh)
    assert spec == PartitionSpec("data", None)


@given(st.integers(1, 64), st.integers(1, 64), st.integers(1, 8),
       st.integers(1, 8))
@settings(max_examples=200, deadline=None)
def test_property_spec_always_divides(d1, d2, m1, m2):
    mesh = _FakeMesh({"data": m1, "model": m2})
    rules = default_rules()
    spec = spec_for(("embed", "ff"), (d1, d2), rules, mesh)
    for dim, s in zip((d1, d2), spec):
        if s is None:
            continue
        axes = (s,) if isinstance(s, str) else s
        size = int(np.prod([mesh.shape[a] for a in axes]))
        assert dim % size == 0


@given(st.integers(1, 8), st.integers(1, 8))
@settings(max_examples=100, deadline=None)
def test_property_no_axis_used_twice(m1, m2):
    mesh = _FakeMesh({"data": m1, "model": m2})
    rules = default_rules()
    spec = spec_for(("embed", "heads", "head_dim", "ff"),
                    (m1 * m2 * 4, m2 * 2, m2 * 2, m2 * 2), rules, mesh)
    used = []
    for s in spec:
        if s is None:
            continue
        used.extend((s,) if isinstance(s, str) else s)
    assert len(used) == len(set(used))
