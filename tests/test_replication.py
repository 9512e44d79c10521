"""Algorithm 1 (partial pipeline replication) — unit + property tests."""
import math

import pytest
from hypothesis import given, settings, strategies as st

from repro.core import replication as repl
from repro.core import sim


def test_paper_fig7c():
    """Fig 7(c): R = <2, 2, 3, 1> and 3 pipelines."""
    stages = ["S1", "S2", "S3", "S4"]
    lat = {"S1": 2.0, "S2": 1.7, "S3": 2.9, "S4": 1.0}
    R = repl.num_replication(stages, lat)
    assert R == {"S1": 2, "S2": 2, "S3": 3, "S4": 1}
    assert repl.num_pipelines(R) == 3


def test_paper_fig8b_pattern_ii():
    """Pipeline (II): split at S2, then S4; prefix stages scale to the min."""
    stages = ["S1", "S2", "S3", "S4"]
    lat = {"S1": 3.0, "S2": 1.0, "S3": 2.5, "S4": 1.2}
    R = repl.num_replication(stages, lat)
    assert R["S2"] == 1 and R["S4"] == 1
    assert R["S1"] == math.ceil(3.0 / 1.0)
    assert R["S3"] == math.ceil(2.5 / 1.2)


def test_uniform_stages_degenerate():
    stages = ["a", "b", "c"]
    R = repl.num_replication(stages, {s: 1.0 for s in stages})
    assert R == {s: 1 for s in stages}


def test_rejects_nonpositive_latency():
    with pytest.raises(ValueError):
        repl.num_replication(["a"], {"a": 0.0})


@st.composite
def pipelines(draw):
    n = draw(st.integers(1, 8))
    lat = {f"s{i}": draw(st.floats(0.1, 50.0)) for i in range(n)}
    return [f"s{i}" for i in range(n)], lat


@given(pipelines())
@settings(max_examples=200, deadline=None)
def test_property_global_min_gets_one(p):
    stages, lat = p
    R = repl.num_replication(stages, lat)
    d = min(stages, key=lambda s: lat[s])
    assert R[d] == 1
    assert all(r >= 1 for r in R.values())


@given(pipelines())
@settings(max_examples=200, deadline=None)
def test_property_capacity_matches_local_min(p):
    """Within each sub-pipeline, every stage's replicated capacity (R/L) is at
    least the capacity of the sub-pipeline's minimum stage."""
    stages, lat = p
    R = repl.num_replication(stages, lat)
    # reconstruct the recursive partition
    rest = list(stages)
    while rest:
        d = min(range(len(rest)), key=lambda i: lat[rest[i]])
        d_cap = 1.0 / lat[rest[d]]
        for s in rest[:d]:
            assert R[s] / lat[s] >= d_cap - 1e-9
        rest = rest[d + 1:]


@given(pipelines())
@settings(max_examples=100, deadline=None)
def test_property_partial_beats_full_when_min_is_last(p):
    """When the global minimum stage is LAST, the whole pipeline is one
    sub-pipeline and Algorithm 1 matches full replication's throughput with
    no more resources: ceil(max/L_d)·n >= Σ ceil(L_i/L_d)."""
    stages, lat = p
    d = min(stages, key=lambda s: lat[s])
    stages = [s for s in stages if s != d] + [d]      # move min to the end
    R = repl.num_replication(stages, lat)
    T_partial = repl.pipeline_throughput(stages, lat, R)
    c = math.ceil(T_partial * max(lat[s] for s in stages))
    full = repl.full_replication(stages, c)
    assert repl.pipeline_throughput(stages, lat, full) >= T_partial - 1e-9
    assert sum(R.values()) <= sum(full.values()) + 1e-9


def test_known_limitation_suffix_bottleneck():
    """Documented property of the paper's Algorithm 1 (DESIGN.md §5): it
    eliminates bubbles within sub-pipelines but does NOT balance a
    long-latency stage sitting AFTER the global minimum — the prefix can be
    overprovisioned relative to the suffix bottleneck. This pins the
    behaviour so any 'fix' is a conscious deviation from the paper."""
    stages = ["S1", "S2", "S3"]
    lat = {"S1": 10.0, "S2": 1.0, "S3": 9.0}
    R = repl.num_replication(stages, lat)
    assert R == {"S1": 10, "S2": 1, "S3": 1}
    # throughput capped by the unreplicated suffix stage S3:
    assert repl.pipeline_throughput(stages, lat, R) == pytest.approx(1 / 9)


@given(pipelines())
@settings(max_examples=30, deadline=None)
def test_property_sim_removes_bubbles(p):
    """Simulated steady-state throughput with R approaches the bottleneck
    service rate once enough sequences are in flight (> max replication)."""
    stages, lat = p
    R = repl.num_replication(stages, lat)
    n = min(4000, max(150, 25 * max(R.values())))
    res = sim.simulate(stages, lat, R, num_seqs=n)
    bound = min(R[s] / lat[s] for s in stages)
    assert res.throughput >= 0.7 * bound
