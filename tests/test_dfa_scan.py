"""The filter-and-verify scan (``kernels/dfa_scan.py``) against the
Aho-Corasick oracle, the scan the regex accelerator runs, the
vectorised automaton build against an independent one, and the data plane
running the Intrusion Detection chain at 64 rules against the benchmark's
plain reference."""
import importlib.util
import pathlib

import jax.numpy as jnp
import numpy as np
import pytest

from repro.apps.nf import SNORT_RULES, intrusion_detection
from repro.core import accel
from repro.core.executor import ParallelDataPlane
from repro.core.graph import PacketBatch
from repro.kernels import dfa_scan, ops, ref
from repro.obs import spans

ROOT = pathlib.Path(__file__).resolve().parents[1]


def _id_reference():
    path = ROOT / "bench" / "configs" / "id.py"
    spec = importlib.util.spec_from_file_location("id_reference", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ID = _id_reference()


def _rules(seed, n, lo, hi, alphabet=(97, 100)):
    rng = np.random.default_rng(seed)
    return ["".join(chr(c) for c in rng.integers(alphabet[0], alphabet[1] + 1,
                                                 size=int(m)))
            for m in rng.integers(lo, hi + 1, size=n)]


def _batch(seed, B, L, alphabet=(97, 100)):
    rng = np.random.default_rng(seed)
    pay = rng.integers(alphabet[0], alphabet[1] + 1, size=(B, L)).astype(np.uint8)
    length = rng.integers(0, L + 1, size=B).astype(np.int32)
    length[:3] = (0, 1, L)
    return pay, length


def _oracle(pats, pay, length):
    table, out = ref.build_aho_corasick(pats)
    return np.asarray(ref.dfa_scan(jnp.asarray(pay), jnp.asarray(length),
                                   jnp.asarray(table), jnp.asarray(out)))


def _scan(pats, pay, length):
    lit = dfa_scan.compile_literals(pats)
    return np.asarray(dfa_scan.scan(
        jnp.asarray(pay), jnp.asarray(length), lit, jnp.asarray(lit.bloom),
        jnp.asarray(lit.buckets), interpret=True))


CASES = {
    "few_states": (["he", "she", "his", "hers"], (101, 115), 8, 40),
    "overlaps_and_duplicates": (["aa", "aaa", "aba", "ab", "aa"], (97, 98), 16, 70),
    "prefixes_and_suffixes": (["abc", "abcd", "bcd", "cd", "d", "dab"], (97, 100), 16, 45),
    "an_empty_rule": (["", "ca", "acd"], (97, 100), 6, 19),
    "rules_longer_than_packets": (["abcdabcdabcdabcd", "bad"], (97, 100), 9, 12),
    "over_256_states": (_rules(3, 80, 4, 9), (97, 100), 24, 96),
    "over_4096_states": (_rules(4, 700, 6, 14), (97, 100), 20, 130),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_the_scan_equals_the_automaton(name):
    pats, alphabet, B, L = CASES[name]
    pay, length = _batch(len(name), B, L, alphabet)
    want = _oracle(pats, pay, length)
    np.testing.assert_array_equal(_scan(pats, pay, length), want)
    if name == "over_4096_states":
        assert ref.trie(pats)[0].size > 1 << 12


def test_matches_across_chunk_words_and_packet_ends():
    """Occurrences that straddle the filter's 32-position bitmap words and
    the kernel's 8-byte steps, and ones cut short by the length."""
    pats = ["qrstuvwxyz", "zq", "xyzq"]
    L = 100
    pay = np.full((4, L), ord("a"), np.uint8)
    for row, at in enumerate((27, 29, 31, 88)):
        pay[row, at:at + 10] = np.frombuffer(b"qrstuvwxyz", np.uint8)
        pay[row, at + 10] = ord("q")
    length = np.asarray([L, 38, 41, 99], np.int32)
    want = _oracle(pats, pay, length)
    assert list(want) == [3, 0, 1, 3]
    np.testing.assert_array_equal(_scan(pats, pay, length), want)


def test_floods_that_fill_more_than_one_compaction():
    """Every position a candidate: more nonzero bitmap words than one
    verification pass takes, and 32 set bits a word."""
    pats = ["aa", "aaa", "ab", "ba"]
    rng = np.random.default_rng(8)
    pay = np.full((16, 1200), ord("a"), np.uint8)
    pay[::3] = rng.integers(97, 99, size=(6, 1200))
    length = rng.integers(900, 1201, size=16).astype(np.int32)
    lit = dfa_scan.compile_literals(pats)
    chunks = -(-(1200 - lit.width + 1) // dfa_scan.CHUNK)
    assert 16 * chunks > dfa_scan.LANES            # more than one pass
    np.testing.assert_array_equal(_scan(pats, pay, length),
                                  _oracle(pats, pay, length))


@pytest.mark.parametrize("impl", ["interpret", "blocked"])
@pytest.mark.parametrize("rules", [3, 120], ids=["small", "large"])
def test_the_matcher_runs_the_filter_scan_at_every_size(rules, impl,
                                                        monkeypatch):
    """Small and large automata alike go to the filter-and-verify scan on
    the device path; ``blocked`` steps the dense automaton. Both equal the
    automaton."""
    pats = _rules(11, rules, 4, 8)
    called = []
    orig = ops._scan.dfa_scan
    monkeypatch.setattr(ops._scan, "dfa_scan", lambda *a, **k:
                        called.append("dfa_scan") or orig(*a, **k))
    match, states, nbytes = ops.regex_matcher(pats, impl=impl)
    assert states == ref.trie(pats)[0].size and nbytes > 0
    pay, length = _batch(5, 16, 64)
    got = np.asarray(match(jnp.asarray(pay), jnp.asarray(length)))
    np.testing.assert_array_equal(got, _oracle(pats, pay, length))
    assert called == (["dfa_scan"] if impl == "interpret" else [])


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_the_automaton_build_equals_an_independent_one(seed):
    pats = _rules(seed, 40 * (seed + 1), 1, 12, alphabet=(97, 97 + 2 + seed))
    table, out = ref.build_aho_corasick(pats)
    want_table, want_hits = ID.automaton(tuple(pats))
    np.testing.assert_array_equal(table.ravel(), want_table)
    np.testing.assert_array_equal(out, want_hits)


def test_compiling_rules_sets_the_gauges_inside_the_span():
    before = spans.totals().get("meili.regex.compile", {"calls": 0})["calls"]
    pats = _rules(21, 64, 4, 32, alphabet=(32, 126))
    accel.regex(pats, impl="interpret")
    assert spans.totals()["meili.regex.compile"]["calls"] == before + 1
    g = spans.gauges()
    assert g["meili.regex.states"] == ref.trie(pats)[0].size
    lit = dfa_scan.compile_literals(pats)
    assert g["meili.regex.device_bytes"] == lit.nbytes


def test_the_plane_equals_the_id_reference_at_64_rules():
    rules = ID.make_rules(7, count=64)
    assert rules[:5] == list(SNORT_RULES)
    plane = ParallelDataPlane(intrusion_detection(rules=rules, impl="interpret"),
                              num_pipelines=4, capacity_per_pipeline=40.0)
    rng = np.random.default_rng(3)
    for k in range(2):
        B, L = 96, 160
        pay = rng.integers(0, 256, size=(B, L)).astype(np.uint8)
        for row in range(0, B, 4):
            rule = rules[row % len(rules)].encode()
            at = int(rng.integers(0, L - len(rule)))
            pay[row, at:at + len(rule)] = np.frombuffer(rule, np.uint8)
        length = rng.integers(1, L + 1, size=B).astype(np.int32)
        five = np.stack([0x0A000000 + rng.integers(0, 50, size=B),
                         np.full(B, 0x0A800000), 1024 + rng.integers(0, 9, size=B),
                         np.full(B, 443), np.full(B, 6)], axis=1).astype(np.int32)
        mask = np.ones(B, bool)
        out = plane.process(PacketBatch(payload=pay, length=length,
                                        five_tuple=five, mask=mask, meta={}))
        want = ID.reference({"payload": pay, "length": length,
                             "five_tuple": five, "mask": mask}, rules)
        assert want["meta"]["match_num"].sum() > 0 and not want["mask"].all()
        for f in ("payload", "length", "five_tuple", "mask"):
            np.testing.assert_array_equal(np.asarray(getattr(out, f)), want[f])
        for key, v in want["meta"].items():
            np.testing.assert_array_equal(np.asarray(out.meta[key]), v)
