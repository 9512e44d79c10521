"""The ``id`` configuration and its plain reference: the committed rules are
the generator's at the recorded seed, the automaton has the size the
configuration states, the reference counts what a brute-force count per
rule gives, and a run of the cell's chain with one rule left out is not
correct."""
import functools

import numpy as np
import pytest

from bench import generator, harness, spec, workcount

CELL = "id.rules2k"


@pytest.fixture(scope="module")
def cell():
    return spec.resolve(CELL)


@pytest.fixture(scope="module")
def modules():
    configs = spec.BENCH / "configs"
    return (spec._load_module(configs / "id.py", "bench_reference_id_test"),
            spec._load_module(configs / "isg.py", "bench_reference_isg_test"))


def test_the_rules_are_make_rules_at_the_recorded_seed(cell, modules):
    ident, _ = modules
    rules, made = cell.config["app_args"]["rules"], cell.config["rule_set"]
    assert rules == ident.make_rules(made["seed"], count=made["count"],
                                     lengths=tuple(made["lengths"]),
                                     alphabet=tuple(made["alphabet"]))
    assert len(rules) == 2048 and rules[:5] == list(ident.SNORT_RULES)
    assert all(4 <= len(r) <= 32 and all(0x20 <= ord(c) <= 0x7E for c in r)
               for r in rules[5:])


def test_the_automaton_has_the_stated_size(cell):
    states = workcount.trie_states(cell.config["app_args"]["rules"])
    assert abs(states - 34788) <= 0.05 * 34788


def _plant(rng, pay, rules, every):
    for row in range(0, pay.shape[0], every):
        rule = np.frombuffer(rules[rng.integers(len(rules))].encode(), np.uint8)
        at = int(rng.integers(0, pay.shape[1] - rule.size + 1))
        pay[row, at:at + rule.size] = rule


CASES = {
    # rules that overlap themselves and each other, on a two-letter payload
    "overlaps": (["aa", "aba", "aaa", "ab", "ba"], (97, 98), 40, 64),
    # rules that are prefixes or suffixes of others, and a duplicate
    "prefixes_suffixes": (["abcd", "abc", "bcd", "cd", "abcd", "d"],
                          (97, 100), 48, 90),
    # rules longer than 8 bytes, planted across the 8-byte steps
    "chunk_boundaries": (["abcdabcdab", "dabcdabcdabcdab", "cdabcdab"],
                         (97, 100), 64, 120),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_the_reference_counts_what_a_brute_force_count_gives(name, modules):
    ident, isg = modules
    rules, alphabet, B, L = CASES[name]
    rng = np.random.default_rng(len(name))
    pay = rng.integers(alphabet[0], alphabet[1] + 1, size=(B, L)).astype(np.uint8)
    _plant(rng, pay, rules, 2)
    length = rng.integers(0, L + 1, size=B).astype(np.int32)
    length[:4] = (0, 1, 3, L)                   # shorter than any rule, full
    got = ident.rule_matches(pay, length, rules)
    np.testing.assert_array_equal(got, isg.rule_matches(pay, length, rules))
    assert got.sum() > B // 2


def test_the_reference_counts_the_committed_rules_like_brute_force(cell, modules):
    ident, isg = modules
    rules = cell.config["app_args"]["rules"]
    rng = np.random.default_rng(9)
    pay = rng.integers(0x20, 0x7F, size=(24, 300)).astype(np.uint8)
    _plant(rng, pay, rules, 1)
    length = rng.integers(0, 301, size=24).astype(np.int32)
    got = ident.rule_matches(pay, length, rules)
    np.testing.assert_array_equal(got, isg.rule_matches(pay, length, rules))
    assert got.sum() >= 8


def tiny(cell):
    cell.mix.update(batch=128, flows=1500, warmup_batches=2)
    return cell


def test_a_run_with_one_rule_left_out_is_not_correct():
    """The program is given the rules less 'attack', one the traffic
    carries; the reference keeps all of them."""
    cell = tiny(spec.resolve(CELL))
    out = harness.measure(cell, 2**31 + 77, 0.3, False, log=lambda _l: None)
    assert out["correct"] is True
    cell = tiny(spec.resolve(CELL))
    cell.config = dict(cell.config, app_args={
        "rules": cell.config["app_args"]["rules"][1:]})
    out = harness.measure(cell, 2**31 + 77, 0.3, False, log=lambda _l: None)
    assert out["correct"] is False
    assert out["check"]["meta.match_num"]["value"] > 0
    assert out["check"]["mask"]["value"] > 0


def test_a_run_with_a_generated_rule_left_out_is_not_correct(monkeypatch):
    """The traffic also carries generated rule 100, and the program runs the
    filter-and-verify scan in interpret mode, given the rules less that one;
    the reference keeps all of them. The cell's own traffic carries only the
    generator's embedded patterns, so this is the check that covers a
    generated rule."""
    rules = spec.resolve(CELL).config["app_args"]["rules"]
    monkeypatch.setattr(generator, "payloads", functools.partial(
        generator.payloads, patterns=("attack", rules[100])))

    def run(program_rules):
        cell = tiny(spec.resolve(CELL))
        cell.mix.update(warmup_batches=1)
        cell.config = dict(cell.config, app_args={"rules": program_rules,
                                                  "impl": "interpret"})
        return harness.measure(cell, 2**31 + 78, 0.3, False,
                               log=lambda _l: None)

    assert run(rules)["correct"] is True
    out = run(rules[:100] + rules[101:])
    assert out["correct"] is False
    assert out["check"]["meta.match_num"]["value"] > 0
    assert out["check"]["mask"]["value"] > 0


def test_the_cells_traffic_makes_the_verdict_drop_and_keep(cell):
    arrays = generator.Traffic(dict(cell.mix, batch=512), 7).batch(30)
    mask = cell.reference(arrays)["mask"]
    assert 0 < (~mask).sum() < mask.size
