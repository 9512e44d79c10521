"""The kernels' work counts, from shapes, and the roofline readers that
divide them by a kernel's traced time."""
import pytest

from bench import spec, workcount
from bench.trace import Reduced

V5E = {"hbm_bytes_per_s": 819e9}


def test_dfa_scan_bytes_count_each_payload_byte_once():
    # 16384 packets of 1500 B, a 43-state automaton.
    assert workcount.dfa_scan_bytes(16384, 1500, 43) == (
        16384 * 1500 + 16384 * 8 + 43 * 256 * 4 + 43 * 4)


def test_crypto_bytes_count_whole_words():
    assert workcount.cipher_bytes(16384, 1500) == 2 * 16384 * 375 * 4 + 16
    assert workcount.cipher_bytes(10, 1502) == 2 * 10 * 375 * 4 + 16
    assert workcount.digest_bytes(16384, 1500) == 16384 * 375 * 4 + 16384 * 16 + 16


def test_trie_states_equal_the_automaton_the_program_builds():
    from repro.apps.nf import SNORT_RULES
    from repro.kernels.ref import build_aho_corasick
    table, _ = build_aho_corasick(SNORT_RULES)
    assert workcount.trie_states(SNORT_RULES) == table.shape[0] == 43
    assert workcount.trie_states(["ab", "ac", "b"]) == 5


def test_least_seconds_at_the_hbm_peak():
    assert workcount.least_seconds(819e9, V5E) == pytest.approx(1.0)


class _Run:
    def __init__(self, cell, reduced):
        self.cell, self.reduced, self.peaks = cell, reduced, V5E


def _reduced(op_s, op_n):
    return Reduced(1.0, 0.5, op_s, op_n, {}, {}, {}, 1)


def test_roofline_readers_divide_the_least_time_by_the_kernel_time():
    cell = spec.resolve("isg.mtu1500")
    B, L = cell.mix["batch"], cell.mix["pkt_bytes"]
    readers = {m.name: m.read for m in cell.per_layer}
    red = _reduced({"jit_dispatch/dfa_regex.1": 0.030,
                    "jit_dispatch/arx_cipher.1": 0.002,
                    "jit_dispatch/keyed_hash.1": 0.001},
                   {"jit_dispatch/dfa_regex.1": 2.0,
                    "jit_dispatch/arx_cipher.1": 2.0,
                    "jit_dispatch/keyed_hash.1": 2.0})
    run = _Run(cell, red)
    dfa = 2 * workcount.dfa_scan_bytes(B, L, 43) / 819e9 / 0.030 * 100
    crypto = 2 * (workcount.cipher_bytes(B, L) + workcount.digest_bytes(B, L)
                  ) / 819e9 / 0.003 * 100
    assert readers["kernel.dfa_regex_roofline"](run) == pytest.approx(dfa)
    assert readers["kernel.crypto_roofline"](run) == pytest.approx(crypto)


def test_roofline_readers_are_silent_without_the_kernel():
    cell = spec.resolve("isg.mtu1500")
    readers = {m.name: m.read for m in cell.per_layer}
    run = _Run(cell, _reduced({"jit_dispatch/fusion.1": 0.01},
                              {"jit_dispatch/fusion.1": 1.0}))
    assert readers["kernel.dfa_regex_roofline"](run) is None
    assert readers["kernel.crypto_roofline"](run) is None
    assert readers["kernel.dfa_regex_roofline"](_Run(cell, None)) is None
