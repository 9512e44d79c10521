"""Roofline share of the regex accelerator's multi-pattern scan (the Pallas
call named ``dfa_scan``, ``kernels/dfa_scan.py``): the least time a DFA
scan of one batch needs (each payload byte read once, the per-packet
length and count, and the dense table of the configuration's automaton,
``trie_states`` of its ``app_args.rules``; see ``bench/workcount.py``) at
the chip's HBM peak, times the executions of the fused dispatch in the
trace, over the summed device time of the ``dfa_scan`` ops (device trace).
The need is counted per batch, not per op event, so a scan split into
several calls cannot overstate it. The call holds the payload's layout and
the prefix filter; the candidates' compaction and verification run as XLA
loops beside it and are not in its time."""
from bench import workcount

KERNEL = "dfa_scan"
PROGRAM = "jit_dispatch"


def read(run):
    red = run.reduced
    if red is None or run.peaks is None:
        return None
    secs, calls = red.op_time(KERNEL)
    batches = sum(n for name, n in red.module_n.items()
                  if name.split("(")[0] == PROGRAM)
    if not calls or not batches or secs <= 0:
        return None
    mix, rules = run.cell.mix, run.cell.config["app_args"]["rules"]
    need = batches * workcount.dfa_scan_bytes(
        mix["batch"], mix["pkt_bytes"], workcount.trie_states(rules))
    return 100.0 * workcount.least_seconds(need, run.peaks) / secs
