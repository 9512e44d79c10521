"""Roofline share of the DFA regex kernel (``kernels/dfa_regex.py``): the
least time its algorithm needs for the packets handed in (each payload
byte read once, the table and the per-packet length and count; see
``bench/workcount.py``) at the chip's HBM peak, over the kernel's summed
device time in the trace (device trace). The rules are the
configuration's ``app_args.rules``."""
from bench import workcount

KERNEL = "dfa_regex"


def read(run):
    red = run.reduced
    if red is None or run.peaks is None:
        return None
    secs, calls = red.op_time(KERNEL)
    if not calls or secs <= 0:
        return None
    mix, rules = run.cell.mix, run.cell.config["app_args"]["rules"]
    need = calls * workcount.dfa_scan_bytes(
        mix["batch"], mix["pkt_bytes"], workcount.trie_states(rules))
    return 100.0 * workcount.least_seconds(need, run.peaks) / secs
