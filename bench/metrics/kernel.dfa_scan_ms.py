"""Device milliseconds of the regex accelerator's multi-pattern scan (the
Pallas call named ``dfa_scan``, ``kernels/dfa_scan.py``) per execution of
the fused dispatch (``jit_dispatch``), from the trace (device trace).

The call holds the payload's transposition and word layout and the prefix
filter. Left outside it, and not counted here: the compaction of candidate
words and their verification, XLA loops that the trace names ``while.N``
and ``fusion.N``; ``dispatch.device_ms`` bounds the whole."""

KERNEL = "dfa_scan"
PROGRAM = "jit_dispatch"


def read(run):
    red = run.reduced
    if red is None:
        return None
    secs, calls = red.op_time(KERNEL)
    batches = sum(n for name, n in red.module_n.items()
                  if name.split("(")[0] == PROGRAM)
    if not calls or not batches:
        return None
    return secs / batches * 1e3
