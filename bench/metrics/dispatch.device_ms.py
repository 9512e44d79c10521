"""Device milliseconds per execution of the fused dispatch program
(``core/executor._dispatch_program``, jitted as ``dispatch``), kernels
inside it included, from its program events in the trace (device trace)."""

PROGRAM = "jit_dispatch"


def read(run):
    red = run.reduced
    if red is None:
        return None
    names = [n for n in red.module_s if n.split("(")[0] == PROGRAM]
    calls = sum(red.module_n[n] for n in names)
    if not calls:
        return None
    return sum(red.module_s[n] for n in names) / calls * 1e3
