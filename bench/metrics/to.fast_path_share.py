"""Share of the window's packets that the flow cache classified (cache hits,
from the orchestrator's ``fast_stats`` deltas); packets of a batch that fell
back to the slow path count as not fast (program counter)."""


def read(run):
    n = run.counters.get("packets", 0)
    return 100.0 * run.counters["fast.hit_pkts"] / n if n else None
