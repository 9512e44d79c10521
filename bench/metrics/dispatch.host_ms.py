"""Host milliseconds per window batch inside ``ParallelDataPlane.process``
but outside ``partition_assign``: index algebra, ring bookkeeping and the
dispatch of the fused program, host-to-device copies included (host clock)."""


def read(run):
    if not run.records:
        return None
    host = sum(r.returned - r.due - r.classify_s for r in run.records)
    return host / len(run.records) * 1e3
