"""Host milliseconds per window batch inside the Traffic Orchestrator's
``partition_assign``, from the benchmark's span around the instance's
method (host clock)."""


def read(run):
    if not run.records:
        return None
    return sum(r.classify_s for r in run.records) / len(run.records) * 1e3
