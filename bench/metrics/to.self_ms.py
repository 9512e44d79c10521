"""Host milliseconds per window batch in the Traffic Orchestrator outside
the device probe: the program's ``meili.to.assign`` span
(``partition_assign``) less its ``meili.to.probe`` span, i.e. the TO's own
host work: flow ids, the miss loop, validation and commit (program span,
host clock)."""
from bench import program_spans


def read(run):
    assign = program_spans.window_ns(run, "meili.to.assign")
    probe = program_spans.window_ns(run, "meili.to.probe")
    if not assign or probe is None:
        return None
    return (assign - probe) / len(run.records) / 1e6
