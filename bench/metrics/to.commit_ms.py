"""Host milliseconds per window batch in the Traffic Orchestrator's commit:
the program's ``meili.to.commit`` span, the fast path's validation, table
commit and cache touch/record, or the slow path's cache record (program
span, host clock)."""
from bench import program_spans


def read(run):
    return program_spans.per_batch_ms(run, "meili.to.commit")
