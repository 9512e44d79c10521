"""Host milliseconds per window batch in the Traffic Orchestrator's
decision loop over flows: the program's ``meili.to.miss_loop`` span, the
fast path's replica loop over cache misses and, on a slow or fallback
batch, the slow loop over every flow (program span, host clock)."""
from bench import program_spans


def read(run):
    return program_spans.per_batch_ms(run, "meili.to.miss_loop")
