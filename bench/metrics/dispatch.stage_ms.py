"""Host milliseconds per window batch in the put of the ingress batch on
the device: the program's ``meili.dispatch.stage`` span at the top of
``ParallelDataPlane.process``, which issues the batch's host-to-device copy
before the Traffic Orchestrator classifies it. The put returns before the
transfer ends, so this reads far below the copy itself; a reading near the
copy's time means the put copied on the calling thread (program span, host
clock)."""
from bench import program_spans


def read(run):
    return program_spans.per_batch_ms(run, "meili.dispatch.stage")
