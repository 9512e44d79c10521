"""Host milliseconds per window batch in the Traffic Orchestrator's flow
ids: the program's ``meili.to.flow_ids`` span in ``partition_assign``
(``flow_ids``, which reads the five-tuple back to the host, and
``np.unique``) (program span, host clock)."""
from bench import program_spans


def read(run):
    return program_spans.per_batch_ms(run, "meili.to.flow_ids")
