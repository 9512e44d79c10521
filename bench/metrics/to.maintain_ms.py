"""Host milliseconds per window batch in the Traffic Orchestrator's state
bounding: the program's ``meili.to.maintain`` span around ``_maintain``
(the flow cache's idle expiry every ``expire_every`` rounds, flow-table
pruning past its cap) (program span, host clock)."""
from bench import program_spans


def read(run):
    return program_spans.per_batch_ms(run, "meili.to.maintain")
