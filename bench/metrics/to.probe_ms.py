"""Host milliseconds per window batch in the flow cache's device probe: the
program's ``meili.to.probe`` span in ``FlowCache.lookup`` (the pending
scatter upload, the ``lookup_jnp`` launch and the blocking read of its
result), so it holds the wait for the device queue ahead of the probe
(program span, host clock)."""
from bench import program_spans


def read(run):
    return program_spans.per_batch_ms(run, "meili.to.probe")
