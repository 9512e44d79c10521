"""Host milliseconds per window batch in the fused dispatch call: the
program's ``meili.dispatch.enqueue`` span in ``ParallelDataPlane.process``,
which holds the host-to-device copies of the batch and its index arrays and
the launch of the dispatch program (program span, host clock)."""
from bench import program_spans


def read(run):
    return program_spans.per_batch_ms(run, "meili.dispatch.enqueue")
