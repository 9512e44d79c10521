"""Host milliseconds per window batch in the fused dispatch's index
algebra: the program's ``meili.dispatch.index`` span in
``ParallelDataPlane.process`` (bincount, counting sort, ``perm`` and
``out_idx``, bucket padding, the rings' allocation check) (program span,
host clock)."""
from bench import program_spans


def read(run):
    return program_spans.per_batch_ms(run, "meili.dispatch.index")
