"""75th percentile (nearest rank) of the admissions' host time: batch-1
prefill, ``adopt_slot`` and the first token read back (the
``serve.admit`` spans), over the requests of ``queue_wait_p75_s``."""

from bench import cell, host_spans


def read(ctx):
    reqs = host_spans.requests(ctx.run)
    return (1e3 * cell.nearest_rank([a.dur for _, a in reqs], 0.75)
            if reqs else None)
