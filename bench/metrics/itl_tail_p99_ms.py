"""99th percentile of the token gaps of the requests due in the window,
read on the host clock as the end-to-end token gaps are, leaving out the
gaps that hold the profiler's start or stop.

Most gaps are one decode step; the tail is made of the few rounds that
also admit a long prompt or two at once, so which of those rounds the
99th percentile lands on swings from run to run (PERF.md). It is kept
here, without a bound, beside the steadier ``itl_p95_ms``."""

from bench import cell


def read(ctx):
    run = ctx.run
    gaps = cell.token_gaps(run, ctx.mix, cell.window_requests(run),
                           cut=run.trace_span or ())
    return 1e3 * cell.nearest_rank(gaps, 0.99) if gaps else None
