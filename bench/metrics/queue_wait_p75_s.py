"""75th percentile (nearest rank) of the requests' waits in the engine's
admission queue, from due time to the start of the admission that took
them (the ``serve.queue`` spans), over the requests due in the window
whose wait and admission miss the profiled interval."""

from bench import cell, host_spans


def read(ctx):
    reqs = host_spans.requests(ctx.run)
    return cell.nearest_rank([q.dur for q, _ in reqs], 0.75) if reqs else None
