"""Sum of the least time of every fused-matmul call in the decode steps of
the traced span, over the fused-matmul kernel time inside decode
programs."""

from bench.work import calls, matmul


def read(ctx):
    t = ctx.trace
    k = t.kernel_s.get(("matmul", "decode"), 0.0) if t else 0.0
    if k <= 0 or not ctx.traced_decode_rounds:
        return None
    least = sum(matmul.least_s(m, kk, n, ctx.peak)
                for r in ctx.traced_decode_rounds
                for m, kk, n in calls.decode_matmuls(ctx.config, len(r)))
    return 100.0 * least / k
