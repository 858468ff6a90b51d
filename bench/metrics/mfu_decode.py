"""Model operations of the tokens decoded (live slots only) in the traced
span, over decode device time times the chip's int8 peak."""

from bench.work import model


def read(ctx):
    t = ctx.trace
    if t is None or not ctx.traced_decode_rounds or t.program_s.get("decode", 0) <= 0:
        return None
    ops = sum(model.token_ops(ctx.config, live)
              for r in ctx.traced_decode_rounds for live in r)
    return 100.0 * ops / (t.program_s["decode"] * ctx.peak["int8_ops_per_s"])
