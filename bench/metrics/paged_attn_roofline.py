"""Sum of the least time of every paged flash-decode attention call in
the decode steps of the traced span (one per layer per step), over that
kernel's time."""

from bench.work import paged_attn


def read(ctx):
    t = ctx.trace
    k = t.kernel_s.get(("paged_attn", "decode"), 0.0) if t else 0.0
    if k <= 0 or not ctx.traced_decode_rounds:
        return None
    c = ctx.config
    h, kv = c["num_attention_heads"], c["num_key_value_heads"]
    hd = c["hidden_size"] // h
    least = c["num_hidden_layers"] * sum(
        paged_attn.least_s(r, h, kv, hd, ctx.peak)
        for r in ctx.traced_decode_rounds)
    return 100.0 * least / k
