"""Share of the window the engine spent admitting (the ``serve.admit``
spans, clipped to the window): each admission stretches the token gap of
every live slot. The profiled interval is left out of both sides."""

from bench import host_spans


def read(ctx):
    run = ctx.run
    got = host_spans.load(run)
    if got is None:
        return None
    sp, cut = got
    if not any(s.name == "serve.round" for s in sp):
        return None                     # the engine recorded nothing here
    lo, hi = run.t_start, run.t_end
    cut_s = host_spans.overlap(lo, hi, *cut) if cut else 0.0
    busy = 0.0
    for s in sp:
        if s.name == "serve.admit":
            busy += host_spans.overlap(lo, hi, s.start, s.end)
            if cut:
                busy -= host_spans.overlap(*cut, max(lo, s.start),
                                           min(hi, s.end))
    return 100.0 * busy / (hi - lo - cut_s) if hi - lo > cut_s else None
