"""Host share of the decode rounds: over the window's scheduling rounds
(``serve.round``) that ran a decode step (a ``serve.wait``) and admitted
nothing (no ``serve.admit``), the time the host spent outside the
device wait, sum(round - feed - wait) / sum(round - feed). The bench's
own feed hook is left out of both sides, and so are the rounds that
meet the profiled interval. The device idles for about this share."""

from bench import host_spans


def read(ctx):
    run = ctx.run
    got = host_spans.load(run)
    if got is None:
        return None
    sp, cut = got
    kids = {}
    for s in sp:
        kids.setdefault(s.parent_id, []).append(s)
    host = total = 0.0
    for r in sp:
        if (r.name != "serve.round" or r.start < run.t_start
                or r.end > run.t_end
                or not host_spans.clear(r.start, r.end, cut)):
            continue
        dur = {}
        for c in kids.get(r.span_id, ()):
            dur[c.name] = dur.get(c.name, 0.0) + c.dur
        if "serve.wait" not in dur or "serve.admit" in dur:
            continue
        own = r.dur - dur.get("serve.feed", 0.0)
        host += own - dur["serve.wait"]
        total += own
    return 100.0 * host / total if total > 0 else None
