"""The engine's own host spans (``repro.runtime.spans``) in a run's
window, for the per-layer readers in ``bench/metrics``.

The spans live in the benchmark's process, in the program's in-memory
ring, on the clock of the ``Tokens`` stamps. A program without the
recorder has nothing to read: ``load`` returns None, and so does each
reader. Every reader leaves out the profiled interval: from the
``serve.feed`` span that holds the profiler's start to the one that
holds its stop (``bench.cell.drive`` starts and stops it inside the feed
hook), which holds the start's stall and the profiler's slowdown.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Tuple

from bench import cell


def load(run, hi: Optional[float] = None):
    """``(spans overlapping [t_start, hi or t_end], profiled interval or
    None)``, or None where the program records no spans."""
    try:
        from repro.runtime import spans
    except ImportError:
        return None
    got = spans.spans(run.t_start, run.t_end if hi is None else hi)
    return got, profiled(run, got)


def profiled(run, got) -> Optional[Tuple[float, float]]:
    if not run.trace_span:
        return None
    feeds = [s for s in got if s.name == "serve.feed"]

    def holding(t):
        return next((s for s in feeds if s.start <= t <= s.end), None)

    a, b = (holding(t) for t in run.trace_span)
    return (a.start if a else run.trace_span[0],
            b.end if b else run.trace_span[1])


def clear(lo: float, hi: float, cut) -> bool:
    """``[lo, hi]`` does not meet the profiled interval."""
    return cut is None or hi < cut[0] or lo > cut[1]


def overlap(lo: float, hi: float, a: float, b: float) -> float:
    return max(0.0, min(hi, b) - max(lo, a))


def requests(run) -> Optional[List[tuple]]:
    """``(serve.queue, serve.admit)`` of each request due in the window
    whose wait and admission stay clear of the profiled interval. A
    request due late in the window is admitted after it, so the spans
    are read up to the run's stop."""
    hi = run.t_stop if run.t_stop > run.t_end else run.t_end
    got = load(run, hi)
    if got is None:
        return None
    sp, cut = got
    by: Dict[str, dict] = {"serve.queue": {}, "serve.admit": {}}
    for s in sp:
        if s.name in by:
            by[s.name][s.rid] = s
    out = []
    for i in cell.window_requests(run):
        q, a = by["serve.queue"].get(i), by["serve.admit"].get(i)
        if q and a and clear(q.start, a.end, cut):
            out.append((q, a))
    return out
