"""The per-layer readers of the engine's host spans, counted by hand on a
synthetic window: requests due before, in and after it, one admitted
after its end, rounds with and without a decode step or an admission,
and a profiled interval that every reader must leave out."""

import types

import pytest

import tiny  # noqa: F401  (puts the repo root and src on sys.path)
import repro.runtime
from bench import host_spans
from bench.cell import Run
from bench.metrics import (admit_p75_ms, admit_share, decode_host_share,
                           queue_wait_p75_s)
from repro.runtime import spans as spans_mod
from repro.runtime.spans import Span

READERS = (queue_wait_p75_s, admit_p75_ms, admit_share, decode_host_share)


def _run():
    # due times 5 .. 31 s; window [10, 30); profiled from 18.01 to 20.99
    due = [5.0, 12.0, 14.0, 17.5, 19.0, 22.0, 29.5, 31.0]
    return Run(specs=[types.SimpleNamespace(due_s=d) for d in due], reqs=[],
               buckets=[], rounds=[], t0=0.0, t_start=10.0, t_end=30.0,
               t_stop=32.0, trace_span=(18.01, 20.99))


def _spans():
    out = []

    def add(name, start, end, parent=None, rid=None):
        out.append(Span(name, start, end, len(out) + 1, parent, rid, {}))
        return len(out)

    # requests: (due, admitted at, admission ends)
    for rid, (d, a, e) in enumerate([
            (5.0, 11.0, 11.2),      # due before the window
            (12.0, 12.5, 12.7),
            (14.0, 14.1, 14.9),
            (17.5, 17.9, 18.05),    # its admission meets the profiled span
            (19.0, 21.5, 21.6),     # waited through it
            (22.0, 22.3, 22.5),
            (29.5, 30.6, 31.0),     # admitted after the window's end
            (31.0, 31.2, 31.4)]):   # due after it
        add("serve.queue", d, a, rid=rid)
        add("serve.admit", a, e, rid=rid)

    def round_(start, end, feed, wait, admit=False):
        r = add("serve.round", start, end)
        if feed:
            add("serve.feed", start, start + feed, r)
        if admit:
            add("serve.admit", start + feed, start + feed + 0.01, r, rid=99)
        if wait:
            w0 = start + feed + 0.01
            add("serve.wait", w0, w0 + wait, r)
            add("serve.readback", w0 + wait, w0 + wait + 0.005, r)
            add("serve.harvest", w0 + wait + 0.005, w0 + wait + 0.01, r)

    round_(9.5, 10.2, 0.01, 0.5)            # starts before the window
    round_(12.0, 12.9, 0.01, 0.3, admit=True)
    round_(13.0, 13.2, 0.01, 0.15)          # host 0.04 of 0.19
    round_(13.2, 13.5, 0.02, 0.2)           # host 0.08 of 0.28
    round_(18.0, 18.4, 0.02, 0.3)           # holds the profiler's start
    round_(18.5, 18.7, 0.01, 0.15)          # inside the profiled interval
    round_(20.98, 21.3, 0.02, 0.2)          # holds its stop
    round_(25.0, 25.4, 0.01, 0.0)           # no decode step
    round_(26.0, 26.3, 0.0, 0.25)           # no feed: host 0.05 of 0.3
    return out


@pytest.fixture
def ctx(monkeypatch):
    made = _spans()

    def fake(lo, hi, name=None):
        return [s for s in made if s.end >= lo and s.start <= hi
                and (name is None or s.name == name)]

    monkeypatch.setattr(spans_mod, "spans", fake)
    return types.SimpleNamespace(run=_run())


def test_the_profiled_interval_runs_from_feed_to_feed(ctx):
    sp, cut = host_spans.load(ctx.run)
    assert cut == (18.0, 21.0)


def test_queue_wait_and_admission_of_the_windows_requests(ctx):
    # requests 1, 2, 5, 6: waits 0.5, 0.1, 0.3, 1.1; admissions 0.2,
    # 0.8, 0.2, 0.4; nearest rank 3 of 4
    assert queue_wait_p75_s.read(ctx) == pytest.approx(0.5)
    assert admit_p75_ms.read(ctx) == pytest.approx(400.0)


def test_admit_share_clips_to_the_window_and_leaves_out_the_profile(ctx):
    # requests 0-5 in the window: 0.2 + 0.2 + 0.8 + (0.15 - 0.05) + 0.1
    # + 0.2, the round's admission 0.01; 20 s less the 3 profiled
    assert admit_share.read(ctx) == pytest.approx(100 * 1.61 / 17)


def test_decode_host_share_over_decode_rounds_that_admit_nothing(ctx):
    assert decode_host_share.read(ctx) == pytest.approx(
        100 * (0.04 + 0.08 + 0.05) / (0.19 + 0.28 + 0.3))


def test_an_untraced_run_cuts_nothing(ctx):
    ctx.run.trace_span = None
    assert host_spans.load(ctx.run)[1] is None
    # request 3 and 4 come back: waits 0.5 0.1 0.4 2.5 0.3 1.1
    assert queue_wait_p75_s.read(ctx) == pytest.approx(1.1)


@pytest.mark.parametrize("reader", READERS, ids=lambda m: m.__name__)
def test_a_program_without_spans_reads_nothing(ctx, monkeypatch, reader):
    import sys
    monkeypatch.delattr(repro.runtime, "spans")
    monkeypatch.setitem(sys.modules, "repro.runtime.spans", None)
    assert reader.read(ctx) is None


@pytest.mark.parametrize("reader", READERS, ids=lambda m: m.__name__)
def test_an_empty_window_reads_nothing(ctx, monkeypatch, reader):
    monkeypatch.setattr(spans_mod, "spans", lambda lo, hi, name=None: [])
    assert reader.read(ctx) is None
