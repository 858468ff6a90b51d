"""Host spans (``repro.runtime.spans``) and the slot engine's use of them.

The recorder: nesting and parent ids, a stack per thread, spans recorded
after the fact, the ring's bound and its refusal to answer for spans it
dropped. The engine: a request's queue wait plus its admission is its
first-token time as the benchmark's ``Tokens`` stamps it; every decode
round holds one wait, one readback and one harvest, plain and
speculative; under the profiler the spans land on the host line the
benchmark's trace reduction reads.
"""

import dataclasses
import json
import os
import shutil
import subprocess
import sys
import textwrap
import threading
import time

import numpy as np
import pytest

from repro.configs import reduced_config
from repro.launch.mesh import make_mesh
from repro.launch.serve import ContinuousBatchingEngine, Request
from repro.quant import QuantConfig
from repro.runtime import spans
from repro.runtime.spans import Recorder, SpansDropped

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench.cell import Tokens  # noqa: E402

_BUCKETS = [8, 16]
_MAXLEN = 48
_PLENS = (5, 11, 3, 8, 14)
_MAXNEW = (4, 3, 5, 2, 4)
_ARRIVALS = (0.0, 0.0, 0.0, 0.0, 0.05)


# ---------------------------------------------------------------------------
# the recorder
# ---------------------------------------------------------------------------


def test_nested_spans_name_their_parents():
    rec = Recorder()
    lo = time.monotonic()
    with rec.span("outer", engine=1) as outer:
        with rec.span("inner", 7, bucket=16) as inner:
            pass
        rec.record("past", lo, inner.start, 7, tries=2)
    with pytest.raises(KeyError):
        with rec.span("failed"):
            raise KeyError("x")
    got = {s.name: s for s in rec.spans(lo, time.monotonic())}
    assert got["outer"].parent_id is None and got["failed"].parent_id is None
    assert got["inner"].parent_id == outer.span_id == got["outer"].span_id
    assert got["past"].parent_id == outer.span_id
    assert got["inner"].rid == 7 and got["inner"].attrs == {"bucket": 16}
    assert got["past"].attrs == {"tries": 2}
    assert got["past"].start == lo and got["past"].end == got["inner"].start
    o, i = got["outer"], got["inner"]
    assert o.start <= i.start <= i.end <= o.end and i.dur >= 0
    # a span raised through is recorded and leaves the stack balanced
    assert rec._stack() == []


def test_each_thread_keeps_its_own_stack():
    rec = Recorder()
    lo = time.monotonic()
    both_open = threading.Barrier(2, timeout=10)

    def work(tag):
        with rec.span("outer", tag):
            both_open.wait()
            with rec.span("inner", tag):
                both_open.wait()

    threads = [threading.Thread(target=work, args=(t,)) for t in "ab"]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=10)
        assert not t.is_alive()
    got = rec.spans(lo, time.monotonic())
    outer = {s.rid: s.span_id for s in got if s.name == "outer"}
    inner = {s.rid: s.parent_id for s in got if s.name == "inner"}
    assert inner == outer and len(set(outer.values())) == 2


def test_spans_are_read_by_interval_and_name():
    rec = Recorder()
    for i in range(5):
        rec.record("a" if i % 2 else "b", float(i), i + 0.5, i)
    assert [s.rid for s in rec.spans(1.6, 3.2)] == [2, 3]
    assert [s.rid for s in rec.spans(1.2, 1.9)] == [1]
    assert [s.rid for s in rec.spans(0.5, 3.0, "a")] == [1, 3]
    assert [s.rid for s in rec.spans(4.5, 9.0)] == [4]


def test_the_ring_is_bounded_and_refuses_dropped_intervals():
    rec = Recorder(maxlen=4)
    for i in range(6):
        rec.record("s", float(i), i + 0.5)
    # spans 0 and 1 (ending at 0.5 and 1.5) were dropped
    assert [s.start for s in rec.spans(1.6, 10.0)] == [2.0, 3.0, 4.0, 5.0]
    with pytest.raises(SpansDropped):
        rec.spans(1.5, 10.0)
    with pytest.raises(SpansDropped):
        rec.spans(0.0, 0.2)
    assert len(rec._ring) == 4


# ---------------------------------------------------------------------------
# the slot engine
# ---------------------------------------------------------------------------


def _cfg(spec: bool = False):
    return dataclasses.replace(
        reduced_config("deepseek-7b"),
        quant=QuantConfig(dtype="fp8_e4m3", accum="mgs_exact",
                          kv_cache="packed", per_row_act=True,
                          block_m=32, block_n=32, block_k=32,
                          draft_layers=1 if spec else None))


@pytest.fixture(scope="module")
def base():
    eng = ContinuousBatchingEngine(_cfg(), make_mesh((1, 1), ("data", "model")),
                                   slots=2, max_len=_MAXLEN)
    eng.warmup(_BUCKETS, max_new=2)
    return eng


def _serve(eng):
    """Serve five requests into two slots, the last one due 50 ms late,
    as the benchmark does: due times on the host clock from just before
    ``serve``, first-token times from ``Tokens`` stamps."""
    rng = np.random.default_rng(3)
    reqs = [Request(rid=i, prompt=rng.integers(1, eng.cfg.vocab, n)
                    .astype(np.int32), max_new_tokens=m, out_tokens=Tokens())
            for i, (n, m) in enumerate(zip(_PLENS, _MAXNEW))]
    t0 = time.monotonic()
    stats = eng.serve(reqs, arrivals=list(_ARRIVALS), feed=lambda: [])
    got = [s for s in spans.spans(t0, time.monotonic())
           if s.attrs.get("engine") == id(eng)]
    return reqs, [t0 + a for a in _ARRIVALS], got, stats["steps"]


@pytest.fixture(scope="module", params=[None, 2], ids=["plain", "spec_k2"])
def served(request, base):
    eng = base
    if request.param:
        eng = ContinuousBatchingEngine(
            _cfg(spec=True), base.mesh, slots=2, max_len=_MAXLEN,
            params=base.params, dims=base.dims, spec_k=request.param)
        eng.warmup(_BUCKETS, max_new=2)
    return _serve(eng)


def test_queue_and_admit_spans_make_the_first_token_time(served):
    reqs, due, got, _ = served
    queue = {s.rid: s for s in got if s.name == "serve.queue"}
    admit = {s.rid: s for s in got if s.name == "serve.admit"}
    assert sorted(queue) == sorted(admit) == [r.rid for r in reqs]
    for r, d in zip(reqs, due):
        q, a = queue[r.rid], admit[r.rid]
        assert q.end == a.start and q.start >= d
        assert a.start <= r.out_tokens.t[0] <= a.end
        assert abs(q.dur + a.dur - (r.out_tokens.t[0] - d)) < 1e-3
        assert a.attrs["bucket"] in _BUCKETS
    # two slots for four requests due at once: the third waited for a
    # slot to free, and counted the rounds it found none
    assert queue[0].attrs["tries"] == queue[1].attrs["tries"] == 0
    assert queue[2].attrs["tries"] >= 1


def test_each_decode_round_holds_one_wait_readback_and_harvest(served):
    _, _, got, steps = served
    kids = {}
    for s in got:
        kids.setdefault(s.parent_id, []).append(s)
    rounds = [s for s in got if s.name == "serve.round"]
    decoded = 0
    for r in rounds:
        names = [c.name for c in kids.get(r.span_id, [])]
        assert names.count("serve.feed") == 1
        if "serve.wait" not in names:
            continue
        decoded += 1
        phases = [c for c in kids[r.span_id] if c.name in (
            "serve.wait", "serve.readback", "serve.harvest")]
        assert [c.name for c in phases] == ["serve.wait", "serve.readback",
                                            "serve.harvest"]
        assert sum(c.dur for c in phases) <= r.dur
        assert all(r.start <= c.start <= c.end <= r.end for c in phases)
    assert decoded == steps
    # admissions hang under the round that made them
    round_ids = {r.span_id for r in rounds}
    assert all(s.parent_id in round_ids for s in got
               if s.name in ("serve.admit", "serve.queue"))


_PROFILED = textwrap.dedent("""
    import dataclasses, glob, json, sys, tempfile
    import jax
    import numpy as np
    from jax.profiler import ProfileData
    from repro.configs import reduced_config
    from repro.launch.mesh import make_mesh
    from repro.launch.serve import ContinuousBatchingEngine, Request
    from repro.quant import QuantConfig
    sys.path.insert(0, sys.argv[1])
    from bench.trace import HOST_LINES

    cfg = dataclasses.replace(
        reduced_config("deepseek-7b"),
        quant=QuantConfig(dtype="fp8_e4m3", accum="mgs_exact",
                          kv_cache="packed", per_row_act=True,
                          block_m=32, block_n=32, block_k=32))
    eng = ContinuousBatchingEngine(cfg, make_mesh((1, 1), ("data", "model")),
                                   slots=2, max_len=32)
    eng.warmup([8], max_new=2)
    reqs = [Request(rid=i, prompt=np.arange(1, 6, dtype=np.int32),
                    max_new_tokens=3) for i in range(2)]
    d = tempfile.mkdtemp()
    jax.profiler.start_trace(d)
    eng.serve(reqs, feed=lambda: [])
    jax.profiler.stop_trace()
    pd = ProfileData.from_file(
        glob.glob(d + "/**/*.xplane.pb", recursive=True)[0])
    kept, other = set(), set()
    for plane in pd.planes:
        for line in plane.lines:
            names = {e.name for e in line.events if e.name.startswith("serve.")}
            ok = (plane.name == "/host:CPU"
                  and line.name.split("/")[0] in HOST_LINES)
            (kept if ok else other).update(names)
    print(json.dumps({"kept": sorted(kept), "other": sorted(other)}))
""")


def test_profiled_spans_sit_on_a_host_line_the_trace_reduction_reads():
    # the profiler names a host line after the process, and the
    # benchmark runs as `python3 bench/run.py`: run the trace as python3
    exe = shutil.which("python3")
    assert exe is not None
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(ROOT, "src"))
    out = subprocess.run([exe, "-c", _PROFILED, ROOT], env=env, cwd=ROOT,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    got = json.loads(out.stdout.strip().splitlines()[-1])
    assert set(got["kept"]) == {"serve.round", "serve.feed", "serve.admit",
                                "serve.wait", "serve.readback",
                                "serve.harvest"}
    # serve.queue is recorded after the fact: memory only
    assert got["other"] == []
