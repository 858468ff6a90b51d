"""One run of one cell: build the engine, drive the window, measure.

Everything that is particular to a configuration, a traffic mix or a
per-layer metric lives in files of its own (``bench/configs``,
``bench/traffic``, ``bench/metrics``); this module reads them by the
names in ``BENCHMARK.json`` and needs no edit for a new cell.

The window drives ``ContinuousBatchingEngine.serve`` with the mix's
requests and their due times (``arrivals=``), so each request is timed
from when it was due on the engine's own clock. Each request's
``out_tokens`` is a list that stamps the host clock at every append: the
engine appends a token right after the host has read it back from the
device, so the stamps are the emission times. The ``feed`` hook, polled
once per scheduling round, gives the per-round clock, starts and stops
the profiler at round boundaries, and ends ``serve()`` at the window's
end (cells that stop) or at the drain cap (cells that drain).
"""

from __future__ import annotations

import dataclasses
import gc
import json
import math
import os
import time
from typing import Dict, List, Optional

import numpy as np

from bench import traffic as traffic_mod

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HERE = os.path.join(ROOT, "bench")


class WindowClosed(Exception):
    """Raised from the feed hook to end ``serve()``."""


class BenchError(Exception):
    """A run that cannot produce a result (no chip, a compile in the
    window, a malformed cell)."""


class Tokens(list):
    """``out_tokens`` that stamps the host clock at every append."""

    def __init__(self):
        super().__init__()
        self.t: List[float] = []

    def append(self, tok):
        self.t.append(time.monotonic())
        super().append(tok)


# --- the cell's files -------------------------------------------------------

def load_cell(workload: str, root: str = ROOT):
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise BenchError(f"unknown workload {workload!r}; known: "
                         f"{sorted(cells)}")
    cell = cells[workload]
    confs = {c["name"]: c for c in bench["configs"]}
    with open(os.path.join(root, confs[cell["config"]]["file"])) as f:
        config = json.load(f)
    mix = traffic_mod.load_mix(cell["traffic"], os.path.join(root, "bench"))
    return bench, cell, config, mix


def load_peaks(root: str = HERE) -> dict:
    with open(os.path.join(root, "peaks.json")) as f:
        return json.load(f)["devices"]


def require_chip(chips: int, peaks: dict):
    """The devices, or BenchError: a TPU, ``chips`` of them, known peaks."""
    import jax
    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        raise BenchError(f"JAX found no TPU (platform {d.platform!r}); the "
                         f"benchmark runs on the chip only")
    if len(devs) < chips:
        raise BenchError(f"the cell needs {chips} chip(s), JAX found "
                         f"{len(devs)}")
    if d.device_kind not in peaks:
        raise BenchError(f"no peaks for device kind {d.device_kind!r} in "
                         f"bench/peaks.json")
    return devs[:chips]


def weight_seed(seed: int) -> int:
    """The int31 the engine's PRNGKey and the reference both take."""
    return int(np.random.SeedSequence(seed).generate_state(1)[0]) & 0x7FFFFFFF


def model_config(config: dict):
    """The program's ModelConfig for a config file, checked against it."""
    from repro.configs import get_config
    from repro.quant import config as qc
    base = get_config(config["arch"])
    cfg = dataclasses.replace(base, n_layers=config["num_hidden_layers"],
                              quant=getattr(qc, config["serving"]["quant"]))
    want = {"d_model": config["hidden_size"], "d_ff": config["intermediate_size"],
            "n_heads": config["num_attention_heads"],
            "n_kv_heads": config["num_key_value_heads"],
            "vocab": config["vocab_size"], "act": config["hidden_act"],
            "norm_eps": config["rms_norm_eps"],
            "rope_theta": config["rope_theta"]}
    have = {k: getattr(cfg, k) for k in want}
    if have != want:
        raise BenchError(f"program config {config['arch']!r} {have} differs "
                         f"from the file {want}")
    return cfg


# --- the run ----------------------------------------------------------------

@dataclasses.dataclass
class Run:
    """What one window recorded on the host."""
    specs: list
    reqs: list                      # engine Requests, parallel to specs
    buckets: List[int]              # each request's padded prompt length
    rounds: List[float]             # feed-hook stamps (round starts)
    t0: float                       # serve() started: due times count from here
    t_start: float
    t_end: float
    t_stop: float = math.nan        # when serve() was ended
    trace_span: Optional[tuple] = None   # (start, stop) host clock
    compiles: int = 0
    sample: List[int] = dataclasses.field(default_factory=list)
    logs: Dict[int, list] = dataclasses.field(default_factory=dict)
    #: one entry per scheduling round inside the window: (stamp, live
    #: slots, KV blocks held, live keys in the cache, device bytes in use)
    occupancy: List[tuple] = dataclasses.field(default_factory=list)
    kv_blocks: int = 0              # the pool's blocks
    resident: bool = False          # requests admitted before the window


def due_times(run: Run) -> List[float]:
    """Each request's due time on the host clock (a resident request's is
    the window's start)."""
    if run.resident:
        return [run.t_start] * len(run.specs)
    return [run.t0 + s.due_s for s in run.specs]


def window_requests(run: Run) -> List[int]:
    """Indices of the requests due inside the window."""
    return [i for i, d in enumerate(due_times(run))
            if run.t_start <= d < run.t_end]


def build_engine(cfg, mix: dict, wseed: int, times: Optional[dict] = None):
    """The cell's engine, warmed on its buckets; ``times`` gets the host
    seconds of the build and of the warm-up."""
    from repro.launch.mesh import make_mesh
    from repro.launch.serve import make_engine
    e = mix["engine"]
    t0 = time.monotonic()
    engine = make_engine(cfg, make_mesh((1, 1), ("data", "model")),
                         batch=e["slots"], max_len=e["max_len"], seed=wseed,
                         continuous=True)
    t1 = time.monotonic()
    # max_new=2: a request done at its first token never reaches a decode
    # step, so one more token is what compiles the decode program
    engine.warmup(e["buckets"], max_new=2)
    if times is not None:
        times.update(build_s=t1 - t0, warmup_s=time.monotonic() - t1)
    return engine


def jit_cache_sizes(engine) -> int:
    return sum(f._cache_size() for f in (engine._prefill, engine._decode_paged,
                                         engine._adopt, engine._release))


class CompileCounter:
    """Counts JAX compile and trace events while ``armed``."""

    def __init__(self):
        import jax
        self.armed = False
        self.n = 0
        self.names: List[str] = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if self.armed and event.startswith("/jax/core/compile"):
            self.n += 1
            self.names.append(event)


def drive(engine, specs, mix: dict, seconds: float, *, counter=None,
          sample=(), trace_dir: Optional[str] = None,
          trace_at: float = 0.4, trace_s: float = 4.0) -> Run:
    """Serve ``specs`` through the lead-in and the window; record the host
    clock.

    ``sample``: indices of the requests whose served logit rows are kept
    for the correctness check (``bench.correct``). The profiler runs
    ``trace_s`` seconds (at most 0.3 of the window) from ``trace_at`` of
    the window, started and stopped at round boundaries.
    """
    from bench.correct import SampleLog
    import jax
    from repro.launch.serve import Request, bucket_for
    from repro.quant import PREP_STATS
    reqs = [Request(rid=i, prompt=s.tokens, max_new_tokens=s.max_new,
                    out_tokens=Tokens()) for i, s in enumerate(specs)]
    buckets = [bucket_for(s.prompt_len, engine._buckets,
                          block=engine.block_size) for s in specs]
    resident = mix["kind"] == "resident"
    stop = mix["end"] == "stop"
    if mix["end"] not in ("stop", "first_token"):
        raise BenchError(f"unknown end {mix['end']!r}")
    run = Run(specs=specs, reqs=reqs, buckets=buckets, rounds=[],
              t0=math.nan, t_start=math.nan, t_end=math.nan,
              sample=list(sample), kv_blocks=engine.n_blocks,
              resident=resident)
    trace_s = min(trace_s, 0.3 * seconds)
    tracing = {"on": False, "done": trace_dir is None}
    in_window: List[int] = []
    dev = jax.devices()[0]
    prep0, cache0 = PREP_STATS["prepared"], jit_cache_sizes(engine)

    def start_window(t):
        run.t_start, run.t_end = t, t + seconds
        in_window[:] = window_requests(run)

    def occupancy(now):
        live = [i for i, r in enumerate(reqs) if r.out_tokens and not r.done]
        stats = dev.memory_stats() or {}
        run.occupancy.append((
            now, len(live), engine.n_blocks - engine.alloc.n_free,
            sum(buckets[i] + len(reqs[i].out_tokens) for i in live),
            int(stats.get("bytes_in_use", 0))))

    def feed():
        now = time.monotonic()
        if not run.rounds:
            # serve() has just reset its log: keep the sample's rows only
            engine._logits_log = run.logs = SampleLog(run.sample)
        run.rounds.append(now)
        if math.isnan(run.t_start):
            # resident: the window opens once every request holds its slot
            if not all(len(r.out_tokens) for r in reqs):
                return []
            start_window(now)
        if now < run.t_start:
            return []                   # the lead-in
        occupancy(now)
        if not tracing["done"]:
            if not tracing["on"] and now >= run.t_start + trace_at * seconds:
                jax.profiler.start_trace(trace_dir)
                tracing.update(on=True, t0=time.monotonic())
            elif tracing["on"] and now >= tracing["t0"] + trace_s:
                t1 = time.monotonic()
                jax.profiler.stop_trace()
                tracing.update(on=False, done=True)
                run.trace_span = (tracing["t0"], t1)
        if now >= run.t_end and (stop or now >= run.t_end + mix["drain_cap_s"]
                                 or all(len(reqs[i].out_tokens)
                                        for i in in_window)):
            run.t_stop = now
            raise WindowClosed
        return []

    arrivals = [0.0 if resident else s.due_s for s in specs]
    if counter is not None:
        counter.armed = True
    run.t0 = time.monotonic()
    if not resident:
        start_window(run.t0 + traffic_mod.lead_s(mix))
    try:
        engine.serve(reqs, arrivals=arrivals, feed=feed, record_logits=True)
        run.t_stop = time.monotonic()
    except WindowClosed:
        pass
    finally:
        if tracing["on"]:
            jax.profiler.stop_trace()
            tracing["on"] = False
        if counter is not None:
            counter.armed = False
    if math.isnan(run.t_start):
        raise BenchError("the window never started")
    run.compiles = ((counter.n if counter is not None else 0)
                    + jit_cache_sizes(engine) - cache0
                    + PREP_STATS["prepared"] - prep0)
    if trace_dir is not None and run.trace_span is None:
        raise BenchError("the traced span did not close inside the window")
    return run


# --- host-clock metrics -----------------------------------------------------

def nearest_rank(values, q: float) -> float:
    v = sorted(values)
    if not v:
        return math.nan
    return v[max(0, math.ceil(q * len(v)) - 1)]


def host_metrics(run: Run, mix: dict) -> Dict[str, float]:
    """End-to-end metrics from the token stamps, and the window's occupancy."""
    stop = mix["end"] == "stop"
    due = due_times(run)
    in_window = window_requests(run)
    ttft = []
    n_out = 0
    for i in in_window:
        t = run.reqs[i].out_tokens.t
        # a request with no token by the drain cap counts at the cap: a
        # lower bound of its (unbounded) time to first token
        ttft.append(t[0] - due[i] if t
                    else run.t_end + mix.get("drain_cap_s", 0.0) - due[i])
    gaps = token_gaps(run, mix, in_window)
    for r in run.reqs:
        t = np.asarray(r.out_tokens.t)
        n_out += int(((t >= run.t_start) & (t <= run.t_end)).sum())
    # cut requests are not failed; one with no first token by the cap is
    failed = 0 if stop else sum(1 for i in in_window
                                if not run.reqs[i].out_tokens)
    occ = [o for o in run.occupancy if o[0] <= run.t_end]
    out = {"output_tok_s": n_out / (run.t_end - run.t_start),
           "attempted": len(in_window), "failed": failed,
           "n_ttft": len(ttft), "n_gaps": len(gaps),
           "drain_s": run.t_stop - run.t_end,
           "live_slots_mean": live_slots_mean(run, run.t_start, run.t_end),
           "kv_blocks_max": max((o[2] for o in occ), default=0),
           "kv_blocks": run.kv_blocks,
           "live_keys_max": max((o[3] for o in occ), default=0),
           "bytes_in_use_max": max((o[4] for o in occ), default=0)}
    # the highest percentile a cell can name depends on its sample size
    # (ten samples beyond it): offer the usual ones, BENCHMARK.json picks
    for p in (50, 65, 70, 75, 80, 85, 90, 95):
        out[f"ttft_p{p}_s"] = nearest_rank(ttft, p / 100)
    for p in (50, 90, 95, 99):
        out[f"itl_p{p}_ms"] = 1e3 * nearest_rank(gaps, p / 100)
    out["longest_rounds"] = longest_rounds(run, in_window)
    return out


def token_gaps(run: Run, mix: dict, requests: List[int],
               cut=()) -> List[float]:
    """Seconds between consecutive tokens of ``requests``. In a cell that
    stops, only the gaps inside the window (a resident request's first
    gap spans the set-up's admissions). A gap that holds one of the
    instants in ``cut`` is left out."""
    gaps: List[float] = []
    for i in requests:
        tt = np.asarray(run.reqs[i].out_tokens.t)
        keep = np.ones(max(tt.size - 1, 0), bool)
        if mix["end"] == "stop":
            keep &= (tt[:-1] >= run.t_start) & (tt[1:] <= run.t_end)
        for c in cut:
            keep &= ~((tt[:-1] < c) & (tt[1:] > c))
        gaps.extend(np.diff(tt)[keep].tolist())
    return gaps


def longest_rounds(run: Run, requests: List[int], n: int = 12) -> list:
    """The ``n`` scheduling rounds that held the longest token gaps of
    ``requests``: ``[longest gap (ms), gaps of ``requests`` that ended in
    it, prompt buckets it admitted]``, longest first. The tail of the
    gaps is made of whole rounds: an admission stretches the gap of every
    slot live in its round."""
    bounds = np.asarray(run.rounds)
    if bounds.size == 0:
        return []
    per: Dict[int, list] = {}
    for i in requests:
        t = np.asarray(run.reqs[i].out_tokens.t)
        for k, g in zip(np.searchsorted(bounds, t[1:]) - 1, np.diff(t)):
            per.setdefault(int(k), []).append(float(g))
    admitted: Dict[int, List[int]] = {}
    for r, b in zip(run.reqs, run.buckets):
        if r.out_tokens.t:
            k = int(np.searchsorted(bounds, r.out_tokens.t[0])) - 1
            admitted.setdefault(k, []).append(b)
    top = sorted(per, key=lambda k: -max(per[k]))[:n]
    return [[round(1e3 * max(per[k]), 3), len(per[k]),
             sorted(admitted.get(k, []))] for k in top]


def live_slots_mean(run: Run, lo: float, hi: float) -> float:
    """Live slots over (lo, hi), weighted by how long each round held them
    (the engine polls while idle, too)."""
    occ = [o for o in run.occupancy if lo <= o[0] < hi]
    if not occ:
        return 0.0
    t = np.array([o[0] for o in occ] + [hi])
    return float(np.dot([o[1] for o in occ], np.diff(t)) / (hi - t[0]))


def round_records(run: Run, lo: float, hi: float):
    """Prefills and decode rounds whose tokens were emitted in (lo, hi].

    Returns ``(prefills, decode_rounds)``: ``prefills`` lists
    ``(prompt_len, bucket)`` of each request whose first token falls in
    the span; ``decode_rounds`` lists, for each scheduling round in the
    span that ran a decode step, the live key counts of its slots.
    """
    bounds = [r for r in run.rounds if lo <= r <= hi]
    prefills = []
    rounds: Dict[int, List[int]] = {}
    for s, r, b in zip(run.specs, run.reqs, run.buckets):
        for j, t in enumerate(r.out_tokens.t):
            if not (lo < t <= hi):
                continue
            if j == 0:
                prefills.append((s.prompt_len, b))
                continue
            k = int(np.searchsorted(bounds, t))      # the round it ended
            rounds.setdefault(k, []).append(b + j)
    return prefills, [rounds[k] for k in sorted(rounds)]


def free_engine(engine):
    from repro.quant import clear_prepared_cache
    engine.params = engine.cache = None
    clear_prepared_cache()
    gc.collect()
