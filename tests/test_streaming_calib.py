"""Streaming calibration: drift-and-replay determinism suite (ISSUE-9).

Pins the contract of ``quant.streaming`` plus the versioned hot-swap
path through the serve engines and the replica fleet:

* the sampling gate and streaming recorder are deterministic /
  convergent / thread-safe (unit tests, no engine);
* runtime flush periods are kernel *operands*, so swapping them never
  retraces, and equal periods give bitwise-equal products;
* both engines stamp every request with the calibration-table version
  it was served under, hot swaps land between decode steps (the group
  engine at a group boundary, the continuous engine behind its drain
  fence), and ``replay(request, version)`` reproduces the logged bits
  under any retained version — including requests that straddled a
  swap;
* the replica driver pushes refreshed tables fleet-wide without drain,
  and survives a fault-injected hot swap with zero drops, no new
  weight preparation and no recompiles (the ``multidevice`` shard).

Multi-device behaviour follows the project rule: the main pytest
process sees exactly 1 device; the chaos test is marked
``multidevice`` and runs natively in the forced-8-device CI shard.
"""

import dataclasses
import threading

import numpy as np
import pytest

from repro.configs import reduced_config
from repro.launch.mesh import make_mesh
from repro.launch.serve import (ContinuousBatchingEngine, Request,
                                ServeEngine)
from repro.quant import (ActivationRecorder, CalibrationTable, QuantConfig,
                         StreamingCalibrator, StreamingRecorder,
                         detect_drift, sample_gate, tv_distance)
from repro.quant.calibrate import _LIMB_LO, _N_LEVELS


def _quant(**kw):
    base = dict(dtype="fp8_e4m3", accum="mgs_exact", use_kernel=True,
                flush_target=1e-6, block_m=32, block_n=32, block_k=32)
    base.update(kw)
    return QuantConfig(**base)


def _cfg(**kw):
    return dataclasses.replace(reduced_config("deepseek-7b"),
                               quant=_quant(**kw))


def _mesh():
    return make_mesh((1, 1), ("data", "model"))


def _requests(cfg, rids, plen=12, max_new=4, seed=0):
    rng = np.random.default_rng(seed)
    return [Request(rid=rid,
                    prompt=rng.integers(1, cfg.vocab, plen).astype(np.int32),
                    max_new_tokens=max_new) for rid in rids]


def _logits_of(stats, reqs):
    return {r.rid: [x.copy() for x in stats["logits"][r.rid]] for r in reqs}


def _assert_bitwise(got, want):
    assert len(got) == len(want)
    for a, b in zip(got, want):
        assert a.tobytes() == b.tobytes()


# ---------------------------------------------------------------------------
# sampling gate
# ---------------------------------------------------------------------------


def test_sample_gate_deterministic_and_periodic():
    """Pure function of (seed, index, period): replaying the same index
    stream gives the same admissions, at exactly 1/period rate."""
    for seed in (0, 7, 123):
        for period in (2, 4, 5):
            first = [sample_gate(seed, i, period) for i in range(4 * period)]
            again = [sample_gate(seed, i, period) for i in range(4 * period)]
            assert first == again
            assert sum(first) == 4
    # period <= 1 admits everything (the fall-through dense gate)
    assert all(sample_gate(3, i, 1) for i in range(10))
    assert all(sample_gate(3, i, 0) for i in range(10))


def test_sample_gate_seed_staggers_replicas():
    """Different seeds shift which indices are sampled — two replicas
    sharing a recorder shadow different traffic, not the same groups."""
    period = 4
    admitted = [{i for i in range(16) if sample_gate(s, i, period)}
                for s in range(period)]
    assert all(a and admitted[0].isdisjoint(a) for a in admitted[1:])
    assert set().union(*admitted) == set(range(16))


# ---------------------------------------------------------------------------
# streaming recorder
# ---------------------------------------------------------------------------


def _limb_stream(rng, n, lo=-12, hi=13):
    return rng.integers(lo, hi, n).astype(np.int64)


def test_streaming_recorder_exact_on_degenerate_stream():
    """On a constant stream every per-call PMF equals the batch PMF, so
    the EMA is *exactly* the batch recorder's answer."""
    ema, batch = StreamingRecorder(decay=0.9), ActivationRecorder()
    limbs = np.full(64, 5, np.int64)
    for _ in range(10):
        ema.record("s", limbs)
        batch.record("s", limbs)
    np.testing.assert_array_equal(ema.pmf("s").probs, batch.pmf("s").probs)
    assert ema.pmf("s").std == batch.pmf("s").std == 0.0
    assert ema.calls("s") == batch.calls("s") == 10


def test_streaming_recorder_converges_to_batch_on_stationary_stream():
    """Stationary traffic: the EMA sigma converges to the batch
    recorder's sigma (the smoke that streaming plans the same flush
    periods as one-shot calibration when nothing drifts)."""
    rng = np.random.default_rng(0)
    ema, batch = StreamingRecorder(decay=0.95), ActivationRecorder()
    for _ in range(400):
        limbs = _limb_stream(rng, 512)
        ema.record("s", limbs)
        batch.record("s", limbs)
    s_ema, s_batch = ema.pmf("s").std, batch.pmf("s").std
    assert s_batch > 0.0
    assert abs(s_ema - s_batch) / s_batch < 0.02
    # normalized by construction (convex combination of normalized PMFs)
    assert abs(ema.pmf("s").probs.sum() - 1.0) < 1e-12


def test_streaming_recorder_tracks_drift_batch_does_not():
    """After a distribution shift the EMA forgets the old regime
    geometrically; the batch recorder averages the regimes forever."""
    rng = np.random.default_rng(1)
    ema, batch = StreamingRecorder(decay=0.9), ActivationRecorder()
    for _ in range(100):
        limbs = _limb_stream(rng, 512, -3, 4)          # narrow regime
        ema.record("s", limbs)
        batch.record("s", limbs)
    for _ in range(100):
        limbs = _limb_stream(rng, 512, -40, 41)        # wide regime
        ema.record("s", limbs)
        batch.record("s", limbs)
    fresh = ActivationRecorder()
    fresh.record("s", _limb_stream(np.random.default_rng(2), 1 << 16,
                                   -40, 41))
    target = fresh.pmf("s").std
    assert abs(ema.pmf("s").std - target) / target < 0.05
    assert abs(batch.pmf("s").std - target) / target > 0.10


def test_streaming_recorder_amax_ema_and_mute():
    ema = StreamingRecorder(decay=0.5)
    ema.record_amax("q", 8.0)
    ema.record_amax("q", 4.0)
    assert ema._amax["q"] == pytest.approx(6.0)   # EMA, not max-fold
    ema.muted = True
    ema.record_amax("q", 100.0)
    ema.record("q", np.zeros(8, np.int64))
    assert ema._amax["q"] == pytest.approx(6.0)
    assert "q" not in ema.sites
    ema.muted = False
    with pytest.raises(ValueError):
        ema.record("q", np.full(4, _LIMB_LO + _N_LEVELS, np.int64))


def test_streaming_recorder_thread_safe():
    """Replica workers share one recorder; concurrent records must not
    corrupt the EMA (normalization / call counts survive a race-free
    interleaving of 8 writers)."""
    rec = StreamingRecorder(decay=0.9)
    errs = []

    def work(seed):
        rng = np.random.default_rng(seed)
        try:
            for _ in range(50):
                rec.record("a", _limb_stream(rng, 64))
                rec.record_amax("a", float(rng.uniform(1, 2)))
        except Exception as e:               # pragma: no cover
            errs.append(e)

    threads = [threading.Thread(target=work, args=(i,)) for i in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errs
    assert rec.calls("a") == 8 * 50
    assert abs(rec.pmf("a").probs.sum() - 1.0) < 1e-12
    assert 1.0 <= rec._amax["a"] <= 2.0


# ---------------------------------------------------------------------------
# drift detection + versioned tables
# ---------------------------------------------------------------------------


def test_tv_distance_basics():
    rec = ActivationRecorder()
    rec.record("a", np.array([0, 0, 1, 1], np.int64))
    rec.record("b", np.array([2, 2, 3, 3], np.int64))
    p, q = rec.pmf("a"), rec.pmf("b")
    assert tv_distance(p, p) == 0.0
    assert tv_distance(p, q) == pytest.approx(1.0)    # disjoint support
    assert tv_distance(p, q) == tv_distance(q, p)


def test_detect_drift_trips_on_sigma_shift_only():
    rng = np.random.default_rng(0)
    stationary = StreamingRecorder(decay=0.9)
    for _ in range(50):
        stationary.record("s", _limb_stream(rng, 1024))
    table = stationary.table()

    calm = detect_drift(stationary, table, sigma_rtol=0.10)
    assert not calm and calm.drifted_sites == ()
    assert calm.sigma_delta["s"] < 0.10

    shifted = StreamingRecorder(decay=0.9)
    for _ in range(50):
        shifted.record("s", _limb_stream(rng, 1024, -50, 51))
    report = detect_drift(shifted, table, sigma_rtol=0.10)
    assert report and "s" in report.drifted_sites

    # TV criterion against a baseline snapshot trips independently of
    # sigma (a reshaped PMF with a preserved second moment still drifts)
    base = {"s": stationary.pmf("s")}
    tv_report = detect_drift(shifted, table, baseline=base,
                             sigma_rtol=np.inf, tv_threshold=0.05)
    assert tv_report and tv_report.tv["s"] > 0.05

    # cold sites (fewer than min_calls) never justify a refresh
    cold = StreamingRecorder(decay=0.9)
    cold.record("s", _limb_stream(rng, 64, -50, 51))
    assert not detect_drift(cold, table, sigma_rtol=0.10, min_calls=2)


def test_calibration_table_versioning():
    t1 = CalibrationTable.from_pairs([("a", 1.0), ("b", 2.0)], version=1)
    t2 = t1.refreshed([("a", 1.5)])
    assert (t1.version, t2.version) == (1, 2)
    assert t2.sigma("a") == 1.5 and t2.sigma("b") == 2.0   # merged universe
    assert t1.content_hash != t2.content_hash
    # the hash fingerprints content, not version: a bit-inert reinstall
    # (same sigmas, new version) is recognizable as such
    t3 = t1.refreshed([])
    assert t3.version == 2 and t3.content_hash == t1.content_hash


def test_streaming_calibrator_refresh_resets_baseline():
    rng = np.random.default_rng(0)
    rec = StreamingRecorder(decay=0.9)
    for _ in range(20):
        rec.record("s", _limb_stream(rng, 1024))
    # the installed table is stale by 2x — one refresh is due
    stale = CalibrationTable.from_pairs(
        [(s, v * 2.0) for s, v in rec.table().to_pairs()], version=1)
    cal = StreamingCalibrator(stale, recorder=rec, sigma_rtol=0.10,
                              min_calls=1)
    installed = []
    report = cal.maybe_refresh(installed.append)
    assert report is not None and cal.refreshes == 1
    assert len(installed) == 1
    assert installed[0].version == cal.table.version == 2
    # the refreshed table is what the drift was measured against now:
    # an immediately repeated check (stationary stream) must be calm
    for _ in range(20):
        rec.record("s", _limb_stream(rng, 1024))
    assert cal.maybe_refresh(installed.append) is None
    assert len(installed) == 1 and cal.refreshes == 1


# ---------------------------------------------------------------------------
# runtime flush periods are operands, not trace constants
# ---------------------------------------------------------------------------


def test_runtime_flush_period_no_retrace_and_bitwise():
    """The kernel takes the flush period as an SMEM scalar: jit cache
    size is flat across period values, a traced scalar reproduces the
    static path bitwise, and huge host-planned periods (near-uniform
    sigmas overflow int32) clamp instead of raising."""
    import jax.numpy as jnp

    from repro.core import formats
    from repro.kernels import ops, ref
    from repro.kernels.mgs_matmul import mgs_matmul_exact_fused_pallas

    rng = np.random.default_rng(0)
    x = jnp.asarray(formats.round_to_format(
        rng.standard_normal((8, 256)).astype(np.float32), formats.E4M3))
    w = jnp.asarray(formats.round_to_format(
        rng.standard_normal((256, 8)).astype(np.float32), formats.E4M3))
    xc = formats.encode_bits(x, formats.E4M3)
    wc = formats.encode_bits(w, formats.E4M3)

    def run(fp):
        return mgs_matmul_exact_fused_pallas(xc, wc, formats.E4M3,
                                             block_m=8, block_n=8,
                                             block_k=64, flush_period=fp,
                                             interpret=True)

    static = run(2)
    n0 = mgs_matmul_exact_fused_pallas._cache_size()
    runtime = run(jnp.asarray(2, jnp.int32))
    n1 = mgs_matmul_exact_fused_pallas._cache_size()
    # same *value* as a runtime operand: bit-identical to the static plan
    assert np.asarray(runtime).tobytes() == np.asarray(static).tobytes()
    for fp in (1, 3, 4, 3337578147):
        got = run(jnp.asarray(min(fp, 2**31 - 1), jnp.int32))
        np.testing.assert_allclose(
            np.asarray(got),
            np.asarray(ref.mgs_matmul_ref(x, w, formats.E4M3, "exact")),
            rtol=1e-6)
    # swapping the period value is an operand change, never a retrace
    assert mgs_matmul_exact_fused_pallas._cache_size() == n1
    assert n1 <= n0 + 1   # at most the one new int32-operand entry

    # the public wrapper clamps oversized *python* periods pre-jit (the
    # eager calibrate path hands it host-planned ints)
    big = ops.mgs_matmul(x, w, formats.E4M3, "exact", flush_period=2**40,
                         block_m=8, block_n=8, block_k=64)
    np.testing.assert_allclose(
        np.asarray(big),
        np.asarray(ref.mgs_matmul_ref(x, w, formats.E4M3, "exact")))


# ---------------------------------------------------------------------------
# group engine: versions, hot swap, replay
# ---------------------------------------------------------------------------


class _SwapAtDecode:
    """Injector-shaped probe: hot-swap a table at a decode step *inside*
    a group, to prove the group's snapshot pins its plan (no tearing)."""

    def __init__(self, engine, table, step):
        self.engine, self.table, self.step = engine, table, step
        self.fired = False

    def before_group(self):
        pass

    def on_decode(self, step):
        if step == self.step and not self.fired:
            self.fired = True
            self.engine.apply_calibration(self.table)


@pytest.mark.slow
def test_group_engine_versioned_hot_swap_and_replay():
    cfg = _cfg()
    eng = ServeEngine(cfg, _mesh(), batch=2, max_len=64, eos_id=None)
    eng.warmup([16], max_new=2)

    # v0: the uncalibrated default plan is a replayable version too
    r0 = _requests(cfg, [0, 1], seed=0)
    l0 = _logits_of(eng.run(r0, record_logits=True), r0)
    assert [r.table_version for r in r0] == [0, 0]

    t1 = eng.calibrate()
    assert eng.table_version == 1
    r1 = _requests(cfg, [2, 3], seed=1)
    l1 = _logits_of(eng.run(r1, record_logits=True), r1)
    assert [r.table_version for r in r1] == [1, 1]

    # two hot swaps; the jitted entry points must survive untouched
    pf, dc = eng._prefill, eng._decode
    sizes = (pf._cache_size(), dc._cache_size())
    t2 = t1.refreshed([(s, v * 1.5) for s, v in t1.to_pairs()])
    assert eng.apply_calibration(t2) == 2
    r2 = _requests(cfg, [4, 5], seed=2)
    eng.run(r2, record_logits=True)
    assert [r.table_version for r in r2] == [2, 2]

    t3 = t2.refreshed([(s, v * 0.5) for s, v in t2.to_pairs()])
    assert eng.apply_calibration(t3) == 3
    r3 = _requests(cfg, [6, 7], seed=3)
    eng.run(r3)
    assert [r.table_version for r in r3] == [3, 3]
    assert eng._prefill is pf and eng._decode is dc
    assert (pf._cache_size(), dc._cache_size()) == sizes

    # a mid-group swap lands at the *next* group: the in-flight group
    # keeps its snapshotted plan and stamp
    t4 = t3.refreshed([(s, v * 2.0) for s, v in t3.to_pairs()])
    r4 = _requests(cfg, [8, 9], seed=4)
    probe = _SwapAtDecode(eng, t4, step=2)
    l4 = _logits_of(eng.run(r4, record_logits=True, injector=probe), r4)
    assert probe.fired
    assert eng.table_version == 4
    assert [r.table_version for r in r4] == [3, 3]

    # replay: every retained version reproduces its logged bits, long
    # after newer tables shipped — including the torn-swap group
    for reqs, logged in ((r0, l0), (r1, l1), (r4, l4)):
        rep, rst = eng.replay(reqs[0], group=reqs)
        assert rep.out_tokens == reqs[0].out_tokens
        _assert_bitwise(rst["logits"][reqs[0].rid], logged[reqs[0].rid])
    assert eng.table_version == 4          # replay never moves the head

    with pytest.raises(KeyError):
        eng.replay(r1[0], version=99, group=r1)


@pytest.mark.slow
def test_group_engine_streaming_refresh_no_recompile():
    """enable_streaming -> gated shadow passes feed the EMA -> forced
    drift refreshes the table fleet-of-one style: version bumps, serve
    bits stay on compiled entry points, old versions still replay."""
    cfg = _cfg()
    eng = ServeEngine(cfg, _mesh(), batch=2, max_len=64, eos_id=None)
    eng.warmup([16], max_new=2)
    eng.calibrate()
    cal = eng.enable_streaming(seed=5, sample_period=2, sigma_rtol=0.0,
                               min_calls=1)

    r1 = _requests(cfg, [0, 1, 2, 3], seed=0)
    l1 = _logits_of(eng.run(r1, record_logits=True), r1)
    assert any(cal.recorder.calls(s) for s in cal.recorder.sites)

    pf, dc = eng._prefill, eng._decode
    sizes = (pf._cache_size(), dc._cache_size())
    report = eng.maybe_refresh_calibration()
    assert report is not None and eng.table_version == 2
    assert cal.table.version == 2

    r2 = _requests(cfg, [4, 5], seed=1)
    eng.run(r2)
    assert [r.table_version for r in r2] == [2, 2]
    assert eng._prefill is pf and eng._decode is dc
    assert (pf._cache_size(), dc._cache_size()) == sizes

    rep, rst = eng.replay(r1[0], group=r1[:2])
    _assert_bitwise(rst["logits"][0], l1[0])
    # a calm recorder does not refresh again
    cal.sigma_rtol = 10.0
    assert eng.maybe_refresh_calibration() is None
    assert eng.table_version == 2


# ---------------------------------------------------------------------------
# continuous engine: fence, static q-scale pinning, straddling replay
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_continuous_engine_fenced_swap_and_straddling_replay():
    cfg = _cfg(kv_cache="packed", per_row_act=True, static_q_scale=True)
    eng = ContinuousBatchingEngine(cfg, _mesh(), slots=2, max_len=64,
                                   eos_id=None)
    eng.warmup([8, 16], max_new=2)
    rng = np.random.default_rng(1)

    def mk(rid, n=10, m=4):
        return Request(rid=rid,
                       prompt=rng.integers(1, cfg.vocab, n).astype(np.int32),
                       max_new_tokens=m)

    r0 = [mk(0), mk(1)]
    l0 = _logits_of(eng.serve(r0, record_logits=True), r0)
    assert [r.table_version for r in r0] == [0, 0]

    t1 = eng.calibrate()
    # calibrate() must feed the *versioned* static decode-query scale —
    # the pre-versioning observe_amax bypass left this at 0 (dynamic)
    assert eng._amax_value > 0.0
    r1 = [mk(2), mk(3)]
    l1 = _logits_of(eng.serve(r1, record_logits=True), r1)
    assert [r.table_version for r in r1] == [1, 1]

    sizes = (eng._prefill._cache_size(), eng._decode_paged._cache_size())

    # hot swap mid-traffic: a flush-plan-changing table must fence (wait
    # for the resident v1 requests), then admit late arrivals under v2
    t2 = t1.refreshed([(s, v * 4.0) for s, v in t1.to_pairs()])
    assert eng._plan_flush_host(t2) != eng._flush_host
    state = {"round": 0, "late": None, "fenced": None}

    def feed():
        state["round"] += 1
        if state["round"] == 3:
            eng.apply_calibration(t2)
            state["fenced"] = eng._pending is not None
            # both in the warmed 16-bucket: the cache-size pin below
            # must see zero compiles from the swap itself, so the late
            # arrivals reuse shapes the v1 traffic already compiled
            state["late"] = [mk(10, 9, 3), mk(11, 10, 3)]
            return state["late"]
        return []

    resident = [mk(4, 12, 5), mk(5, 11, 5)]
    s2 = eng.serve(resident, record_logits=True, feed=feed)
    late = state["late"]
    assert state["fenced"] is True
    assert all(len(r.out_tokens) == r.max_new_tokens
               for r in resident + late)          # zero drops
    assert [r.table_version for r in resident] == [1, 1]   # no tearing
    assert [r.table_version for r in late] == [2, 2]
    assert eng._pending is None and eng.table_version == 2
    li = _logits_of(s2, resident + late)

    # swapping was a state-array move: zero recompiles
    assert (eng._prefill._cache_size(),
            eng._decode_paged._cache_size()) == sizes

    # a bit-inert swap (same content, new version) installs immediately
    # even under a live engine — no fence needed
    t3 = t2.refreshed([])
    assert t3.content_hash == t2.content_hash
    assert eng.apply_calibration(t3) == 3
    assert eng._pending is None

    # replay every era bitwise: pre-calibration, v1, both sides of the
    # fenced swap — the static q-scale regression rides on v1 vs v2
    # having different amax entries
    for req, logged in ((r0[0], l0[0]), (r1[0], l1[2]),
                        (resident[0], li[4]), (late[0], li[10])):
        rep, rst = eng.replay(req)
        assert rep.out_tokens == req.out_tokens
        _assert_bitwise(rst["logits"][req.rid], logged)
    assert eng.table_version == 3


# ---------------------------------------------------------------------------
# replica fleet: shared recorder, no-drain push, routed replay
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_replica_driver_streaming_refresh_and_replay():
    from repro.launch.replica import ReplicaServeDriver

    cfg = _cfg()
    with ReplicaServeDriver(cfg, 1, batch=2, max_len=64) as driver:
        driver.warmup(plen_buckets=[16], max_new=2)
        driver.calibrate()
        cal = driver.enable_streaming(seed=7, sample_period=2,
                                      sigma_rtol=0.0, min_calls=1)

        reqs = _requests(cfg, range(4), seed=3)
        driver.run(reqs)
        assert {r.table_version for r in reqs} == {1}
        assert any(cal.recorder.calls(s) for s in cal.recorder.sites)

        report = driver.maybe_refresh_calibration()
        assert report is not None
        assert [e.table_version for e in driver.engines] == [2]

        more = _requests(cfg, [10, 11], seed=4)
        driver.run(more)
        assert {r.table_version for r in more} == {2}

        g1 = reqs[:2]
        rep, _ = driver.replay(g1[0], group=g1)
        assert rep.out_tokens == g1[0].out_tokens
        events = [e["event"] for e in driver.events()]
        assert events == ["calib_swap", "calib_refresh"]
        with pytest.raises(KeyError):
            driver.replay(more[0], version=42, group=more)


# ---------------------------------------------------------------------------
# native multi-device chaos: fault-injected fleet hot swap
# ---------------------------------------------------------------------------


def _native_device_count():
    import jax
    return jax.device_count()


@pytest.mark.multidevice
@pytest.mark.skipif(_native_device_count() < 8,
                    reason="needs XLA_FLAGS forced >= 8 host devices "
                           "(scripts/ci.sh multi-device shard)")
def test_native_fleet_hot_swap_under_faults():
    """R=2 on the forced-8-device set, fault injector live: a fleet hot
    swap lands mid-traffic with zero dropped requests, zero new weight
    preparations, zero recompiles, and the health machine undisturbed
    (the injected fault retries on the same replica — no failover)."""
    from repro.launch.replica import ReplicaServeDriver
    from repro.quant import PREP_STATS
    from repro.runtime.fault_tolerance import FaultInjector, FaultSpec

    cfg = _cfg()
    inj = FaultInjector([FaultSpec(kind="raise", replica=0, group=1,
                                   count=1)], seed=11)
    with ReplicaServeDriver(cfg, 2, batch=2, max_len=64, injector=inj,
                            max_retries=2) as driver:
        driver.warmup(plen_buckets=[12], max_new=3)
        t1 = driver.calibrate()
        assert [e.table_version for e in driver.engines] == [1, 1]

        first = _requests(cfg, range(8), max_new=3, seed=0)
        driver.run(first)
        assert inj.fired()
        assert {r.table_version for r in first} == {1}
        assert all(len(r.out_tokens) == 3 for r in first)

        prep0 = PREP_STATS["prepared"]
        sizes = [(e._prefill._cache_size(), e._decode._cache_size())
                 for e in driver.engines]

        # no-drain push while the fleet serves: overlap the swap with
        # in-flight traffic, then traffic submitted after it
        futs = driver.submit_many(_requests(cfg, range(20, 26),
                                            max_new=3, seed=1))
        v2 = driver.apply_calibration(
            t1.refreshed([(s, v * 1.5) for s, v in t1.to_pairs()]))
        assert v2 == 2
        post = _requests(cfg, range(30, 34), max_new=3, seed=2)
        futs += driver.submit_many(post)
        driver.drain()
        done = [f.result() for f in futs]

        assert all(len(r.out_tokens) == 3 for r in done)   # zero drops
        assert {r.table_version for r in done} <= {1, 2}
        assert {r.table_version for r in post} == {2}
        assert [e.table_version for e in driver.engines] == [2, 2]
        # the swap moved state arrays only: nothing re-prepared,
        # nothing recompiled, on either replica
        assert PREP_STATS["prepared"] == prep0
        assert [(e._prefill._cache_size(), e._decode._cache_size())
                for e in driver.engines] == sizes

        stats = driver.stats()
        assert stats["failovers"] == 0 and stats["rebuilds"] == 0
        assert all(h["state"] == "healthy" for h in stats["health"])

        # both replicas retain both versions; replay reproduces tokens
        assert all(set(e._tables) == {1, 2} for e in driver.engines)
        g = first[:2]
        rep, _ = driver.replay(g[0], group=g)
        assert rep.out_tokens == g[0].out_tokens
