"""Unified quantized-einsum dispatch + calibration (ISSUE-3).

Three layers of coverage:

* canonicalization: every supported einsum spec matches ``jnp.einsum`` at
  fp32 and the unfused exact path bit-for-bit at fp8, including the
  grouped/expert and multi-axis-K shapes the model call sites use
  (property-tested with hypothesis when available);
* calibration: the one-pass activation trace feeds observed per-site limb
  sigmas into the Markov flush planner (observed-sigma plan != the
  default-sigma plan), end-to-end through ``ServeEngine.calibrate``,
  without changing results (exact kernels are flush-invariant);
* cross-mesh bit-identity: an 8-device **data-axis (FSDP)** ServeEngine
  produces logits bit-identical to the single-device fused path — the
  guarantee PR 2 could only give for pure TP. Multi-device behaviour runs
  in subprocesses with forced host devices (project rule: the main pytest
  process sees exactly 1 device); ``multidevice``-marked tests run
  natively in the forced-8-device CI shards (scripts/ci.sh).
"""

import dataclasses
import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.quant import (CalibrationTable, QuantConfig, calibrating,
                         plan_qeinsum, prepare_weight, qeinsum, qmatmul)

_SRC = os.path.join(os.path.dirname(__file__), "..", "src")

_CFG_NONE = QuantConfig()
_CFG_FP8 = QuantConfig(dtype="fp8_e4m3", accum="mgs_exact",
                       block_m=32, block_n=32, block_k=32)
_CFG_KERNEL = dataclasses.replace(_CFG_FP8, use_kernel=True)

# (spec, x shape, w shape) — every model call-site family:
# plain proj, attention out-proj (multi-axis K), MoE router, MoE expert
# einsums (batched w), decode score/value einsums (batched activation w),
# and the logits head (transposed w term).
SPECS = [
    ("mk,kn->mn", (8, 96), (96, 16)),
    ("btk,kn->btn", (2, 5, 64), (64, 24)),
    ("bthd,hdo->bto", (2, 4, 3, 32), (3, 32, 40)),
    ("gtd,de->gte", (3, 8, 64), (64, 6)),
    ("gecd,edf->gecf", (2, 3, 4, 64), (3, 64, 24)),
    ("gecf,efd->gecd", (2, 3, 4, 64), (3, 64, 24)),
    ("btkgh,bskh->bkgts", (2, 4, 2, 3, 32), (2, 6, 2, 32)),
    ("bkgts,bskh->btkgh", (2, 2, 3, 4, 16), (2, 16, 2, 32)),
    ("btd,vd->btv", (2, 4, 64), (48, 64)),
    ("btd,dv->btv", (2, 4, 64), (64, 48)),
]


def _operands(rng, x_shape, w_shape):
    x = jnp.asarray(rng.normal(0, 1, x_shape).astype(np.float32))
    w = jnp.asarray(rng.normal(0, 0.1, w_shape).astype(np.float32))
    return x, w


# ---------------------------------------------------------------------------
# canonicalization
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("spec,x_shape,w_shape", SPECS)
def test_qeinsum_none_matches_jnp_einsum(rng, spec, x_shape, w_shape):
    """dtype=none dispatch == jnp.einsum with fp32 accumulation, bitwise."""
    x, w = _operands(rng, x_shape, w_shape)
    got = qeinsum(spec, x, w, _CFG_NONE)
    want = jnp.einsum(spec, x, w, preferred_element_type=jnp.float32)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("spec,x_shape,w_shape", SPECS)
def test_qeinsum_fp8_kernel_matches_emulation(rng, spec, x_shape, w_shape):
    """The fused-kernel dispatch == the unfused jnp exact path, bit for
    bit, through the same canonicalization for every supported spec."""
    x, w = _operands(rng, x_shape, w_shape)
    got = qeinsum(spec, x, w, _CFG_KERNEL)
    want = qeinsum(spec, x, w, _CFG_FP8.replace(use_kernel=False))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_qeinsum_fp8_canonicalization_matches_manual_qmatmul(rng):
    """The expert einsum's batch loop == manual per-expert qmatmul."""
    x, w = _operands(rng, (2, 3, 4, 64), (3, 64, 24))
    got = np.asarray(qeinsum("gecd,edf->gecf", x, w, _CFG_FP8))
    for e in range(3):
        want = np.asarray(qmatmul(x[:, e].reshape(-1, 64), w[e], _CFG_FP8)
                          ).reshape(2, 4, 24)
        np.testing.assert_array_equal(got[:, e], want)


def test_qeinsum_prepared_grouped_and_multik(rng):
    """Prepared expert (stacked) and out-proj (k_ndim=2) weights feed
    qeinsum bit-identically to per-call quantization."""
    cfg = _CFG_KERNEL
    xe, we = _operands(rng, (2, 3, 4, 64), (3, 64, 24))
    pwe = prepare_weight(we, cfg, stack_ndim=1)
    np.testing.assert_array_equal(
        np.asarray(qeinsum("gecd,edf->gecf", xe, pwe, cfg)),
        np.asarray(qeinsum("gecd,edf->gecf", xe, we, cfg)))
    xo, wo = _operands(rng, (2, 4, 3, 32), (3, 32, 40))
    pwo = prepare_weight(wo, cfg, k_ndim=2)
    assert pwo.codes.shape == (96, 40)
    np.testing.assert_array_equal(
        np.asarray(qeinsum("bthd,hdo->bto", xo, pwo, cfg)),
        np.asarray(qeinsum("bthd,hdo->bto", xo, wo, cfg)))


def test_qeinsum_epilogue_matches_proj_contract(rng):
    """bias/activation epilogue follows the proj contract: in-kernel on
    the fused path, after the output cast otherwise — never both."""
    x, w = _operands(rng, (8, 96), (96, 16))
    bias = jnp.asarray(rng.normal(0, 1, (16,)).astype(np.float32))
    for cfg in (_CFG_NONE, _CFG_FP8, _CFG_KERNEL):
        got = qeinsum("mk,kn->mn", x, w, cfg, bias=bias, activation="gelu")
        plain = qeinsum("mk,kn->mn", x, w, cfg)
        assert got.shape == plain.shape
        assert np.isfinite(np.asarray(got)).all()
    # fused and unfused epilogues agree to float tolerance (the fused
    # kernel FMA-contracts scale*out+bias into one rounding)
    np.testing.assert_allclose(
        np.asarray(qeinsum("mk,kn->mn", x, w, _CFG_KERNEL, bias=bias,
                           activation="gelu")),
        np.asarray(qeinsum("mk,kn->mn", x, w,
                           _CFG_FP8.replace(use_kernel=False), bias=bias,
                           activation="gelu")), rtol=1e-5, atol=1e-5)


def test_qeinsum_rejects_bad_specs(rng):
    x, w = _operands(rng, (8, 16), (16, 8))
    for spec in ("mk,kn", "mk,kn,nj->mj", "mm,mn->mn", "mk,kn->mkn",
                 "mk,kn->n"):
        with pytest.raises(ValueError):
            qeinsum(spec, x, w, _CFG_NONE)
    with pytest.raises(ValueError, match="no contracted"):
        plan_qeinsum("m,n->mn")
    with pytest.raises(ValueError, match="size"):
        qeinsum("mk,kn->mn", x, jnp.zeros((8, 8)), _CFG_NONE)
    with pytest.raises(ValueError, match="dims"):
        qeinsum("mk,kn->mn", x, w, _CFG_NONE, dims={"k": 99})


def test_qeinsum_plan_classification():
    p = plan_qeinsum("gecd,edf->gecf")
    assert (p.batch, p.m, p.k, p.n) == ("e", "gc", "d", "f")
    assert p.canonical_w
    p = plan_qeinsum("btkgh,bskh->bkgts")
    assert (p.batch, p.m, p.k, p.n) == ("bk", "tg", "h", "s")
    assert not p.canonical_w           # w term is (b, s, k, h)
    p = plan_qeinsum("bthd,hdo->bto")
    assert (p.batch, p.m, p.k, p.n) == ("", "bt", "hd", "o")


def _property_body(spec_shapes, seed):
    """Any supported spec, random operands: fp32 == jnp.einsum bitwise,
    fp8 fused kernel == fp8 emulation bitwise."""
    spec, x_shape, w_shape = spec_shapes
    rng = np.random.default_rng(seed)
    x, w = _operands(rng, x_shape, w_shape)
    np.testing.assert_array_equal(
        np.asarray(qeinsum(spec, x, w, _CFG_NONE)),
        np.asarray(jnp.einsum(spec, x, w,
                              preferred_element_type=jnp.float32)))
    np.testing.assert_array_equal(
        np.asarray(qeinsum(spec, x, w, _CFG_KERNEL)),
        np.asarray(qeinsum(spec, x, w,
                           _CFG_FP8.replace(use_kernel=False))))


try:  # hypothesis is optional (as in test_property.py) — the seeded
    # fallback below keeps the property exercised without it, guarded so
    # a missing dependency never skips the rest of this module.
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=25, deadline=None)
    @given(st.sampled_from(SPECS), st.integers(0, 2 ** 31 - 1))
    def test_qeinsum_property_fp32_and_fp8(spec_shapes, seed):
        _property_body(spec_shapes, seed)
except ImportError:
    @pytest.mark.parametrize("seed", [1, 17, 123])
    @pytest.mark.parametrize("spec_shapes", SPECS,
                             ids=[s for s, _, _ in SPECS])
    def test_qeinsum_property_fp32_and_fp8(spec_shapes, seed):
        _property_body(spec_shapes, seed)


# ---------------------------------------------------------------------------
# calibration
# ---------------------------------------------------------------------------


def test_calibration_records_per_site_pmfs(rng):
    x, w = _operands(rng, (16, 96), (96, 16))
    with calibrating() as rec:
        qmatmul(x, w, _CFG_FP8, site="ffn.wg")
        qmatmul(x * 3.0, w, _CFG_FP8, site="ffn.wd")
        qmatmul(x, w, _CFG_FP8)            # untagged: not recorded
    assert rec.sites == ("ffn.wd", "ffn.wg")
    pmf = rec.pmf("ffn.wg")
    assert pmf.probs.sum() == pytest.approx(1.0)
    table = rec.table()
    assert 0 < table.sigma("ffn.wg") < 64


def test_observed_sigma_flush_plan_differs_from_default(rng):
    """The acceptance pin: the observed-sigma path != default-sigma path.

    Activations quantized from (absmax-scaled) normals have limb sigmas
    well under the uniform-limb default, so the Markov planner licenses a
    strictly longer flush period at the same overflow target.
    """
    from repro.core.markov import limb_sigma_default, plan_flush_period
    x, w = _operands(rng, (32, 128), (128, 16))
    with calibrating() as rec:
        qmatmul(x, w, _CFG_FP8, site="ffn.wg")
    sigma = rec.table().sigma("ffn.wg")
    assert sigma < limb_sigma_default()
    p_obs = plan_flush_period(4096, target_overflow=1e-6,
                              sigma_limb_x=sigma)
    p_def = plan_flush_period(4096, target_overflow=1e-6)
    assert p_obs != p_def
    assert p_obs > p_def               # longer period, fewer flushes


def test_calibration_table_roundtrip_through_config():
    table = CalibrationTable({"ffn.wg": 20.0, "attn.wq": 18.5})
    cfg = _CFG_FP8.with_calibration(table)
    assert cfg.act_sigma("ffn.wg") == 20.0
    assert cfg.act_sigma("missing") is None
    assert cfg.act_sigma(None) is None
    # hashable (usable as a jit static) and round-trippable
    hash(cfg)
    assert CalibrationTable.from_pairs(cfg.calibration).sigma(
        "attn.wq") == 18.5
    assert cfg.with_calibration(None).calibration is None


@pytest.mark.slow
def test_serve_engine_calibration_end_to_end(rng):
    """ServeEngine.calibrate: the observed table covers the model's call
    sites, installs per-site flush planning, and (exact kernels being
    flush-invariant) leaves served tokens unchanged."""
    from repro.configs import reduced_config
    from repro.launch.mesh import make_mesh
    from repro.launch.serve import Request, ServeEngine
    cfg = dataclasses.replace(
        reduced_config("deepseek-7b"),
        quant=dataclasses.replace(_CFG_KERNEL, flush_target=1e-6))
    mesh = make_mesh((1, 1), ("data", "model"))
    prompt = rng.integers(1, cfg.vocab, 8).astype(np.int32)

    e1 = ServeEngine(cfg, mesh, batch=2, max_len=32)
    r1 = [Request(rid=0, prompt=prompt.copy(), max_new_tokens=4)]
    e1.run(r1)

    e2 = ServeEngine(cfg, mesh, batch=2, max_len=32, params=e1.params)
    table = e2.calibrate()
    # prefill sites + the decode-only attention sites are all observed
    for site in ("attn.wq", "attn.wo", "attn.scores", "attn.values",
                 "ffn.wg", "ffn.wd", "logits"):
        assert table.sigma(site) is not None, site
    assert e2.cfg.quant.act_sigma("ffn.wg") == table.sigma("ffn.wg")
    # calibrated PreparedWeights carry the stamped act sigma
    assert e2.params["layers"]["ffn"]["wg"].act_sigma == pytest.approx(
        table.sigma("ffn.wg"))
    r2 = [Request(rid=0, prompt=prompt.copy(), max_new_tokens=4)]
    e2.run(r2)
    assert r1[0].out_tokens == r2[0].out_tokens
    # engines constructed with a table start calibrated
    e3 = ServeEngine(cfg, mesh, batch=2, max_len=32, params=e1.params,
                     calibration=table)
    assert e3.cfg.quant.act_sigma("logits") == table.sigma("logits")


# ---------------------------------------------------------------------------
# cross-mesh bit-identity (subprocess: forced host devices)
# ---------------------------------------------------------------------------


def _run(code: str, devices: int = 8, timeout: int = 420):
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = _SRC + os.pathsep + env.get("PYTHONPATH", "")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, timeout=timeout,
                         env=env)
    assert out.returncode == 0, out.stderr[-4000:]
    return out.stdout


_ENGINE_SETUP = """
    import dataclasses, json
    import jax, jax.numpy as jnp, numpy as np
    from repro.configs import reduced_config
    from repro.launch.mesh import make_mesh
    from repro.launch.serve import Request, ServeEngine
    from repro.models import init_cache, init_params
    from repro.parallel.sharding import use_rules
    from repro.quant import QuantConfig

    cfg = dataclasses.replace(reduced_config("deepseek-7b"),
        quant=QuantConfig(dtype="fp8_e4m3", accum="mgs_exact",
                          use_kernel=True,
                          block_m=32, block_n=32, block_k=32))
    params, dims = init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    prompt = rng.integers(1, cfg.vocab, 8).astype(np.int32)
    toks = jnp.asarray(np.stack([prompt, prompt]))

    def engine_logits(mesh):
        e = ServeEngine(cfg, mesh, batch=2, max_len=16, params=params,
                        dims=dims)
        reqs = [Request(rid=0, prompt=prompt, max_new_tokens=4)]
        e.run(reqs)
        cache, _ = init_cache(cfg, 2, 16)
        with use_rules(e.rules):
            lg, _ = e._prefill(e.params, {"tokens": toks}, cache)
        return e, np.asarray(lg), reqs[0].out_tokens
"""


@pytest.mark.slow
def test_fsdp_engine_bit_identical_logits():
    """ISSUE-3 acceptance: the 8-device data-axis (FSDP) ServeEngine —
    prepared planes sharded over the data axis — produces logits and
    greedy tokens bit-identical to the single-device fused path."""
    out = _run(_ENGINE_SETUP + """
    e1, lg1, t1 = engine_logits(make_mesh((1, 1), ("data", "model")))
    e8, lg8, t8 = engine_logits(make_mesh((8, 1), ("data", "model")))
    pw = e8.params["layers"]["ffn"]["wg"]
    print(json.dumps({
        "ndev": jax.device_count(),
        "codes_devs": len(pw.codes.sharding.device_set),
        "logits_bitwise": bool((lg1 == lg8).all()),
        "tokens_equal": t1 == t8}))
    """, timeout=560)
    res = json.loads(out.strip().splitlines()[-1])
    assert res["ndev"] == 8
    assert res["codes_devs"] == 8      # genuinely FSDP-sharded planes
    assert res["logits_bitwise"]
    assert res["tokens_equal"]


@pytest.mark.slow
def test_mixed_mesh_engine_bit_identical_logits():
    """data x model (2, 4) — both axes active — is bit-identical too."""
    out = _run(_ENGINE_SETUP + """
    e1, lg1, t1 = engine_logits(make_mesh((1, 1), ("data", "model")))
    em, lgm, tm = engine_logits(make_mesh((2, 4), ("data", "model")))
    print(json.dumps({
        "logits_bitwise": bool((lg1 == lgm).all()),
        "tokens_equal": t1 == tm}))
    """, timeout=560)
    res = json.loads(out.strip().splitlines()[-1])
    assert res["logits_bitwise"]
    assert res["tokens_equal"]


# ---------------------------------------------------------------------------
# native multi-device tests (the forced-8-device CI shards)
# ---------------------------------------------------------------------------


def _native_device_count():
    return jax.device_count()


@pytest.mark.multidevice
@pytest.mark.skipif(_native_device_count() < 8,
                    reason="needs XLA_FLAGS forced >= 8 host devices "
                           "(scripts/ci.sh multi-device shards)")
def test_native_fsdp_qeinsum_bit_identical():
    """The FSDP (data > 1) shard's pin: a data-axis mesh qeinsum over
    FSDP-sharded prepared planes == the local computation, bitwise."""
    from jax.sharding import NamedSharding

    from repro.launch.mesh import make_serve_mesh
    from repro.parallel.sharding import make_rules, prepared_specs
    rng = np.random.default_rng(0)
    cfg = _CFG_KERNEL
    x = jnp.asarray(rng.normal(0, 1, (2, 4, 3, 32)).astype(np.float32))
    w = jnp.asarray(rng.normal(0, 0.1, (3, 32, 64)).astype(np.float32))

    mesh = make_serve_mesh(model_parallel=1)       # (8, 1): pure data axis
    assert dict(mesh.shape)["data"] == 8
    rules = make_rules(mesh, "serve", shard_batch=False)
    specs = prepared_specs(("heads", "head_dim", "embed"), w.shape, rules,
                           k_ndim=2)
    sh = tuple(NamedSharding(mesh, s) for s in specs)
    pw = prepare_weight(w, cfg, k_ndim=2, shardings=sh)
    assert len(pw.codes.sharding.device_set) == 8  # embed over data
    got = jax.jit(lambda x, pw: qeinsum("bthd,hdo->bto", x, pw, cfg))(x, pw)
    pw_local = prepare_weight(jnp.array(np.asarray(w)), cfg, k_ndim=2)
    want = qeinsum("bthd,hdo->bto", x, pw_local, cfg)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
