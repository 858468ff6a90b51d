"""Packed-FP8 quantized KV cache + MGS flash-decode attention (ISSUE-5).

Pins the four contracts of the packed cache:

* append re-quantizes ONLY the new entries (old codes/scales bit-frozen);
* the Pallas flash-decode kernel and the pure-jnp emulation are bitwise
  identical, at the kernel level and through full model decode logits;
* the packed cache stays within fp8 quantization noise of the float
  cache on a real model forward;
* cross-mesh bit-identity holds with the quantized cache on (the
  ``test_sharded_serving`` guarantee extended to the packed decode
  path) — subprocess with forced host devices, plus a native
  ``multidevice`` variant for the CI shard.
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

from repro.configs import reduced_config
from repro.core.formats import E4M3, decode_bits
from repro.kernels.mgs_attention import mgs_flash_attention
from repro.models import decode_step, init_cache, init_params, prefill
from repro.quant import QuantConfig
from repro.quant.kvcache import (QuantizedKVCache, append_kv, dequantize_kv,
                                 init_quantized_kv, kv_cache_bytes,
                                 quantize_kv)
from repro.quant.quantize import quantize_fp8

_SRC = os.path.join(os.path.dirname(__file__), "..", "src")

_PACKED = dict(dtype="fp8_e4m3", accum="mgs_exact", kv_cache="packed")


# ---------------------------------------------------------------------------
# cache data structure
# ---------------------------------------------------------------------------


def test_append_requantizes_only_new_entries(rng):
    """Old codes and scales are bit-frozen across appends; the appended
    region equals quantizing the new entries in isolation. Plane layout
    is (B, KV, S, ...): heads before sequence, so the decode view is a
    reshape and the sequence axis here is axis 2."""
    B, S, KV, hd = 2, 12, 2, 8
    cache = init_quantized_kv((B,), KV, S, hd)
    k1 = jnp.asarray(rng.normal(0, 1, (B, 5, KV, hd)).astype(np.float32))
    v1 = jnp.asarray(rng.normal(0, 1, (B, 5, KV, hd)).astype(np.float32))
    c1 = append_kv(cache, k1, v1, 0, E4M3)
    k2 = jnp.asarray(rng.normal(0, 3, (B, 1, KV, hd)).astype(np.float32))
    v2 = jnp.asarray(rng.normal(0, 3, (B, 1, KV, hd)).astype(np.float32))
    c2 = append_kv(c1, k2, v2, 5, E4M3)
    # positions [0, 5) untouched bit-for-bit, [6, S) still zero
    for plane in ("k_codes", "v_codes", "k_scale", "v_scale"):
        a, b = np.asarray(getattr(c1, plane)), np.asarray(getattr(c2, plane))
        np.testing.assert_array_equal(a[:, :, :5], b[:, :, :5])
        np.testing.assert_array_equal(b[:, :, 6:], np.zeros_like(b[:, :, 6:]))
    # the new entry == quantizing it in isolation (per-entry scales make
    # append history-free)
    kc, ks = quantize_kv(k2, E4M3)
    np.testing.assert_array_equal(np.asarray(c2.k_codes[:, :, 5:6]),
                                  np.asarray(kc.transpose(0, 2, 1, 3)))
    np.testing.assert_array_equal(np.asarray(c2.k_scale[:, :, 5:6]),
                                  np.asarray(ks.transpose(0, 2, 1)))


def test_quantize_dequantize_roundtrip_error(rng):
    """Per-entry absmax scaling keeps reconstruction within E4M3 ulp."""
    x = jnp.asarray(rng.normal(0, 2, (3, 7, 2, 16)).astype(np.float32))
    codes, scale = quantize_kv(x, E4M3)
    back = decode_bits(codes, E4M3) * scale[..., None]
    # E4M3 relative step is 2^-3 per binade; absmax scaling bounds the
    # elementwise error by amax * 2^-3.5-ish
    err = np.abs(np.asarray(back - x))
    bound = np.asarray(scale)[..., None] * E4M3.max_finite * (2.0 ** -3.5)
    assert (err <= bound + 1e-7).all()


def test_kv_cache_bytes_accounting():
    """1 B/elem codes + 4 B/entry scales vs 2 B/elem bf16 — the docs
    memory-table numbers."""
    f = kv_cache_bytes(8, 4096, 8, 128, quantized=False)
    q = kv_cache_bytes(8, 4096, 8, 128, quantized=True)
    assert f == 2 * 8 * 4096 * 8 * 128 * 2
    assert q == 2 * (8 * 4096 * 8 * 128 + 4 * 8 * 4096 * 8)
    assert f / q > 1.8


# ---------------------------------------------------------------------------
# flash kernel
# ---------------------------------------------------------------------------


def _flash_case(rng, N=2, T=3, S=40, D=16):
    k = jnp.asarray(rng.normal(0, 1, (N, S, D)).astype(np.float32))
    v = jnp.asarray(rng.normal(0, 1, (N, S, D)).astype(np.float32))
    q = jnp.asarray(rng.normal(0, 1, (N, T, D)).astype(np.float32))
    kc, ks = quantize_kv(k, E4M3)
    vc, vs = quantize_kv(v, E4M3)
    qt = quantize_fp8(q.reshape(N, T * D), E4M3, axis=1)
    qv = qt.q.reshape(N, T, D)
    qk = jnp.broadcast_to(qt.scale, (N, S)) * ks * (D ** -0.5)
    bias = np.zeros((N, S), np.float32)  # per-key mask row (decode form)
    bias[:, -7:] = -1e30                 # mask a ragged tail
    return qv, kc, vc, qk, vs, jnp.asarray(bias), (ks, q, qt, D)


def test_flash_kernel_bitwise_vs_emulation(rng):
    qv, kc, vc, qk, vs, bias, _ = _flash_case(rng)
    got_k = mgs_flash_attention(qv, kc, vc, qk, vs, bias, E4M3, chunk=16,
                                use_kernel=True)
    got_r = mgs_flash_attention(qv, kc, vc, qk, vs, bias, E4M3, chunk=16,
                                use_kernel=False)
    np.testing.assert_array_equal(np.asarray(got_k), np.asarray(got_r))


def test_flash_chunk_invariance_of_masked_tail(rng):
    """Chunk-size padding is exactly inert: different chunkings agree on
    the running-state algebra only up to reassociation, so pin the
    padded-vs-exact-fit case, which must be bitwise."""
    qv, kc, vc, qk, vs, bias, _ = _flash_case(rng, S=32)
    a = mgs_flash_attention(qv, kc, vc, qk, vs, bias, E4M3, chunk=16,
                            use_kernel=False)
    # S=32 padded up from 32 -> identical; now slice to S=30 (pad=2)
    b = mgs_flash_attention(qv[:, :, :], kc[:, :30], vc[:, :30],
                            qk[:, :30], vs[:, :30], bias[:, :30],
                            E4M3, chunk=16, use_kernel=False)
    c = mgs_flash_attention(qv[:, :, :], kc[:, :30], vc[:, :30],
                            qk[:, :30], vs[:, :30], bias[:, :30],
                            E4M3, chunk=16, use_kernel=True)
    np.testing.assert_array_equal(np.asarray(b), np.asarray(c))
    assert np.isfinite(np.asarray(a)).all()


def test_flash_close_to_float_oracle(rng):
    """The exact-MGS flash path tracks float attention over the
    dequantized operands to fp8 softmax-weight noise."""
    qv, kc, vc, qk, vs, bias, (ks, q, qt, D) = _flash_case(rng)
    out = np.asarray(mgs_flash_attention(qv, kc, vc, qk, vs, bias, E4M3,
                                         chunk=16, use_kernel=False))
    kd, vd = dequantize_kv(QuantizedKVCache(kc, vc, ks, vs), E4M3)
    qd = np.asarray(qt.q * qt.scale).reshape(q.shape)
    s = np.einsum("ntd,nsd->nts", qd, np.asarray(kd)) * (D ** -0.5) \
        + np.asarray(bias)[:, None, :]
    w = np.exp(s - s.max(-1, keepdims=True))
    w /= w.sum(-1, keepdims=True)
    want = np.einsum("nts,nsd->ntd", w, np.asarray(vd))
    rel = np.abs(out - want).max() / np.abs(want).max()
    assert rel < 0.05


# ---------------------------------------------------------------------------
# model-level decode
# ---------------------------------------------------------------------------


def _packed_cfg(**kw):
    base = dict(_PACKED)
    base.update(kw)
    return dataclasses.replace(reduced_config("deepseek-7b"),
                               quant=QuantConfig(**base))


def test_quantized_cache_decode_logits_bitwise_kernel_vs_emulation(rng):
    """Full-model decode through the packed cache: the Pallas kernel tier
    (interpret mode on CPU) and the pure-jnp emulation tier produce
    bit-identical logits — the flash kernel extends the existing
    kernel-vs-emulation guarantee to the decode attention step.

    f32 compute: with bf16 the *fused-activation* layers differ between
    tiers by design (the kernel applies the activation in f32 before the
    output cast; the emulation tier after it) — orthogonal to the cache
    path under test."""
    cfg0 = dataclasses.replace(_packed_cfg(), compute_dtype="float32")
    params, _ = init_params(cfg0, jax.random.PRNGKey(0))
    toks = jnp.asarray(rng.integers(1, 256, (2, 8)), jnp.int32)
    outs = {}
    for use_kernel in (False, True):
        cfg = dataclasses.replace(
            _packed_cfg(use_kernel=use_kernel,
                        block_m=32, block_n=32, block_k=32),
            compute_dtype="float32")
        cache, _ = init_cache(cfg, 2, 12)
        lg, cache = prefill(params, cfg, {"tokens": toks[:, :6]}, cache)
        lg, cache = decode_step(params, cfg, toks[:, 6:7], cache)
        lg, cache = decode_step(params, cfg, toks[:, 7:8], cache)
        outs[use_kernel] = np.asarray(lg)
        assert cache["k"].dtype == jnp.uint8
    np.testing.assert_array_equal(outs[False], outs[True])


def test_quantized_vs_float_cache_error_bound(rng):
    """Real model forward: packed-cache decode logits stay within fp8
    quantization noise of the float-cache run (same weights)."""
    base = dataclasses.replace(reduced_config("deepseek-7b"),
                               compute_dtype="float32")
    params, _ = init_params(base, jax.random.PRNGKey(0))
    toks = jnp.asarray(rng.integers(1, base.vocab, (2, 12)), jnp.int32)
    outs = {}
    for name, kv in (("float", "float"), ("packed", "packed")):
        cfg = dataclasses.replace(base, quant=QuantConfig(
            dtype="fp8_e4m3", accum="mgs_exact", kv_cache=kv))
        cache, _ = init_cache(cfg, 2, 16)
        lg, cache = prefill(params, cfg, {"tokens": toks[:, :8]}, cache)
        for t in range(8, 12):
            lg, cache = decode_step(params, cfg, toks[:, t:t + 1], cache)
        outs[name] = np.asarray(lg, np.float32)
    rel = (np.abs(outs["packed"] - outs["float"]).max()
           / np.abs(outs["float"]).max())
    assert rel < 0.1


def test_packed_cache_config_validation():
    with pytest.raises(ValueError, match="packed"):
        QuantConfig(dtype="none", kv_cache="packed")
    with pytest.raises(ValueError, match="kv_format"):
        QuantConfig(**dict(_PACKED, kv_format="e5m2"))
    assert QuantConfig(**_PACKED).quantized_kv
    assert QuantConfig(**_PACKED).kv_fmt.name == "e4m3"
    with pytest.raises(ValueError, match="draft_layers"):
        QuantConfig(**dict(_PACKED), draft_layers=0)


# ---------------------------------------------------------------------------
# packed cross-attention (encoder-decoder), ISSUE-8 satellite
# ---------------------------------------------------------------------------


def _whisper_cfg(kv: str):
    return dataclasses.replace(
        reduced_config("whisper-tiny"), compute_dtype="float32",
        quant=QuantConfig(dtype="fp8_e4m3", accum="mgs_exact",
                          kv_cache=kv))


def test_packed_cross_attention_whisper(rng):
    """Whisper decode through packed-FP8 cross planes: the codes are
    written exactly once at prefill (bit-frozen across decode steps),
    equal ``quantize_kv`` of the float-path projected encoder K/V bit
    for bit, and end-to-end decode logits stay within fp8 noise of the
    float-cross run."""
    B = 2
    cfg_f = _whisper_cfg("float")
    cfg_p = _whisper_cfg("packed")
    params, _ = init_params(cfg_p, jax.random.PRNGKey(0))
    toks = jnp.asarray(rng.integers(1, cfg_p.vocab, (B, 8)), jnp.int32)
    audio = jnp.asarray(
        rng.normal(0, 1, (B, cfg_p.encoder_len, cfg_p.d_model))
        .astype(np.float32))
    outs, caches = {}, {}
    for cfg in (cfg_f, cfg_p):
        kv = cfg.quant.kv_cache
        cache, _ = init_cache(cfg, B, 12)
        lg, cache = prefill(params, cfg,
                            {"tokens": toks[:, :6], "audio_embeds": audio},
                            cache)
        snap = {k: np.asarray(cache[k]).copy()
                for k in ("cross_k", "cross_v")}
        for t in (6, 7):
            lg, cache = decode_step(params, cfg, toks[:, t:t + 1], cache)
        for k, v in snap.items():     # write-once: decode never touches
            np.testing.assert_array_equal(np.asarray(cache[k]), v)
        outs[kv] = np.asarray(lg, np.float32)
        caches[kv] = cache
    pc = caches["packed"]
    assert pc["cross_k"].dtype == jnp.uint8
    # packed planes == quantize_kv of the full-precision projected
    # encoder K/V (recomputed here; the float cache stores them rounded
    # to kv_cache_dtype, so it is NOT the bitwise source of truth),
    # zero-padded to the chunk multiple
    from repro.models.linear import proj as _proj
    from repro.models.transformer import _cast_params, _encode
    cast = _cast_params(params, cfg_p)
    enc_out = _encode(cast, cfg_p, audio)
    ck, cv = jax.lax.map(
        lambda pcl: (_proj(enc_out, pcl["attn"]["wk"], cfg_p.quant),
                     _proj(enc_out, pcl["attn"]["wv"], cfg_p.quant)),
        cast["cross"])
    enc = cfg_p.encoder_len
    for plane, scale, fk in (("cross_k", "cross_k_scale", ck),
                             ("cross_v", "cross_v_scale", cv)):
        qc, qs = quantize_kv(fk, cfg_p.quant.kv_fmt)
        np.testing.assert_array_equal(
            np.asarray(jnp.swapaxes(pc[plane], 2, 3)[:, :, :enc]),
            np.asarray(qc))
        np.testing.assert_array_equal(
            np.asarray(jnp.swapaxes(pc[scale], 2, 3)[:, :, :enc]),
            np.asarray(qs))
        # the pad tail beyond encoder_len is never written
        assert not np.asarray(pc[plane])[:, :, :, enc:].any()
    # two quantized caches (self + cross) compound: noise-level bound,
    # plus greedy-decision agreement (the serving observable)
    rel = (np.abs(outs["packed"] - outs["float"]).max()
           / np.abs(outs["float"]).max())
    assert rel < 0.5, rel
    assert (outs["packed"].argmax(-1) == outs["float"].argmax(-1)).all()


def test_packed_cross_decode_bitwise_kernel_vs_emulation(rng):
    """The cross-attention packed path honors the repo-wide tier
    contract at its granularity — the op: one decoder layer's
    cross-attention over the same packed encoder planes is bit-identical
    between the Pallas kernel tier (interpret mode) and the pure-jnp
    emulation tier, exactly like the dense-matmul pins in test_qeinsum
    and the flash-kernel pin above. End-to-end whisper logits are
    pinned to noise-bound + argmax agreement only: the encoder/decoder
    float glue (rms_norm, residual adds) compiles into a different XLA
    program per tier and drifts at the ulp level, which the per-entry
    cache quantization can amplify into a code flip — op-level tier
    equality, not whole-program bit equality, is the contract."""
    from repro.models.attention import attention_apply

    B = 2
    base = _whisper_cfg("packed")
    KV, hd, S = base.n_kv_heads, base.head_dim, base.encoder_len
    params, _ = init_params(base, jax.random.PRNGKey(0))
    x = jnp.asarray(rng.normal(0, 1, (B, 1, base.d_model))
                    .astype(np.float32))
    kf = jnp.asarray(rng.normal(0, 1, (B, S, KV, hd)).astype(np.float32))
    vf = jnp.asarray(rng.normal(0, 1, (B, S, KV, hd)).astype(np.float32))
    kc, ks = quantize_kv(kf, base.quant.kv_fmt)
    vc, vs = quantize_kv(vf, base.quant.kv_fmt)
    ckv = QuantizedKVCache(jnp.swapaxes(kc, 1, 2), jnp.swapaxes(vc, 1, 2),
                           jnp.swapaxes(ks, 1, 2), jnp.swapaxes(vs, 1, 2))
    pl = jax.tree_util.tree_map(lambda a: a[0], params["cross"])
    positions = jnp.full((B, 1), 6, jnp.int32)

    def tier(use_kernel):
        return dataclasses.replace(
            base, quant=dataclasses.replace(
                base.quant, use_kernel=use_kernel,
                block_m=32, block_n=32, block_k=32))

    op = {}
    for use_kernel in (False, True):
        o, _ = attention_apply(pl["attn"], x, tier(use_kernel),
                               positions=positions, causal=False,
                               cross_kv=ckv)
        op[use_kernel] = np.asarray(o)
    np.testing.assert_array_equal(op[False], op[True])

    toks = jnp.asarray(rng.integers(1, base.vocab, (B, 8)), jnp.int32)
    audio = jnp.asarray(
        rng.normal(0, 1, (B, base.encoder_len, base.d_model))
        .astype(np.float32))
    outs = {}
    for use_kernel in (False, True):
        cfg = tier(use_kernel)
        cache, _ = init_cache(cfg, B, 12)
        lg, cache = prefill(params, cfg,
                            {"tokens": toks[:, :6], "audio_embeds": audio},
                            cache)
        for t in (6, 7):
            lg, cache = decode_step(params, cfg, toks[:, t:t + 1], cache)
        outs[use_kernel] = np.asarray(lg)
    rel = (np.abs(outs[False] - outs[True]).max()
           / np.abs(outs[False]).max())
    assert rel < 0.1, rel
    assert (outs[False].argmax(-1) == outs[True].argmax(-1)).all()


# ---------------------------------------------------------------------------
# calibrated static decode-q scale, ISSUE-8 satellite
# ---------------------------------------------------------------------------


def test_static_q_scale_pin_and_fallback(rng):
    """The static (calibrated-amax) decode-q path: for a row whose
    absmax equals the calibrated amax, codes AND scale are bitwise
    identical to the dynamic per-step path (the f32 ``amax /
    max_finite`` division is shared); without a table — or with
    ``static_q_scale`` off — the helper IS the dynamic path; and the
    coarser shared scale keeps absolute error within the format's
    top-binade ulp."""
    from repro.models.attention import _quantize_decode_q
    from repro.quant import CalibrationTable

    q2 = jnp.asarray(rng.normal(0, 3, (4, 32)).astype(np.float32))
    amax = float(np.abs(np.asarray(q2)).max())
    row = int(np.abs(np.asarray(q2)).max(axis=1).argmax())
    dyn_cfg = QuantConfig(**_PACKED, per_row_act=True)
    st_cfg = dataclasses.replace(
        dyn_cfg, static_q_scale=True).with_calibration(
            CalibrationTable({"attn.q.amax": amax}))
    dyn = quantize_fp8(q2, E4M3, axis=1)
    st = _quantize_decode_q(q2, st_cfg)
    assert st.scale.shape == dyn.scale.shape
    # the pin: the amax-achieving row quantizes identically
    np.testing.assert_array_equal(np.asarray(st.scale[row]),
                                  np.asarray(dyn.scale[row]))
    np.testing.assert_array_equal(np.asarray(st.q[row]),
                                  np.asarray(dyn.q[row]))
    # dynamic fallback: flag off, missing table, and degenerate amax
    for qc in (dyn_cfg,
               dataclasses.replace(dyn_cfg, static_q_scale=True),
               dataclasses.replace(
                   dyn_cfg, static_q_scale=True).with_calibration(
                       CalibrationTable({"attn.q.amax": 0.0}))):
        fb = _quantize_decode_q(q2, qc)
        np.testing.assert_array_equal(np.asarray(fb.q),
                                      np.asarray(dyn.q))
        np.testing.assert_array_equal(np.asarray(fb.scale),
                                      np.asarray(dyn.scale))
    # coarser static scale still reconstructs within top-binade ulp
    deq = np.asarray(st.q) * np.asarray(st.scale)
    assert np.abs(deq - np.asarray(q2)).max() <= amax * 0.05


def test_decode_records_q_amax_under_calibration(rng):
    """An eager decode step under ``calibrating()`` observes the decode
    query absmax at the ``attn.q`` site; the table carries it as
    ``attn.q.amax`` through the existing sigma-pairs plumbing, where
    ``act_sigma`` (the static path's lookup) finds it."""
    from repro.quant import CalibrationTable, calibrating

    cfg = dataclasses.replace(_packed_cfg(per_row_act=True),
                              compute_dtype="float32")
    params, _ = init_params(cfg, jax.random.PRNGKey(0))
    toks = jnp.asarray(rng.integers(1, 256, (2, 7)), jnp.int32)
    cache, _ = init_cache(cfg, 2, 10)
    lg, cache = prefill(params, cfg, {"tokens": toks[:, :6]}, cache)
    with calibrating() as rec:
        decode_step(params, cfg, toks[:, 6:7], cache)
    table = rec.table()
    amax = table.sigma("attn.q.amax")
    assert amax is not None and amax > 0.0
    qc = cfg.quant.with_calibration(table)
    assert qc.act_sigma("attn.q.amax") == pytest.approx(amax)
    # round-trips through the pairs encoding
    assert CalibrationTable.from_pairs(qc.calibration).sigma(
        "attn.q.amax") == pytest.approx(amax)


# ---------------------------------------------------------------------------
# cross-mesh bit-identity (subprocess with forced host devices)
# ---------------------------------------------------------------------------


def _run(code: str, devices: int = 8, timeout: int = 560):
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = _SRC + os.pathsep + env.get("PYTHONPATH", "")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, timeout=timeout,
                         env=env)
    assert out.returncode == 0, out.stderr[-4000:]
    return out.stdout


@pytest.mark.slow
def test_packed_cache_sharded_bit_identity():
    """ISSUE-5 acceptance: quantized-cache ServeEngine logits (and greedy
    tokens) are bit-identical across a 1-device and a forced-8-device
    mesh — the ``test_sharded_serving`` guarantee with the packed cache
    and the MGS flash-decode step in the loop."""
    out = _run("""
    import dataclasses, json
    import jax, jax.numpy as jnp, numpy as np
    from repro.configs import reduced_config
    from repro.launch.mesh import make_mesh, make_serve_mesh
    from repro.launch.serve import Request, ServeEngine
    from repro.models import init_cache
    from repro.models import init_params
    from repro.parallel.sharding import use_rules
    from repro.quant import QuantConfig

    cfg = dataclasses.replace(reduced_config("deepseek-7b"),
        quant=QuantConfig(dtype="fp8_e4m3", accum="mgs_exact",
                          use_kernel=True, kv_cache="packed",
                          block_m=32, block_n=32, block_k=32))
    params, dims = init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    prompt = rng.integers(1, cfg.vocab, 6).astype(np.int32)

    def engine_run(mesh):
        e = ServeEngine(cfg, mesh, batch=2, max_len=12, params=params,
                        dims=dims)
        reqs = [Request(rid=0, prompt=prompt, max_new_tokens=4)]
        e.run(reqs)
        cache, _ = init_cache(cfg, 2, 12)
        toks = jnp.asarray(np.stack([prompt, prompt]))
        with use_rules(e.rules):
            lg, cache = e._prefill(e.params, {"tokens": toks}, cache)
            cur = jnp.argmax(lg, -1)[:, None].astype(jnp.int32)
            lg2, cache = e._decode(e.params, cur, cache)
        return np.asarray(lg), np.asarray(lg2), reqs[0].out_tokens

    lg1, dg1, t1 = engine_run(make_mesh((1, 1), ("data", "model")))
    lg8, dg8, t8 = engine_run(make_serve_mesh())
    print(json.dumps({
        "ndev": jax.device_count(),
        "prefill_bitwise": bool((lg1 == lg8).all()),
        "decode_bitwise": bool((dg1 == dg8).all()),
        "tokens_equal": t1 == t8}))
    """)
    res = json.loads(out.strip().splitlines()[-1])
    assert res["ndev"] == 8
    assert res["prefill_bitwise"]
    assert res["decode_bitwise"]
    assert res["tokens_equal"]


# ---------------------------------------------------------------------------
# native multi-device test (the forced-8-device CI shard)
# ---------------------------------------------------------------------------


def _native_device_count():
    return jax.device_count()


@pytest.mark.multidevice
@pytest.mark.skipif(_native_device_count() < 8,
                    reason="needs XLA_FLAGS forced >= 8 host devices "
                           "(scripts/ci.sh multi-device shard)")
def test_native_packed_cache_bit_identity():
    from repro.launch.mesh import make_mesh, make_serve_mesh
    from repro.launch.serve import Request, ServeEngine

    cfg = _packed_cfg(use_kernel=True, block_m=32, block_n=32, block_k=32)
    params, dims = init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    prompt = rng.integers(1, cfg.vocab, 6).astype(np.int32)

    def tokens_on(mesh):
        e = ServeEngine(cfg, mesh, batch=2, max_len=12, params=params,
                        dims=dims)
        reqs = [Request(rid=0, prompt=prompt.copy(), max_new_tokens=4)]
        e.run(reqs)
        return reqs[0].out_tokens

    t1 = tokens_on(make_mesh((1, 1), ("data", "model")))
    t8 = tokens_on(make_serve_mesh())
    assert t1 == t8
