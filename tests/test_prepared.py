"""Streaming fused kernel + PreparedWeight cache semantics.

Fused-kernel bit-identity against the jnp oracle (interpret mode),
prepared weights matching per-call quantization exactly, cache-hit
accounting, and scan-sliced stacked preparation (the transformer
layer-stack layout).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import formats
from repro.kernels import ops, ref
from repro.kernels.mgs_matmul import (limb_decompose,
                                      mgs_matmul_exact_fused_pallas)
from repro.quant import (PREP_STATS, QuantConfig, prepare_params,
                         prepare_weight, qmatmul)

_F = formats.E4M3
_CFG = QuantConfig(dtype="fp8_e4m3", accum="mgs_exact", use_kernel=True,
                   block_m=32, block_n=32, block_k=32)


def _fp8(rng, shape, scale=1.0, fmt=_F):
    x = rng.normal(0, scale, shape).astype(np.float32)
    return np.asarray(formats.round_to_format(x, fmt))


SHAPES = [
    (8, 16, 8),       # tiny, single block
    (32, 64, 32),     # one block exactly
    (48, 300, 56),    # ragged: padding on every dim
    (128, 257, 64),   # K just over two blocks
    (1, 128, 1),      # degenerate M/N
]


# ---------------------------------------------------------------------------
# fused kernel numerics
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("mkn", SHAPES)
def test_fused_kernel_bit_identical_to_ref(rng, mkn):
    M, K, N = mkn
    x = jnp.asarray(_fp8(rng, (M, K)))
    w = jnp.asarray(_fp8(rng, (K, N)))
    xc = formats.encode_bits(x, _F)
    wc = formats.encode_bits(w, _F)
    got = mgs_matmul_exact_fused_pallas(xc, wc, _F, block_m=32, block_n=32,
                                        block_k=64, interpret=True)
    want = ref.mgs_matmul_ref(x, w, _F, "exact")
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_fused_epilogue(rng):
    """activation(out * scale + bias), computed in-kernel.

    XLA contracts the scale-multiply + bias-add into an FMA, so parity
    with the two-rounding host expression is ~1 ulp, not bitwise.
    """
    M, K, N = 16, 96, 24
    x = jnp.asarray(_fp8(rng, (M, K)))
    w = jnp.asarray(_fp8(rng, (K, N)))
    xc, wc = formats.encode_bits(x, _F), formats.encode_bits(w, _F)
    scale = jnp.asarray(rng.uniform(0.5, 2.0, (1, N)).astype(np.float32))
    bias = jnp.asarray(rng.normal(0, 1, (N,)).astype(np.float32))
    base = np.asarray(ref.mgs_matmul_ref(x, w, _F, "exact"))
    for act in ("none", "relu", "gelu", "silu"):
        got = mgs_matmul_exact_fused_pallas(
            xc, wc, _F, scale=scale, bias=bias, activation=act,
            block_m=32, block_n=32, block_k=32, interpret=True)
        want = ops.apply_epilogue(
            jnp.asarray(base) * scale, None, bias, act)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)


def test_fused_rejects_unknown_activation(rng):
    x = jnp.asarray(_fp8(rng, (8, 32)))
    xc = formats.encode_bits(x, _F)
    with pytest.raises(ValueError, match="activation"):
        mgs_matmul_exact_fused_pallas(xc, xc.T, _F, activation="tanh",
                                      interpret=True)


# ---------------------------------------------------------------------------
# PreparedWeight semantics
# ---------------------------------------------------------------------------


SCALES = {"per-tensor": {}, "per-channel": dict(per_channel=True),
          "per-row": dict(per_row_act=True)}


@pytest.mark.parametrize("use_kernel", [True, False],
                         ids=["kernel", "emulation"])
@pytest.mark.parametrize("scales", list(SCALES))
def test_prepared_matches_per_call_quantization(rng, scales, use_kernel):
    """Prepared planes give the bits of quantizing the raw weight per
    call, on the kernel and the emulation path, under every scale
    layout. Per-row activation scales are the served config: there the
    epilogue leaves the kernel for a (rows, N) scale."""
    cfg = dataclasses.replace(_CFG, use_kernel=use_kernel, **SCALES[scales])
    x = jnp.asarray(rng.normal(0, 1, (4, 8, 96)).astype(np.float32))
    w = jnp.asarray(rng.normal(0, 0.1, (96, 16)).astype(np.float32))
    pw = prepare_weight(w, cfg)
    np.testing.assert_array_equal(np.asarray(qmatmul(x, w, cfg)),
                                  np.asarray(qmatmul(x, pw, cfg)))


def test_prepare_cache_hit_semantics(rng):
    w = jnp.asarray(rng.normal(0, 0.1, (64, 8)).astype(np.float32))
    n0, h0 = PREP_STATS["prepared"], PREP_STATS["cache_hits"]
    pw1 = prepare_weight(w, _CFG)
    assert PREP_STATS["prepared"] == n0 + 1
    pw2 = prepare_weight(w, _CFG)
    assert pw2 is pw1                       # same object, no rebuild
    assert PREP_STATS["prepared"] == n0 + 1
    assert PREP_STATS["cache_hits"] == h0 + 1
    # a different config is a different cache entry
    pw3 = prepare_weight(w, dataclasses.replace(_CFG, per_channel=True))
    assert pw3 is not pw1
    assert PREP_STATS["prepared"] == n0 + 2
    # a different (equal-valued) array is a different entry too: identity,
    # not value, keys the cache
    w2 = jnp.array(np.asarray(w))
    prepare_weight(w2, _CFG)
    assert PREP_STATS["prepared"] == n0 + 3


def test_prepared_values_roundtrip(rng):
    w = jnp.asarray(rng.normal(0, 0.1, (64, 8)).astype(np.float32))
    pw = prepare_weight(w, _CFG)
    from repro.quant import quantize_fp8
    qt = quantize_fp8(w, _F, margin=1.0)
    np.testing.assert_array_equal(np.asarray(pw.values()), np.asarray(qt.q))
    np.testing.assert_allclose(np.asarray(pw.scale), np.asarray(qt.scale))
    np.testing.assert_array_equal(np.asarray(pw.codes),
                                  np.asarray(formats.encode_bits(qt.q, _F)))
    # the planner's weight sigma is the std of the same fixed-point limbs
    want = float(jnp.std(limb_decompose(qt.q, _F).astype(jnp.float32)))
    assert pw.limb_sigma == pytest.approx(want, rel=1e-6)


def test_prepared_stacked_scan_slices(rng):
    """Stacked (L, K, *tail) preparation == per-layer preparation."""
    x = jnp.asarray(rng.normal(0, 1, (4, 96)).astype(np.float32))
    ws = jnp.asarray(rng.normal(0, 0.1, (3, 96, 4, 4)).astype(np.float32))
    pws = prepare_weight(ws, _CFG, stacked=True)
    assert pws.codes.shape == (3, 96, 16)
    assert pws.scale.shape == (3,)
    assert pws.tail == (4, 4)

    def body(c, pw_slice):
        return c, qmatmul(x, pw_slice, _CFG)

    _, outs = jax.lax.scan(body, 0, pws)
    for i in range(3):
        want = np.asarray(qmatmul(x, ws[i].reshape(96, 16), _CFG))
        np.testing.assert_array_equal(np.asarray(outs)[i], want)


def test_prepare_params_converts_only_proj_weights(rng):
    from repro.quant import PreparedWeight
    params = {
        "embed": jnp.zeros((32, 16)),
        "layers": {
            "attn": {"wq": jnp.asarray(
                rng.normal(0, 0.1, (2, 16, 4, 4)).astype(np.float32)),
                "wo": jnp.zeros((2, 4, 4, 16))},
            "ffn": {"wg": jnp.asarray(
                rng.normal(0, 0.1, (2, 16, 32)).astype(np.float32))},
            "ln1": jnp.ones((2, 16)),
        },
    }
    out = prepare_params(params, _CFG)
    assert isinstance(out["layers"]["attn"]["wq"], PreparedWeight)
    assert isinstance(out["layers"]["ffn"]["wg"], PreparedWeight)
    assert out["layers"]["attn"]["wq"].codes.shape == (2, 16, 16)
    # the out-projection is qeinsum-consumed with (heads, head_dim)
    # flattened into the kernel's K (k_ndim=2)
    assert isinstance(out["layers"]["attn"]["wo"], PreparedWeight)
    assert out["layers"]["attn"]["wo"].codes.shape == (2, 16, 16)
    assert out["layers"]["attn"]["wo"].tail == (16,)
    # embedding tables (shared with the lookup path) / norms stay raw
    assert not isinstance(out["embed"], PreparedWeight)
    assert not isinstance(out["layers"]["ln1"], PreparedWeight)
    # idempotent: preparing a prepared tree builds nothing new
    n0 = PREP_STATS["prepared"]
    out2 = prepare_params(out, _CFG)
    assert PREP_STATS["prepared"] == n0
    assert out2["layers"]["attn"]["wq"] is out["layers"]["attn"]["wq"]
    # non-mgs configs pass through untouched
    assert prepare_params(params, QuantConfig()) is params


def test_fused_config_prepare_drops_limb_planes(rng):
    """A PreparedWeight holds the packed codes and the scale, nothing
    else: the kernel decodes limbs per tile, the emulation path decodes
    values from the codes, so one build serves both."""
    x = jnp.asarray(rng.normal(0, 1, (8, 96)).astype(np.float32))
    w = jnp.asarray(rng.normal(0, 0.1, (96, 16)).astype(np.float32))
    pw = prepare_weight(w, _CFG)
    leaves = jax.tree_util.tree_leaves(pw)
    assert [leaf.dtype for leaf in leaves] == [jnp.uint8, jnp.float32]
    assert leaves[0] is pw.codes and leaves[1] is pw.scale
    assert pw.limb_sigma is not None and pw.limb_sigma > 0
    # the emulation-path config is served by the very same build
    cfg_emu = dataclasses.replace(_CFG, use_kernel=False)
    assert prepare_weight(w, cfg_emu) is pw
    for cfg in (_CFG, cfg_emu):
        np.testing.assert_array_equal(np.asarray(qmatmul(x, pw, cfg)),
                                      np.asarray(qmatmul(x, w, cfg)))


def test_prepare_cache_does_not_pin_source_weight():
    """The cache holds the source array weakly: dropping the raw weight
    after preparation releases it (the prepared planes replace it)."""
    import gc
    import weakref
    w = jnp.ones((32, 8), jnp.float32) * 0.25
    pw = prepare_weight(w, _CFG)
    ref = weakref.ref(w)
    del w
    gc.collect()
    assert ref() is None          # raw weight released
    assert pw.codes is not None   # prepared planes remain valid


def test_prepared_rejects_wrong_config(rng):
    w = jnp.asarray(rng.normal(0, 0.1, (64, 8)).astype(np.float32))
    pw = prepare_weight(w, _CFG)
    x = jnp.asarray(rng.normal(0, 1, (4, 64)).astype(np.float32))
    with pytest.raises(ValueError, match="fp8"):
        qmatmul(x, pw, QuantConfig(dtype="int8", accum="wide"))
    with pytest.raises(ValueError, match="fp8"):
        prepare_weight(w, QuantConfig(dtype="int8", accum="wide"))


def test_markov_flush_target_keeps_exactness(rng):
    """Markov-planned (longer) flush periods must not change results on
    layer-sized problems (class sums stay in f32-exact range)."""
    cfg = dataclasses.replace(_CFG, flush_target=1e-6)
    x = jnp.asarray(rng.normal(0, 1, (8, 256)).astype(np.float32))
    w = jnp.asarray(rng.normal(0, 0.1, (256, 16)).astype(np.float32))
    np.testing.assert_array_equal(
        np.asarray(qmatmul(x, w, cfg)),
        np.asarray(qmatmul(x, w, dataclasses.replace(cfg,
                                                     flush_target=None))))


# ---------------------------------------------------------------------------
# unembedding-view cache (ISSUE-4 satellite)
# ---------------------------------------------------------------------------


def test_prepare_unembed_bitwise_matches_per_call_path(rng):
    """The cached unembedding view == quantizing the raw tied table per
    call, bit for bit (same storage-dtype quantization, transposed)."""
    from repro.quant import prepare_unembed, qeinsum
    embed = jnp.asarray(rng.normal(0, 0.1, (48, 16)).astype(np.float32))
    x = jnp.asarray(rng.normal(0, 1, (2, 3, 16)).astype(np.float32))
    pw = prepare_unembed(embed, _CFG)
    assert pw.codes.shape == (16, 48)       # (d_model, vocab) planes
    got = qeinsum("btd,dv->btv", x, pw, _CFG, site="logits")
    want = qeinsum("btd,vd->btv", x, embed, _CFG, site="logits")
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_prepare_unembed_cached_on_table_identity(rng):
    from repro.quant import prepare_unembed
    embed = jnp.asarray(rng.normal(0, 0.1, (32, 8)).astype(np.float32))
    n0, h0 = PREP_STATS["prepared"], PREP_STATS["cache_hits"]
    pw = prepare_unembed(embed, _CFG)
    assert PREP_STATS["prepared"] == n0 + 1
    assert prepare_unembed(embed, _CFG) is pw   # keyed on the raw table
    assert PREP_STATS["prepared"] == n0 + 1
    assert PREP_STATS["cache_hits"] == h0 + 1
    with pytest.raises(ValueError, match="fp8"):
        prepare_unembed(embed, QuantConfig(dtype="int8", accum="wide"))
    with pytest.raises(ValueError, match="2D"):
        prepare_unembed(jnp.zeros((4, 4, 4)), _CFG)


def test_prepare_logits_head_tied_and_untied(rng):
    """Tied trees gain an ``unembed_prepared`` view; untied trees get
    their raw ``unembed`` replaced; both are idempotent."""
    from repro.quant import PreparedWeight, prepare_logits_head
    embed = jnp.asarray(rng.normal(0, 0.1, (32, 8)).astype(np.float32))
    tied = prepare_logits_head({"embed": embed}, _CFG, tied=True)
    assert isinstance(tied["unembed_prepared"], PreparedWeight)
    assert tied["unembed_prepared"].codes.shape == (8, 32)
    n0 = PREP_STATS["prepared"]
    again = prepare_logits_head(tied, _CFG, tied=True)
    assert again["unembed_prepared"] is tied["unembed_prepared"]
    assert PREP_STATS["prepared"] == n0

    unembed = jnp.asarray(rng.normal(0, 0.1, (8, 32)).astype(np.float32))
    untied = prepare_logits_head({"unembed": unembed}, _CFG, tied=False)
    assert isinstance(untied["unembed"], PreparedWeight)
    assert prepare_logits_head(untied, _CFG,
                               tied=False)["unembed"] is untied["unembed"]
    # non-MGS configs pass straight through
    plain = {"embed": embed}
    assert prepare_logits_head(plain, QuantConfig(), tied=True) is plain
