"""Pallas kernel validation: shape/dtype sweeps against the pure-jnp
oracles, executed in interpret mode (kernel bodies run on CPU)."""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import formats
from repro.kernels import ops, ref
from repro.kernels.mgs_matmul import (limb_decompose,
                                      mgs_matmul_exact_fused_pallas,
                                      worst_case_flush_period)


def _fp8(rng, shape, scale=1.0, fmt=formats.E4M3):
    x = rng.normal(0, scale, shape).astype(np.float32)
    return np.asarray(formats.round_to_format(x, fmt))


SHAPES = [
    (8, 16, 8),       # tiny, single block
    (32, 64, 32),     # one block exactly
    (48, 300, 56),    # ragged: padding on every dim
    (128, 257, 64),   # K just over two blocks
    (1, 128, 1),      # degenerate M/N
]


@pytest.mark.parametrize("mkn", SHAPES)
def test_exact_kernel_vs_ref(rng, mkn):
    M, K, N = mkn
    x = jnp.asarray(_fp8(rng, (M, K)))
    w = jnp.asarray(_fp8(rng, (K, N)))
    got = ops.mgs_matmul(x, w, formats.E4M3, "exact",
                         block_m=32, block_n=32, block_k=64)
    want = ref.mgs_matmul_ref(x, w, formats.E4M3, "exact")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=0, atol=0)


@pytest.mark.parametrize("mkn", SHAPES[:4])
def test_dmac_kernel_vs_ref(rng, mkn):
    M, K, N = mkn
    x = jnp.asarray(_fp8(rng, (M, K), scale=0.2))
    w = jnp.asarray(_fp8(rng, (K, N), scale=0.2))
    got = ops.mgs_matmul(x, w, formats.E4M3, "dmac",
                         block_m=32, block_n=32, block_k=64)
    want = ref.mgs_matmul_ref(x, w, formats.E4M3, "dmac")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=0, atol=0)


def test_exact_kernel_vs_float64_oracle(rng):
    M, K, N = 16, 512, 16
    x = _fp8(rng, (M, K))
    w = _fp8(rng, (K, N))
    got = np.asarray(ops.mgs_matmul(jnp.asarray(x), jnp.asarray(w),
                                    formats.E4M3, "exact",
                                    block_m=16, block_n=16, block_k=128))
    want = (x.astype(np.float64) @ w.astype(np.float64))
    np.testing.assert_allclose(got, want, rtol=1e-6)


def test_kernel_batched_lhs(rng):
    x = jnp.asarray(_fp8(rng, (2, 5, 96)))
    w = jnp.asarray(_fp8(rng, (96, 24)))
    got = ops.mgs_matmul(x, w, formats.E4M3, "exact",
                         block_m=32, block_n=32, block_k=32)
    assert got.shape == (2, 5, 24)
    want = ref.mgs_matmul_ref(x.reshape(10, 96), w, formats.E4M3, "exact")
    np.testing.assert_allclose(np.asarray(got).reshape(10, 24),
                               np.asarray(want))


@pytest.mark.parametrize("flush_period", [1, 2, 3])
def test_flush_period_forces_multiple_flushes(rng, flush_period):
    """Exactness must survive mid-K flushes (narrow->wide spills), every
    K-step, every other one, and at a period that does not divide the
    grid's 8 K-steps."""
    M, K, N = 8, 512, 8
    x = jnp.asarray(_fp8(rng, (M, K)))
    w = jnp.asarray(_fp8(rng, (K, N)))
    got = mgs_matmul_exact_fused_pallas(
        formats.encode_bits(x, formats.E4M3),
        formats.encode_bits(w, formats.E4M3), formats.E4M3, block_m=8,
        block_n=8, block_k=64, flush_period=flush_period, interpret=True)
    want = ref.mgs_matmul_ref(x, w, formats.E4M3, "exact")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6)


def test_limb_decompose_reconstructs(rng):
    v = jnp.asarray(_fp8(rng, (64,)))
    limbs = limb_decompose(v, formats.E4M3)
    assert limbs.dtype == jnp.int8
    ix = sum(limbs[i].astype(np.int64) * (128 ** i) for i in range(3))
    want = np.asarray(v, np.float64) * 2.0 ** 10
    np.testing.assert_array_equal(np.asarray(ix, np.float64), want)


def test_worst_case_flush_period():
    assert worst_case_flush_period(128) == (2**31 - 1) // (128 * 3 * 4096)
    assert worst_case_flush_period(2**18) >= 1


def test_e5m2_rejected_in_exact_mode(rng):
    """E5M2's 33-bit fixed-point form exceeds the int32 limb scheme —
    exact mode is E4M3-only, mirroring the paper's Fig. 8 hardware."""
    x = jnp.asarray(_fp8(rng, (8, 32), fmt=formats.E5M2))
    w = jnp.asarray(_fp8(rng, (32, 8), fmt=formats.E5M2))
    with pytest.raises(ValueError, match="dmac mode"):
        ops.mgs_matmul(x, w, formats.E5M2, "exact")


def test_dmac_honors_block_shapes_within_budget(rng):
    """Caller block shapes within the VMEM budget are not clobbered."""
    import warnings
    x = jnp.asarray(_fp8(rng, (40, 64), scale=0.2))
    w = jnp.asarray(_fp8(rng, (64, 40), scale=0.2))
    with warnings.catch_warnings():
        warnings.simplefilter("error")  # any clamp warning -> failure
        got = ops.mgs_matmul(x, w, formats.E4M3, "dmac",
                             block_m=40, block_n=40, block_k=64)
    want = ref.mgs_matmul_ref(x, w, formats.E4M3, "dmac")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=0, atol=0)


def test_dmac_warns_and_clamps_oversized_blocks(rng):
    """Blocks implying an over-budget VMEM product tile warn (never a
    silent clobber) and are halved until they fit."""
    x = jnp.asarray(_fp8(rng, (16, 64), scale=0.2))
    w = jnp.asarray(_fp8(rng, (64, 16), scale=0.2))
    with pytest.warns(UserWarning, match="VMEM"):
        got = ops.mgs_matmul(x, w, formats.E4M3, "dmac",
                             block_m=256, block_n=256, block_k=256)
    want = ref.mgs_matmul_ref(x, w, formats.E4M3, "dmac")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=0, atol=0)


def test_e5m2_dmac_kernel(rng):
    M, K, N = 16, 128, 16
    x = jnp.asarray(_fp8(rng, (M, K), scale=0.05, fmt=formats.E5M2))
    w = jnp.asarray(_fp8(rng, (K, N), scale=0.05, fmt=formats.E5M2))
    got = np.asarray(ops.mgs_matmul(x, w, formats.E5M2, "dmac",
                                    block_m=16, block_n=16, block_k=64))
    want = np.asarray(ref.mgs_matmul_ref(x, w, formats.E5M2, "dmac"))
    # E5M2 spans 32 bins; the final f32 shift+combine differs from the
    # reference only in summation order (+-1 ulp)
    np.testing.assert_allclose(got, want, rtol=1e-5)
