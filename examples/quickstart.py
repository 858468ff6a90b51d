"""Quickstart: the MGS pipeline end to end on one dot product / matmul.

    PYTHONPATH=src python examples/quickstart.py

1. Quantize two vectors to FP8 E4M3.
2. Accumulate their dot product four ways: FP32 baseline, sequential
   narrow accumulator (swamping — the failure the paper fixes), MGS
   dMAC emulation (bit-faithful Fig. 8), MGS exact (the TPU limb kernel).
3. Size the narrow accumulator with the Markov model (§4) and estimate
   dMAC energy savings (§6.4) from the measured overflow statistics.
"""

import numpy as np
import jax.numpy as jnp

from repro.core import energy, formats, markov, mgs, summation
from repro.kernels import ops
from repro.quant import QuantConfig, qmatmul


def main():
    rng = np.random.default_rng(0)
    K = 2048
    x = rng.normal(0, 1, K).astype(np.float32)
    w = rng.normal(0, 1, K).astype(np.float32)

    f = formats.E4M3
    xq = np.asarray(formats.round_to_format(x, f))
    wq = np.asarray(formats.round_to_format(w, f))
    true = float(np.sum(xq.astype(np.float64) * wq.astype(np.float64)))

    print(f"== FP8 dot product, K={K} ==")
    print(f"exact (float64 oracle):            {true:+.6f}")

    p = np.asarray(mgs.round_product(jnp.asarray(xq * wq), f)[0])
    seq = float(summation.sequential_sum(jnp.asarray(p),
                                         summation.acc_format(4)))
    print(f"sequential, 4-bit-mantissa acc:    {seq:+.6f}   "
          f"(err {abs(seq - true):.4f} — swamping, Fig. 2/3)")

    v_dmac, stats = mgs.mgs_dot_dmac(jnp.asarray(xq), jnp.asarray(wq), f, 5)
    print(f"MGS dMAC (16x5-bit bins + wide):   {float(v_dmac):+.6f}   "
          f"(err {abs(float(v_dmac) - true):.4f})")
    print(f"   overflows {int(stats.wide_flushes)} / "
          f"{int(stats.narrow_adds)} narrow adds "
          f"({float(stats.overflow_rate):.1%}), "
          f"{int(stats.skipped)} subnormal-gated")

    v_exact = float(mgs.mgs_dot_exact(jnp.asarray(xq), jnp.asarray(wq), f,
                                      "exact"))
    print(f"MGS exact (limb kernel numerics):  {v_exact:+.6f}   "
          f"(err {abs(v_exact - true):.2e})")

    print("\n== Markov accumulator sizing (paper §4) ==")
    pw = markov.gaussian_quantized_pmf(5)
    px = markov.gaussian_quantized_pmf(7, half=True)
    pp = markov.product_pmf(pw, px)
    for bits in (8, 10, 12):
        e = markov.expected_sums_before_overflow(pp, bits)
        print(f"  {bits:2d}-bit narrow accumulator: "
              f"E[sums before overflow] = {e:8.1f}")
    print(f"  kernel flush period (CLT, eps=1e-4, 10-bit): "
          f"{markov.plan_chunk_length_clt(10, pp.std, 1e-4)}")

    print("\n== dMAC energy (paper §6.4, calibrated model) ==")
    m = energy.FP8_MODEL
    s = m.savings(int(stats.narrow_adds),
                  int(stats.wide_flushes) + int(stats.final_flushes),
                  int(stats.skipped), skipping=True)
    print(f"  estimated savings vs conventional FP8 MAC: {s:.1%} "
          f"(paper: 34.1% w/ skipping)")

    print("\n== Quantized matmul through the framework path ==")
    X = rng.normal(0, 1, (8, 256)).astype(np.float32)
    W = rng.normal(0, 0.05, (256, 32)).astype(np.float32)
    ref = X @ W
    for q in (QuantConfig(dtype="fp8_e4m3", accum="wide"),
              QuantConfig(dtype="fp8_e4m3", accum="mgs_exact",
                          use_kernel=True),
              QuantConfig(dtype="fp8_e4m3", accum="mgs_dmac")):
        out = np.asarray(qmatmul(jnp.asarray(X), jnp.asarray(W), q))
        rel = np.abs(out - ref).max() / np.abs(ref).max()
        print(f"  {q.dtype}/{q.accum:10s} rel err vs fp32: {rel:.4f}")


if __name__ == "__main__":
    main()
