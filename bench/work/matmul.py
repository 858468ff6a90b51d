"""Work and bytes of one fused MGS matmul call, ``(M, K) @ (K, N)``.

Work counts the contraction, not its implementation: ``2 M N K``
operations over the real rows ``M`` (padding rows and the kernel's nine
limb dots are not counted), so a roofline share reads the same whichever
kernel computes the product. Bytes are what the call must move at the
least: the 1-byte FP8 operand codes, the f32 dequantization scales (one
per activation row and one per weight tensor) and the f32 output.
"""

from __future__ import annotations


def ops(m: int, k: int, n: int) -> float:
    return 2.0 * m * n * k


def hbm_bytes(m: int, k: int, n: int) -> float:
    codes = m * k + k * n           # FP8 codes, 1 byte each
    scales = 4 * (m + 1)            # per-row activation + per-tensor weight
    out = 4 * m * n                 # f32 output
    return float(codes + scales + out)


def least_s(m: int, k: int, n: int, peak: dict) -> float:
    """The least time the chip could take for the call."""
    return max(ops(m, k, n) / peak["int8_ops_per_s"],
               hbm_bytes(m, k, n) / peak["hbm_bytes_per_s"])
