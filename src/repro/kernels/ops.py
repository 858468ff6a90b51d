"""Jitted public wrappers around the MGS Pallas kernels.

``mgs_matmul`` dispatches to the Pallas kernels (TPU; tests run them in
interpret mode on CPU) or to the pure-jnp reference. Batched LHS
(..., K) is flattened to (M, K).

Exact mode has one kernel, ``mgs_matmul.mgs_matmul_exact_fused_pallas``:
operands are streamed as packed FP8 codes (1 byte/elem), decoded +
limb-split per tile in VMEM, with the dequant-scale / bias / activation
epilogue fused into the kernel's final grid step. It takes its tiles
from the call's shape (``mgs_matmul.fused_tiles``), not from
``block_m/n``; ``block_k`` stays its K tile only under a runtime flush
period, whose steps it counts. ``block_m/n/k`` are the dmac kernel's
tiles.

``scale``, ``bias`` and ``activation`` form the exact-mode epilogue
``activation(out * scale + bias)``; on the reference path it is applied
as a follow-up XLA elementwise pass so both exact paths share a single
calling convention.
"""

from __future__ import annotations

import warnings

import jax
import jax.numpy as jnp

from repro.core.formats import E4M3, FPFormat, encode_bits
from repro.parallel.sharding import replicated_kernel_call
from . import ref as _ref
from .mgs_matmul import (ACTIVATIONS, default_interpret, fused_tiles,
                         mgs_matmul_dmac_pallas,
                         mgs_matmul_exact_fused_pallas)

__all__ = ["mgs_matmul", "apply_epilogue"]

# The dmac kernel materializes a (block_m, block_k, block_n) f32 product
# tile in VMEM; tiles beyond this budget cannot fit alongside the bin
# accumulators on real TPUs (~16 MB VMEM/core).
_DMAC_TILE_BUDGET_BYTES = 2 << 20


def _dmac_block_shapes(block_m: int, block_n: int, block_k: int):
    """Validate dmac block shapes against the VMEM product-tile budget.

    Shapes within budget are honored as-is (the caller's QuantConfig is
    authoritative). Oversized shapes are halved along m/n until they fit —
    with a warning, never silently (this used to clobber any block_m > 32
    down to 32 even when the requested tile fit comfortably).
    """
    bm, bn = block_m, block_n
    while bm * block_k * bn * 4 > _DMAC_TILE_BUDGET_BYTES and (
            bm > 8 or bn > 8):
        if bm >= bn and bm > 8:
            bm //= 2
        else:
            bn //= 2
    if (bm, bn) != (block_m, block_n):
        warnings.warn(
            f"dmac mode: block_m={block_m}, block_n={block_n}, "
            f"block_k={block_k} implies a "
            f"{block_m * block_k * block_n * 4 / 2**20:.0f} MB f32 product "
            f"tile (> {_DMAC_TILE_BUDGET_BYTES / 2**20:.0f} MB VMEM "
            f"budget); clamping to block_m={bm}, block_n={bn}. Set smaller "
            "QuantConfig block shapes to silence this.",
            stacklevel=3)
    return bm, bn


def apply_epilogue(out, scale, bias, activation: str):
    if scale is not None:
        out = out * scale
    if bias is not None:
        out = out + bias
    return ACTIVATIONS[activation](out)


def mgs_matmul(x, w, fmt: FPFormat = E4M3, mode: str = "exact", *,
               use_kernel: bool = True, gate_subnormal: bool = True,
               block_m: int = 128, block_n: int = 128, block_k: int = 128,
               flush_period: int | None = None, scale=None, bias=None,
               activation: str = "none", interpret: bool | None = None):
    """MGS quantized matmul: (..., K) @ (K, N) with MGS numerics.

    ``x`` must hold format-exact FP8 values (see quant.quantize_fp8).
    ``w`` is either a (K, N) array of format-exact values or a
    ``quant.prepared.PreparedWeight`` (duck-typed: anything with
    ``codes`` / ``values()``), whose cached planes feed the kernels
    without per-call re-quantization.

    ``scale``/``bias``/``activation`` (exact mode only) apply
    ``activation(out * scale + bias)`` — inside the kernel when
    ``use_kernel``, as a follow-up elementwise pass otherwise.
    """
    if interpret is None:
        interpret = default_interpret()
    prepared = hasattr(w, "codes")
    ix_bits = fmt.mbits + 1 + fmt.emax  # fixed-point width of sm << e
    if mode == "exact" and ix_bits > 21:
        # The 3x7-bit limb scheme needs ix = sm << e to fit ~20 bits;
        # wide-exponent formats (E5M2: 33-bit ix) cannot use it — mirror
        # the paper's hardware, which is E4M3-only (Fig. 8).
        raise ValueError(
            f"exact mode supports narrow-exponent formats only (E4M3/"
            f"E3M4); {fmt.name} (ix={ix_bits}b) needs dmac mode")
    if mode != "exact" and (scale is not None or bias is not None
                            or activation != "none"):
        raise ValueError("epilogue (scale/bias/activation) is exact-mode "
                         "only; rescale dmac outputs in the caller")
    if isinstance(flush_period, int):
        # host-planned periods can exceed int32 (near-uniform sigmas);
        # the kernel clips to its K grid anyway, so clamp before the
        # period crosses the jit boundary as an int32 operand
        flush_period = min(flush_period, 2**31 - 1)
    lead = x.shape[:-1]
    K = x.shape[-1]
    x2 = x.reshape((-1, K))
    n_out = w.codes.shape[-1] if prepared else w.shape[-1]
    if not use_kernel:
        w_vals = w.values() if prepared else w
        out = _ref.mgs_matmul_ref(x2, w_vals, fmt, mode, gate_subnormal)
        out = apply_epilogue(out, scale, bias, activation)
    elif mode == "exact":
        xc = x2 if x2.dtype == jnp.uint8 else encode_bits(x2, fmt)
        wc = w.codes if prepared else encode_bits(w, fmt)
        # tiles from the call's shape; a runtime flush period counts
        # K-steps of the caller's block_k, which then stays the K tile
        block_m, block_n, block_k = fused_tiles(
            x2.shape[0], K, n_out,
            flush_block_k=None if flush_period is None else block_k)
        out = replicated_kernel_call(
            lambda xc, wc, s, b, fp: mgs_matmul_exact_fused_pallas(
                xc, wc, fmt, scale=s, bias=b, activation=activation,
                block_m=block_m, block_n=block_n, block_k=block_k,
                flush_period=fp, interpret=interpret),
            xc, wc, scale, bias, flush_period)
    elif mode == "dmac":
        bm, bn = _dmac_block_shapes(block_m, block_n, block_k)
        w_vals = w.values() if prepared else w
        out = replicated_kernel_call(
            lambda x2, wv: mgs_matmul_dmac_pallas(
                x2, wv, fmt, gate_subnormal, block_m=bm, block_n=bn,
                block_k=block_k, interpret=interpret),
            x2, w_vals)
    else:
        raise ValueError(f"unknown mode {mode!r}")
    return out.reshape(lead + (n_out,))
