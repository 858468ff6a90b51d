"""Pallas TPU kernels for the paper's compute hot-spot: the MGS matmul.

Public entry points (all jitted; tests run them in interpret mode on CPU):

* :func:`mgs_matmul_exact_fused_pallas` — the exact kernel:
  (M, K) x (K, N) over *packed* uint8 FP8 codes (1 byte/elem HBM), decode
  + limb-split per tile in VMEM, fused scale/bias/activation epilogue.
* :func:`fused_tiles` — its tiles, read off the call's shape (real rows
  in M, long K and N tiles that divide the weight); ``TILE_STATS``
  records each traced call shape's tiles and grid steps.
* :func:`mgs_matmul_dmac_pallas` — paper-faithful Fig. 8 numerics
  (per-product E4M3 rounding into 16 exponent-bin accumulators).
* :func:`limb_decompose` — (…) format-exact values -> (3, …) balanced
  int8 limbs (host-side, for limb statistics; the kernel decodes its
  own per tile).
* :func:`worst_case_flush_period` — deterministic no-overflow flush
  period for a given ``block_k`` (the Markov planner's safety fallback).
* ``ACTIVATIONS`` — the epilogue activation table shared with the model
  layers (bit-for-bit identical definitions).

``ops.mgs_matmul`` is the dispatching wrapper every call site routes
through; ``ref`` holds the pure-jnp oracles the kernels are tested
against.
"""
from . import ops, ref
from .mgs_attention import (flash_chunk_limit, mgs_flash_attention,
                            mgs_flash_attention_ref,
                            mgs_paged_flash_attention,
                            mgs_paged_verify_attention)
from .mgs_matmul import (ACTIVATIONS, TILE_STATS, fused_tiles,
                         limb_decompose, mgs_matmul_dmac_pallas,
                         mgs_matmul_exact_fused_pallas,
                         worst_case_flush_period)

__all__ = ["ops", "ref", "ACTIVATIONS", "TILE_STATS",
           "fused_tiles", "limb_decompose", "mgs_matmul_dmac_pallas",
           "mgs_matmul_exact_fused_pallas", "worst_case_flush_period",
           "mgs_flash_attention", "mgs_flash_attention_ref",
           "mgs_paged_flash_attention", "mgs_paged_verify_attention",
           "flash_chunk_limit"]
