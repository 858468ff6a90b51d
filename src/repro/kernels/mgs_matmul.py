"""Pallas TPU kernels for MGS quantized matmuls.

Three kernels, matching the contracts in :mod:`repro.kernels.ref`:

``mgs_matmul_exact_fused_kernel`` — the production serving path. Operands
arrive as *packed* format-exact FP8 codes (``core.formats.encode_bits``,
1 byte/element in HBM). Each tile is decoded and limb-split **in VMEM**
(pure integer bit-twiddling, no host-side pre-decomposition), the 9
limb-pair int8×int8→int32 contractions run on the MXU, and an optional
fused epilogue (output scale · bias add · activation) finishes the tile so
linear layers need no follow-up elementwise pass. Streaming the packed
bytes instead of materialized limb planes cuts operand HBM traffic 3×
(the §5.2 amortization argument applied to *data movement*: prep work is
re-done per tile in fast memory rather than stored in slow memory).

``mgs_matmul_exact_kernel`` — the pre-decomposed A/B baseline. E4M3
operands are limb-decomposed host-side into 20-bit fixed-point integers
split into three balanced 7-bit limbs (int8, 3 bytes/element in HBM);
the kernel body is otherwise identical. Kept for benchmarking the fused
variant's bandwidth win and as the path for callers that already hold
limb planes (e.g. ``quant.prepared.PreparedWeight``).

Both exact kernels keep 5 per-weight-class int32 accumulators resident in
VMEM and flush them into a float32 wide accumulator every
``flush_period`` K-steps. The period comes from either the deterministic
``worst_case_flush_period`` (no int32 overflow possible — the default) or
the Markov planner (``core.markov.plan_flush_period``) which uses observed
limb statistics to lengthen the period (fewer f32 combines per output
tile) at a provably negligible overflow probability. One flush per period
amortizes all mantissa alignment, exactly the paper's §5.2 insight.

``mgs_matmul_dmac_kernel`` — paper-faithful Fig. 8 numerics. Product tiles
are materialized in VMEM, RNE-rounded to E4M3 (subnormal-gated per §5.3),
decomposed into signed mantissas + exponent bins, and accumulated into 16
per-bin int32 registers (the dMAC's 16 narrow accumulators, widened to
int32 so the in-VMEM totals are exact — the wide-fallback path never loses
bits, so this is bit-identical to the hardware). The 16× shift+combine
runs once per output tile.

Memory accounting (per grid step, MXU-aligned 128×128×128 tiles):

* HBM operand bytes per full (M, K) @ (K, N) matmul:
    fused:          M·K + K·N          (packed codes, 1 B/elem)
    pre-decomposed: 3·(M·K + K·N)      (3 int8 limb planes)
  plus 4·M·N output bytes either way — the fused path's operand traffic
  is exactly 1/3 of the pre-decomposed path's.
* VMEM, fused: bm·bk + bk·bn uint8 codes + 3·(bm·bk + bk·bn) int8 decoded
  limbs (transient) + 5·bm·bn int32 + bm·bn f32 + 2·bn f32 epilogue rows
  ≈ 0.6 MB.
* VMEM, pre-decomposed: 3·(bm·bk + bk·bn) int8 + 5·bm·bn int32 + bm·bn
  f32 ≈ 0.5 MB.
* dmac: bm·bk·bn f32 product tile dominates (32·128·32·4 = 0.5 MB).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.formats import E4M3, FPFormat

__all__ = ["mgs_matmul_exact_pallas", "mgs_matmul_exact_fused_pallas",
           "mgs_matmul_dmac_pallas", "limb_decompose",
           "worst_case_flush_period", "default_interpret", "ACTIVATIONS"]

_LIMB_BASE = 7
_N_LIMBS = 3
_N_CLASSES = 2 * _N_LIMBS - 1  # limb-weight classes a+b in [0, 4]


def default_interpret() -> bool:
    """Interpret-mode default for every kernel entry point: Mosaic on a
    TPU backend, the Pallas interpreter anywhere else (CPU tests)."""
    return jax.default_backend() != "tpu"


# Epilogue activations the fused kernel can apply in-VMEM. Must match the
# model-layer definitions (models.common) bit-for-bit so fusing an
# activation into the kernel is numerically transparent.
ACTIVATIONS = {
    "none": lambda r: r,
    "relu": lambda r: jnp.maximum(r, 0.0),
    "gelu": lambda r: jax.nn.gelu(r, approximate=True),
    "silu": jax.nn.silu,
}


def limb_decompose(v, fmt: FPFormat = E4M3):
    """Format-exact values -> 3 balanced base-128 int8 limbs of the
    fixed-point integer ix = sm << max(e, 1) (value = ix * 2^-(bias+mbits))."""
    from repro.core.formats import decompose
    sm, e = decompose(v.astype(jnp.float32), fmt)
    ix = sm << jnp.maximum(e, 1)
    return jnp.stack(_limb_split(ix))  # (3, ...) int8


def _limb_split(ix):
    """Split int32 fixed-point values into 3 balanced base-128 int8 limbs."""
    half, mod = 1 << (_LIMB_BASE - 1), 1 << _LIMB_BASE
    limbs, rem = [], ix
    for _ in range(_N_LIMBS - 1):
        c = ((rem + half) & (mod - 1)) - half
        limbs.append(c.astype(jnp.int8))
        rem = (rem - c) >> _LIMB_BASE
    limbs.append(rem.astype(jnp.int8))
    return limbs


def _decode_limbs(codes, fmt: FPFormat):
    """Packed FP8 codes (uint8) -> 3 balanced int8 limbs, in-kernel.

    Pure integer bit-twiddling (shifts/masks/selects), so it lowers inside
    Pallas on TPU — this is the per-tile "prep" the fused kernel re-does in
    VMEM instead of streaming pre-decomposed planes from HBM. The code
    layout lives in one place (formats.decode_sm_e), shared with the
    host-side decode_bits.
    """
    from repro.core.formats import decode_sm_e
    sm, e = decode_sm_e(codes, fmt)
    ix = sm << jnp.maximum(e, 1)
    return _limb_split(ix)


def worst_case_flush_period(block_k: int) -> int:
    """Deterministic no-overflow flush period for the int32 class accums.

    Per K element, a weight class accumulates at most
    max_pairs_per_class * 64 * 64 = 3 * 4096; the int32 register is safe for
    floor((2^31 - 1) / (block_k * 12288)) grid K-steps between flushes.
    The Markov planner (core.markov.plan_flush_period) lengthens this using
    observed limb statistics; this bound is its safety fallback.
    """
    per_step = block_k * _N_LIMBS * (1 << (_LIMB_BASE - 1)) ** 2
    return max(1, (2**31 - 1) // per_step)


# ---------------------------------------------------------------------------
# exact mode — shared accumulate/flush body
# ---------------------------------------------------------------------------


def _accumulate_classes(acc_i, lx, lw):
    """9 limb-pair MXU contractions, accumulated per weight class a+b."""
    for a in range(_N_LIMBS):
        xa = lx[a]
        for b in range(_N_LIMBS):
            acc_i[a + b] += jax.lax.dot_general(
                xa, lw[b], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.int32)


def _flush_classes(acc_i, acc_f):
    """The "wide accumulator" add: one shift+combine per period."""
    tot = acc_f[...]
    for c in range(_N_CLASSES):
        tot += acc_i[c].astype(jnp.float32) * (2.0 ** (_LIMB_BASE * c))
    acc_f[...] = tot
    acc_i[...] = jnp.zeros_like(acc_i)


def _exact_kernel(lx_ref, lw_ref, fp_ref, o_ref, acc_i, acc_f, *,
                  nsteps: int, out_scale: float):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_i[...] = jnp.zeros_like(acc_i)
        acc_f[...] = jnp.zeros_like(acc_f)

    _accumulate_classes(acc_i, lx_ref, lw_ref)

    @pl.when((jax.lax.rem(k + 1, fp_ref[0, 0]) == 0) | (k == nsteps - 1))
    def _flush():
        _flush_classes(acc_i, acc_f)

    @pl.when(k == nsteps - 1)
    def _done():
        o_ref[...] = acc_f[...] * out_scale


def _flush_scalar(flush_period, block_k: int, nsteps: int):
    """Flush period as a (1, 1) int32 SMEM kernel operand.

    The period is a *runtime scalar*, not a trace constant: re-planning
    it (e.g. from a hot-swapped calibration table) must never cost a
    recompile. Note the period IS bit-affecting: the int32 class
    partials are exact regardless, but each flush rounds them into the
    f32 wide accumulator, so different periods can differ in the last
    ulp — which is why serve engines version the period alongside the
    table and pin it per request. A period beyond the grid means "flush
    once at the end"; the in-graph clamp also keeps the in-kernel rem()
    in int32 range for Markov-planned periods.
    """
    if flush_period is None:
        flush_period = worst_case_flush_period(block_k)
    if isinstance(flush_period, int):
        # Markov plans on near-uniform sigmas can exceed int32; any
        # period >= nsteps means the same thing ("flush once at the end")
        flush_period = min(flush_period, 2**31 - 1)
    fp = jnp.clip(jnp.asarray(flush_period, jnp.int32), 1, nsteps)
    return fp.reshape(1, 1)


_FP_SPEC = pl.BlockSpec(memory_space=pltpu.SMEM)


@functools.partial(
    jax.jit,
    static_argnames=("fmt", "block_m", "block_n", "block_k", "interpret"))
def mgs_matmul_exact_pallas(x, w, fmt: FPFormat = E4M3, *, block_m: int = 128,
                            block_n: int = 128, block_k: int = 128,
                            flush_period: int | None = None,
                            w_limbs=None, interpret: bool = False):
    """Exact fixed-point FP8 matmul: out = x @ w with no accumulation error.

    Args:
      x: (M, K) format-exact FP8 values (``quant.quantize_fp8``).
      w: (K, N) format-exact weight values (limb-decomposed here,
        host-side), or ``None`` when ``w_limbs`` is given.
      fmt: narrow-exponent FP8 format (E4M3 default).
      block_m / block_n / block_k: Pallas tile sizes.
      flush_period: K-grid steps between narrow->wide flushes (``None`` =
        :func:`worst_case_flush_period`). A **runtime scalar** (python
        int or traced int32), shipped to the kernel through SMEM — never
        a trace constant, so re-planned periods swap in without a
        recompile. The period is bit-affecting (each flush rounds the
        exact int32 partials into the f32 wide accumulator), so it is
        versioned calibration state upstream.
      w_limbs: (3, K, N) int8 pre-decomposed limb planes (e.g. a cached
        ``quant.prepared.PreparedWeight.limbs`` plane).
      interpret: run in Pallas interpret mode (CPU tests).

    Returns:
      (M, N) float32 fixed-point-exact ``x @ w``.
    """
    M, K = x.shape
    if w_limbs is not None:
        K2, N = w_limbs.shape[1:]
    else:
        K2, N = w.shape
    assert K == K2, (x.shape, K2, N)
    Mp, Np, Kp = (_ceil(M, block_m) * block_m, _ceil(N, block_n) * block_n,
                  _ceil(K, block_k) * block_k)
    lx = limb_decompose(_pad2(x, Mp, Kp), fmt)          # (3, Mp, Kp) int8
    if w_limbs is not None:
        lw = jnp.pad(w_limbs, ((0, 0), (0, Kp - K), (0, Np - N)))
    else:
        lw = limb_decompose(_pad2(w, Kp, Np), fmt)      # (3, Kp, Np) int8
    nsteps = Kp // block_k
    fp = _flush_scalar(flush_period, block_k, nsteps)
    out_scale = 2.0 ** (-2 * (fmt.bias + fmt.mbits))

    grid = (Mp // block_m, Np // block_n, nsteps)
    kernel = functools.partial(_exact_kernel, nsteps=nsteps,
                               out_scale=out_scale)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((_N_LIMBS, block_m, block_k),
                         lambda i, j, k: (0, i, k)),
            pl.BlockSpec((_N_LIMBS, block_k, block_n),
                         lambda i, j, k: (0, k, j)),
            _FP_SPEC,
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Mp, Np), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((_N_CLASSES, block_m, block_n), jnp.int32),
            pltpu.VMEM((block_m, block_n), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(lx, lw, fp)
    return out[:M, :N]


# ---------------------------------------------------------------------------
# exact mode — streaming limb-fused variant (packed codes in, epilogue out)
# ---------------------------------------------------------------------------


def _epilogue(r, scale_ref, bias_ref, activation: str, has_scale: bool,
              has_bias: bool):
    """Fused output epilogue: activation(r * scale + bias), in-VMEM."""
    if has_scale:
        r = r * scale_ref[...]            # (1, bn) broadcast row
    if has_bias:
        r = r + bias_ref[...]
    return ACTIVATIONS[activation](r)


def _exact_fused_kernel(xc_ref, wc_ref, scale_ref, bias_ref, fp_ref, o_ref,
                        acc_i, acc_f, *, nsteps: int,
                        out_scale: float, fmt: FPFormat, activation: str,
                        has_scale: bool, has_bias: bool):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_i[...] = jnp.zeros_like(acc_i)
        acc_f[...] = jnp.zeros_like(acc_f)

    # in-VMEM decode: packed byte tiles -> balanced int8 limbs.
    lx = _decode_limbs(xc_ref[...], fmt)
    lw = _decode_limbs(wc_ref[...], fmt)
    _accumulate_classes(acc_i, lx, lw)

    @pl.when((jax.lax.rem(k + 1, fp_ref[0, 0]) == 0) | (k == nsteps - 1))
    def _flush():
        _flush_classes(acc_i, acc_f)

    @pl.when(k == nsteps - 1)
    def _done():
        o_ref[...] = _epilogue(acc_f[...] * out_scale, scale_ref, bias_ref,
                               activation, has_scale, has_bias)


def _exact_fused_stationary_kernel(xc_ref, wc_ref, scale_ref, bias_ref,
                                   fp_ref, o_ref, limbs, acc_i, acc_f, *,
                                   cache_weight: bool, nsteps: int,
                                   out_scale: float,
                                   fmt: FPFormat, activation: str,
                                   has_scale: bool, has_bias: bool):
    """One K-resident stationary kernel body for both cached operands.

    The output-stationary kernel re-decodes both operand tiles at every
    grid step. The stationary schedules put the *other* operand's grid
    axis at ``program_id(1)``, so its first sweep (``pid == 0``) decodes
    each K-tile of the cached operand once into the K-resident ``limbs``
    VMEM scratch (3 limb planes x the whole padded K stripe) and every
    later sweep reuses it:

    * ``cache_weight=True`` — weight-stationary, grid (j, i, k): the
      i == 0 sweep caches the weight stripe of output column j; weight
      decode work drops ``grid_m``-fold.
    * ``cache_weight=False`` — activation-stationary, grid (i, j, k):
      the j == 0 sweep caches the activation stripe of output row i;
      activation decode work drops ``grid_n``-fold (wide-N layers such
      as the logits head).

    Accumulator/flush/epilogue logic is identical to the
    output-stationary kernel, so results are bit-identical.
    """
    sweep = pl.program_id(1)
    k = pl.program_id(2)
    cached_ref = wc_ref if cache_weight else xc_ref

    @pl.when(sweep == 0)
    def _decode_cached():
        lc = _decode_limbs(cached_ref[...], fmt)
        for a in range(_N_LIMBS):
            limbs[k, a] = lc[a]

    @pl.when(k == 0)
    def _init():
        acc_i[...] = jnp.zeros_like(acc_i)
        acc_f[...] = jnp.zeros_like(acc_f)

    cached = [limbs[k, a] for a in range(_N_LIMBS)]
    if cache_weight:
        lx, lw = _decode_limbs(xc_ref[...], fmt), cached
    else:
        lx, lw = cached, _decode_limbs(wc_ref[...], fmt)
    _accumulate_classes(acc_i, lx, lw)

    @pl.when((jax.lax.rem(k + 1, fp_ref[0, 0]) == 0) | (k == nsteps - 1))
    def _flush():
        _flush_classes(acc_i, acc_f)

    @pl.when(k == nsteps - 1)
    def _done():
        o_ref[...] = _epilogue(acc_f[...] * out_scale, scale_ref, bias_ref,
                               activation, has_scale, has_bias)


# VMEM budget for a stationary schedule's K-resident decoded limb stripe
# (3 int8 planes x Kp x block_n for "weight", x block_m for
# "activation"). Above this the schedule cannot co-reside with the
# accumulators on real TPUs (~16 MB VMEM/core).
WS_STRIPE_BUDGET_BYTES = 8 << 20


def ws_stripe_bytes(K: int, block: int, block_k: int) -> int:
    """VMEM bytes of a K-resident decoded limb stripe.

    ``block`` is the non-K tile edge the stripe spans: ``block_n`` for
    the weight-stationary schedule, ``block_m`` for the
    activation-stationary one. The single size formula shared by the
    kernel-side hard check and the ops-side warn-and-fallback, so the
    two can never disagree.
    """
    Kp = -(-K // block_k) * block_k
    return _N_LIMBS * Kp * block


@functools.partial(
    jax.jit,
    static_argnames=("fmt", "block_m", "block_n", "block_k",
                     "activation", "schedule", "interpret"))
def mgs_matmul_exact_fused_pallas(x_codes, w_codes, fmt: FPFormat = E4M3, *,
                                  scale=None, bias=None,
                                  activation: str = "none",
                                  block_m: int = 128, block_n: int = 128,
                                  block_k: int = 128,
                                  flush_period: int | None = None,
                                  schedule: str = "output",
                                  interpret: bool = False):
    """Streaming limb-fused exact matmul over *packed* FP8 codes.

    Args:
      x_codes: (M, K) uint8 packed codes
        (:func:`repro.core.formats.encode_bits`) — 1 byte/element of HBM
        traffic vs 3 for the pre-decomposed kernel.
      w_codes: (K, N) uint8 packed weight codes (e.g. a cached
        ``quant.prepared.PreparedWeight.codes`` plane).
      fmt: operand FP8 format (narrow-exponent; E4M3 default).
      scale: optional dequant scale broadcastable to (1, N) (e.g.
        per-channel quantization scales), fused into the epilogue.
      bias: optional (N,) bias row, fused into the epilogue.
      activation: one of ``ACTIVATIONS``, fused into the epilogue.
      block_m / block_n / block_k: Pallas tile sizes (MXU-aligned
        defaults).
      flush_period: K-grid steps between narrow->wide accumulator
        flushes; ``None`` = deterministic
        :func:`worst_case_flush_period`, or a Markov-planned period from
        :func:`repro.core.markov.plan_flush_period`. A **runtime
        scalar** (python int or traced int32) shipped through SMEM, not
        a trace constant — re-planned periods (hot-swapped calibration)
        swap in with zero recompiles. Bit-affecting: each flush rounds
        the exact int32 partials into the f32 wide accumulator, so the
        period is versioned calibration state upstream.
      schedule: ``"output"`` (output-stationary — decode both operand
        tiles every grid step), ``"weight"`` (K-resident
        weight-stationary — cache the decoded weight limb stripe in VMEM
        across the M-grid axis, cutting in-kernel weight decode work
        ``grid_m``-fold; VMEM cost 3·Kp·block_n bytes) or
        ``"activation"`` (K-resident activation-stationary — cache the
        decoded x limb stripe across the N-grid axis, cutting activation
        decode work ``grid_n``-fold for wide-N layers; VMEM cost
        3·Kp·block_m bytes). Stationary stripes are guarded by
        ``WS_STRIPE_BUDGET_BYTES``.
      interpret: run in Pallas interpret mode (CPU tests).

    Returns:
      (M, N) float32 ``activation(x @ w * out_scale * scale + bias)``.
      Decode + limb-split happens per tile in VMEM; with scale/bias
      omitted and activation "none" the result is bit-identical to
      ``mgs_matmul_exact_pallas`` / ``mgs_matmul_ref`` under either
      schedule.
    """
    if activation not in ACTIVATIONS:
        raise ValueError(f"activation {activation!r} not in "
                         f"{sorted(ACTIVATIONS)}")
    if schedule not in ("output", "weight", "activation"):
        raise ValueError(f"schedule {schedule!r} not in ('output', "
                         f"'weight', 'activation')")
    M, K = x_codes.shape
    K2, N = w_codes.shape
    assert K == K2, (x_codes.shape, w_codes.shape)
    assert x_codes.dtype == jnp.uint8 and w_codes.dtype == jnp.uint8, (
        x_codes.dtype, w_codes.dtype)
    Mp, Np, Kp = (_ceil(M, block_m) * block_m, _ceil(N, block_n) * block_n,
                  _ceil(K, block_k) * block_k)
    xc = _pad2(x_codes, Mp, Kp)   # code 0 == +0.0
    wc = _pad2(w_codes, Kp, Np)
    has_scale, has_bias = scale is not None, bias is not None
    srow = jnp.zeros((1, Np), jnp.float32)
    if has_scale:
        srow = jnp.pad(
            jnp.broadcast_to(jnp.asarray(scale, jnp.float32).reshape(1, -1),
                             (1, N)), ((0, 0), (0, Np - N)))
    brow = jnp.zeros((1, Np), jnp.float32)
    if has_bias:
        brow = jnp.pad(jnp.asarray(bias, jnp.float32).reshape(1, N)[:1],
                       ((0, 0), (0, Np - N)))
    nsteps = Kp // block_k
    fp = _flush_scalar(flush_period, block_k, nsteps)
    out_scale = 2.0 ** (-2 * (fmt.bias + fmt.mbits))

    kw = dict(nsteps=nsteps, out_scale=out_scale,
              fmt=fmt, activation=activation, has_scale=has_scale,
              has_bias=has_bias)
    if schedule in ("weight", "activation"):
        cache_weight = schedule == "weight"
        block = block_n if cache_weight else block_m
        stripe_bytes = ws_stripe_bytes(K, block, block_k)
        if stripe_bytes > WS_STRIPE_BUDGET_BYTES:
            raise ValueError(
                f"{schedule}-stationary schedule needs a "
                f"{stripe_bytes / 2**20:.1f} MB K-resident limb stripe "
                f"(3 x Kp={Kp} x {block}) > "
                f"{WS_STRIPE_BUDGET_BYTES / 2**20:.0f} MB VMEM budget; "
                f"use schedule='output' for this shape")
        # the cached operand's stripe is decoded on the first sweep of
        # the OTHER operand's grid axis, which therefore sits at grid
        # position 1: weight-stationary runs (j, i, k) — the i == 0
        # sweep caches the weight stripe of column j; activation-
        # stationary runs (i, j, k) — the j == 0 sweep caches the
        # activation stripe of row i.
        if cache_weight:
            grid = (Np // block_n, Mp // block_m, nsteps)
            x_map = lambda j, i, k: (i, k)
            w_map = lambda j, i, k: (k, j)
            row_map = lambda j, i, k: (0, j)
            out_map = lambda j, i, k: (i, j)
            stripe_shape = (nsteps, _N_LIMBS, block_k, block_n)
        else:
            grid = (Mp // block_m, Np // block_n, nsteps)
            x_map = lambda i, j, k: (i, k)
            w_map = lambda i, j, k: (k, j)
            row_map = lambda i, j, k: (0, j)
            out_map = lambda i, j, k: (i, j)
            stripe_shape = (nsteps, _N_LIMBS, block_m, block_k)
        out = pl.pallas_call(
            functools.partial(_exact_fused_stationary_kernel,
                              cache_weight=cache_weight, **kw),
            grid=grid,
            in_specs=[
                pl.BlockSpec((block_m, block_k), x_map),
                pl.BlockSpec((block_k, block_n), w_map),
                pl.BlockSpec((1, block_n), row_map),
                pl.BlockSpec((1, block_n), row_map),
                _FP_SPEC,
            ],
            out_specs=pl.BlockSpec((block_m, block_n), out_map),
            out_shape=jax.ShapeDtypeStruct((Mp, Np), jnp.float32),
            scratch_shapes=[
                pltpu.VMEM(stripe_shape, jnp.int8),
                pltpu.VMEM((_N_CLASSES, block_m, block_n), jnp.int32),
                pltpu.VMEM((block_m, block_n), jnp.float32),
            ],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary", "arbitrary")),
            interpret=interpret,
        )(xc, wc, srow, brow, fp)
        return out[:M, :N]
    out = pl.pallas_call(
        functools.partial(_exact_fused_kernel, **kw),
        grid=(Mp // block_m, Np // block_n, nsteps),
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda i, j, k: (i, k)),
            pl.BlockSpec((block_k, block_n), lambda i, j, k: (k, j)),
            pl.BlockSpec((1, block_n), lambda i, j, k: (0, j)),
            pl.BlockSpec((1, block_n), lambda i, j, k: (0, j)),
            _FP_SPEC,
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Mp, Np), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((_N_CLASSES, block_m, block_n), jnp.int32),
            pltpu.VMEM((block_m, block_n), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(xc, wc, srow, brow, fp)
    return out[:M, :N]


# ---------------------------------------------------------------------------
# dmac (paper-faithful) mode
# ---------------------------------------------------------------------------


def _round_decompose_e4m3(p, fmt: FPFormat, gate_subnormal: bool):
    """Kernel-local RNE round-to-fmt + mantissa/exponent decomposition.

    Uses exponent-field bit extraction (exact) instead of frexp so it
    lowers inside Pallas on TPU. ``p`` is a float32 tile of exact products
    of fmt values (such products are exactly representable in f32).
    """
    ap = jnp.abs(p)
    bits = jax.lax.bitcast_convert_type(ap, jnp.int32)
    eu = jnp.clip((bits >> 23) - 127, fmt.emin_unbiased, fmt.emax_unbiased)
    q = jnp.exp2((eu - fmt.mbits).astype(jnp.float32))
    r = jnp.rint(ap / q) * q
    r = jnp.minimum(r, fmt.max_finite)
    if gate_subnormal:
        r = jnp.where(ap < fmt.min_subnormal, 0.0, r)
    r = jnp.where(ap == 0, 0.0, r) * jnp.sign(p)
    # decompose the rounded value
    rbits = jax.lax.bitcast_convert_type(jnp.abs(r), jnp.int32)
    eu2 = jnp.clip((rbits >> 23) - 127, fmt.emin_unbiased, fmt.emax_unbiased)
    is_sub = jnp.abs(r) < 2.0 ** fmt.emin_unbiased
    e = jnp.where(is_sub, 0, eu2 + fmt.bias).astype(jnp.int32)
    sc = jnp.exp2(-(jnp.maximum(e, 1) - (fmt.bias + fmt.mbits)).astype(
        jnp.float32))
    sm = jnp.rint(r * sc).astype(jnp.int32)
    return sm, e


def _dmac_kernel(x_ref, w_ref, o_ref, acc_bins, *, nsteps: int,
                 fmt: FPFormat, gate_subnormal: bool):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_bins[...] = jnp.zeros_like(acc_bins)

    xt = x_ref[...].astype(jnp.float32)   # (bm, bk)
    wt = w_ref[...].astype(jnp.float32)   # (bk, bn)
    p = xt[:, :, None] * wt[None, :, :]   # (bm, bk, bn) exact in f32
    sm, e = _round_decompose_e4m3(p, fmt, gate_subnormal)
    # the 16 narrow exponent-bin accumulators (int32-exact totals)
    for b in range(fmt.n_bins):
        acc_bins[b] += jnp.sum(jnp.where(e == b, sm, 0), axis=1)

    @pl.when(k == nsteps - 1)
    def _done():
        # final 16x shift+add (once per dot product — §5.2 amortization)
        tot = jnp.zeros_like(o_ref)
        for b in range(fmt.n_bins):
            tot += acc_bins[b].astype(jnp.float32) * (
                2.0 ** (max(b, 1) - (fmt.bias + fmt.mbits)))
        o_ref[...] = tot


@functools.partial(
    jax.jit,
    static_argnames=("fmt", "gate_subnormal", "block_m", "block_n", "block_k",
                     "interpret"))
def mgs_matmul_dmac_pallas(x, w, fmt: FPFormat = E4M3,
                           gate_subnormal: bool = True, *, block_m: int = 32,
                           block_n: int = 32, block_k: int = 128,
                           interpret: bool = False):
    """Paper-faithful MGS matmul (per-product E4M3 rounding, Fig. 8)."""
    M, K = x.shape
    K2, N = w.shape
    assert K == K2, (x.shape, w.shape)
    Mp, Np, Kp = (_ceil(M, block_m) * block_m, _ceil(N, block_n) * block_n,
                  _ceil(K, block_k) * block_k)
    xp = _pad2(x.astype(jnp.float32), Mp, Kp)
    wp = _pad2(w.astype(jnp.float32), Kp, Np)
    nsteps = Kp // block_k

    kernel = functools.partial(_dmac_kernel, nsteps=nsteps, fmt=fmt,
                               gate_subnormal=gate_subnormal)
    out = pl.pallas_call(
        kernel,
        grid=(Mp // block_m, Np // block_n, nsteps),
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda i, j, k: (i, k)),
            pl.BlockSpec((block_k, block_n), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Mp, Np), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((fmt.n_bins, block_m, block_n), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(xp, wp)
    return out[:M, :N]


def _ceil(a: int, b: int) -> int:
    return -(-a // b)


def _pad2(x, r: int, c: int):
    return jnp.pad(x, ((0, r - x.shape[0]), (0, c - x.shape[1])))
