"""Pallas TPU kernels for MGS quantized matmuls.

Two kernels, matching the contracts in :mod:`repro.kernels.ref`:

``mgs_matmul_exact_fused_pallas`` — the exact kernel. Operands arrive as
*packed* format-exact FP8 codes (``core.formats.encode_bits``, 1
byte/element in HBM). Each tile is decoded and split into three balanced
7-bit int8 limbs of its 20-bit fixed-point integer **in VMEM** (pure
integer bit-twiddling), the 9 limb-pair int8×int8→int32 contractions run
on the MXU, and an optional fused epilogue (output scale · bias add ·
activation) finishes the tile so linear layers need no follow-up
elementwise pass. Streaming the packed bytes instead of materialized
limb planes keeps operand HBM traffic at 1 byte/element (the §5.2
amortization argument applied to *data movement*: prep work is re-done
per tile in fast memory rather than stored in slow memory).

The kernel keeps 5 per-weight-class int32 accumulators resident in VMEM
and flushes them into a float32 wide accumulator every ``flush_period``
K-steps. The period comes from either the deterministic
``worst_case_flush_period`` (no int32 overflow possible — the default) or
the Markov planner (``core.markov.plan_flush_period``) which uses observed
limb statistics to lengthen the period (fewer f32 combines per output
tile) at a provably negligible overflow probability. One flush per period
amortizes all mantissa alignment, exactly the paper's §5.2 insight.

``mgs_matmul_dmac_pallas`` — paper-faithful Fig. 8 numerics. Product tiles
are materialized in VMEM, RNE-rounded to E4M3 (subnormal-gated per §5.3),
decomposed into signed mantissas + exponent bins, and accumulated into 16
per-bin int32 registers (the dMAC's 16 narrow accumulators, widened to
int32 so the in-VMEM totals are exact — the wide-fallback path never loses
bits, so this is bit-identical to the hardware). The 16× shift+combine
runs once per output tile.

Memory accounting (per grid step):

* HBM operand bytes per full (M, K) @ (K, N) exact matmul: M·K + K·N
  (packed codes, 1 B/elem), plus 4·M·N output bytes.
* VMEM, exact kernel, at the tiles :func:`fused_tiles` reads off the
  call's shape (``ops.mgs_matmul`` passes them): bm = the call's rows
  rounded up to 32 (at most 256 a tile), bk and bn the longest
  128-multiples up to 512 that divide the 128-padded K and N. Per step,
  :func:`fused_vmem_bytes`: double-buffered uint8 code tiles
  2·(bm·bk + bk·bn), transient int8 limbs 3·(bm·bk + bk·bn) and int32
  decode temporaries 8·(bm·bk + bk·bn), 5·bm·bn int32 class
  accumulators, bm·bn f32 wide accumulator, a double-buffered bm·bn f32
  output tile, two bm·bn int32 dot results and 4·bn f32 epilogue rows:
  4.1 MB at a decode step's 32×512×512 (M×K×N), 9.9 MB at a prefill's
  256×512×512, 1.0 MB at 128×128×128; at most
  ``FUSED_VMEM_BUDGET_BYTES`` (12 MB, under the 16 MB default scoped
  limit).
* dmac: bm·bk·bn f32 product tile dominates (32·128·32·4 = 0.5 MB).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.formats import E4M3, FPFormat

__all__ = ["mgs_matmul_exact_fused_pallas",
           "mgs_matmul_dmac_pallas", "limb_decompose",
           "worst_case_flush_period", "default_interpret", "ACTIVATIONS",
           "fused_tiles", "fused_vmem_bytes", "FUSED_VMEM_BUDGET_BYTES",
           "TILE_STATS"]

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
    Pallas on TPU — this is the per-tile "prep" the kernel re-does in
    VMEM instead of streaming limb planes from HBM. The code
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
# exact mode — packed codes in, limbs decoded per tile, epilogue out
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


# ---------------------------------------------------------------------------
# output-stationary tiles, chosen from the call's shape
# ---------------------------------------------------------------------------

_LANE = 128          # lane tile: K and N tiles are multiples of it
_U8_SUBLANE = 32     # rows of a uint8 sublane tile: M tiles are multiples
# Largest tiles the chooser takes (v5e): a decode step's 32 slot rows
# are one M tile, a prefill bucket of 512 rows two.
_BM_CAP, _BK_CAP, _BN_CAP = 256, 512, 512

# VMEM an output-stationary grid step may take: under the chip's 16 MB
# default scoped limit, with room for what ``fused_vmem_bytes`` leaves out.
FUSED_VMEM_BUDGET_BYTES = 12 << 20

#: trace-time record of each fused call shape: (M, K, N) ->
#: (block_m, block_n, block_k, grid steps). Written when a shape is
#: traced, so a compiled program's calls appear once each.
TILE_STATS: dict = {}


def fused_vmem_bytes(block_m: int, block_n: int, block_k: int) -> int:
    """VMEM bytes of one output-stationary fused grid step.

    Per operand element (``bm·bk + bk·bn``): the double-buffered uint8
    code tiles (2 B), the three int8 limbs (3 B) and two live int32
    decode temporaries (8 B). Per output element (``bm·bn``): the five
    int32 class accumulators (20 B), the f32 wide accumulator (4 B), the
    double-buffered f32 output tile (8 B) and two int32 dot results
    (8 B); plus the double-buffered scale and bias rows. The one size
    formula the chooser (:func:`fused_tiles`) and the kernel's hard
    check share. It bounds what Mosaic allocates for these tiles: the
    least scoped VMEM limit a v5e compile accepts at M x K x N tiles
    32x512x512 / 256x512x512 / 256x256x512 is 3.44 / 9.17 / 6.98 MB,
    this gives 4.09 / 9.88 / 7.45 MB.
    """
    operand = block_m * block_k + block_k * block_n
    return (operand * (2 + _N_LIMBS + 2 * 4)
            + block_m * block_n * (_N_CLASSES * 4 + 4 + 2 * 4 + 2 * 4)
            + 2 * 2 * 4 * block_n)


def _row_tile(m: int, cap: int) -> int:
    """Fewest M tiles of at most ``cap`` rows, each rounded up to the
    uint8 sublane tile: 32 slot rows are one tile of 32, 300 rows two
    of 160."""
    tiles = _ceil(max(m, 1), cap)
    return _ceil(_ceil(max(m, 1), tiles), _U8_SUBLANE) * _U8_SUBLANE


def _lane_tiles(d: int, cap: int):
    """K or N tiles, longest first: each a multiple of 128 up to ``cap``
    that divides ``d`` rounded up to 128, so a weight whose dims are
    multiples of 128 is never padded. A dimension under one lane tile is
    its own (full-dimension) tile."""
    if d < _LANE:
        return [d]
    units = _ceil(d, _LANE)
    return [t * _LANE for t in range(min(cap // _LANE, units), 0, -1)
            if units % t == 0]


def fused_tiles(M: int, K: int, N: int, *,
                flush_block_k: int | None = None):
    """Tiles ``(block_m, block_n, block_k)`` of the output-stationary
    fused kernel for an ``(M, K) @ (K, N)`` call, read off its shape.

    ``block_m`` holds the real rows (rounded up to 32, at most 256 per
    tile); ``block_k`` and ``block_n`` are the longest 128-multiples up
    to 512 that divide the 128-padded K and N (deepseek-7b: 512 for
    4096 and 102400, 256 for 11008 = 128·86). The integer limb sums are
    exact under any partition, so the result keeps every bit of the
    128x128x128 tiling provided the flushes fall at the same K
    positions:

    * ``flush_block_k=None`` — the period is the static worst-case bound.
      ``block_k`` is taken only where K then still flushes once, at its
      end (``K / block_k <= worst_case_flush_period(block_k)``).
    * ``flush_block_k=b`` — a runtime period counts K-steps of ``b``
      (the caller's ``block_k``, as the planner planned it), so
      ``block_k = b``.

    Only a long caller ``block_k`` can overflow the VMEM budget
    (:func:`fused_vmem_bytes` of the capped tiles is 9.9 MB); then
    ``block_n``, and after it ``block_m``, shrink until it fits.
    """
    if flush_block_k is not None:
        bk = flush_block_k
    else:
        bk = next((t for t in _lane_tiles(K, _BK_CAP)
                   if _ceil(K, t) <= worst_case_flush_period(t)), _LANE)
    bm = _row_tile(M, _BM_CAP)
    bns = _lane_tiles(N, _BN_CAP)
    bn = next((t for t in bns if fused_vmem_bytes(bm, t, bk)
               <= FUSED_VMEM_BUDGET_BYTES), bns[-1])
    while (fused_vmem_bytes(bm, bn, bk) > FUSED_VMEM_BUDGET_BYTES
           and bm > _U8_SUBLANE):
        bm = _ceil(bm // 2, _U8_SUBLANE) * _U8_SUBLANE
    return bm, bn, bk


@functools.partial(
    jax.jit,
    static_argnames=("fmt", "block_m", "block_n", "block_k",
                     "activation", "interpret"))
def mgs_matmul_exact_fused_pallas(x_codes, w_codes, fmt: FPFormat = E4M3, *,
                                  scale=None, bias=None,
                                  activation: str = "none",
                                  block_m: int = 128, block_n: int = 128,
                                  block_k: int = 128,
                                  flush_period: int | None = None,
                                  interpret: bool = False):
    """Streaming limb-fused exact matmul over *packed* FP8 codes.

    Args:
      x_codes: (M, K) uint8 packed codes
        (:func:`repro.core.formats.encode_bits`) — 1 byte/element of HBM
        traffic.
      w_codes: (K, N) uint8 packed weight codes (e.g. a cached
        ``quant.prepared.PreparedWeight.codes`` plane).
      fmt: operand FP8 format (narrow-exponent; E4M3 default).
      scale: optional dequant scale broadcastable to (1, N) (e.g.
        per-channel quantization scales), fused into the epilogue.
      bias: optional (N,) bias row, fused into the epilogue.
      activation: one of ``ACTIVATIONS``, fused into the epilogue.
      block_m / block_n / block_k: Pallas tile sizes; ``ops.mgs_matmul``
        passes :func:`fused_tiles` of the call's shape. They must fit
        ``FUSED_VMEM_BUDGET_BYTES`` (:func:`fused_vmem_bytes`).
      flush_period: K-grid steps between narrow->wide accumulator
        flushes; ``None`` = deterministic
        :func:`worst_case_flush_period`, or a Markov-planned period from
        :func:`repro.core.markov.plan_flush_period`. A **runtime
        scalar** (python int or traced int32) shipped through SMEM, not
        a trace constant — re-planned periods (hot-swapped calibration)
        swap in with zero recompiles. Bit-affecting: each flush rounds
        the exact int32 partials into the f32 wide accumulator, so the
        period is versioned calibration state upstream.
      interpret: run in Pallas interpret mode (CPU tests).

    Returns:
      (M, N) float32 ``activation(x @ w * out_scale * scale + bias)``.
      Decode + limb-split happens per tile in VMEM; with scale/bias
      omitted and activation "none" the result is bit-identical to
      ``ref.mgs_matmul_ref``.
    """
    if activation not in ACTIVATIONS:
        raise ValueError(f"activation {activation!r} not in "
                         f"{sorted(ACTIVATIONS)}")
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
    TILE_STATS[(M, K, N)] = (block_m, block_n, block_k,
                             (Mp // block_m) * (Np // block_n) * nsteps)
    vmem = fused_vmem_bytes(block_m, block_n, block_k)
    if vmem > FUSED_VMEM_BUDGET_BYTES:
        raise ValueError(
            f"output-stationary tiles {block_m}x{block_k}x{block_n} "
            f"(MxKxN) need {vmem / 2**20:.1f} MB of VMEM > "
            f"{FUSED_VMEM_BUDGET_BYTES / 2**20:.0f} MB budget; take "
            f"fused_tiles(M, K, N) or smaller tiles")
    out = pl.pallas_call(
        functools.partial(_exact_fused_kernel, nsteps=nsteps,
                          out_scale=out_scale, fmt=fmt,
                          activation=activation, has_scale=has_scale,
                          has_bias=has_bias),
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
