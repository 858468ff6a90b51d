"""Fused MGS flash-decode attention over a packed-FP8 KV cache.

Decode attention is the serving hot path the matmul kernels don't cover:
the score (``q @ k^T``) and value (``softmax @ v``) contractions stream
the *whole* KV cache per step. This kernel runs both contractions on the
exact MGS limb-summation path (the same 9 limb-pair int8 MXU
contractions + weighted f32 combine as :mod:`repro.kernels.mgs_matmul`),
consuming the cache as **packed FP8 codes** (1 byte/element of HBM
traffic, decoded + limb-split per tile in VMEM) with a flash-style
online softmax across key chunks — scores never round-trip HBM.

Structure (one ``(T, D)`` query slice attending ``(S, D)`` keys/values):

* grid = (slices, S-chunks), sequential ("arbitrary"); the softmax
  running state (row max ``m``, denominator ``l``, output accumulator
  ``o``) lives in VMEM scratch across the chunk axis, exactly like the
  matmul kernels' class accumulators live across the K grid axis.
* the query's decoded limbs are cached in VMEM scratch on the first
  chunk (the activation-stationary trick from ``mgs_matmul``): q is
  decoded once, not once per chunk.
* scores: exact integer contraction of q and k limbs over ``D``, single
  flush (``D`` fits one tile, far inside ``worst_case_flush_period``),
  then one f32 scale per key — ``qk_scale[s]`` carries the query
  quantization scale x the cache entry's scale x ``head_dim**-0.5``, so
  the per-entry cache scales factor cleanly out of the ``D``
  contraction.
* values: per-entry cache scales do **not** factor out of the ``S``
  contraction, so they are folded into the softmax weights *before*
  those are quantized (per-row absmax, in-VMEM RNE rounding via the
  same bit-twiddling as the dmac kernel) — then the weight/value limb
  contraction runs exactly and one per-row f32 scale rescales the
  chunk's contribution.

**Ragged lengths / paged blocks.** Both contractions walk the cache as
fixed ``chunk``-key tiles addressed through a *block table*: the kernel
grid is ``(N, nb)`` and a scalar-prefetch table ``bt[n, j]`` names the
physical tile the ``j``-th logical chunk of slice ``n`` lives in
(``pltpu.PrefetchScalarGridSpec`` — index maps read the table, so the
DMA engine fetches through it). The dense entry point
(:func:`mgs_flash_attention`) passes an identity table over a reshaped
contiguous cache; the paged entry point
(:func:`mgs_paged_flash_attention`) passes a vLLM-style block pool +
per-slice tables. A per-slice ``live`` length gates every chunk update
(``@pl.when(j * chunk < live[n])``): chunks past the live prefix are
skipped — dead tiles clamp their table index to the last live chunk so
no out-of-range DMA is issued — which makes a short context's decode
cost track *its own* length, not the longest co-scheduled one.
Skipping is bitwise-identical to walking inert tails (zero codes and
scales, large-negative bias): an inert chunk's probabilities underflow
to exactly ``+0.0`` (``exp(-1e30 - m)``), so ``alpha == 1``,
``l + 0.0 == l`` and ``o + 0.0 == o`` leave every running quantity
bit-unchanged — ``tests/test_paged_kv.py`` pins this at ragged,
length-0 and block-boundary lengths.

Bit-identity contract: every chunk update — both contractions, the
running-max/exp/rescale algebra, and the **shape-independent pairwise
row sums** — is a single function (:func:`_attn_tile_step`) traced
verbatim by the Pallas kernel body *and* the pure-jnp reference, so
``use_kernel`` never changes a bit, and no reduction's grouping depends
on mesh-local shapes (the docs/serving.md cross-mesh guarantee extended
to decode attention). Integer class sums are exact; the f32 combine is a
fixed 5-term ascending-class sequence shared by both paths.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.formats import E4M3, FPFormat, encode_bits
from repro.parallel.sharding import replicated_kernel_call

from .mgs_matmul import (_decode_limbs, _limb_split, _N_CLASSES, _N_LIMBS,
                         _LIMB_BASE, _round_decompose_e4m3,
                         default_interpret)

__all__ = ["mgs_flash_attention", "mgs_flash_attention_ref",
           "mgs_paged_flash_attention", "mgs_paged_verify_attention",
           "flash_chunk_limit"]

_TINY = 1e-30
_MAX_PAIR = _N_LIMBS * (1 << (_LIMB_BASE - 1)) ** 2  # per-K-elem class bound
# row count at which _class_dots switches from 9 separate limb-pair dots
# to the single stacked GEMM (see its docstring; both are bit-identical)
_STACK_MIN_ROWS = 8


def flash_chunk_limit() -> int:
    """Largest key-chunk whose per-class int32 score/value accumulation
    cannot overflow (the ``worst_case_flush_period`` bound with the chunk
    as the contraction depth — each chunk is flushed to f32 immediately,
    so this is the only overflow surface)."""
    return (2**31 - 1) // _MAX_PAIR


def _combine_classes(accs):
    """Exact int32 class sums -> f32, fixed 5-term ascending order.

    Shared by the kernel and the reference so the (potentially rounding)
    f32 combine associates identically on both paths.
    """
    tot = accs[0].astype(jnp.float32)
    for c in range(1, _N_CLASSES):
        tot = tot + accs[c].astype(jnp.float32) * (2.0 ** (_LIMB_BASE * c))
    return tot


def _class_dots(lx, lw, contract):
    """Limb-pair integer contractions, summed per weight class a+b.

    ``contract``: ((x_dim,), (w_dim,)) dot_general contracting dims —
    (1,),(1,) for q @ k^T (both operands are (rows, D)); (1,),(0,) for
    p @ v ((T, chunk) x (chunk, D)). int32 sums are exact.

    Two bit-identical schedules, picked by the static row count:

    * single-row slices (plain decode, T = 1) run the 9 limb-pair dots
      as separate matvec-shaped contractions — fastest when each dot is
      tiny;
    * multi-row slices (the speculative verify's T x R query block) run
      ONE stacked contraction: the limb planes concatenate along each
      operand's non-contracted axis, a single integer GEMM produces
      every pair product, and the 9 blocks are sliced back out and
      summed per class, paying the per-call GEMM overhead once instead
      of 9 times (measured ~1.7x on the whole verify round at the
      emulation tier).

    Integer sums are exact under any partition, so the class totals —
    and everything downstream — are bit-identical either way; the gate
    is on a compile-time shape and can never change an output.
    """
    (xd,), (wd,) = contract
    if lx[0].shape[1 - xd] < _STACK_MIN_ROWS:
        accs = [None] * _N_CLASSES
        for a in range(_N_LIMBS):
            for b in range(_N_LIMBS):
                d = jax.lax.dot_general(lx[a], lw[b],
                                        (contract, ((), ())),
                                        preferred_element_type=jnp.int32)
                c = a + b
                accs[c] = d if accs[c] is None else accs[c] + d
        return accs
    xs = jnp.concatenate(list(lx), axis=1 - xd)
    ws = jnp.concatenate(list(lw), axis=1 - wd)
    d = jax.lax.dot_general(xs, ws, (contract, ((), ())),
                            preferred_element_type=jnp.int32)
    xn = lx[0].shape[1 - xd]
    wn = lw[0].shape[1 - wd]
    accs = [None] * _N_CLASSES
    for a in range(_N_LIMBS):
        for b in range(_N_LIMBS):
            blk = jax.lax.slice(d, (a * xn, b * wn),
                                ((a + 1) * xn, (b + 1) * wn))
            c = a + b
            accs[c] = blk if accs[c] is None else accs[c] + blk
    return accs


def _pairwise_sum_cols(x):
    """Shape-independent pairwise sum over the last axis, keepdims.

    The in-tile twin of ``models.common.pairwise_sum_last``: an explicit
    halving tree of elementwise adds whose association order is fixed by
    the graph, so the softmax denominator is identical on every mesh and
    on both the kernel and reference paths.

    Level ``s`` adds each column to the one ``s`` lanes to its right
    (a lane rotate, which Mosaic lowers; the strided ``x[..., 0::2]``
    halving it replaces lowers to a gather Mosaic refuses). Column 0
    then holds ``(x0 + x1) + (x2 + x3) ...``: the halving tree's exact
    association, so the sum is bit-for-bit what the halving tree gave.
    Wrapped-around columns are never read.
    """
    n = x.shape[-1]
    p = 1 << max(0, (n - 1).bit_length())
    if p != n:
        x = jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, p - n)])
    s = 1
    while s < p:
        x = x + jnp.roll(x, -s, axis=-1)
        s *= 2
    return x[..., :1]


def _attn_tile_step(lq, k_codes, v_codes, qk_row, v_row, bias, m, l, o,
                    fmt: FPFormat):
    """One online-softmax chunk update — the bitwise contract.

    Traced verbatim by the Pallas kernel body and the jnp reference.

    Args:
      lq: 3 decoded query limb planes, each (T, D) int8.
      k_codes / v_codes: (chunk, D) uint8 packed cache codes.
      qk_row: (1 | T, chunk) f32 per-key score scale (sigma_q *
        k_scale[s] * head_dim**-0.5) — one shared row, or one row per
        query row (the multi-query verify path, where each token
        carries its own quantization scale).
      v_row: (1 | T, chunk) f32 per-key value scale.
      bias: (1 | T, chunk) f32 additive mask row — shared when masks
        depend only on the key position (sequential decode), per row
        when each token has its own causal horizon (verify). Every op
        that consumes these is elementwise over rows, so a shared row
        is bitwise the per-row broadcast.
      m / l: (T, 1) f32 running row max / denominator.
      o: (T, D) f32 running (unnormalized) output.

    Returns:
      Updated (m, l, o).
    """
    out_scale = 2.0 ** (-2 * (fmt.bias + fmt.mbits))
    # scores: exact integer q.k^T over D, one f32 scale per key column
    lk = _decode_limbs(k_codes, fmt)
    s = _combine_classes(_class_dots(lq, lk, ((1,), (1,)))) * out_scale
    s = s * qk_row + bias
    # online softmax; max is exactly associative, the denominator sum is
    # an explicit pairwise tree (shape-independent)
    m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
    alpha = jnp.exp(m - m_new)
    p = jnp.exp(s - m_new)
    l_new = l * alpha + _pairwise_sum_cols(p)
    # values: fold the per-key cache scales into the weights, quantize
    # them per row (absmax -> in-VMEM RNE rounding, the dmac kernel's
    # bit-twiddling), then the exact weight x value limb contraction
    pv = p * v_row
    sp = jnp.maximum(jnp.max(jnp.abs(pv), axis=-1, keepdims=True),
                     _TINY) / fmt.max_finite
    sm, e = _round_decompose_e4m3(pv / sp, fmt, gate_subnormal=False)
    lp = _limb_split(sm << jnp.maximum(e, 1))
    lv = _decode_limbs(v_codes, fmt)
    o_chunk = _combine_classes(_class_dots(lp, lv, ((1,), (0,)))) \
        * out_scale * sp
    o_new = o * alpha + o_chunk
    return m_new, l_new, o_new


def _last_live_chunk(live, chunk):
    """Index of the last live chunk per slice, clamped to 0.

    Dead grid steps clamp their block-table lookup here so the DMA engine
    never chases a table entry past the live prefix (free slots hold
    zeroed tables; the trash block would still be in-range, but
    re-fetching the last live tile keeps the prefetch stream monotone).
    """
    return jnp.maximum(-(-live // chunk) - 1, 0).astype(jnp.int32)


# ---------------------------------------------------------------------------
# Pallas kernel — grid (N slices, nb chunks), block-table indirection
# ---------------------------------------------------------------------------


def _flash_kernel(bt_ref, live_ref, last_ref, qc_ref, kp_ref, vp_ref,
                  qk_ref, vs_ref, bias_ref, o_ref, q_limbs, m_ref, l_ref,
                  acc_ref, *, nsteps: int, chunk: int, fmt: FPFormat):
    del bt_ref, last_ref  # consumed by the index maps, not the body
    n = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        # decode q once into the chunk-resident limb scratch (the
        # activation-stationary trick: every later chunk reuses it)
        lq0 = _decode_limbs(qc_ref[0], fmt)
        for a in range(_N_LIMBS):
            q_limbs[a] = lq0[a]
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # masked-chunk early-exit: chunks past the slice's live prefix leave
    # every running quantity untouched (bitwise == walking inert tails)
    @pl.when(j * chunk < live_ref[n])
    def _update():
        lq = [q_limbs[a] for a in range(_N_LIMBS)]
        m_new, l_new, o_new = _attn_tile_step(
            lq, kp_ref[0], vp_ref[0], qk_ref[0], vs_ref[0],
            bias_ref[0], m_ref[...], l_ref[...], acc_ref[...], fmt)
        m_ref[...] = m_new
        l_ref[...] = l_new
        acc_ref[...] = o_new

    @pl.when(j == nsteps - 1)
    def _done():
        o_ref[0] = acc_ref[...] / jnp.maximum(l_ref[...], _TINY)


def _flash_pallas(q_codes, k_pool, v_pool, bt, live, qk_scale, v_scale,
                  bias, fmt: FPFormat, interpret: bool):
    """All (T, D) slices through one block-table Pallas launch.

    ``k_pool`` / ``v_pool`` are physical ``(P, chunk, D)`` tile pools;
    ``bt[n, j]`` names slice ``n``'s ``j``-th tile. The scale/bias rows
    stay *logical* ``(N, rs, nb * chunk)`` with ``rs in (1, T)`` — the
    caller gathers them through the table (they are ~1/D of the code
    traffic), which keeps the kernel's scalar-prefetch surface to the
    table + live lengths.
    """
    N, T, D = q_codes.shape
    rs = qk_scale.shape[1]
    nb = bt.shape[1]
    chunk = k_pool.shape[1]
    last = _last_live_chunk(live, chunk)

    def _at_table(n, j, bt_, lv, lt):
        del lv
        return (bt_[n, jnp.minimum(j, lt[n])], 0, 0)

    def _at_row(n, j, bt_, lv, lt):
        del bt_, lv
        return (n, 0, jnp.minimum(j, lt[n]))

    def _at_slice(n, j, bt_, lv, lt):
        del j, bt_, lv, lt
        return (n, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(N, nb),
        in_specs=[
            pl.BlockSpec((1, T, D), _at_slice),
            pl.BlockSpec((1, chunk, D), _at_table),
            pl.BlockSpec((1, chunk, D), _at_table),
            pl.BlockSpec((1, rs, chunk), _at_row),
            pl.BlockSpec((1, rs, chunk), _at_row),
            pl.BlockSpec((1, rs, chunk), _at_row),
        ],
        out_specs=pl.BlockSpec((1, T, D), _at_slice),
        scratch_shapes=[
            pltpu.VMEM((_N_LIMBS, T, D), jnp.int8),
            pltpu.VMEM((T, 1), jnp.float32),
            pltpu.VMEM((T, 1), jnp.float32),
            pltpu.VMEM((T, D), jnp.float32),
        ],
    )
    return pl.pallas_call(
        functools.partial(_flash_kernel, nsteps=nb, chunk=chunk, fmt=fmt),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((N, T, D), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
    )(bt, live, last, q_codes, k_pool, v_pool, qk_scale, v_scale, bias)


# ---------------------------------------------------------------------------
# jnp reference (emulation path) — same tile step, lax.scan over chunks
# ---------------------------------------------------------------------------


def _flash_ref(q_codes, k_pool, v_pool, bt, live, qk_scale, v_scale, bias,
               fmt: FPFormat):
    """Pure-jnp twin of :func:`_flash_pallas` — table gather via
    ``jnp.take``, dead chunks masked out of the scan carry (selecting the
    old carry is bitwise the kernel's skipped update)."""
    N, T, D = q_codes.shape
    rs = qk_scale.shape[1]
    nb = bt.shape[1]
    chunk = k_pool.shape[1]

    def one(qc, bt_n, live_n, qk, vs, bs):
        lq = _decode_limbs(qc, fmt)
        kc = jnp.take(k_pool, bt_n, axis=0)
        vc = jnp.take(v_pool, bt_n, axis=0)
        qkc = qk.reshape(rs, nb, chunk).transpose(1, 0, 2)
        vsc = vs.reshape(rs, nb, chunk).transpose(1, 0, 2)
        bc = bs.reshape(rs, nb, chunk).transpose(1, 0, 2)

        def step(carry, xs):
            kb, vb, qkb, vsb, bb, j = xs
            upd = _attn_tile_step(lq, kb, vb, qkb, vsb, bb, *carry, fmt)
            keep = j * chunk < live_n
            return tuple(jnp.where(keep, u, c)
                         for u, c in zip(upd, carry)), None

        m0 = jnp.full((T, 1), -jnp.inf, jnp.float32)
        l0 = jnp.zeros((T, 1), jnp.float32)
        o0 = jnp.zeros((T, D), jnp.float32)
        (m, l, o), _ = jax.lax.scan(
            step, (m0, l0, o0),
            (kc, vc, qkc, vsc, bc, jnp.arange(nb, dtype=jnp.int32)))
        return o / jnp.maximum(l, _TINY)

    return jax.vmap(one)(q_codes, bt, live, qk_scale, v_scale, bias)


def _dispatch(q_codes, k_pool, v_pool, bt, live, qk_scale, v_scale, bias,
              fmt: FPFormat, use_kernel: bool, interpret):
    if interpret is None:
        interpret = default_interpret()
    if k_pool.shape[1] > flash_chunk_limit():
        raise ValueError(f"chunk {k_pool.shape[1]} exceeds the int32 "
                         f"class-accumulator bound {flash_chunk_limit()}")
    if qk_scale.ndim == 2:
        # one shared scale/bias row per slice (sequential decode) — the
        # rs == 1 degenerate case of the per-row layout
        qk_scale = qk_scale[:, None]
        v_scale = v_scale[:, None]
        bias = bias[:, None]
    assert qk_scale.shape[1] in (1, q_codes.shape[1]), (
        qk_scale.shape, q_codes.shape)
    live = live.astype(jnp.int32)
    if use_kernel:
        return replicated_kernel_call(
            lambda *a: _flash_pallas(*a, fmt, interpret), q_codes, k_pool,
            v_pool, bt, live, qk_scale, v_scale, bias)
    return _flash_ref(q_codes, k_pool, v_pool, bt, live, qk_scale,
                      v_scale, bias, fmt)


# ---------------------------------------------------------------------------
# public dispatch
# ---------------------------------------------------------------------------


def mgs_flash_attention_ref(q, k_codes, v_codes, qk_scale, v_scale, bias,
                            fmt: FPFormat = E4M3, *, chunk: int = 256,
                            lengths=None):
    """Pure-jnp oracle of :func:`mgs_flash_attention` (``use_kernel=False``
    path). Same signature and — by construction — the same bits."""
    return mgs_flash_attention(q, k_codes, v_codes, qk_scale, v_scale, bias,
                               fmt, chunk=chunk, use_kernel=False,
                               lengths=lengths)


@functools.partial(
    jax.jit, static_argnames=("fmt", "chunk", "use_kernel", "interpret"))
def mgs_flash_attention(q, k_codes, v_codes, qk_scale, v_scale, bias,
                        fmt: FPFormat = E4M3, *, chunk: int = 256,
                        use_kernel: bool = True,
                        interpret: bool | None = None, lengths=None):
    """Flash-style exact-MGS attention over packed-code keys/values.

    Args:
      q: ``(N, T, D)`` **format-exact** FP8 query values
        (``quant.quantize_fp8``; the slice's quantization scale belongs
        in ``qk_scale``). ``N`` flattens whatever leading axes the caller
        has (batch x kv-head x group); every slice attends its own keys.
      k_codes / v_codes: ``(N, S, D)`` uint8 packed cache codes
        (``quant.kvcache.QuantizedKVCache`` planes, flattened the same
        way).
      qk_scale: ``(N, S)`` f32 per-key score multiplier — the caller
        folds the query scale, the cache entry scale, and the
        ``head_dim**-0.5`` softmax scaling into it.
      v_scale: ``(N, S)`` f32 per-key value scale
        (``QuantizedKVCache.v_scale``).
      bias: ``(N, S)`` f32 additive mask row (0 / large-negative),
        shared by every query row of the slice — decode-time masks
        (causal validity, sliding window) depend only on the key
        position, so no per-(head, row) mask tensor is ever
        materialized in HBM.
      fmt: the cache's narrow-exponent FP8 format.
      chunk: keys per online-softmax tile (the kernel grid step; must
        not exceed :func:`flash_chunk_limit`). ``S`` is padded up to a
        multiple with exactly-inert entries (zero codes/scales,
        large-negative bias).
      use_kernel: Pallas kernel (TPU; interpret mode on CPU) vs the
        pure-jnp reference — bit-identical either way.
      interpret: Pallas interpret mode (default: not on TPU).
      lengths: optional ``(N,)`` int32 live key counts. When given,
        chunks whose first key is ``>= lengths[n]`` are skipped (the
        masked-chunk early-exit) — bitwise-identical to the full walk
        whenever the skipped tail is inert (zero codes/scales,
        large-negative bias), which both the engine's zero-initialized
        dense cache and this function's own padding guarantee. ``None``
        walks every chunk (the pre-ragged behavior, bit for bit).

    Returns:
      ``(N, T, D)`` float32 attention outputs,
      ``softmax(qk_scale * (q @ k^T) + bias) @ (v * v_scale)`` with both
      contractions exact under MGS limb summation.
    """
    N, T, D = q.shape
    S = k_codes.shape[1]
    assert k_codes.shape == (N, S, D) and v_codes.shape == (N, S, D), (
        q.shape, k_codes.shape, v_codes.shape)
    assert qk_scale.shape == (N, S) and v_scale.shape == (N, S), (
        qk_scale.shape, v_scale.shape)
    assert bias.shape == (N, S), (bias.shape, (N, S))
    nc = -(-S // chunk)
    Sp = nc * chunk
    pad = Sp - S
    q_codes = encode_bits(q, fmt)
    if pad:
        # inert padding: zero codes and scales, large-negative bias —
        # padded keys contribute exact zeros to every running quantity
        k_codes = jnp.pad(k_codes, ((0, 0), (0, pad), (0, 0)))
        v_codes = jnp.pad(v_codes, ((0, 0), (0, pad), (0, 0)))
        qk_scale = jnp.pad(qk_scale, ((0, 0), (0, pad)))
        v_scale = jnp.pad(v_scale, ((0, 0), (0, pad)))
        bias = jnp.pad(bias, ((0, 0), (0, pad)), constant_values=-1e30)
    # the contiguous cache is a degenerate pool: slice n's chunk j is
    # physical tile n * nc + j (identity block table)
    k_pool = k_codes.reshape(N * nc, chunk, D)
    v_pool = v_codes.reshape(N * nc, chunk, D)
    bt = jnp.arange(N * nc, dtype=jnp.int32).reshape(N, nc)
    if lengths is None:
        live = jnp.full((N,), Sp, jnp.int32)
    else:
        assert lengths.shape == (N,), (lengths.shape, N)
        live = jnp.clip(lengths.astype(jnp.int32), 0, Sp)
    return _dispatch(q_codes, k_pool, v_pool, bt, live, qk_scale, v_scale,
                     bias, fmt, use_kernel, interpret)


@functools.partial(
    jax.jit, static_argnames=("fmt", "use_kernel", "interpret"))
def mgs_paged_flash_attention(q, k_pool, v_pool, block_table, lengths,
                              qk_scale, v_scale, bias,
                              fmt: FPFormat = E4M3, *,
                              use_kernel: bool = True,
                              interpret: bool | None = None):
    """Flash-style exact-MGS attention over a **paged** packed-code pool.

    The paged twin of :func:`mgs_flash_attention`: keys/values live in a
    physical block pool shared by every slice, and each slice walks its
    own block table — the serving engine's continuous-batching layout
    (``quant.kvcache.PagedKVCache``), where a slot's logical cache is
    scattered over whatever blocks the allocator handed it.

    Args:
      q: ``(N, T, D)`` format-exact FP8 query values.
      k_pool / v_pool: ``(P, bs, D)`` uint8 physical code pools —
        ``bs`` (the block size) is the kernel's chunk; the caller
        flattens per-head pools into the leading ``P`` axis
        (``PagedKVCache`` planes are ``(P, KV, bs, hd)``, a pure
        reshape).
      block_table: ``(N, nb)`` int32 physical tile ids —
        ``pool[block_table[n, j]]`` holds keys
        ``[j * bs, (j + 1) * bs)`` of slice ``n``. Entries past
        ``ceil(lengths[n] / bs)`` are never read (their DMAs are
        clamped to the last live tile and their updates gated off), so
        free slots may leave their tables zeroed.
      lengths: ``(N,)`` int32 live key counts (0 = dead slice: the
        output row is exactly zero).
      qk_scale / v_scale / bias: ``(N, nb * bs)`` f32 *logical* rows,
        exactly as in the dense entry point — the caller gathers scale
        rows through the table (``gather_paged_kv`` rows are ~1/D of
        the code traffic) and computes bias from positions.
      fmt / use_kernel / interpret: as in :func:`mgs_flash_attention`.

    Returns:
      ``(N, T, D)`` float32 attention outputs. Bitwise-identical to
      running the dense kernel over the gathered contiguous cache with
      the same ``lengths`` (and hence to an isolated single-request
      dense run — the continuous-batching determinism contract,
      ``tests/test_continuous.py``).
    """
    N, T, D = q.shape
    P, bs, Dp = k_pool.shape
    nb = block_table.shape[1]
    S = nb * bs
    assert Dp == D and v_pool.shape == (P, bs, D), (k_pool.shape,
                                                    v_pool.shape, q.shape)
    assert block_table.shape == (N, nb), (block_table.shape, N)
    assert lengths.shape == (N,), (lengths.shape, N)
    assert qk_scale.shape == (N, S) and v_scale.shape == (N, S), (
        qk_scale.shape, v_scale.shape, (N, S))
    assert bias.shape == (N, S), (bias.shape, (N, S))
    q_codes = encode_bits(q, fmt)
    live = jnp.clip(lengths.astype(jnp.int32), 0, S)
    return _dispatch(q_codes, k_pool, v_pool,
                     block_table.astype(jnp.int32), live, qk_scale,
                     v_scale, bias, fmt, use_kernel, interpret)


@functools.partial(
    jax.jit, static_argnames=("fmt", "use_kernel", "interpret"))
def mgs_paged_verify_attention(q, k_pool, v_pool, block_table, lengths,
                               qk_scale, v_scale, bias,
                               fmt: FPFormat = E4M3, *,
                               use_kernel: bool = True,
                               interpret: bool | None = None):
    """Multi-query (T > 1) verify attention over the paged pool.

    The speculative-decoding verify step scores ``T`` candidate
    positions of every slice in one launch, and — the step's perf
    contract — walks each slice's KV blocks **once**, not ``T`` times:
    all ``T * R`` query rows of a slice (``T`` candidate tokens x their
    GQA group of ``R`` rows) batch into a single kernel slice that
    shares every chunk's limb decode, while the score scale and mask
    bias stay *per row* (token ``t`` folds its own query quantization
    scale and its own causal-horizon mask). Without the sharing, verify
    costs ``T`` sequential steps of attention and speculation cannot
    beat sequential decode.

    Bitwise identity to ``T`` sequential decode steps survives the
    batching because nothing in the tile step couples rows: the q @ k^T
    and p @ v limb contractions are integer-exact per row, and the
    online softmax, score scaling, and weight re-quantization are
    row-wise. The one asymmetry — a row whose causal horizon ends
    before the slice's last live chunk still *walks* the tail chunks
    the sequential step at that position never would — is an exact
    no-op on its running state: the caller's bias holds every key past
    a token's horizon at ``-1e30``, which absorbs any finite score
    exactly, and since every live token attends at least its own
    freshly-appended position, its running max stays a finite real
    score; masked keys then contribute ``exp(-1e30 - m) == 0.0``
    exactly, and ``l * 1.0 + 0.0`` / ``o * 1.0 + 0.0`` are IEEE
    identities (``tests/test_paged_kv.py`` pins this per token).

    Args:
      q: ``(N, T, R, D)`` format-exact FP8 query values — ``T``
        candidate tokens x ``R`` query rows per token (the GQA group of
        the slice's kv head; sequential decode is the ``T == 1``
        degenerate case).
      k_pool / v_pool: ``(P, bs, D)`` uint8 physical code pools, as in
        :func:`mgs_paged_flash_attention`.
      block_table: ``(N, nb)`` int32 physical tile ids — shared by all
        ``T`` tokens of a slice (candidates extend the same logical
        sequence).
      lengths: ``(N, T)`` int32 per-token live key counts
        (``pos + t + 1`` for live slots, 0 for dead ones). The slice
        walks to the *largest* horizon; shorter tokens' tails are
        bias-masked (see above).
      qk_scale / v_scale / bias: ``(N, T, nb * bs)`` f32 logical rows,
        per token — ``qk_scale`` folds each token's own query
        quantization scale; ``bias`` must hold every key past token
        ``t``'s horizon at the mask floor (the model's causal +
        sentinel mask does).
      fmt / use_kernel / interpret: as in :func:`mgs_flash_attention`.

    Returns:
      ``(N, T, R, D)`` float32 attention outputs.
    """
    N, T, R, D = q.shape
    P, bs, Dp = k_pool.shape
    nb = block_table.shape[1]
    S = nb * bs
    assert Dp == D and v_pool.shape == (P, bs, D), (k_pool.shape,
                                                    v_pool.shape, q.shape)
    assert block_table.shape == (N, nb), (block_table.shape, N)
    assert lengths.shape == (N, T), (lengths.shape, (N, T))
    assert qk_scale.shape == (N, T, S) and v_scale.shape == (N, T, S), (
        qk_scale.shape, v_scale.shape, (N, T, S))
    assert bias.shape == (N, T, S), (bias.shape, (N, T, S))
    # one slice per pool row, T * R query rows each, token-major — every
    # chunk's KV limb decode is shared by all T tokens of the slice
    q_codes = encode_bits(q, fmt).reshape(N, T * R, D)
    # per-row scale/bias: token t's logical row serves its R query rows
    qk = jnp.repeat(qk_scale, R, axis=1)
    vs = jnp.repeat(v_scale, R, axis=1)
    bias_r = jnp.repeat(bias, R, axis=1)
    # walk to the farthest causal horizon of the slice (token T - 1);
    # dead slots report 0 everywhere and stay exactly zero
    live = jnp.clip(lengths.astype(jnp.int32), 0, S).max(axis=1)
    out = _dispatch(q_codes, k_pool, v_pool,
                    block_table.astype(jnp.int32), live, qk, vs, bias_r,
                    fmt, use_kernel, interpret)
    return out.reshape(N, T, R, D)
