"""Attention: GQA/MQA/MHA, causal / sliding-window / bidirectional / cross,
dense or online-softmax KV-chunked, with decode KV caches — float
(:class:`KVCache`) or packed-FP8 (:class:`repro.quant.QuantizedKVCache`,
decode served by the MGS flash-decode kernel).

One code path serves every arch in the pool: gemma3's 5:1 local:global
pattern is a *traced* per-layer flag selecting the window mask (so the
layer stack can still be a homogeneous ``lax.scan``), whisper's encoder
uses ``bidirectional=True`` and its decoder passes ``cross_kv``.
"""

from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.kernels.mgs_attention import (mgs_flash_attention,
                                         mgs_paged_flash_attention,
                                         mgs_paged_verify_attention)
from repro.parallel.sharding import constrain, on_multi_device_mesh
from repro.quant import (PagedKVCache, QuantizedKVCache, append_kv,
                         paged_append_kv, qeinsum)
from repro.quant.quantize import QTensor, quantize_fp8, quantize_fp8_static
from .common import ParamFactory, apply_rope
from .linear import proj

__all__ = ["attention_init", "attention_apply", "KVCache"]

_NEG_INF = -1e30
# Sentinel key position marking invalid cache slots / chunk padding:
# beyond every reachable query position, so the mask bounds kill it for
# causal *and* bidirectional attention.
_POS_SENTINEL = 2**30


class KVCache(NamedTuple):
    k: jnp.ndarray  # (B, S_max, KV, hd)
    v: jnp.ndarray  # (B, S_max, KV, hd)


def attention_init(f: ParamFactory, cfg: ModelConfig, cross: bool = False):
    d, H, KV, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    f.normal("wq", (d, H, hd), ("embed", "heads", "head_dim"))
    f.normal("wk", (d, KV, hd), ("embed", "kv_heads", "head_dim"))
    f.normal("wv", (d, KV, hd), ("embed", "kv_heads", "head_dim"))
    f.normal("wo", (H, hd, d), ("heads", "head_dim", "embed"),
             scale=1.0 / (H * hd) ** 0.5)


def _mask(q_pos, k_pos, *, causal: bool, window: int, is_global):
    """(..., Tq, Tk) additive mask from position vectors."""
    dq = q_pos[..., :, None]
    dk = k_pos[..., None, :]
    ok = jnp.ones(jnp.broadcast_shapes(dq.shape, dk.shape), bool)
    if causal:
        ok &= dk <= dq
    if window > 0:
        in_win = dq - dk < window
        ok &= jnp.where(is_global, True, in_win)
    return jnp.where(ok, 0.0, _NEG_INF)


def _sdpa_dense(q, k, v, bias, quant=None):
    """q: (B,T,KV,G,hd)  k/v: (B,S,KV,hd)  bias: (B,1,1,T,S) or (B,T,S).

    With an fp8 ``quant`` config the score and value contractions route
    through the unified quantized-einsum dispatch, so they accumulate
    under the same numerics as the weight matmuls — required for the
    cross-mesh bit-identity guarantee (docs/serving.md): a float dot's
    accumulation order depends on the local operand shape, so a
    batch-sharded mesh would diverge from the single device at float
    level. Routing covers *all* fp8 accums (not just mgs_exact) so the
    wide/swamp baselines quantize the same operand set as MGS and the
    accuracy comparison isolates accumulation alone. The integer
    emulation modes (int4/int8 clip/wrap) keep float attention — their
    research contract quantizes linear-layer operands only. The chunked
    prefill path (``_sdpa_chunked``) applies the same fp8 routing inside
    its online-softmax scan.
    """
    scale = q.shape[-1] ** -0.5
    if quant is None or not quant.is_fp8:
        scores = jnp.einsum("btkgh,bskh->bkgts", q, k,
                            preferred_element_type=jnp.float32) * scale
        scores = scores + bias
        w = jax.nn.softmax(scores.astype(jnp.float32),
                           axis=-1).astype(q.dtype)
        return jnp.einsum("bkgts,bskh->btkgh", w, v)
    from .common import pairwise_sum_last
    scores = qeinsum("btkgh,bskh->bkgts", q, k, quant,
                     site="attn.scores", out_dtype=jnp.float32) * scale
    scores = scores + bias
    # shape-independent softmax: max is exactly associative, but the
    # denominator sum is an XLA reduce whose grouping varies with the
    # local (mesh-dependent) batch shape — use the deterministic
    # pairwise tree instead (see pairwise_sum_last / docs/serving.md).
    m = jnp.max(scores, axis=-1, keepdims=True)
    e = jnp.exp(scores - m)
    w = (e / pairwise_sum_last(e)[..., None]).astype(q.dtype)
    return qeinsum("bkgts,bskh->btkgh", w, v, quant, site="attn.values",
                   out_dtype=q.dtype)


def _sdpa_chunked(q, k, v, q_pos, k_pos, *, causal, window, is_global,
                  chunk: int, quant=None):
    """Online-softmax attention over KV chunks (flash-style, pure lax.scan).

    Keeps peak memory at O(T * chunk) instead of O(T * S) — required for
    the 32k-prefill cells and available to training via cfg.attn_chunk.

    The key length must be chunk-aligned: ``attention_apply`` owns the
    padding (masked sentinel positions via :func:`_pad_kv_to_chunk`), so
    an unaligned ``S`` here is a caller bug, not something to paper over
    — the old silent zero-padding attended the padded keys in the
    bidirectional (whisper-encoder) case.

    The mask is folded to per-query position bounds ``lo <= k_pos <= hi``
    hoisted out of the scan body (the old body rebuilt the full mask
    tensor per chunk); the ``hi`` bound also kills the sentinel for
    non-causal attention. The softmax denominator uses the
    shape-independent pairwise tree on *every* config (float chunked
    training shifts by reassociation ulps vs the old ``jnp.sum``, and in
    exchange is mesh-invariant), and with an fp8 ``quant`` the
    score/value contractions additionally route through ``qeinsum``
    (sites ``attn.scores`` / ``attn.values``) — extending the cross-mesh
    bit-identity guarantee to the chunked-prefill path (docs/serving.md).
    """
    from .common import pairwise_sum_last
    B, T, KV, G, hd = q.shape
    S = k.shape[1]
    if S % chunk:
        raise ValueError(
            f"chunked attention needs a chunk-aligned key length: "
            f"S={S} % attn_chunk={chunk} != 0. Pad K/V with masked "
            f"sentinel positions first (attention_apply does) or pick "
            f"an attn_chunk dividing the padded prompt/cache length.")
    n_chunks = S // chunk
    kc = k.reshape(B, n_chunks, chunk, KV, hd).transpose(1, 0, 2, 3, 4)
    vc = v.reshape(B, n_chunks, chunk, KV, hd).transpose(1, 0, 2, 3, 4)
    pc = k_pos.reshape(B, n_chunks, chunk).transpose(1, 0, 2)
    scale = hd ** -0.5
    # hoisted visibility bounds: a key at position kp is visible from the
    # query at qp iff lo <= kp <= hi. Both depend only on the query side,
    # so they are computed once, outside the scan body.
    dq = q_pos[..., :, None]                               # (B, T, 1)
    hi = dq if causal else jnp.full_like(dq, _POS_SENTINEL - 1)
    if window > 0:
        lo = jnp.where(is_global, -_POS_SENTINEL, dq - window + 1)
    else:
        lo = jnp.full_like(dq, -_POS_SENTINEL)
    fp8 = quant is not None and quant.is_fp8

    def step(carry, xs):
        m, l, o = carry
        kb, vb, pb = xs
        if fp8:
            s = qeinsum("btkgh,bskh->bkgts", q, kb, quant,
                        site="attn.scores", out_dtype=jnp.float32) * scale
        else:
            s = jnp.einsum("btkgh,bskh->bkgts", q, kb,
                           preferred_element_type=jnp.float32) * scale
        dk = pb[:, None, :]                                # (B, 1, chunk)
        ok = (dk <= hi) & (dk >= lo)
        s = s + jnp.where(ok, 0.0, _NEG_INF)[:, None, None]
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[..., None])
        l_new = l * alpha + pairwise_sum_last(p)
        if fp8:
            pv = qeinsum("bkgts,bskh->bkgth", p.astype(q.dtype), vb, quant,
                         site="attn.values", out_dtype=jnp.float32)
        else:
            pv = jnp.einsum("bkgts,bskh->bkgth", p.astype(q.dtype),
                            vb).astype(jnp.float32)
        o_new = o * alpha[..., None] + pv
        return (m_new, l_new, o_new), None

    m0 = jnp.full((B, KV, G, T), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, KV, G, T), jnp.float32)
    o0 = jnp.zeros((B, KV, G, T, hd), jnp.float32)
    # remat the chunk body: backward recomputes the (T x chunk) score tile
    # instead of stashing one per chunk — the flash-attention memory shape.
    (m, l, o), _ = jax.lax.scan(jax.checkpoint(step), (m0, l0, o0),
                                (kc, vc, pc))
    out = o / jnp.maximum(l, 1e-30)[..., None]
    return out.transpose(0, 3, 1, 2, 4).astype(q.dtype)  # (B,T,KVG,hd)


def _pad_kv_to_chunk(k, v, k_pos, chunk: int):
    """Pad keys/values to a chunk multiple with masked sentinel positions.

    The sentinel (``_POS_SENTINEL``) exceeds every ``hi`` bound in
    ``_sdpa_chunked``, so padded keys are masked for causal *and*
    bidirectional attention (the old zero-padding was attended by the
    whisper encoder).
    """
    S = k.shape[1]
    pad = -S % chunk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        k_pos = jnp.pad(k_pos, ((0, 0), (0, pad)),
                        constant_values=_POS_SENTINEL)
    return k, v, k_pos


#: Calibration site of the decode-query quantization. During a
#: calibration pass :func:`repro.quant.calibrate.observe_amax` records
#: its running absmax; the table emits it as ``"attn.q.amax"``, which
#: ``QuantConfig.static_q_scale`` consumers read back here.
_Q_SITE = "attn.q"


def _quantize_decode_q(q2, quant, batch: int | None = None) -> QTensor:
    """Per-row decode-query quantization — dynamic absmax or calibrated.

    ``q2``: ``(N, K)`` float query rows (one per kernel slice). The
    dynamic path is ``quantize_fp8(axis=1)`` — a per-step absmax reduce
    over every row. With ``quant.static_q_scale`` and a calibrated
    ``"attn.q.amax"`` entry on the config, the reduce is replaced by a
    *fixed* scale derived from the calibrated absmax
    (:func:`repro.quant.quantize.quantize_fp8_static`): rows are clipped
    into the calibrated range and rounded with the same jit-compiled f32
    scale-division as the dynamic path, so any row whose own absmax
    equals the calibrated value produces bit-identical codes and scale
    (``tests/test_kvcache.py`` pins this), and rows within the range
    differ only by the scale the dynamic path would have *chosen* — the
    standard static-quantization contract. Falls back to dynamic when no
    calibrated entry exists.

    An active ``quant.calibrate.applied_calib_state`` context overrides
    the config entry with its ``"q_amax"`` array — a runtime value
    flowing through the engine's jitted step, so a hot-swapped table
    re-scales with zero retraces. Scalar ``q_amax`` applies to every
    row; a per-slot ``(B,)`` vector (continuous engine) is expanded to
    this call's rows via ``batch`` (the leading slot count ``N`` is a
    multiple of). Entries ``<= 0`` select the dynamic per-row reduce
    for that row, bit-identically to ``quantize_fp8(axis=1)`` — that is
    how a request admitted under an amax-free table keeps its dynamic
    scales while a co-resident slot uses its pinned static one.
    """
    fmt = quant.kv_fmt
    from repro.quant.calibrate import current_calib_state, observe_amax
    observe_amax(_Q_SITE, q2)
    if quant.static_q_scale:
        cs = current_calib_state()
        if cs is not None and "q_amax" in cs:
            a = jnp.asarray(cs["q_amax"], jnp.float32)
            if a.ndim == 0:
                rows = jnp.broadcast_to(a, (q2.shape[0], 1))
            else:
                rows = jnp.repeat(a, q2.shape[0] // batch).reshape(-1, 1)
            # dynamic fallback rows: replicate quantize_fp8's reduce
            # exactly (same maximum-with-tiny guard) so a <= 0 entry is
            # bit-identical to the dynamic path
            dyn = jnp.maximum(
                jnp.max(jnp.abs(q2.astype(jnp.float32)), axis=1,
                        keepdims=True),
                jnp.finfo(jnp.float32).tiny)
            return quantize_fp8_static(q2, fmt, jnp.where(rows > 0.0,
                                                          rows, dyn))
        amax = quant.act_sigma(_Q_SITE + ".amax")
    else:
        amax = None
    if amax is None or amax <= 0.0:
        return quantize_fp8(q2, fmt, axis=1)
    return quantize_fp8_static(q2, fmt, amax)


def _sdpa_packed_cache(q, cache: QuantizedKVCache, bias, quant,
                       lengths=None):
    """Decode attention over the packed-FP8 cache: the MGS flash kernel.

    q: (B, T=1, KV, G, hd) compute-dtype queries. cache planes:
    (B, KV, S, hd) uint8 codes + (B, KV, S) scales — heads before
    sequence, so every kernel operand below is a *reshape* of the cache
    (no cache-sized transpose/copy in the hot loop). bias: (B, 1, S) —
    the decode mask depends only on the key position, so it is passed
    to the kernel as one per-key row per (batch, kv-head) slice, never
    materialized per (head, query-row).

    The query is quantized once per (batch, kv-head) slice — the same
    granularity the dense path's qeinsum batch dims give it — and its
    scale, the per-entry cache scales, and the ``head_dim**-0.5``
    softmax scaling are folded into the kernel's per-key score
    multiplier. Both contractions then run the exact MGS limb path over
    packed codes (:func:`repro.kernels.mgs_attention.mgs_flash_attention`
    — 1 byte/element of cache HBM traffic, no score round-trips), and
    every reduction is shape-independent, so the cross-mesh bit-identity
    guarantee covers the packed-cache decode step.

    ``lengths`` (``(B,)`` live key counts) turns on the kernel's
    masked-chunk early-exit: chunks past a row's live prefix are
    skipped, bitwise-identical to walking them because the cache's
    unwritten tail is exactly inert (zero codes and scales from
    ``init_quantized_kv``, large-negative bias from the validity mask).
    """
    B, T, KV, G, hd = q.shape
    S = cache.k_codes.shape[2]
    fmt = quant.kv_fmt
    # (B, T, KV, G, hd) -> (B*KV, G*T, hd) rows; per-slice quantization
    # (q is one token's projections — this transpose is O(B*H*hd))
    q2 = q.transpose(0, 2, 3, 1, 4).reshape(B * KV, G * T * hd)
    qt = _quantize_decode_q(q2, quant, batch=B)
    qvals = qt.q.reshape(B * KV, G * T, hd)
    if quant.accum in ("mgs_exact", "mgs_dmac"):
        from repro.quant.calibrate import observe
        observe("attn.scores", qvals, fmt)
    ks = cache.k_scale.reshape(B * KV, S)
    vs = cache.v_scale.reshape(B * KV, S)
    qk = (qt.scale * ks) * (hd ** -0.5)
    kc = cache.k_codes.reshape(B * KV, S, hd)
    vc = cache.v_codes.reshape(B * KV, S, hd)
    bias2 = jnp.broadcast_to(bias.reshape(B, 1, S), (B, KV, S)).reshape(
        B * KV, S)
    live = (None if lengths is None
            else jnp.repeat(lengths.astype(jnp.int32), KV))
    out = mgs_flash_attention(qvals, kc, vc, qk, vs, bias2, fmt,
                              chunk=quant.block_k,
                              use_kernel=quant.use_kernel, lengths=live)
    return out.reshape(B, KV, G, T, hd).transpose(0, 3, 1, 2, 4).astype(
        q.dtype)


def _paged_operands(cache: PagedKVCache, block_table, layer):
    """Layer ``layer``'s paged-kernel operands from the stacked pool.

    ``cache`` planes are the stacked ``(La, P, KV, bs, hd)`` pool the
    paged steps carry through their layer scan. On one device the
    kernel reads the whole pool in place: ``(La * P * KV, bs, hd)`` is a
    pure reshape, and layer ``l``'s block ``p`` head ``h`` is tile
    ``(l * P + p) * KV + h``, so no layer slice is ever materialized.
    A multi-device mesh gathers every kernel operand
    (:func:`repro.parallel.sharding.replicated_kernel_call`), so there
    the one layer is sliced out first, still sharded, rather than
    gathering ``La`` times the bytes. Either way the kernel sees the
    same bytes at the same tiles.

    Returns ``(k_tiles, v_tiles, tile_ids (B*KV, nb), k_scale_rows,
    v_scale_rows)``; the scale rows are gathered into logical
    ``(B*KV, nb * bs)`` order (~1/hd of the code bytes), because they
    fold into the per-key score/value multipliers before the launch.
    """
    P, KV, bs, hd = cache.k_codes.shape[1:]
    if on_multi_device_mesh():
        dims = ("blocks", "kv_heads", "block", "head_dim")
        cache = PagedKVCache(*(constrain(jax.lax.dynamic_index_in_dim(
            plane, layer, keepdims=False), dims[:plane.ndim - 1])
            for plane in cache))
        base = 0
    else:
        base = layer * P
    tiles = block_table.astype(jnp.int32) + base
    B, nb = tiles.shape

    def rows(scale):
        r = jnp.take(scale.reshape(-1, KV, bs), tiles.reshape(-1), axis=0)
        return r.reshape(B, nb, KV, bs).transpose(0, 2, 1, 3).reshape(
            B * KV, nb * bs)

    tile_ids = (tiles[:, None, :] * KV
                + jnp.arange(KV, dtype=jnp.int32)[None, :, None]).reshape(
                    B * KV, nb)
    return (cache.k_codes.reshape(-1, bs, hd),
            cache.v_codes.reshape(-1, bs, hd), tile_ids,
            rows(cache.k_scale), rows(cache.v_scale))


def _sdpa_paged_cache(q, cache: PagedKVCache, block_table, bias, lengths,
                      quant, layer):
    """Decode attention over the paged pool: the block-table MGS kernel.

    The paged twin of :func:`_sdpa_packed_cache`. Codes never move — the
    kernel (:func:`repro.kernels.mgs_attention.mgs_paged_flash_attention`)
    walks each slot's blocks of layer ``layer`` of the stacked pool
    through a scalar-prefetched table (:func:`_paged_operands`).
    ``lengths`` are the per-slot live key counts (0 = free slot: that
    row's every chunk is gated off and its output is exactly zero).
    Per-slice query scales + per-entry cache scales + the gated walk
    make each row's output a function of that slot's own history alone —
    the continuous-batching invariance contract.
    """
    B, T, KV, G, hd = q.shape
    S = block_table.shape[1] * cache.k_codes.shape[-2]
    fmt = quant.kv_fmt
    q2 = q.transpose(0, 2, 3, 1, 4).reshape(B * KV, G * T * hd)
    qt = _quantize_decode_q(q2, quant, batch=B)
    qvals = qt.q.reshape(B * KV, G * T, hd)
    if quant.accum in ("mgs_exact", "mgs_dmac"):
        from repro.quant.calibrate import observe
        observe("attn.scores", qvals, fmt)
    kp, vp, bt_nk, ks, vs = _paged_operands(cache, block_table, layer)
    qk = (qt.scale * ks) * (hd ** -0.5)
    live = jnp.repeat(lengths.astype(jnp.int32), KV)
    bias2 = jnp.broadcast_to(bias.reshape(B, 1, S), (B, KV, S)).reshape(
        B * KV, S)
    out = mgs_paged_flash_attention(qvals, kp, vp, bt_nk, live, qk, vs,
                                    bias2, fmt,
                                    use_kernel=quant.use_kernel)
    return out.reshape(B, KV, G, T, hd).transpose(0, 3, 1, 2, 4).astype(
        q.dtype)


def _sdpa_paged_verify(q, cache: PagedKVCache, block_table, bias,
                       positions, lengths, quant, layer):
    """Multi-query (T > 1) verify attention over the paged pool.

    The speculative verify step's twin of :func:`_sdpa_paged_cache`.
    Every (slot, kv-head, token) triple is its own kernel slice: the
    query is quantized per ``(G * hd)`` row-slice — **exactly** the
    granularity the sequential ``T == 1`` decode step uses, so token
    ``t``'s quantized query (and hence its scores, softmax, and output)
    is bit-identical to the sequential decode step at position
    ``pos + t``. Per-token live lengths give each token its own causal
    horizon over the freshly appended candidate entries; the mask bias
    is already per-token.

    ``positions``: ``(B, T)`` query positions (``pos + t``); a token's
    live key count is ``positions + 1`` (its prefix plus itself),
    gated to 0 for dead slots (``lengths == 0``).
    """
    B, T, KV, G, hd = q.shape
    S = block_table.shape[1] * cache.k_codes.shape[-2]
    fmt = quant.kv_fmt
    # (B, T, KV, G, hd) -> (B*KV*T, G*hd) rows, token-fastest — the
    # sequential decode step's per-slice quantization granularity
    q2 = q.transpose(0, 2, 1, 3, 4).reshape(B * KV * T, G * hd)
    qt = _quantize_decode_q(q2, quant, batch=B)
    qvals = qt.q.reshape(B * KV, T, G, hd)
    if quant.accum in ("mgs_exact", "mgs_dmac"):
        from repro.quant.calibrate import observe
        observe("attn.scores", qvals, fmt)
    kp, vp, bt_nk, ks, vs = _paged_operands(cache, block_table, layer)
    qk = qt.scale.reshape(B * KV, T, 1) * ks[:, None, :] * (hd ** -0.5)
    vs3 = jnp.broadcast_to(vs[:, None, :], (B * KV, T, S))
    # per-token causal horizons: token t's live keys end at positions+1
    live_t = jnp.where(lengths[:, None] > 0,
                       positions.astype(jnp.int32) + 1, 0)
    live = jnp.repeat(live_t, KV, axis=0)
    bias3 = jnp.broadcast_to(bias.reshape(B, 1, T, S),
                             (B, KV, T, S)).reshape(B * KV, T, S)
    out = mgs_paged_verify_attention(qvals, kp, vp, bt_nk, live, qk, vs3,
                                     bias3, fmt,
                                     use_kernel=quant.use_kernel)
    return out.reshape(B, KV, T, G, hd).transpose(0, 2, 1, 3, 4).astype(
        q.dtype)


def attention_apply(p, x, cfg: ModelConfig, *, positions,
                    is_global=True, causal: bool = True,
                    cache: Optional[KVCache] = None,
                    cache_pos=None,
                    cross_kv: Optional[KVCache] = None,
                    kv_positions=None, block_table=None, lengths=None,
                    layer=None):
    """Self- or cross-attention.

    x: (B, T, d). positions: (B, T) int32 token positions of the queries.
    cache: decode-time KV cache — a float :class:`KVCache`, a
    packed-code :class:`repro.quant.QuantizedKVCache`, or a paged
    :class:`repro.quant.PagedKVCache` pool; new K/V are written at
    ``cache_pos``. With the packed cache, the decode step (T == 1)
    attends the cache *codes* through the MGS flash-decode kernel
    (:mod:`repro.kernels.mgs_attention`); prefill (T > 1) attends the
    freshly-projected float K/V and only *stores* them quantized. With
    the paged pool (decode-only), ``cache`` is the stacked
    ``(La, P, KV, bs, hd)`` pool and ``layer`` the traced index of this
    layer in it, ``cache_pos`` is a per-slot ``(B,)`` position vector,
    ``block_table`` ``(B, nb)`` names each slot's physical blocks and
    ``lengths`` ``(B,)`` its live key count (0 = free slot); the whole
    stacked pool comes back with this layer's entries appended.
    cross_kv: precomputed encoder K/V (whisper decoder) — overrides
    self-attention K/V entirely.
    Returns (out (B, T, d), new_cache | None).
    """
    B, T, d = x.shape
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    G = H // KV

    q = proj(x, p["wq"], cfg.quant, site="attn.wq")       # (B,T,H,hd)
    q = apply_rope(q, positions, cfg.rope_theta)
    q = q.reshape(B, T, KV, G, hd)

    new_cache = None
    packed_out = None
    if isinstance(cross_kv, QuantizedKVCache):
        # packed encoder K/V (written once at prefill, quant.kvcache):
        # decode attends the codes through the MGS flash kernel — the
        # self-attention packed contract applied to cross-attention, so
        # encoder-decoder decode stops streaming a float cross cache.
        if T != 1:
            raise NotImplementedError(
                "packed cross-attention is decode-only (T == 1): the "
                "decoder prefill attends the fresh float encoder K/V "
                "and only stores them quantized")
        S = cross_kv.k_codes.shape[2]
        enc_len = cfg.encoder_len
        k_pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None],
                                 (B, S))
        k_pos = jnp.where(k_pos < enc_len, k_pos, _POS_SENTINEL)
        bias3 = _mask(positions, k_pos, causal=False, window=cfg.window,
                      is_global=is_global)
        packed_out = _sdpa_packed_cache(
            q, cross_kv, bias3, cfg.quant,
            lengths=jnp.full((B,), enc_len, jnp.int32))
    elif cross_kv is not None:
        k, v = cross_kv.k, cross_kv.v
        k_pos = (jnp.zeros((B, k.shape[1]), jnp.int32)
                 + jnp.arange(k.shape[1], dtype=jnp.int32)
                 if kv_positions is None else kv_positions)
        causal = False
    else:
        k = proj(x, p["wk"], cfg.quant, site="attn.wk")   # (B,T,KV,hd)
        k = apply_rope(k, positions, cfg.rope_theta)
        v = proj(x, p["wv"], cfg.quant, site="attn.wv")
        if isinstance(cache, PagedKVCache):
            # decode (T == 1) or speculative verify (T == k): append all
            # T candidate entries through the block table, then attend.
            # Prompts still enter the pool via slot adoption
            # (models.adopt_slot); this path extends live sequences only.
            new_cache = paged_append_kv(cache, k, v, cache_pos,
                                        block_table, cfg.quant.kv_fmt,
                                        layer=layer)
            S = block_table.shape[1] * cache.k_codes.shape[-2]
            k_pos = jnp.broadcast_to(
                jnp.arange(S, dtype=jnp.int32)[None], (B, S))
            valid = k_pos <= positions[:, -1:]
            k_pos = jnp.where(valid, k_pos, _POS_SENTINEL)
            bias3 = _mask(positions, k_pos, causal=causal,
                          window=cfg.window, is_global=is_global)
            if T == 1:
                packed_out = _sdpa_paged_cache(q, new_cache, block_table,
                                               bias3, lengths, cfg.quant,
                                               layer)
            else:
                packed_out = _sdpa_paged_verify(q, new_cache, block_table,
                                                bias3, positions, lengths,
                                                cfg.quant, layer)
        elif isinstance(cache, QuantizedKVCache):
            # packed cache: re-quantize ONLY the new entries (per-entry
            # scales — old codes are bit-frozen, see quant.kvcache)
            new_cache = append_kv(cache, k, v, cache_pos, cfg.quant.kv_fmt)
            if T == 1:
                # decode: stream the cache codes through the MGS
                # flash-decode kernel
                S = cache.k_codes.shape[2]
                k_pos = jnp.broadcast_to(
                    jnp.arange(S, dtype=jnp.int32)[None], (B, S))
                valid = k_pos <= positions[:, -1:]
                k_pos = jnp.where(valid, k_pos, _POS_SENTINEL)
                bias3 = _mask(positions, k_pos, causal=causal,
                              window=cfg.window, is_global=is_global)
                # masked-chunk early-exit: live keys end at the decode
                # position (the unwritten tail is zero-inert, so
                # skipping it is bitwise-identical to walking it)
                packed_out = _sdpa_packed_cache(
                    q, new_cache, bias3, cfg.quant,
                    lengths=positions[:, -1] + 1)
            else:
                # prefill: attend the fresh float K/V (the cache stores
                # them quantized for the decode steps to come). This is
                # a from-scratch prefill contract — attending ONLY the
                # fresh K/V is wrong for a continued prefill over an
                # already-populated cache, so reject that shape instead
                # of silently dropping the cached context.
                if not (isinstance(cache_pos, int) and cache_pos == 0):
                    raise NotImplementedError(
                        "packed-cache prefill (T > 1) supports "
                        "cache_pos == 0 only: a continued prefill would "
                        "need to attend the cached codes as well")
                k_pos = positions
        elif cache is not None:
            # decode: write the new entries at cache_pos, attend over cache
            k = jax.lax.dynamic_update_slice(
                cache.k, k.astype(cache.k.dtype), (0, cache_pos, 0, 0))
            v = jax.lax.dynamic_update_slice(
                cache.v, v.astype(cache.v.dtype), (0, cache_pos, 0, 0))
            new_cache = KVCache(k, v)
            S = k.shape[1]
            k_pos = jnp.broadcast_to(
                jnp.arange(S, dtype=jnp.int32)[None], (B, S))
            # entries beyond the decode position are invalid
            valid = k_pos <= positions[:, -1:]
            k_pos = jnp.where(valid, k_pos, _POS_SENTINEL)
        else:
            k_pos = positions

    if packed_out is not None:
        out = packed_out
    elif cfg.attn_chunk and T > 1:
        kp, vp, k_pos_p = _pad_kv_to_chunk(k.astype(q.dtype),
                                           v.astype(q.dtype), k_pos,
                                           cfg.attn_chunk)
        out = _sdpa_chunked(q, kp, vp, positions, k_pos_p, causal=causal,
                            window=cfg.window, is_global=is_global,
                            chunk=cfg.attn_chunk, quant=cfg.quant)
    else:
        bias = _mask(positions, k_pos, causal=causal, window=cfg.window,
                     is_global=is_global)[:, None, None]  # (B,1,1,T,S)
        out = _sdpa_dense(q, k.astype(q.dtype), v.astype(q.dtype), bias,
                          quant=cfg.quant)

    out = out.reshape(B, T, H, hd)
    # out-projection: (heads, head_dim) flatten into the kernel's K —
    # prepared as a k_ndim=2 PreparedWeight on the serving path.
    y = qeinsum("bthd,hdo->bto", out, p["wo"], cfg.quant, site="attn.wo")
    return y, new_cache
