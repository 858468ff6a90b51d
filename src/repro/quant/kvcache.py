"""Packed-FP8 quantized KV cache for decode serving.

Serving decode is memory-bound on the KV cache: every decode step streams
the whole cache through the score/value contractions. A bf16 cache costs
2 bytes/element of HBM traffic per step *and* (under an fp8 QuantConfig)
re-quantizes the full cache every step — the absmax/round work grows with
the context length even though all but one entry is unchanged. This
module stores the cache the way the paper stores operands (PAPER.md §4):
**packed FP8 codes**, 1 byte/element, plus one float32 scale per cached
(position, head) entry:

    k[b, s, h, :] == decode_bits(k_codes[b, s, h, :]) * k_scale[b, s, h]

The per-entry scale is what makes the cache *append-only*: a new entry's
absmax never touches old entries, so :func:`append_kv` quantizes exactly
the ``T`` new positions and ``dynamic_update_slice``-writes them — old
codes and scales are bit-frozen for the life of the request
(``tests/test_kvcache.py`` pins this property). Decode attention then
consumes the codes directly: the MGS flash-decode kernel
(:mod:`repro.kernels.mgs_attention`) decodes them in VMEM and runs the
exact limb-summation contractions, so the narrow cache *improves* on
naive fp8 attention accuracy instead of trading it away — the paper's
accumulation argument applied to the serving hot path.

Layout (leading dims free — per-layer stacks prepend axes):

* ``k_codes`` / ``v_codes``: ``(..., KV, S, hd)`` uint8 packed codes
  (:func:`repro.core.formats.encode_bits`).
* ``k_scale`` / ``v_scale``: ``(..., KV, S)`` float32 dequantization
  scales (absmax of the entry's ``hd`` values over the format range).

The kv-head axis sits **before** the sequence axis so the decode step's
flash-kernel view ``(B * KV, S, hd)`` is a pure reshape of adjacent
dims: the hot loop never transposes (= copies) the cache planes.
Appends transpose only the ``T`` fresh entries — O(new), not O(S).

``QuantizedKVCache`` is a NamedTuple of arrays, so it passes through
``jax.lax.scan`` / ``jax.jit`` like any pytree: the model's
scan-over-layers slices the stacked planes along the leading layer axis
transparently (``models.transformer``).
"""

from __future__ import annotations

from collections import deque
from typing import List, NamedTuple, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.core.formats import (E4M3, FPFormat, decode_bits, encode_bits,
                                round_to_format)
from repro.parallel.sharding import on_multi_device_mesh

__all__ = ["QuantizedKVCache", "quantize_kv", "append_kv",
           "init_quantized_kv", "dequantize_kv", "kv_cache_bytes",
           "PagedKVCache", "BlockAllocator", "TRASH_BLOCK",
           "init_paged_kv", "paged_append_kv", "paged_rollback_kv",
           "gather_paged_kv"]


class QuantizedKVCache(NamedTuple):
    """Packed-code KV cache planes (one attention layer's view).

    The stacked multi-layer cache (``models.init_cache``) holds the same
    four planes with a leading ``layers`` axis; ``lax.scan`` slices them
    into this per-layer view.
    """

    k_codes: jnp.ndarray   # (..., KV, S, hd) uint8
    v_codes: jnp.ndarray   # (..., KV, S, hd) uint8
    k_scale: jnp.ndarray   # (..., KV, S) float32
    v_scale: jnp.ndarray   # (..., KV, S) float32


def quantize_kv(x, fmt: FPFormat = E4M3) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Quantize K or V entries to packed codes + per-entry scales.

    Args:
      x: ``(..., hd)`` float K or V vectors.
      fmt: narrow-exponent FP8 cache format (``QuantConfig.kv_fmt``).

    Returns:
      ``(codes, scale)`` — ``(..., hd)`` uint8 packed codes and ``(...)``
      float32 scales such that ``decode_bits(codes) * scale[..., None]``
      reconstructs the quantized values. The scale is the entry's absmax
      mapped onto the format's max finite value (the standard FP8 recipe,
      per (position, head) so appends never re-scale old entries). All
      reductions are over the static trailing ``hd`` axis, so the result
      is independent of how leading (mesh-sharded) axes are laid out —
      the bit-identity contract of docs/serving.md.
    """
    x = x.astype(jnp.float32)
    amax = jnp.maximum(jnp.max(jnp.abs(x), axis=-1),
                       jnp.finfo(jnp.float32).tiny)
    scale = amax / fmt.max_finite
    q = round_to_format(x / scale[..., None], fmt)
    return encode_bits(q, fmt), scale


def init_quantized_kv(lead, n_heads: int, seq: int,
                      head_dim: int) -> QuantizedKVCache:
    """Allocate an all-zero packed cache.

    ``lead`` carries the leading axes (e.g. ``(layers, batch)``); the
    planes come out ``(*lead, n_heads, seq, head_dim)`` / scale
    ``(*lead, n_heads, seq)`` — heads before sequence, so the decode
    view is a reshape (module docstring). Code 0 decodes to +0.0 and a
    0.0 scale keeps the product exactly zero, so unwritten positions
    contribute nothing even before the validity mask lands.
    """
    full = tuple(lead) + (n_heads, seq, head_dim)
    srow = tuple(lead) + (n_heads, seq)
    return QuantizedKVCache(
        k_codes=jnp.zeros(full, jnp.uint8),
        v_codes=jnp.zeros(full, jnp.uint8),
        k_scale=jnp.zeros(srow, jnp.float32),
        v_scale=jnp.zeros(srow, jnp.float32))


def append_kv(cache: QuantizedKVCache, k_new, v_new, pos,
              fmt: FPFormat = E4M3) -> QuantizedKVCache:
    """Write new K/V entries at ``pos``, re-quantizing **only** them.

    Args:
      cache: per-layer ``(B, KV, S, hd)`` cache view.
      k_new / v_new: ``(B, T, KV, hd)`` fresh projections (prefill: the
        whole prompt; decode: T == 1) — the layer layout; only these
        ``T`` entries are transposed into the cache's (KV, S) order.
      pos: starting sequence position (traced scalar is fine).
      fmt: the cache's code format.

    Returns:
      The cache with positions ``[pos, pos + T)`` replaced. Every other
      code/scale element is carried through untouched (a pure
      ``dynamic_update_slice``), which is what keeps append O(T) instead
      of O(S) in quantization work.
    """
    kc, ks = quantize_kv(k_new, fmt)
    vc, vs = quantize_kv(v_new, fmt)
    kc, vc = kc.transpose(0, 2, 1, 3), vc.transpose(0, 2, 1, 3)
    ks, vs = ks.transpose(0, 2, 1), vs.transpose(0, 2, 1)
    at4 = (0, 0, pos, 0)
    at3 = (0, 0, pos)
    return QuantizedKVCache(
        k_codes=jax.lax.dynamic_update_slice(cache.k_codes, kc, at4),
        v_codes=jax.lax.dynamic_update_slice(cache.v_codes, vc, at4),
        k_scale=jax.lax.dynamic_update_slice(cache.k_scale, ks, at3),
        v_scale=jax.lax.dynamic_update_slice(cache.v_scale, vs, at3))


def dequantize_kv(cache: QuantizedKVCache, fmt: FPFormat = E4M3,
                  dtype=jnp.float32) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Reconstruct float K/V from the packed planes (tests / fallbacks).

    The hot decode path never calls this — the flash kernel decodes codes
    in VMEM — but error-bound tests and non-MGS consumers need the float
    view: ``value = decode_bits(code) * scale``.
    """
    k = decode_bits(cache.k_codes, fmt, jnp.float32) * cache.k_scale[..., None]
    v = decode_bits(cache.v_codes, fmt, jnp.float32) * cache.v_scale[..., None]
    return k.astype(dtype), v.astype(dtype)


# ---------------------------------------------------------------------------
# Paged layout — block tables over the same packed code + scale planes
# ---------------------------------------------------------------------------

#: Physical block 0 is the **trash block**: free slots keep zeroed block
#: tables, so their (gated, never-read) decode appends land here instead
#: of corrupting a live slot's blocks. Its *content* is scratch — several
#: free slots may scatter to the same (block, offset) in one step, and
#: XLA leaves the winner unspecified — but nothing ever reads it: the
#: flash kernel gates every chunk of a ``live == 0`` slice off, and
#: :class:`BlockAllocator` never hands block 0 out.
TRASH_BLOCK = 0


class PagedKVCache(NamedTuple):
    """Packed-code KV planes chopped into a physical block pool.

    The paged twin of :class:`QuantizedKVCache` for continuous-batching
    serving: the sequence axis is split into ``block_size`` tiles, and a
    slot's logical cache is whatever pool blocks its block table names —
    so admitting or releasing a request moves *table entries*, never
    cache bytes, and the pool is shared by every slot. The block size
    equals the flash kernel's chunk (``QuantConfig.block_k``), so each
    physical block is exactly one kernel tile
    (``kernels.mgs_paged_flash_attention`` walks the table directly via
    scalar prefetch).

    Per-entry scales carry over unchanged from the dense layout — they
    are what keep appends O(new) and old codes bit-frozen — and the head
    axis still precedes the in-block position axis, so the kernel's
    ``(P * KV, bs, hd)`` pool view is a pure reshape.
    """

    k_codes: jnp.ndarray   # (..., P, KV, bs, hd) uint8
    v_codes: jnp.ndarray   # (..., P, KV, bs, hd) uint8
    k_scale: jnp.ndarray   # (..., P, KV, bs) float32
    v_scale: jnp.ndarray   # (..., P, KV, bs) float32


class BlockAllocator:
    """Deterministic host-side FIFO pool allocator.

    Pure Python bookkeeping (never traced): the engine allocates blocks
    at admission and returns them at release. FIFO reuse keeps the
    assignment a pure function of the admission/release *sequence* — two
    replicas replaying the same schedule hand every request the same
    physical blocks, which keeps even the (value-irrelevant) table
    contents deterministic. Block :data:`TRASH_BLOCK` is reserved and
    never handed out.
    """

    def __init__(self, n_blocks: int):
        if n_blocks < 2:
            raise ValueError(f"need >= 2 blocks (1 is the trash block), "
                             f"got {n_blocks}")
        self._free: deque = deque(range(1, n_blocks))

    @property
    def n_free(self) -> int:
        return len(self._free)

    def alloc(self, n: int) -> List[int]:
        """Take ``n`` blocks (raises ``RuntimeError`` when exhausted)."""
        if n > len(self._free):
            raise RuntimeError(f"paged KV pool exhausted: want {n} blocks, "
                               f"{len(self._free)} free")
        return [self._free.popleft() for _ in range(n)]

    def free(self, blocks: Sequence[int]) -> None:
        """Return blocks to the pool (they may hold stale codes; the next
        owner's prefill adoption overwrites every byte before its live
        length ever covers them)."""
        for b in blocks:
            if b == TRASH_BLOCK:
                raise ValueError("block 0 is the reserved trash block")
            self._free.append(b)


def init_paged_kv(lead, n_blocks: int, n_heads: int, block_size: int,
                  head_dim: int) -> PagedKVCache:
    """Allocate an all-zero block pool.

    ``lead`` carries the leading axes (e.g. ``(layers,)``); the planes
    come out ``(*lead, n_blocks, n_heads, block_size, head_dim)`` /
    scale ``(*lead, n_blocks, n_heads, block_size)``. Zero codes/scales
    make every unwritten entry exactly inert, same as the dense init.
    """
    full = tuple(lead) + (n_blocks, n_heads, block_size, head_dim)
    srow = tuple(lead) + (n_blocks, n_heads, block_size)
    return PagedKVCache(
        k_codes=jnp.zeros(full, jnp.uint8),
        v_codes=jnp.zeros(full, jnp.uint8),
        k_scale=jnp.zeros(srow, jnp.float32),
        v_scale=jnp.zeros(srow, jnp.float32))


def paged_append_kv(cache: PagedKVCache, k_new, v_new, pos, block_table,
                    fmt: FPFormat = E4M3, layer=None) -> PagedKVCache:
    """Write each slot's ``T`` new K/V entries through its block table.

    The paged twin of :func:`append_kv`: quantize the ``B * T`` fresh
    entries (per-entry scales, O(new) work) and scatter token ``t`` of
    slot ``b`` into physical block ``block_table[b, (pos[b] + t) // bs]``
    at in-block offset ``(pos[b] + t) % bs``. Old codes and scales are
    bit-frozen — the scatter touches exactly the written (position,
    head) rows. Sequential decode uses ``T == 1``; the speculative
    verify step writes all ``k`` candidate positions in one call, and a
    later :func:`paged_rollback_kv` physically zeroes the rejected tail.

    Per-entry quantization makes the write *idempotent*: re-appending a
    position already holding the same float K/V rewrites the identical
    code/scale bytes, which is why a verify append may overwrite entries
    a cheap draft pass left behind without any bit drift.

    Args:
      cache: per-layer ``(P, KV, bs, hd)`` pool view, or the stacked
        ``(La, P, KV, bs, hd)`` pool when ``layer`` is given.
      k_new / v_new: ``(B, T, KV, hd)`` fresh projections.
      pos: ``(B,)`` int32 logical write positions of token 0 (a free
        slot's ``pos = 0`` lands in its zeroed table's
        :data:`TRASH_BLOCK`).
      block_table: ``(B, nb)`` int32 physical block ids.
      fmt: the cache's code format.
      layer: traced layer index into a stacked pool: the rows land at
        ``[layer, phys, :, off]``.

    Returns:
      The pool with ``T`` entries per slot replaced.
    """
    bs = cache.k_codes.shape[-2]
    nb = block_table.shape[1]
    B, T, KV, hd = k_new.shape
    pos = pos.astype(jnp.int32)
    kc, ks = quantize_kv(k_new, fmt)
    vc, vs = quantize_kv(v_new, fmt)
    pos_t = pos[:, None] + jnp.arange(T, dtype=jnp.int32)[None, :]
    # Clip the table index: a free slot's pos stays 0 so it never
    # overruns, but a live slot's last verify positions may exceed its
    # *bucket* while still inside the admission-reserved blocks; the
    # clip only guards the (never-read) trash scatter of free slots.
    blk = jnp.clip(pos_t // bs, 0, nb - 1)
    phys = jnp.take_along_axis(block_table.astype(jnp.int32), blk, axis=1)
    off = pos_t % bs
    if on_multi_device_mesh():
        # the pool is sharded over its head axis: keep that axis whole
        # in the update window, so each device scatters its own heads
        row = (phys.reshape(-1), slice(None), off.reshape(-1))
        if layer is not None:
            row = (layer,) + row

        def put(plane, new):
            return plane.at[row].set(new.reshape((B * T,) + new.shape[2:]))
    else:
        # one scatter of B*T*KV (tile, offset) rows into the pool's
        # (..., bs, hd) tile view: a pure reshape whose scatter dims
        # lead in order, so XLA updates the donated or loop-carried pool
        # in place, with no transposed copy of it
        if layer is not None:
            phys = phys + layer * cache.k_codes.shape[-4]
        tile = phys[:, :, None] * KV + jnp.arange(KV, dtype=jnp.int32)
        row = (tile.reshape(-1), jnp.repeat(off.reshape(-1), KV))

        def put(plane, new):
            tail = new.shape[3:]
            flat = plane.reshape((-1, bs) + tail)
            return flat.at[row].set(new.reshape((-1,) + tail)).reshape(
                plane.shape)
    return PagedKVCache(put(cache.k_codes, kc), put(cache.v_codes, vc),
                        put(cache.k_scale, ks), put(cache.v_scale, vs))


def paged_rollback_kv(cache: PagedKVCache, block_table, start, count,
                      max_count: int) -> PagedKVCache:
    """Physically zero logical positions ``[start, start + count)``.

    The speculative-decoding rewind: a verify step appended ``k``
    candidate entries, acceptance kept a prefix, and the rejected tail
    must vanish — not just be masked out by ``lengths``, but restored to
    the all-zero bytes a never-drafted pool would hold, so the
    bit-identity harness can compare whole pools and block release/reuse
    stays oblivious to speculation. Codes and scales both go to 0
    (exactly the :func:`init_paged_kv` state for those rows).

    Args:
      cache: stacked or per-layer ``(..., P, KV, bs, hd)`` pool view —
        the zeroing mask is per (block, offset), broadcast over every
        leading (layer) and head axis.
      block_table: ``(B, nb)`` int32 physical block ids.
      start: ``(B,)`` int32 first logical position to zero.
      count: ``(B,)`` int32 number of entries to zero (0 = no-op for
        that slot; released/free slots pass 0).
      max_count: static upper bound on ``count`` (the engine's
        ``spec_k``); the scatter is fixed-shape ``B * max_count``.

    Returns:
      The pool with the named rows zeroed. :data:`TRASH_BLOCK` is never
      zeroed (its content is scratch by contract, and masked-out
      lanes of the scatter are redirected there).
    """
    bs = cache.k_codes.shape[-2]
    nb = block_table.shape[1]
    P = cache.k_codes.shape[-4]
    start = start.astype(jnp.int32)
    count = count.astype(jnp.int32)
    pos_t = start[:, None] + jnp.arange(max_count, dtype=jnp.int32)[None, :]
    valid = jnp.arange(max_count, dtype=jnp.int32)[None, :] < count[:, None]
    blk = jnp.clip(pos_t // bs, 0, nb - 1)
    phys = jnp.take_along_axis(block_table.astype(jnp.int32), blk, axis=1)
    phys = jnp.where(valid, phys, TRASH_BLOCK)
    off = pos_t % bs
    hit = jnp.zeros((P, bs), jnp.bool_)
    hit = hit.at[phys.reshape(-1), off.reshape(-1)].set(True)
    hit = hit.at[TRASH_BLOCK].set(False)
    zero4 = hit[:, None, :, None]   # vs (..., P, KV, bs, hd)
    zero3 = hit[:, None, :]         # vs (..., P, KV, bs)
    return PagedKVCache(
        k_codes=jnp.where(zero4, jnp.uint8(0), cache.k_codes),
        v_codes=jnp.where(zero4, jnp.uint8(0), cache.v_codes),
        k_scale=jnp.where(zero3, jnp.float32(0), cache.k_scale),
        v_scale=jnp.where(zero3, jnp.float32(0), cache.v_scale))


def gather_paged_kv(cache: PagedKVCache,
                    block_table) -> QuantizedKVCache:
    """Materialize dense per-slot planes from the pool (tests / debug).

    ``pool[block_table[b, j]]`` becomes positions ``[j * bs, (j+1) * bs)``
    of slot ``b`` — the dense ``(B, KV, nb * bs, hd)`` view whose
    dequantization must match the pre-paging cache bit for bit
    (``tests/test_paged_kv.py``). The hot path never calls this; the
    kernel reads the pool through the table in place.
    """
    bt = block_table.astype(jnp.int32)
    B, nb = bt.shape
    kc = jnp.take(cache.k_codes, bt.reshape(-1), axis=0)
    vc = jnp.take(cache.v_codes, bt.reshape(-1), axis=0)
    ks = jnp.take(cache.k_scale, bt.reshape(-1), axis=0)
    vs = jnp.take(cache.v_scale, bt.reshape(-1), axis=0)
    KV, bs, hd = kc.shape[1:]
    kc = kc.reshape(B, nb, KV, bs, hd).transpose(0, 2, 1, 3, 4)
    vc = vc.reshape(B, nb, KV, bs, hd).transpose(0, 2, 1, 3, 4)
    ks = ks.reshape(B, nb, KV, bs).transpose(0, 2, 1, 3)
    vs = vs.reshape(B, nb, KV, bs).transpose(0, 2, 1, 3)
    return QuantizedKVCache(
        k_codes=kc.reshape(B, KV, nb * bs, hd),
        v_codes=vc.reshape(B, KV, nb * bs, hd),
        k_scale=ks.reshape(B, KV, nb * bs),
        v_scale=vs.reshape(B, KV, nb * bs))


def kv_cache_bytes(batch: int, seq: int, kv_heads: int, head_dim: int, *,
                   quantized: bool, float_itemsize: int = 2) -> int:
    """Analytic HBM bytes of one layer's K+V cache.

    ``quantized``: 1 byte/element of codes plus 4 bytes per (position,
    head) scale; float: ``float_itemsize`` bytes/element (bf16 default).
    Used by ``benchmarks/decode_bench.py`` and the docs/serving.md memory
    table — decode streams this much per layer per step.
    """
    elems = batch * seq * kv_heads * head_dim
    if quantized:
        return 2 * (elems + 4 * batch * seq * kv_heads)
    return 2 * elems * float_itemsize
