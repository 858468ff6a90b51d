"""Paged-KV block pool + masked-chunk ragged attention (ISSUE-7).

Seeded, derandomized property-style suites (hypothesis is not in the
image, so each property runs over a deterministic family of generated
cases) pinning the contracts the continuous-batching engine leans on:

* :class:`~repro.quant.kvcache.BlockAllocator` — alloc/free round-trips,
  FIFO determinism (block assignment is a pure function of the
  admission/release sequence), exhaustion, and the reserved trash block;
* :func:`~repro.quant.kvcache.paged_append_kv` — append-only bit-freeze:
  every pool byte outside the one written (position, head) row carries
  through untouched, including across block boundaries;
* dense/paged equivalence — ``dequantize_kv(gather_paged_kv(...))`` is
  bitwise-equal to dequantizing the dense :func:`append_kv` cache at
  arbitrary ragged lengths, whatever blocks the allocator handed out;
* :func:`~repro.kernels.mgs_attention.mgs_paged_flash_attention` — the
  Pallas kernel and the pure-jnp reference agree bitwise at ragged
  length patterns including length-0 (dead slot) and exact
  block-boundary lengths, and both match the dense kernel over the
  gathered cache;
* the masked-chunk early-exit (``lengths=``) on the dense entry point is
  bitwise-identical to walking the zero-inert tail in full;
* the paged steps carry the stacked pool through their layer scan: the
  compiled program moves no pool-sized buffer, and every logit and pool
  byte equals the per-layer ``xs``/``ys`` form, round after round.
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import reduced_config
from repro.core.formats import E4M3
from repro.kernels.mgs_attention import (mgs_flash_attention,
                                         mgs_flash_attention_ref,
                                         mgs_paged_flash_attention)
from repro.quant.kvcache import (BlockAllocator, PagedKVCache,
                                 QuantizedKVCache, TRASH_BLOCK, append_kv,
                                 dequantize_kv, gather_paged_kv,
                                 init_paged_kv, init_quantized_kv,
                                 paged_append_kv, paged_rollback_kv,
                                 quantize_kv)
from repro.models import init_params
from repro.models.transformer import (_cast_params, _dense_body,
                                      _embed_tokens, _global_flags,
                                      _logits, decode_step_paged,
                                      draft_step_paged, init_paged_cache,
                                      rewind_slots, verify_step_paged)
from repro.models.common import rms_norm
from repro.quant import QuantConfig
from repro.quant.quantize import quantize_fp8


# ---------------------------------------------------------------------------
# BlockAllocator properties
# ---------------------------------------------------------------------------


def test_allocator_roundtrip_restores_pool():
    """alloc/free round-trips conserve the pool and never hand out the
    trash block or a block twice."""
    rng = np.random.default_rng(11)
    for case in range(20):
        n_blocks = int(rng.integers(3, 40))
        alloc = BlockAllocator(n_blocks)
        assert alloc.n_free == n_blocks - 1
        held = []
        for _ in range(30):
            if held and rng.random() < 0.4:
                alloc.free(held.pop(rng.integers(0, len(held))))
                continue
            want = int(rng.integers(1, 4))
            if want > alloc.n_free:
                continue
            got = alloc.alloc(want)
            assert TRASH_BLOCK not in got
            flat = [b for blocks in held for b in blocks]
            assert not set(got) & set(flat), "block handed out twice"
            held.append(got)
        for blocks in held:
            alloc.free(blocks)
        assert alloc.n_free == n_blocks - 1


def test_allocator_fifo_is_pure_function_of_schedule():
    """Two allocators replaying the same alloc/free sequence hand out
    identical block lists — the replica bit-determinism precondition."""
    rng = np.random.default_rng(5)
    script = []
    for _ in range(40):
        if script and rng.random() < 0.35:
            script.append(("free", int(rng.integers(0, len(script)))))
        else:
            script.append(("alloc", int(rng.integers(1, 3))))

    def replay():
        alloc = BlockAllocator(64)
        got, live = [], {}
        for i, (op, arg) in enumerate(script):
            if op == "alloc":
                blocks = alloc.alloc(arg)
                live[i] = blocks
                got.append(tuple(blocks))
            elif arg in live:
                alloc.free(live.pop(arg))
        return got

    assert replay() == replay()


def test_allocator_exhaustion_and_trash_block():
    alloc = BlockAllocator(4)  # blocks 1..3 allocatable
    got = alloc.alloc(3)
    assert sorted(got) == [1, 2, 3]
    with pytest.raises(RuntimeError, match="exhausted"):
        alloc.alloc(1)
    with pytest.raises(ValueError, match="trash block"):
        alloc.free([TRASH_BLOCK])
    with pytest.raises(ValueError, match=">= 2 blocks"):
        BlockAllocator(1)
    alloc.free(got)
    assert alloc.n_free == 3


# ---------------------------------------------------------------------------
# paged append: bit-freeze + dense equivalence
# ---------------------------------------------------------------------------

_KV, _HD, _BS = 2, 8, 4


def test_paged_append_bit_freezes_everything_else(rng):
    """A decode append touches exactly one (position, head) row per slot;
    every other pool byte — other blocks, other offsets, other heads —
    is bit-identical, including when slots sit at block boundaries
    (offset 0 of a fresh block)."""
    B = 3
    P = 10
    pool = init_paged_kv((), P, _KV, _BS, _HD)
    # pre-fill the pool with recognizable garbage so freezes are visible
    pool = pool._replace(
        k_codes=jnp.asarray(rng.integers(0, 255, pool.k_codes.shape),
                            jnp.uint8),
        v_codes=jnp.asarray(rng.integers(0, 255, pool.v_codes.shape),
                            jnp.uint8),
        k_scale=jnp.asarray(rng.normal(0, 1, pool.k_scale.shape)
                            .astype(np.float32)),
        v_scale=jnp.asarray(rng.normal(0, 1, pool.v_scale.shape)
                            .astype(np.float32)))
    table = np.array([[1, 2, 3], [4, 5, 6], [7, 8, 9]], np.int32)
    # positions: mid-block, block boundary (offset 0), last offset
    for pos in (np.array([1, 4, 11]), np.array([0, 8, 3])):
        k_new = jnp.asarray(rng.normal(0, 1, (B, 1, _KV, _HD))
                            .astype(np.float32))
        v_new = jnp.asarray(rng.normal(0, 1, (B, 1, _KV, _HD))
                            .astype(np.float32))
        new = paged_append_kv(pool, k_new, v_new, jnp.asarray(pos),
                              jnp.asarray(table), E4M3)
        touched = {(int(table[b, p // _BS]), int(p % _BS))
                   for b, p in enumerate(pos)}
        for plane in ("k_codes", "v_codes", "k_scale", "v_scale"):
            a = np.asarray(getattr(pool, plane))
            c = np.asarray(getattr(new, plane))
            mask = np.ones(a.shape, bool)
            for blk, off in touched:
                mask[blk, :, off] = False
            np.testing.assert_array_equal(a[mask], c[mask])
        # and the written row equals quantizing the entry in isolation
        kc, ks = quantize_kv(k_new, E4M3)
        for b, p in enumerate(pos):
            blk, off = int(table[b, p // _BS]), int(p % _BS)
            np.testing.assert_array_equal(
                np.asarray(new.k_codes[blk, :, off]),
                np.asarray(kc[b, 0]))
            np.testing.assert_array_equal(
                np.asarray(new.k_scale[blk, :, off]),
                np.asarray(ks[b, 0]))


def test_paged_append_multi_token_bitwise(rng):
    """The speculative verify append (T > 1, one call) writes exactly the
    bytes T sequential single-token appends would — including across a
    block boundary."""
    nb, T = 2, 3
    pos0 = _BS - 2   # tokens straddle the block boundary
    table = jnp.asarray([[1, 2]], jnp.int32)
    k = jnp.asarray(rng.normal(0, 2, (1, T, _KV, _HD)).astype(np.float32))
    v = jnp.asarray(rng.normal(0, 2, (1, T, _KV, _HD)).astype(np.float32))
    seq = init_paged_kv((), nb + 1, _KV, _BS, _HD)
    for t in range(T):
        seq = paged_append_kv(seq, k[:, t:t + 1], v[:, t:t + 1],
                              jnp.asarray([pos0 + t], jnp.int32), table,
                              E4M3)
    multi = paged_append_kv(init_paged_kv((), nb + 1, _KV, _BS, _HD),
                            k, v, jnp.asarray([pos0], jnp.int32), table,
                            E4M3)
    for f in PagedKVCache._fields:
        np.testing.assert_array_equal(np.asarray(getattr(multi, f)),
                                      np.asarray(getattr(seq, f)),
                                      err_msg=f)


def test_paged_dense_dequantize_bitwise_ragged(rng):
    """The headline layout property: build the same logical caches twice
    — densely via append_kv and paged via allocator blocks + interleaved
    decode appends — at ragged length families (length-0, partial block,
    exact block boundary, full table), and require
    dequantize(gather(paged)) == dequantize(dense) bit for bit."""
    nb = 4
    S = nb * _BS
    for case, lengths in enumerate([(0, 5, 16, 9), (4, 0, 13, 8),
                                    (16, 16, 0, 1), (3, 12, 7, 15)]):
        B = len(lengths)
        alloc = BlockAllocator(B * nb + 1)
        pool = init_paged_kv((), B * nb + 1, _KV, _BS, _HD)
        table = np.zeros((B, nb), np.int32)
        denses = [init_quantized_kv((1,), _KV, S, _HD) for _ in range(B)]
        for b, ln in enumerate(lengths):
            if ln:
                blocks = alloc.alloc(-(-ln // _BS))
                table[b, :len(blocks)] = blocks
        # grow slots token by token, round-robin, so writes from
        # different slots interleave in pool history (order-free)
        for step in range(max(lengths)):
            for b, ln in enumerate(lengths):
                if step >= ln:
                    continue
                k = jnp.asarray(rng.normal(0, 2, (1, 1, _KV, _HD))
                                .astype(np.float32))
                v = jnp.asarray(rng.normal(0, 2, (1, 1, _KV, _HD))
                                .astype(np.float32))
                denses[b] = append_kv(denses[b], k, v, step, E4M3)
                pool = paged_append_kv(
                    pool, k, v, jnp.asarray([step], jnp.int32),
                    jnp.asarray(table[b:b + 1]), E4M3)
        dense = QuantizedKVCache(*[
            jnp.concatenate([getattr(d, f) for d in denses])
            for f in QuantizedKVCache._fields])
        kd_p, vd_p = dequantize_kv(gather_paged_kv(pool,
                                                   jnp.asarray(table)), E4M3)
        kd_d, vd_d = dequantize_kv(dense, E4M3)
        for b, ln in enumerate(lengths):
            np.testing.assert_array_equal(
                np.asarray(kd_p[b, :, :ln]), np.asarray(kd_d[b, :, :ln]),
                err_msg=f"case {case} slot {b} K")
            np.testing.assert_array_equal(
                np.asarray(vd_p[b, :, :ln]), np.asarray(vd_d[b, :, :ln]),
                err_msg=f"case {case} slot {b} V")


# ---------------------------------------------------------------------------
# ragged / paged kernel bitwise pins
# ---------------------------------------------------------------------------

_RAGGED_PATTERNS = [
    (0, 7, 16, 3),     # dead slot + partial + exact boundary + tiny
    (16, 0, 0, 12),    # one full, two dead
    (1, 15, 8, 16),    # minimal + boundary-1 + mid-boundary + full
    (5, 5, 5, 5),      # uniform partial
]


def _paged_case(rng, lengths, nb=4, bs=16, D=16, T=1, shuffle_seed=0):
    """Build a shuffled physical pool + tables + logical scale/bias rows
    for the given ragged lengths. Returns kernel args for both the paged
    entry and the equivalent dense contiguous cache."""
    N = len(lengths)
    S = nb * bs
    P = N * nb + 1  # + trash block
    k = rng.normal(0, 1, (N, S, D)).astype(np.float32)
    v = rng.normal(0, 1, (N, S, D)).astype(np.float32)
    q = rng.normal(0, 1, (N, T, D)).astype(np.float32)
    # zero the dead tails so early-exit == full-walk holds exactly
    for n, ln in enumerate(lengths):
        k[n, ln:] = 0.0
        v[n, ln:] = 0.0
    kc, ks = quantize_kv(jnp.asarray(k), E4M3)
    vc, vs = quantize_kv(jnp.asarray(v), E4M3)
    ks = jnp.where(jnp.arange(S)[None] < jnp.asarray(lengths)[:, None],
                   ks, 0.0)
    vs = jnp.where(jnp.arange(S)[None] < jnp.asarray(lengths)[:, None],
                   vs, 0.0)
    qt = quantize_fp8(jnp.asarray(q).reshape(N, T * D), E4M3, axis=1)
    qv = qt.q.reshape(N, T, D)
    qk = jnp.broadcast_to(qt.scale, (N, S)) * ks * (D ** -0.5)
    bias = np.where(np.arange(S)[None] < np.asarray(lengths)[:, None],
                    0.0, -1e30).astype(np.float32)
    # scatter logical tiles into a shuffled physical pool; dead slots
    # keep zeroed tables (pointing at the trash block)
    shuf = np.random.default_rng(shuffle_seed)
    order = 1 + shuf.permutation(P - 1)
    k_pool = np.zeros((P, bs, D), np.uint8)
    v_pool = np.zeros((P, bs, D), np.uint8)
    bt = np.zeros((N, nb), np.int32)
    nxt = 0
    for n, ln in enumerate(lengths):
        for j in range(-(-ln // bs)):
            phys = int(order[nxt])
            nxt += 1
            bt[n, j] = phys
            k_pool[phys] = np.asarray(kc[n, j * bs:(j + 1) * bs])
            v_pool[phys] = np.asarray(vc[n, j * bs:(j + 1) * bs])
    live = jnp.asarray(lengths, jnp.int32)
    return (qv, jnp.asarray(k_pool), jnp.asarray(v_pool),
            jnp.asarray(bt), live, qk, vs, jnp.asarray(bias),
            (kc, vc, bs))


@pytest.mark.parametrize("lengths", _RAGGED_PATTERNS)
def test_paged_kernel_bitwise_vs_ref(rng, lengths):
    """Pallas paged kernel == pure-jnp reference, bit for bit, at ragged
    length patterns including length-0 and block-boundary lengths."""
    qv, kp, vp, bt, live, qk, vs, bias, _ = _paged_case(rng, lengths)
    got_k = mgs_paged_flash_attention(qv, kp, vp, bt, live, qk, vs, bias,
                                      E4M3, use_kernel=True)
    got_r = mgs_paged_flash_attention(qv, kp, vp, bt, live, qk, vs, bias,
                                      E4M3, use_kernel=False)
    np.testing.assert_array_equal(np.asarray(got_k), np.asarray(got_r))
    # dead slots produce exactly-zero output rows
    for n, ln in enumerate(lengths):
        if ln == 0:
            np.testing.assert_array_equal(np.asarray(got_k[n]),
                                          np.zeros_like(got_k[n]))


@pytest.mark.parametrize("lengths", _RAGGED_PATTERNS)
def test_paged_kernel_matches_dense_gathered(rng, lengths):
    """Walking a shuffled physical pool through block tables is
    bitwise-identical to the dense kernel over the contiguous cache with
    the same ``lengths`` — block placement never changes a bit."""
    qv, kp, vp, bt, live, qk, vs, bias, (kc, vc, bs) = _paged_case(
        rng, lengths, shuffle_seed=3)
    paged = mgs_paged_flash_attention(qv, kp, vp, bt, live, qk, vs, bias,
                                      E4M3, use_kernel=True)
    dense = mgs_flash_attention(qv, kc, vc, qk, vs, bias, E4M3, chunk=bs,
                                use_kernel=True, lengths=live)
    np.testing.assert_array_equal(np.asarray(paged), np.asarray(dense))


@pytest.mark.parametrize("lengths", _RAGGED_PATTERNS)
def test_dense_early_exit_bitwise_vs_full_walk(rng, lengths):
    """The masked-chunk early-exit (``lengths=``) over a zero-inert tail
    is bitwise-identical to walking every chunk, on both tiers."""
    qv, _, _, _, live, qk, vs, bias, (kc, vc, bs) = _paged_case(
        rng, lengths)
    for use_kernel in (False, True):
        early = mgs_flash_attention(qv, kc, vc, qk, vs, bias, E4M3,
                                    chunk=bs, use_kernel=use_kernel,
                                    lengths=live)
        full = mgs_flash_attention(qv, kc, vc, qk, vs, bias, E4M3,
                                   chunk=bs, use_kernel=use_kernel,
                                   lengths=None)
        np.testing.assert_array_equal(np.asarray(early), np.asarray(full))
    ref = mgs_flash_attention_ref(qv, kc, vc, qk, vs, bias, E4M3,
                                  chunk=bs, lengths=live)
    kern = mgs_flash_attention(qv, kc, vc, qk, vs, bias, E4M3, chunk=bs,
                               use_kernel=True, lengths=live)
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(kern))


def test_paged_kernel_ignores_trash_and_stale_blocks(rng):
    """Garbage in the trash block and in unreferenced (freed, stale)
    blocks never changes a live slot's output: rewrite every block the
    live tables do not name with random bytes and require bit-identity."""
    lengths = (7, 0, 16)
    qv, kp, vp, bt, live, qk, vs, bias, _ = _paged_case(rng, lengths)
    before = mgs_paged_flash_attention(qv, kp, vp, bt, live, qk, vs,
                                       bias, E4M3, use_kernel=True)
    bs = kp.shape[1]
    used = set()
    for n, ln in enumerate(lengths):
        used |= set(np.asarray(bt)[n, :-(-ln // bs)].tolist())
    kp2, vp2 = np.asarray(kp).copy(), np.asarray(vp).copy()
    for p in range(kp2.shape[0]):
        if p not in used:
            kp2[p] = rng.integers(0, 255, kp2[p].shape)
            vp2[p] = rng.integers(0, 255, vp2[p].shape)
    after = mgs_paged_flash_attention(qv, jnp.asarray(kp2),
                                      jnp.asarray(vp2), bt, live, qk, vs,
                                      bias, E4M3, use_kernel=True)
    np.testing.assert_array_equal(np.asarray(before), np.asarray(after))


# ---------------------------------------------------------------------------
# speculative rollback: draft-then-rewind leaves no trace (ISSUE-8)
# ---------------------------------------------------------------------------


def _grown_pool(rng, table, length):
    """A pool grown ``length`` committed tokens via sequential appends."""
    pool = init_paged_kv((), int(np.asarray(table).max()) + 1, _KV, _BS,
                         _HD)
    for t in range(length):
        k = jnp.asarray(rng.normal(0, 2, (1, 1, _KV, _HD))
                        .astype(np.float32))
        v = jnp.asarray(rng.normal(0, 2, (1, 1, _KV, _HD))
                        .astype(np.float32))
        pool = paged_append_kv(pool, k, v, jnp.asarray([t], jnp.int32),
                               table, E4M3)
    return pool


@pytest.mark.parametrize("accepted", [0, 1, 2, 3])
def test_paged_rollback_restores_never_drafted_state(rng, accepted):
    """The engine's speculative round at the pool level: append ``k``
    candidate rows, accept ``e``, roll back the rest — the pool must be
    bitwise equal to one that only ever appended the ``e`` accepted
    tokens. Exercised across a block boundary."""
    k_spec = 3
    pos0 = _BS - 1   # candidates straddle the boundary
    table = jnp.asarray([[1, 2]], jnp.int32)
    committed = _grown_pool(rng, table, pos0)
    k = jnp.asarray(rng.normal(0, 2, (1, k_spec, _KV, _HD))
                    .astype(np.float32))
    v = jnp.asarray(rng.normal(0, 2, (1, k_spec, _KV, _HD))
                    .astype(np.float32))
    spec = paged_append_kv(committed, k, v, jnp.asarray([pos0], jnp.int32),
                           table, E4M3)
    rolled = paged_rollback_kv(
        spec, table, jnp.asarray([pos0 + accepted], jnp.int32),
        jnp.asarray([k_spec - accepted], jnp.int32), k_spec)
    baseline = committed
    if accepted:
        baseline = paged_append_kv(committed, k[:, :accepted],
                                   v[:, :accepted],
                                   jnp.asarray([pos0], jnp.int32), table,
                                   E4M3)
    for f in PagedKVCache._fields:
        np.testing.assert_array_equal(np.asarray(getattr(rolled, f)),
                                      np.asarray(getattr(baseline, f)),
                                      err_msg=f"accepted={accepted} {f}")


def test_paged_rollback_preserves_other_slots_and_allocator(rng):
    """Rolling back one slot's rejected tail never touches another
    slot's bytes, the trash block, or the allocator: rollback is pure
    pool arithmetic — blocks stay owned by their slot, so the free list
    is bitwise the same host object state afterwards."""
    alloc = BlockAllocator(6)
    t0 = alloc.alloc(2)
    t1 = alloc.alloc(2)
    free_before = list(alloc._free)
    table = jnp.asarray([t0, t1], jnp.int32)
    pool = init_paged_kv((), 6, _KV, _BS, _HD)
    pool = pool._replace(
        k_codes=jnp.asarray(rng.integers(0, 255, pool.k_codes.shape),
                            jnp.uint8))
    k = jnp.asarray(rng.normal(0, 2, (2, 2, _KV, _HD)).astype(np.float32))
    pos = jnp.asarray([1, _BS - 1], jnp.int32)
    spec = paged_append_kv(pool, k, k, pos, table, E4M3)
    # slot 0 keeps 0 of 2 candidates, slot 1 keeps both (count 0)
    rolled = paged_rollback_kv(spec, table, pos,
                               jnp.asarray([2, 0], jnp.int32), 2)
    assert list(alloc._free) == free_before
    # slot 1's candidate rows survive untouched
    for t in range(2):
        p = int(pos[1]) + t
        blk, off = int(table[1, p // _BS]), p % _BS
        np.testing.assert_array_equal(
            np.asarray(rolled.k_codes[blk, :, off]),
            np.asarray(spec.k_codes[blk, :, off]))
    # the trash block is never zeroed by a rollback (dead slots park
    # their rejected rows there via TRASH_BLOCK-masked tables)
    np.testing.assert_array_equal(np.asarray(rolled.k_codes[TRASH_BLOCK]),
                                  np.asarray(spec.k_codes[TRASH_BLOCK]))
    # slot 0's rejected rows are back to the pre-append bytes... which a
    # count=0 rollback of everything leaves fully intact
    ident = paged_rollback_kv(spec, table, pos,
                              jnp.asarray([0, 0], jnp.int32), 2)
    for f in PagedKVCache._fields:
        np.testing.assert_array_equal(np.asarray(getattr(ident, f)),
                                      np.asarray(getattr(spec, f)),
                                      err_msg=f)


# ---------------------------------------------------------------------------
# multi-query verify kernel: per-token bitwise factoring (ISSUE-8)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("base_lengths", [(5, 0, 14), (1, 16, 8)])
def test_paged_verify_bitwise_per_token(rng, base_lengths):
    """The T>1 verify entry is a pure flattening: token ``t`` of slice
    ``n`` comes out bitwise equal to a standalone T=1 paged call with
    that token's own length/scale/bias rows — on both tiers — so exact
    ``==`` acceptance against sequential decode is sound."""
    from repro.kernels.mgs_attention import mgs_paged_verify_attention
    T, R = 3, 2
    _, kp, vp, bt, _, _, _, _, _ = _paged_case(rng, base_lengths)
    N = len(base_lengths)
    S = bt.shape[1] * kp.shape[1]
    q = jnp.asarray(rng.normal(0, 1, (N, T, R, 16)).astype(np.float32))
    # per-token causal horizons: dead slots stay dead for every token
    lengths = np.zeros((N, T), np.int32)
    for n, ln in enumerate(base_lengths):
        for t in range(T):
            lengths[n, t] = min(ln + t + 1, S) if ln else 0
    qk = rng.normal(0, 1, (N, T, S)).astype(np.float32)
    vs = rng.normal(0, 1, (N, T, S)).astype(np.float32)
    live_mask = np.arange(S)[None, None] < lengths[:, :, None]
    qk = np.where(live_mask, qk, 0.0).astype(np.float32)
    vs = np.where(live_mask, vs, 0.0).astype(np.float32)
    bias = np.where(live_mask, 0.0, -1e30).astype(np.float32)
    lengths, qk, vs, bias = map(jnp.asarray, (lengths, qk, vs, bias))
    for use_kernel in (False, True):
        got = mgs_paged_verify_attention(q, kp, vp, bt, lengths, qk, vs,
                                         bias, E4M3,
                                         use_kernel=use_kernel)
        assert got.shape == (N, T, R, 16)
        for t in range(T):
            solo = mgs_paged_flash_attention(
                q[:, t], kp, vp, bt, lengths[:, t], qk[:, t], vs[:, t],
                bias[:, t], E4M3, use_kernel=use_kernel)
            np.testing.assert_array_equal(
                np.asarray(got[:, t]), np.asarray(solo),
                err_msg=f"kernel={use_kernel} token {t}")


def test_paged_kernel_reads_a_layer_of_the_stacked_pool_in_place(rng):
    """The kernel reading layer ``l`` of a stacked pool through tile ids
    offset by ``l * P`` gives the bits of the call on that layer's slice,
    on both tiers — whatever the other layers hold."""
    qv, kp, vp, bt, live, qk, vs, bias, _ = _paged_case(rng, (7, 0, 16))
    P = kp.shape[0]

    def stacked(pool):   # layer 1 of three; the others random bytes
        junk = rng.integers(0, 255, (2,) + pool.shape).astype(np.uint8)
        return jnp.concatenate([junk[0], pool, junk[1]])
    ks, vs_ = stacked(kp), stacked(vp)
    for use_kernel in (False, True):
        alone = mgs_paged_flash_attention(qv, kp, vp, bt, live, qk, vs,
                                          bias, E4M3, use_kernel=use_kernel)
        in_place = mgs_paged_flash_attention(qv, ks, vs_, bt + P, live, qk,
                                             vs, bias, E4M3,
                                             use_kernel=use_kernel)
        np.testing.assert_array_equal(np.asarray(in_place),
                                      np.asarray(alone))


# ---------------------------------------------------------------------------
# paged steps: the stacked pool rides the layer scan's carry
# ---------------------------------------------------------------------------

_SPEC_K = 3
_STEPS = {
    "decode": decode_step_paged,
    "verify": verify_step_paged,
    "draft": functools.partial(draft_step_paged, offset=1),
}


def _step_cfg(draft_layers=1):
    return dataclasses.replace(
        reduced_config("deepseek-7b"),
        quant=QuantConfig(dtype="fp8_e4m3", accum="mgs_exact",
                          kv_cache="packed", per_row_act=True,
                          block_m=32, block_n=32, block_k=32,
                          draft_layers=draft_layers))


@pytest.mark.parametrize("kind", sorted(_STEPS))
def test_paged_step_moves_no_pool_buffer(kind, pool_moves):
    """Compiled with the cache donated, as the engine runs it, a paged
    step holds no copy, fill, slice, update-slice or concatenation of a
    pool-sized buffer: each layer's append is one in-place scatter and
    its attention reads the carried pool where it lies."""
    cfg = _step_cfg()
    params = jax.eval_shape(
        lambda: init_params(cfg, jax.random.PRNGKey(0))[0])
    cache = jax.eval_shape(lambda: init_paged_cache(cfg, 4, 64, 37)[0])
    t = _SPEC_K if kind == "verify" else 1
    tokens = jax.ShapeDtypeStruct((4, t), jnp.int32)
    step = jax.jit(lambda p, tok, c: _STEPS[kind](p, cfg, tok, c),
                   donate_argnums=(2,))
    text = step.lower(params, tokens, cache).compile().as_text()
    assert "scatter(" in text
    assert pool_moves(text, cache["k"].shape) == []


def _xs_ys_step(kind, params, cfg, tokens, cache, offset=0):
    """Oracle: the paged steps with the pool planes as scan ``xs``/``ys``
    — each layer's planes sliced out, appended and attended as a
    one-layer pool, restacked, and the draft's upper layers concatenated
    back."""
    params = _cast_params(params, cfg)
    pos = cache["pos"]
    live = pos > 0
    L = cfg.quant.draft_layers if kind == "draft" else cfg.n_layers
    qpos = jnp.where(live, pos + offset, pos)
    lengths = jnp.where(live, qpos + 1, 0)
    positions = qpos[:, None] + jnp.arange(tokens.shape[1])[None]
    planes = ("k", "v", "k_scale", "v_scale")

    def body(x, xs):
        pl, isg, *kvl = xs
        x, kv, _ = _dense_body(pl, x, positions, cfg, isg,
                               PagedKVCache(*(a[None] for a in kvl)), qpos,
                               None, None, block_table=cache["block_table"],
                               lengths=lengths, layer=0)
        return x, tuple(a[0] for a in kv)
    x, kvs = jax.lax.scan(
        body, _embed_tokens(params, cfg, tokens),
        (jax.tree.map(lambda a: a[:L], params["layers"]),
         _global_flags(cfg)[:L], *(cache[p][:L] for p in planes)))
    new = dict(cache, **{p: jnp.concatenate([u, cache[p][L:]])
                         for p, u in zip(planes, kvs)})
    if kind == "decode":
        new["pos"] = jnp.where(live, pos + 1, pos)
    logits = _logits(params, cfg, rms_norm(x, params["final_norm"],
                                           cfg.norm_eps))
    return (logits if kind == "verify" else logits[:, 0]), new


def _ragged_pool(cfg, rng):
    """Five slots over a pool whose every block holds quantized data:
    slots 0 and 3 free, slot 1 at the last offset of its first block,
    slot 2 at the first offset of its second, slot 4 mid-block."""
    cache, _ = init_paged_cache(cfg, 5, 96, 16)
    for p, s in (("k", "k_scale"), ("v", "v_scale")):
        codes, scale = quantize_kv(jnp.asarray(rng.normal(
            0, 1, cache[p].shape).astype(np.float32)), E4M3)
        cache[p], cache[s] = codes, scale
    cache["block_table"] = jnp.asarray(
        [[0, 0, 0], [3, 7, 9], [1, 4, 11], [0, 0, 0], [2, 5, 14]], jnp.int32)
    cache["pos"] = jnp.asarray([0, 31, 32, 0, 5], jnp.int32)
    return cache


def _assert_same(got, want, what):
    for g, w, name in zip(jax.tree.leaves(got), jax.tree.leaves(want),
                          jax.tree_util.tree_flatten_with_path(got)[0]):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w),
                                      err_msg=f"{what} {name[0]}")


@pytest.mark.parametrize("kind", sorted(_STEPS))
def test_paged_step_bitwise_vs_xs_ys_form(kind, rng):
    """Three rounds of each step kind (each speculative round closed by
    a rewind) over a ragged pool with free slots and block-boundary
    positions: logits and every pool byte, codes and scales, equal the
    per-layer ``xs``/``ys`` form's after every round. The draft runs
    two of the four layers, so it too addresses a layer past the first."""
    cfg = _step_cfg(draft_layers=2)
    params, _ = init_params(cfg, jax.random.PRNGKey(3))
    new = old = _ragged_pool(cfg, rng)
    B = new["pos"].shape[0]
    steps = {"decode": [0], "verify": [0], "draft": [0, 1]}[kind]
    t = _SPEC_K if kind == "verify" else 1
    for rnd in range(3):
        for offset in steps:
            tokens = jnp.asarray(rng.integers(1, cfg.vocab, (B, t)),
                                 jnp.int32)
            if kind == "draft":
                got = jax.jit(draft_step_paged, static_argnums=1)(
                    params, cfg, tokens, new, offset)
            else:
                got = jax.jit(_STEPS[kind], static_argnums=1)(
                    params, cfg, tokens, new)
            want = jax.jit(_xs_ys_step, static_argnums=(0, 2))(
                kind, params, cfg, tokens, old, offset)
            _assert_same(got, want, f"{kind} round {rnd} offset {offset}")
            new, old = got[1], want[1]
        if kind != "decode":
            keep = jnp.asarray(rng.integers(1, _SPEC_K + 1, B), jnp.int32)
            new = rewind_slots(new, keep, _SPEC_K)
            old = rewind_slots(old, keep, _SPEC_K)
            _assert_same(new, old, f"{kind} round {rnd} rewind")
