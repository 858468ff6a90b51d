"""Output-stationary fused matmul tiles chosen from the call's shape.

``kernels.mgs_matmul.fused_tiles`` picks ``(block_m, block_n, block_k)``
for an ``(M, K) @ (K, N)`` call; ``ops.mgs_matmul``'s exact branch uses
it. The limb sums are exact under any partition, so the chosen tiles
must give the very bits of the 128x128x128 tiling, flush for flush.
The chooser tests run no kernel: they hold its choices for deepseek-7b's
real call shapes to the rules the kernel relies on.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import formats
from repro.kernels import ops
from repro.kernels.mgs_matmul import (FUSED_VMEM_BUDGET_BYTES, TILE_STATS,
                                      fused_tiles, fused_vmem_bytes,
                                      mgs_matmul_exact_fused_pallas,
                                      worst_case_flush_period)

_F = formats.E4M3
D_MODEL, D_FF, VOCAB, LAYERS = 4096, 11008, 102400, 8   # deepseek-7b-d8
SLOTS = 32


def _codes(rng, shape):
    """Packed E4M3 codes of values spread over most of the format's
    exponent range, so that a flush at another K position rounds the
    f32 wide accumulator differently."""
    v = rng.normal(0, 1, shape) * 2.0 ** rng.integers(-7, 6, shape)
    return formats.encode_bits(
        jnp.asarray(formats.round_to_format(v.astype(np.float32), _F)), _F)


def _at_128(xc, wc, **kw):
    return mgs_matmul_exact_fused_pallas(xc, wc, _F, block_m=128,
                                         block_n=128, block_k=128,
                                         interpret=True, **kw)


# (M, K, N): M one row, one sublane tile, one over, two M tiles; K = 640
# (5 x 128: no longer 128-multiple divides it) and 384 (3 x 128); N that
# 512 does not divide (768 -> 384, 1280 -> 256)
BITWISE = {
    "m1-k384-n768": (1, 384, 768, {}),
    "m32-k1024-n512": (32, 1024, 512, {}),
    "m33-k640-n1280": (33, 640, 1280, {}),
    "m300-k384-n768": (300, 384, 768, {}),
    "epilogue-silu": (33, 1024, 768, dict(epilogue=True)),
    "flush-mid-k": (33, 1024, 768, dict(flush_period=3)),
    "flush-mid-k-epilogue": (300, 640, 384,
                             dict(flush_period=2, epilogue=True)),
}


@pytest.mark.parametrize("case", list(BITWISE), ids=list(BITWISE))
def test_chosen_tiles_bitwise_equal_128_tiles(rng, case):
    """The kernel at ``fused_tiles``' tiles, called directly and through
    ``ops.mgs_matmul``'s exact branch, equals the 128x128x128 call bit
    for bit; a runtime flush period keeps the caller's 128-wide K tile
    and so flushes at the same K positions."""
    M, K, N, opt = BITWISE[case]
    xc, wc = _codes(rng, (M, K)), _codes(rng, (K, N))
    fp = opt.get("flush_period")
    epi = {}
    if opt.get("epilogue"):
        epi = dict(scale=jnp.asarray(rng.uniform(0.5, 2, (1, N)), jnp.float32),
                   bias=jnp.asarray(rng.normal(0, 1, (N,)), jnp.float32),
                   activation="silu")
    bm, bn, bk = fused_tiles(M, K, N,
                             flush_block_k=None if fp is None else 128)
    assert (bm, bn, bk) != (128, 128, 128)
    if fp is not None:
        assert bk == 128
    want = np.asarray(_at_128(xc, wc, flush_period=fp, **epi))
    got = mgs_matmul_exact_fused_pallas(xc, wc, _F, block_m=bm, block_n=bn,
                                        block_k=bk, flush_period=fp,
                                        interpret=True, **epi)
    np.testing.assert_array_equal(np.asarray(got), want)
    via_ops = ops.mgs_matmul(xc, _Codes(wc), _F, "exact",
                             block_m=128, block_n=128, block_k=128,
                             flush_period=fp, interpret=True, **epi)
    np.testing.assert_array_equal(np.asarray(via_ops), want)


class _Codes:
    """A prepared-weight stand-in: ``ops.mgs_matmul`` streams ``codes``."""

    def __init__(self, codes):
        self.codes = codes


def test_tile_stats_records_the_call(rng):
    """The exact branch records each traced call shape with its tiles
    and grid steps: 40 rows are one tile of 64, K = 768 = 6 x 128 takes
    384, N = 1024 takes 512."""
    xc, wc = _codes(rng, (40, 768)), _codes(rng, (768, 1024))
    ops.mgs_matmul(xc, _Codes(wc), _F, "exact", interpret=True)
    assert TILE_STATS[(40, 768, 1024)] == (64, 512, 384, 1 * 2 * 2)


def test_runtime_flush_period_is_bit_affecting_here(rng):
    """The data above can tell flush positions apart: flushing every
    K-step of 128 differs from flushing once, so the mid-K cases would
    catch a longer K tile that moved the flushes."""
    xc, wc = _codes(rng, (33, 1024)), _codes(rng, (1024, 768))
    once = np.asarray(_at_128(xc, wc))
    every = np.asarray(_at_128(xc, wc, flush_period=1))
    assert not np.array_equal(once, every)


# ---------------------------------------------------------------------------
# the chooser on the cell's shapes (no kernel runs)
# ---------------------------------------------------------------------------

LAYER_KN = {"wq": (D_MODEL, D_MODEL), "wg": (D_MODEL, D_FF),
            "wd": (D_FF, D_MODEL)}
# calls of one decode step: q/k/v/o, gate/up, down per layer; the head
DECODE_CALLS = [((SLOTS,) + LAYER_KN["wq"], 4 * LAYERS),
                ((SLOTS,) + LAYER_KN["wg"], 2 * LAYERS),
                ((SLOTS,) + LAYER_KN["wd"], LAYERS),
                ((SLOTS, D_MODEL, VOCAB), 1)]
CELL_SHAPES = (
    [(f"decode-{n}", (SLOTS, k, w)) for n, (k, w) in LAYER_KN.items()]
    + [("decode-head", (SLOTS, D_MODEL, VOCAB)),
       ("prefill-head", (1, D_MODEL, VOCAB))]
    + [(f"prefill{m}-{n}", (m, k, w)) for m in (128, 256, 512, 1024, 2048)
       for n, (k, w) in LAYER_KN.items()])


def _grid(M, K, N, tiles):
    bm, bn, bk = tiles
    return -(-M // bm) * -(-N // bn) * -(-K // bk)


@pytest.mark.parametrize("shape", [s for _, s in CELL_SHAPES],
                         ids=[n for n, _ in CELL_SHAPES])
def test_chooser_on_cell_shapes(shape):
    """No weight is padded, the static flush bound still flushes once at
    the end of K, the tiles fit the shared VMEM formula, M is covered by
    the fewest 32-row-aligned tiles, and the tiles are long."""
    M, K, N = shape
    bm, bn, bk = fused_tiles(M, K, N)
    assert K % bk == 0 and N % bn == 0 and bk % 128 == 0 and bn % 128 == 0
    assert K // bk <= worst_case_flush_period(bk)
    assert fused_vmem_bytes(bm, bn, bk) <= FUSED_VMEM_BUDGET_BYTES
    tiles = -(-M // bm)
    assert tiles == -(-M // 256)                  # fewest tiles of <= 256
    assert bm % 32 == 0 and bm < -(-M // tiles) + 32   # the real rows
    assert bk >= 256 and bn >= 256


def test_chooser_cuts_a_decode_step_tenfold():
    """One decode step of the cell runs 11,904 grid steps (124,416 at
    128x128x128): 1,288 a layer and 1,600 for the head."""
    chosen = sum(c * _grid(*s, fused_tiles(*s)) for s, c in DECODE_CALLS)
    old = sum(c * _grid(*s, (128, 128, 128)) for s, c in DECODE_CALLS)
    assert old == 124_416
    assert chosen <= 12_500
    assert chosen == 8 * 1_288 + 1_600


@pytest.mark.parametrize("shape,flush_bk,want", [
    ((32, 4096, 4096), 128, (32, 512, 128)),      # runtime period: K stays
    ((8, 64, 40), None, (32, 40, 64)),            # dims under a lane tile
    ((1, 300, 200), None, (32, 256, 384)),        # ragged: 128-padded
    ((4096, 8192, 8192), None, (256, 512, 512)),  # M over the cap
    ((512, 8192, 4096), 2048, (256, 128, 2048)),  # long K tile: N shrinks
    ((512, 8192, 4096), 4096, (64, 128, 4096)),   # ... then M
], ids=["runtime-flush", "small", "ragged", "large", "shrink-n",
        "shrink-m"])
def test_chooser_edge_shapes(shape, flush_bk, want):
    assert fused_tiles(*shape, flush_block_k=flush_bk) == want


def test_chooser_keeps_flush_once_for_long_k():
    """Past the K one static flush covers, the chooser falls back to the
    128 K tile, whose flush positions are the 128x128x128 tiling's."""
    K = (worst_case_flush_period(512) + 1) * 512
    assert fused_tiles(32, K, 4096)[2] < 512
    K = 2 * worst_case_flush_period(128) * 128
    assert fused_tiles(32, K, 4096)[2] == 128


def test_kernel_refuses_tiles_over_the_vmem_budget():
    x = jnp.zeros((512, 2048), jnp.uint8)
    w = jnp.zeros((2048, 1024), jnp.uint8)
    assert fused_vmem_bytes(512, 1024, 1024) > FUSED_VMEM_BUDGET_BYTES
    with pytest.raises(ValueError, match="VMEM"):
        mgs_matmul_exact_fused_pallas(x, w, _F, block_m=512, block_n=1024,
                                      block_k=1024, interpret=True)
