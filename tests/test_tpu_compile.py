"""The main path's Pallas kernels compile for a TPU v5e chip.

Each test compiles one kernel, or the paged decode step around them, at
deepseek-7b widths for a chip that is described
(``jax.experimental.topologies``) and not attached, with the kernels
out of interpret mode: what Mosaic or XLA:TPU refuses here it would
refuse on the chip. Nothing runs, so these say nothing about results or
times; the kernel-vs-reference tests cover results.
"""

import dataclasses
import math
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels import mgs_attention, ops
from repro.kernels.mgs_attention import (mgs_paged_flash_attention,
                                         mgs_paged_verify_attention)
from repro.kernels.mgs_matmul import (fused_tiles,
                                      mgs_matmul_exact_fused_pallas)
from repro.models import init_params
from repro.models.transformer import decode_step_paged, init_paged_cache
from repro.quant import prepare_logits_head, prepare_params
from repro.quant import prepared as prepared_mod
from repro.quant.config import FP8_MGS_SERVE_PAGED

D_MODEL, D_FF, VOCAB = 4096, 11008, 102400   # deepseek-7b
KV_HEADS, HEAD_DIM, BLOCK = 32, 128, 128
SLOTS, CONTEXT = 8, 2048


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_persistent_cache():
    """A compile for a described chip cannot be read back without one."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, *shapes):
    text = jax.jit(fn).lower(*shapes).compile().as_text()
    assert 'custom_call_target="tpu_custom_call"' in text


@pytest.mark.parametrize("variant", ["plain", "runtime-flush",
                                     "epilogue-silu", "epilogue-gelu"])
@pytest.mark.parametrize("m", [8, 512], ids=["decode", "prefill"])
@pytest.mark.parametrize("k,n", [(D_MODEL, D_MODEL), (D_MODEL, D_FF),
                                 (D_FF, D_MODEL)], ids=["wq", "wg", "wd"])
def test_fused_matmul_compiles(one_chip, variant, m, k, n):
    """The exact kernel at the tiles ``ops.mgs_matmul`` gives it
    (``fused_tiles``): plain; under a traced int32 flush period with the
    caller's 128-wide K tile, the program a calibration hot swap serves;
    and with the per-tensor presets' in-kernel epilogue, a (1, N) scale,
    a bias and the activation."""
    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)
    flush_block_k = 128 if variant == "runtime-flush" else None
    tiles = dict(zip(("block_m", "block_n", "block_k"),
                     fused_tiles(m, k, n, flush_block_k=flush_block_k)))
    operands = [shape((m, k), jnp.uint8), shape((k, n), jnp.uint8)]
    if variant == "runtime-flush":
        operands.append(shape((), jnp.int32))

        def fn(x, w, fp):
            return mgs_matmul_exact_fused_pallas(
                x, w, flush_period=fp, interpret=False, **tiles)
    elif variant.startswith("epilogue-"):
        operands += [shape((1, n), jnp.float32), shape((n,), jnp.float32)]

        def fn(x, w, scale, bias):
            return mgs_matmul_exact_fused_pallas(
                x, w, scale=scale, bias=bias,
                activation=variant.removeprefix("epilogue-"),
                interpret=False, **tiles)
    else:
        def fn(x, w):
            return mgs_matmul_exact_fused_pallas(x, w, interpret=False,
                                                 **tiles)
    _compile(fn, *operands)


@pytest.mark.parametrize("m,k,n", [
    (32, D_MODEL, VOCAB), (1, D_MODEL, VOCAB),
    (32, D_MODEL, D_MODEL), (32, D_MODEL, D_FF), (32, D_FF, D_MODEL),
    (2048, D_MODEL, D_MODEL), (2048, D_MODEL, D_FF), (2048, D_FF, D_MODEL),
], ids=["head-decode", "head-prefill", "wq-slots", "wg-slots", "wd-slots",
        "wq-2048", "wg-2048", "wd-2048"])
def test_fused_matmul_compiles_at_chosen_tiles(one_chip, m, k, n):
    """The cell's decode calls (32 slot rows), the logits head at decode
    and at batch-1 prefill, and the largest prefill bucket, each at the
    tiles ``fused_tiles`` gives."""
    test_fused_matmul_compiles(one_chip, "plain", m, k, n)


def _paged_operands(one_chip, t):
    """(q, pools, table, lengths, score/value/bias rows) for ``t`` query
    tokens per slice: SLOTS slots x KV_HEADS heads over CONTEXT tokens."""
    def s(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    n, nb = SLOTS * KV_HEADS, CONTEXT // BLOCK
    pool = s(((SLOTS * nb + 1) * KV_HEADS, BLOCK, HEAD_DIM), jnp.uint8)
    lead = (n,) if t is None else (n, t)
    q = s((n, 1, HEAD_DIM) if t is None else (n, t, 1, HEAD_DIM))
    rows = s(lead + (CONTEXT,))
    return (q, pool, pool, s((n, nb), jnp.int32), s(lead, jnp.int32),
            rows, rows, rows)


def test_paged_flash_attention_compiles(one_chip):
    _compile(lambda *a: mgs_paged_flash_attention(*a, interpret=False),
             *_paged_operands(one_chip, None))


def test_paged_verify_attention_compiles(one_chip):
    _compile(lambda *a: mgs_paged_verify_attention(*a, interpret=False),
             *_paged_operands(one_chip, 4))


def test_paged_decode_step_moves_no_pool_buffer(one_chip, monkeypatch,
                                                pool_moves):
    """The slot engine's decode step (2 layers at deepseek-7b widths, 32
    slots, a 641-block pool, blocks of 128, fused kernels), compiled for
    the chip with the cache donated, appends by in-place scatter and
    copies, fills, slices, update-slices and concatenates no pool-sized
    buffer. The weights are the raw tree (the step quantizes them); the
    engine's prepared planes do not touch the pool."""
    for mod in (mgs_attention, ops):
        monkeypatch.setattr(mod, "default_interpret", lambda: False)
    cfg = dataclasses.replace(get_config("deepseek-7b"), n_layers=2,
                              quant=FP8_MGS_SERVE_PAGED)
    assert cfg.quant.block_k == BLOCK

    def on_chip(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)
    params = on_chip(jax.eval_shape(
        lambda: init_params(cfg, jax.random.PRNGKey(0))[0]))
    cache = on_chip(jax.eval_shape(
        lambda: init_paged_cache(cfg, 32, 2560, 641)[0]))
    tokens = jax.ShapeDtypeStruct((32, 1), jnp.int32, sharding=one_chip)
    step = jax.jit(lambda p, t, c: decode_step_paged(p, cfg, t, c),
                   donate_argnums=(2,))
    text = step.lower(params, tokens, cache).compile().as_text()
    assert 'custom_call_target="tpu_custom_call"' in text
    assert "scatter(" in text
    assert pool_moves(text, cache["k"].shape) == []


_U8_MOVE = re.compile(r"= u8\[([\d,]+)\](?:\{[^}]*\})? (pad|copy)\(")


def test_paged_decode_step_pads_no_weight(one_chip, monkeypatch):
    """The same decode step over the engine's prepared weights (packed
    uint8 codes of every layer weight and of the tied logits head):
    the fused matmul's K and N tiles divide every weight, so no code
    plane is padded or copied into a larger one."""
    for mod in (mgs_attention, ops):
        monkeypatch.setattr(mod, "default_interpret", lambda: False)
    cfg = dataclasses.replace(get_config("deepseek-7b"), n_layers=2,
                              quant=FP8_MGS_SERVE_PAGED)

    # shapes only: the build reads each weight's limb sigma back as a
    # python float, which a shape-only trace has no value for
    monkeypatch.setattr(prepared_mod, "float", lambda v: 1.0, raising=False)

    def prepared():
        p = init_params(cfg, jax.random.PRNGKey(0))[0]
        p = prepare_params(p, cfg.quant)
        return prepare_logits_head(p, cfg.quant, tied=cfg.tie_embeddings)
    params = jax.eval_shape(prepared)
    codes = [a.size for a in jax.tree.leaves(params) if a.dtype == jnp.uint8]
    assert min(codes) >= D_MODEL * D_MODEL

    def on_chip(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)
    cache = on_chip(jax.eval_shape(
        lambda: init_paged_cache(cfg, 32, 2560, 641)[0]))
    tokens = jax.ShapeDtypeStruct((32, 1), jnp.int32, sharding=one_chip)
    step = jax.jit(lambda p, t, c: decode_step_paged(p, cfg, t, c),
                   donate_argnums=(2,))
    text = step.lower(on_chip(params), tokens, cache).compile().as_text()
    assert 'custom_call_target="tpu_custom_call"' in text
    moved = [line.strip() for line in text.splitlines()
             if (m := _U8_MOVE.search(line))
             and math.prod(int(d) for d in m.group(1).split(","))
             >= D_MODEL * D_MODEL]
    assert moved == []
