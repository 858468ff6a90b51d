"""The main path's Pallas kernels compile for a TPU v5e chip.

Each test compiles one kernel at deepseek-7b widths for a chip that is
described (``jax.experimental.topologies``) and not attached, with
``interpret=False`` passed explicitly: what Mosaic refuses here it would
refuse on the chip. Nothing runs, so these say nothing about results or
times; the kernel-vs-reference tests cover results.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.mgs_attention import (mgs_paged_flash_attention,
                                         mgs_paged_verify_attention)
from repro.kernels.mgs_matmul import (WS_STRIPE_BUDGET_BYTES,
                                      mgs_matmul_exact_fused_pallas,
                                      ws_stripe_bytes)

D_MODEL, D_FF = 4096, 11008            # deepseek-7b
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


@pytest.mark.parametrize("schedule", ["output", "weight", "activation"])
@pytest.mark.parametrize("m", [8, 512], ids=["decode", "prefill"])
@pytest.mark.parametrize("k,n", [(D_MODEL, D_MODEL), (D_MODEL, D_FF),
                                 (D_FF, D_MODEL)], ids=["wq", "wg", "wd"])
def test_fused_matmul_compiles(one_chip, schedule, m, k, n):
    def fn(x, w):
        return mgs_matmul_exact_fused_pallas(x, w, schedule=schedule,
                                             interpret=False)
    _compile(fn, jax.ShapeDtypeStruct((m, k), jnp.uint8, sharding=one_chip),
             jax.ShapeDtypeStruct((k, n), jnp.uint8, sharding=one_chip))


@pytest.mark.parametrize("schedule", ["weight", "activation"])
def test_stationary_stripe_at_budget_compiles(one_chip, schedule):
    """The largest K whose K-resident limb stripe the budget admits fits
    the chip's scoped VMEM (the compiler refuses about twice that)."""
    k = WS_STRIPE_BUDGET_BYTES // (3 * 128) // 128 * 128
    assert ws_stripe_bytes(k, 128, 128) <= WS_STRIPE_BUDGET_BYTES
    test_fused_matmul_compiles(one_chip, schedule, 512, k, D_MODEL)


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
