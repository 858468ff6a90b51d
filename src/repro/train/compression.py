"""Int8 gradient compression with error feedback (distributed-opt trick).

For data-parallel configurations the gradient all-reduce dominates the
collective roofline term at scale; compressing the reduction payload to
int8 cuts those bytes 4x vs f32 (2x vs bf16) at the cost of quantization
noise, which an error-feedback residual re-injects on the next step
(1-bit-Adam lineage). The collective is made explicit with ``shard_map``
over the data axes: per-shard quantize -> psum(int32) -> dequantize.

Used by the pure-DP train path (``launch/train.py --compress-grads``);
not applied when FSDP shards parameters over the data axis (GSPMD then
reduce-scatters sharded grads — already bandwidth-optimal).
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

__all__ = ["init_error_state", "compress_leaf_psum",
           "make_compressed_reduce"]


def init_error_state(grads):
    """Error-feedback residuals, one per gradient leaf (f32)."""
    return jax.tree.map(lambda g: jnp.zeros(g.shape, jnp.float32), grads)


def _quantize_int8(x):
    amax = jnp.maximum(jnp.max(jnp.abs(x)), 1e-12)
    scale = amax / 127.0
    q = jnp.clip(jnp.rint(x / scale), -127, 127).astype(jnp.int8)
    return q, scale


def compress_leaf_psum(g, err, axes: Tuple[str, ...]):
    """Error-feedback int8 mean-reduce of one leaf (call inside shard_map).

    Returns (mean_gradient f32, new_error_residual f32).
    """
    x = g.astype(jnp.float32) + err
    q, scale = _quantize_int8(x)
    new_err = x - q.astype(jnp.float32) * scale
    total = jax.lax.psum(q.astype(jnp.int32), axes).astype(jnp.float32)
    mean_scale = jax.lax.pmean(scale, axes)
    nrep = jax.lax.psum(jnp.ones((), jnp.float32), axes)
    return total * mean_scale / nrep, new_err


def make_compressed_reduce(mesh: Mesh, data_axes: Tuple[str, ...]):
    """(local_grads, err) -> (mean_grads, err) with int8 payload.

    ``local_grads`` leaves are per-data-shard gradients with *full* logical
    shape (replicated layout within each shard); the result is the
    compressed mean across the data axes.
    """

    def body(grads, err):
        flat_g, td = jax.tree.flatten(grads)
        flat_e = td.flatten_up_to(err)
        outs = [compress_leaf_psum(g, e, data_axes)
                for g, e in zip(flat_g, flat_e)]
        return (td.unflatten([o[0] for o in outs]),
                td.unflatten([o[1] for o in outs]))

    def apply(grads, err):
        specs_g = jax.tree.map(lambda _: P(), grads)
        specs_e = jax.tree.map(lambda _: P(), err)
        fn = jax.shard_map(body, mesh=mesh, in_specs=(specs_g, specs_e),
                           out_specs=(specs_g, specs_e), check_vma=False)
        return fn(grads, err)

    return apply
