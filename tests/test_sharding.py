"""Logical-axis sharding rules: resolution, divisibility fallback,
priorities, spec trees. (Pure logic — multi-device behaviour is covered
by test_distributed.py subprocesses.)"""

import jax
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from repro.parallel.sharding import (Rules, make_rules, prepared_plane_dims,
                                     prepared_specs, resolve_spec)


def _mesh(shape=(4, 2), axes=("data", "model")):
    devs = np.array([jax.devices()[0]] * int(np.prod(shape))).reshape(shape)
    return Mesh(devs, axes)


def test_basic_resolution():
    mesh = _mesh()
    rules = make_rules(mesh, "train")
    spec = rules.resolve(("embed", "heads", "head_dim"), (64, 8, 16))
    assert spec == P("data", "model")


def test_divisibility_fallback():
    mesh = _mesh()
    rules = make_rules(mesh, "serve")
    # kv_heads=3 does not divide model=2 -> replicated
    spec = rules.resolve(("layers", "batch", "kv_seq", "kv_heads",
                          "head_dim"), (4, 8, 128, 3, 16))
    assert spec == P(None, "data", "model")  # kv_seq picks up model


def test_priority_kv_heads_over_kv_seq():
    mesh = _mesh()
    rules = make_rules(mesh, "serve")
    spec = rules.resolve(("layers", "batch", "kv_seq", "kv_heads",
                          "head_dim"), (4, 8, 128, 4, 16))
    # kv_heads divisible -> it wins the model axis; kv_seq left with none
    assert spec == P(None, "data", None, "model")


def test_batch_tuple_on_multipod():
    mesh = _mesh((2, 2, 2), ("pod", "data", "model"))
    rules = make_rules(mesh, "train")
    # batch divisible by pod*data*model -> pure ZeRO-3 layout (§Perf E)
    spec = rules.resolve(("batch", "seq"), (8, 64))
    assert spec == P(("pod", "data", "model"))
    # batch too small for all axes -> (pod, data) + SP over model
    spec = rules.resolve(("batch", "seq"), (4, 64))
    assert spec == P(("pod", "data"), "model")


def test_missing_axis_skipped_on_single_pod():
    mesh = _mesh()
    rules = make_rules(mesh, "train")
    # single-pod: batch spreads over (data, model) when divisible
    assert rules.resolve(("batch", "seq"), (8, 64)) == P(("data", "model"))
    # batch can't fill data x model -> data only, seq takes model (SP)
    assert rules.resolve(("batch", "seq"), (4, 64)) == P("data", "model")
    # MoE layout keeps batch off the model axis entirely
    sp = make_rules(mesh, "train", prefer_sp=True)
    assert sp.resolve(("batch", "seq"), (8, 64)) == P("data", "model")


def test_batch_one_replicates():
    mesh = _mesh()
    rules = make_rules(mesh, "serve")
    spec = rules.resolve(("batch", "kv_seq"), (1, 1024))
    assert spec == P(None, "data")


def test_no_axis_used_twice():
    mesh = _mesh((4, 4), ("data", "model"))
    rules = make_rules(mesh, "train")
    spec = rules.resolve(("experts", "embed", "ffn"), (16, 64, 128))
    flat = [a for part in spec for a in
            (part if isinstance(part, tuple) else (part,)) if a]
    assert len(flat) == len(set(flat))


def test_resolve_spec_tree():
    mesh = _mesh()
    rules = make_rules(mesh, "train")
    dims = {"w": ("embed", "ffn"), "b": ("ffn",), "step": (None,)}
    shapes = {"w": (64, 128), "b": (128,), "step": ()}
    specs = resolve_spec(dims, shapes, rules)
    assert specs["w"] == P("data", "model")
    assert specs["b"] == P("model")
    assert specs["step"] == P()


def test_scalar_dims_none():
    mesh = _mesh()
    rules = make_rules(mesh, "train")
    assert rules.resolve((None,), ()) == P()


def test_size_one_axes_canonicalized_away():
    """Degenerate (size-1) mesh axes shard nothing and must not appear in
    resolved specs — a pure-TP (1, N) mesh resolves exactly like a mesh
    without the data axis."""
    mesh = _mesh((1, 8), ("data", "model"))
    rules = make_rules(mesh, "serve")
    assert rules.resolve(("batch", "seq"), (8, 64)) == P()
    assert rules.resolve(("embed", "ffn"), (64, 128)) == P(None, "model")


# ---------------------------------------------------------------------------
# PreparedWeight plane specs (ISSUE-2)
# ---------------------------------------------------------------------------


def test_prepared_plane_dims_uses_leading_tail_dim():
    rules = make_rules(_mesh(), "serve")
    codes_d, out_d = prepared_plane_dims(
        ("layers", "embed", "heads", "head_dim"), rules, stacked=True)
    assert out_d == "heads"                      # leading tail dim
    assert codes_d == ("layers", "embed", "heads")
    # unstacked FFN weight: single tail dim
    codes_d, out_d = prepared_plane_dims(("embed", "ffn"), rules)
    assert (codes_d, out_d) == (("embed", "ffn"), "ffn")
    # a candidate-less leading tail dim never falls through to later
    # dims: sharding the flat axis by a trailing dim would cut across
    # leading-dim slices
    _, out_d = prepared_plane_dims(("embed", "head_dim", "heads"), rules)
    assert out_d is None


def test_prepared_specs_planes():
    """codes take the weight's (in, out) layout; per-channel scales
    follow the out dim."""
    rules = make_rules(_mesh((4, 2), ("data", "model")), "serve")
    w_dims = ("layers", "embed", "heads", "head_dim")
    w_shape = (4, 64, 8, 16)                     # codes (4, 64, 128)
    codes, scale = prepared_specs(w_dims, w_shape, rules, stacked=True,
                                  per_channel=True)
    assert codes == P(None, "data", "model")
    assert scale == P(None, None, "model")
    # per-tensor scale: one scalar per layer slice, replicated
    _, scale_pt = prepared_specs(w_dims, w_shape, rules, stacked=True,
                                 per_channel=False)
    assert scale_pt == P()


def test_prepared_specs_divisibility_fallback():
    """An out dim that does not divide the mesh axis replicates, exactly
    like the raw weight would."""
    rules = make_rules(_mesh((2, 8), ("data", "model")), "serve")
    codes, _ = prepared_specs(("embed", "heads", "head_dim"),
                              (64, 3, 7), rules)   # heads=3, model=8
    assert codes == P("data")


def test_prepared_specs_never_shard_mid_head():
    """Divisibility is checked against the head count, not the flattened
    size: heads=4 on model=8 replicates even though n = 4*16 = 64 is
    divisible — a shard must never cut across a head boundary."""
    rules = make_rules(_mesh((2, 8), ("data", "model")), "serve")
    codes, _ = prepared_specs(("embed", "heads", "head_dim"),
                              (64, 4, 16), rules)
    assert codes == P("data")                    # out axis replicated
    # divisible head count shards head-aligned
    codes, _ = prepared_specs(("embed", "heads", "head_dim"),
                              (64, 8, 16), rules)
    assert codes == P("data", "model")
