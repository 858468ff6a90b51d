"""Logical-axis sharding: rules, best-fit resolution, activation constraints.

Models annotate parameters and activations with *logical* dimension names
("batch", "heads", "ffn", "experts", ...). A ``Rules`` object maps each
name to an ordered list of candidate mesh axes; resolution is greedy and
divisibility-checked, so e.g. ``kv_heads=8`` on a 16-way model axis falls
back to replication instead of crashing, and a non-divisible vocab simply
stays unsharded while the embed dim picks up the model axis.

Activation constraints are applied through a context (``use_rules``): model
code calls :func:`constrain` unconditionally; outside a rules context it is
an identity, so the same model runs single-device tests unchanged.

Prepared-weight serving (:mod:`repro.quant.prepared`) derives the mesh
layout of each weight's kernel-ready planes from the same logical dims via
:func:`prepared_specs` / :func:`prepared_plane_dims` (see the section at
the bottom of this module).
"""

from __future__ import annotations

import contextlib
import threading
from typing import Dict, Optional, Sequence, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = ["Rules", "TRAIN_RULES", "make_rules", "train_rules", "use_rules",
           "constrain", "resolve_spec", "current_rules", "named_sharding",
           "prepared_plane_dims", "prepared_specs", "replicated_kernel_call",
           "on_multi_device_mesh"]


class Rules:
    """Logical dim -> ordered candidate mesh axes, with dim priorities.

    Resolution is greedy over dims in *priority* order (then positional),
    divisibility-checked, never assigning a mesh axis twice within one
    tensor — so e.g. a KV cache prefers sharding kv_heads over kv_seq,
    but falls back to the seq dim when head count doesn't divide.
    """

    def __init__(self, mesh: Mesh, table: Dict[str, Sequence],
                 priority: Sequence[str] = (), name: str = "rules"):
        self.mesh = mesh
        self.table = dict(table)
        self.priority = list(priority)
        self.name = name

    def axis_size(self, axis) -> int:
        if isinstance(axis, tuple):
            n = 1
            for a in axis:
                n *= self.mesh.shape[a]
            return n
        return self.mesh.shape[axis]

    def resolve(self, dims: Tuple[Optional[str], ...],
                shape: Optional[Tuple[int, ...]] = None) -> P:
        used = set()
        parts: list = [None] * len(dims)
        names = set(self.mesh.axis_names)

        def rank(i_dim):
            i, dim = i_dim
            try:
                return (0, self.priority.index(dim), i)
            except ValueError:
                return (1, 0, i)

        for i, dim in sorted(enumerate(dims), key=rank):
            for cand in self.table.get(dim, ()):  # ordered candidates
                flat = cand if isinstance(cand, tuple) else (cand,)
                if any(a not in names for a in flat):
                    continue  # axis absent from this mesh (e.g. single-pod)
                # canonical form: drop size-1 mesh axes (they shard
                # nothing) and emit a bare axis instead of a 1-tuple —
                # P(("data",)) and P("data") shard identically, and a
                # spec free of degenerate axes is comparable to
                # hand-written specs and emits no spurious partitioner
                # work on collapsed meshes. (The single-pod batch_axes
                # tuple used to leak through here as ('data',).)
                eff = tuple(a for a in flat if self.mesh.shape[a] > 1)
                if any(a in used for a in eff):
                    continue
                if shape is not None and shape[i] % self.axis_size(eff):
                    continue
                if eff:
                    parts[i] = eff[0] if len(eff) == 1 else eff
                    used.update(eff)
                break
        while parts and parts[-1] is None:
            parts.pop()
        return P(*parts)


_PRIORITY = ["batch", "experts", "vocab", "heads", "kv_heads", "ffn",
             "inner", "embed", "kv_seq", "seq", "vocab_act"]


def make_rules(mesh: Mesh, strategy: str = "train",
               seq_shard_kv: bool = True, prefer_sp: bool = False,
               shard_seq: bool = True, shard_batch: bool = True) -> Rules:
    """Production rule sets for the (pod?, data, model) meshes.

    strategy="train" — FSDP(ZeRO-3)+SP: batch over (pod, data), sequence
      over model (Megatron-style sequence parallelism keeps the remat
      stash per-device bounded), every parameter fully sharded: its
      "parallel" dim (heads/ffn/experts/vocab) over model and its embed
      dim over (pod, data). GSPMD materializes the per-layer weight
      all-gathers inside the scan (the ZeRO-3 schedule).

    strategy="serve" — TP + weight-sharding: batch over (pod, data),
      heads/ffn/experts over model (tensor parallelism does the work
      split), weights additionally sharded over (pod, data) on the embed
      dim; KV caches shard kv_heads over model when divisible, falling
      back to kv_seq, then the data axis when the batch is tiny
      (long_500k batch=1).

    ``shard_batch`` (serve only): with ``False``, batch-indexed
      activations and caches replicate across the data axes instead of
      sharding — the *deterministic* serving layout ``ServeEngine`` uses.
      Weights and prepared planes stay FSDP-sharded over data (the
      memory win), but every float op then sees mesh-invariant local
      shapes, which is what extends the engine's bit-identity guarantee
      to data-axis meshes (docs/serving.md). ``True`` keeps the
      batch-over-data throughput layout (per-device float ops may then
      drift at ulp level across mesh shapes).
    """
    batch_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    fsdp_axes = [batch_axes, "data"]
    common = {
        "head_dim": [], "ssm_state": [], "dt_rank": [], "conv_k": [],
        "layers": [], "groups": [], "sub": [], "enc_seq": [],
        "groups_act": [batch_axes, "data"],
        "experts_act": ["model"],
        "embed_act": [],
        # params
        "vocab": ["model"],
        "heads": ["model"],
        "kv_heads": ["model"],
        "ffn": ["model"],
        "experts": ["model"],
        "inner": ["model"],
        "embed": fsdp_axes,
    }
    if strategy == "train":
        # Two training layouts (EXPERIMENTS.md §Perf E/F):
        # * dense archs: spread the batch over every axis (pure ZeRO-3 —
        #   attention stays local, no per-layer KV gathers; measured ~4x
        #   peak-fraction gain on deepseek-7b vs sequence parallelism).
        # * prefer_sp (MoE archs): batch over (pod, data) + sequence
        #   parallelism over model. MoE dispatch needs token groups to
        #   stay data-sharded while experts own the model axis — batch-
        #   over-model forces a G:[256]->[16,16] reshard GSPMD can only
        #   do by full replication (measured +25.8 GB/device on dbrx).
        #   Their GQA KV is small (kv=8), so the SP KV gathers are cheap.
        # The pod axis is never left idle (no redundant compute).
        if prefer_sp:
            batch_cands = [batch_axes, "data"]
        elif "pod" in mesh.axis_names:
            batch_cands = [("pod", "data", "model"), ("pod", "data"),
                           "data"]
        else:
            batch_cands = [("data", "model"), "data"]
        table = dict(common)
        table.update({
            "batch": batch_cands,
            # SSM archs must not shard seq: lax.scan over time chunks
            # forces its xs to be materialized unsharded along the scan
            # axis, gathering the full sequence per layer (§Perf H).
            "seq": ["model"] if shard_seq else [],
            "vocab_act": ["model"],
            "kv_seq": [],
        })
    elif strategy == "serve":
        table = dict(common)
        table.update({
            "batch": ([batch_axes, "data"] if shard_batch else []),
            "seq": [],
            "vocab_act": ["model"],
            "kv_seq": (["data", "model"] if seq_shard_kv else []),
        })
        if not shard_batch:
            table["groups_act"] = []
    else:
        raise ValueError(f"unknown strategy {strategy!r}")
    return Rules(mesh, table, priority=_PRIORITY, name=strategy)


def train_rules(mesh: Mesh, fsdp: bool = True, seq_shard_kv: bool = True,
                **_kw) -> Rules:
    """Backward-compatible alias for make_rules(strategy='train')."""
    return make_rules(mesh, "train", seq_shard_kv)


TRAIN_RULES = train_rules  # alias


_ctx = threading.local()


def current_rules() -> Optional[Rules]:
    return getattr(_ctx, "rules", None)


@contextlib.contextmanager
def use_rules(rules: Optional[Rules]):
    prev = current_rules()
    _ctx.rules = rules
    try:
        yield rules
    finally:
        _ctx.rules = prev


def on_multi_device_mesh() -> bool:
    """Whether an active rules context spans more than one device —
    where :func:`replicated_kernel_call` gathers its operands, and a
    sharded array keeps its sharding only through shape-aligned ops."""
    rules = current_rules()
    return rules is not None and rules.mesh.size > 1


def replicated_kernel_call(fn, *args):
    """Run one Pallas kernel call whole on every device of the active mesh.

    GSPMD cannot partition a Mosaic kernel: on a TPU mesh of more than
    one device XLA refuses a bare kernel call. Under such a mesh the call
    goes through a ``shard_map`` whose specs are all replicated: sharded
    operands are gathered, each device runs the full call, and the
    result is replicated — exactly the one-device result, so the
    cross-mesh bit-identity contract holds. Weight planes stay sharded
    at rest; kernel compute does not scale with the mesh. ``args`` may
    hold arrays, ``None`` and Python scalars; ``fn`` closes over static
    values only.
    """
    if not on_multi_device_mesh():
        return fn(*args)
    return jax.shard_map(fn, mesh=current_rules().mesh, in_specs=P(),
                         out_specs=P(), check_vma=False)(*args)


def constrain(x, dims: Tuple[Optional[str], ...]):
    """Apply a with_sharding_constraint from logical dims (no-op outside a
    rules context).

    A spec that resolves fully replicated is skipped entirely: it
    constrains nothing, and the dangling sharding custom-call would still
    run the SPMD partitioner pipeline over the op — which on some
    backends perturbs fusion decisions (and hence low-order float bits)
    for no layout benefit. Skipping it keeps replicated mesh programs
    bit-identical to their single-device compilation — the property the
    sharded serving tests pin down.
    """
    rules = current_rules()
    if rules is None:
        return x
    spec = rules.resolve(dims, tuple(x.shape))
    if not any(part is not None for part in spec):
        return x
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(rules.mesh, spec))


def resolve_spec(dims_tree, shapes_tree, rules: Rules):
    """Map a dims tree (+ matching shapes) to a PartitionSpec tree."""
    return jax.tree.map(
        lambda dims, shape: rules.resolve(tuple(dims), tuple(shape)),
        dims_tree, shapes_tree,
        is_leaf=lambda d: isinstance(d, tuple) and all(
            isinstance(s, (str, type(None))) for s in d))


def named_sharding(spec_tree, mesh: Mesh):
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s), spec_tree,
        is_leaf=lambda s: isinstance(s, P))


# ---------------------------------------------------------------------------
# PreparedWeight plane specs
# ---------------------------------------------------------------------------
#
# A ``quant.prepared.PreparedWeight`` stores a (*stack, K, *tail) weight as
# two kernel-ready planes whose trailing output axes are *flattened*:
#
#   codes  (*stack, K, n)        packed FP8 codes, n = prod(tail)
#   scale  (*stack, 1, n) | (*stack,)   per-channel | per-tensor scales
#
# The planes must live on the mesh exactly where the owning weight's
# logical dims put them: the K axis keeps the weight's input dim (e.g.
# "embed" -> the FSDP axes), the flattened output axis inherits the
# *leading* tail dim (e.g. ("heads", "head_dim") -> the "heads" mesh
# axes, with divisibility checked against the head count so a shard
# always covers whole heads), and per-channel scales follow the output
# axis. The helpers below derive those dims and resolve them through the
# same greedy, divisibility-checked machinery as every other parameter.


def prepared_plane_dims(w_dims: Tuple[Optional[str], ...], rules: Rules, *,
                        stacked: bool = False,
                        stack_ndim: Optional[int] = None, k_ndim: int = 1):
    """Logical dims of a PreparedWeight's planes from the raw weight's dims.

    Args:
      w_dims: the owning weight's logical dims, ``(*stack, *k, *tail)`` —
        e.g. ``("layers", "embed", "heads", "head_dim")`` for a stacked
        attention projection, ``("layers", "experts", "embed", "ffn")``
        for a per-expert MoE weight (two stack axes), or ``("layers",
        "heads", "head_dim", "embed")`` for the out-projection (two
        contracted axes).
      rules: the active :class:`Rules` (its priority order picks which
        tail dim names the flattened output axis).
      stacked: back-compat alias for ``stack_ndim=1``.
      stack_ndim: number of leading per-slice stack axes (matching
        ``prepare_weight(stack_ndim=...)``).
      k_ndim: number of contracted axes flattened into the plane's K. A
        single contracted axis keeps its logical dim on the plane's K
        axis; a flattened multi-axis K stays replicated (a mesh chunk of
        it could split a head, and the exact kernel consumes K whole).

    Returns:
      ``(codes_dims, out_dim)``: the dims tuple of the codes plane, and
      the logical name chosen for the flattened output axis. Only the
      *leading* tail dim may name it: a chunk of the flattened axis then
      covers whole trailing slices (e.g. whole heads), so the plane
      layout stays aligned with the raw weight's. ``None`` when the
      leading tail dim has no mesh candidates.
    """
    n_stack = (1 if stacked else 0) if stack_ndim is None else stack_ndim
    stack_dims = tuple(w_dims[:n_stack])
    in_dim = w_dims[n_stack] if k_ndim == 1 else None
    tail_dims = tuple(w_dims[n_stack + k_ndim:])
    out_dim = None
    if tail_dims and tail_dims[0] is not None and rules.table.get(
            tail_dims[0]):
        out_dim = tail_dims[0]
    return stack_dims + (in_dim, out_dim), out_dim


def prepared_specs(w_dims: Tuple[Optional[str], ...],
                   w_shape: Tuple[int, ...], rules: Rules, *,
                   stacked: bool = False, stack_ndim: Optional[int] = None,
                   k_ndim: int = 1, per_channel: bool = False):
    """PartitionSpecs for a PreparedWeight's planes.

    Args:
      w_dims / w_shape: logical dims and shape of the *raw* weight,
        ``(*stack, *k, *tail)`` (shape before flattening — the flattened
        plane shapes are derived here).
      rules: active sharding rules. Divisibility is checked against the
        *leading tail dim's size* (e.g. the head count), not the
        flattened output size: a mesh axis that does not divide it falls
        back to replication exactly like the raw weight would, and a
        shard of the flattened axis always covers whole trailing slices
        (never a partial head).
      stacked: back-compat alias for ``stack_ndim=1``.
      stack_ndim: number of leading per-slice stack axes (per-layer scan
        stacks, the per-expert axis of MoE weights, or both).
      k_ndim: number of contracted axes flattened into the plane's K
        (see :func:`prepared_plane_dims`).
      per_channel: whether the scale plane is per-output-channel,
        shape ``(*stack, 1, n)`` (else per-tensor, shape ``(*stack,)``).

    Returns:
      ``(codes_spec, scale_spec)`` PartitionSpecs, shaped for the
      corresponding plane ranks (specs over the flattened ``n`` axis
      — an axis dividing the leading tail dim also divides ``n``).
    """
    n_stack = (1 if stacked else 0) if stack_ndim is None else stack_ndim
    stack_shape = tuple(int(s) for s in w_shape[:n_stack])
    K = 1
    for s in w_shape[n_stack:n_stack + k_ndim]:
        K *= int(s)
    tail = tuple(int(s) for s in w_shape[n_stack + k_ndim:])
    out_size = tail[0] if tail else 1
    codes_dims, out_dim = prepared_plane_dims(
        w_dims, rules, stack_ndim=n_stack, k_ndim=k_ndim)
    codes_spec = rules.resolve(codes_dims, stack_shape + (K, out_size))
    if per_channel:
        scale_spec = rules.resolve(tuple(w_dims[:n_stack]) + (None, out_dim),
                                   stack_shape + (1, out_size))
    else:
        scale_spec = rules.resolve(tuple(w_dims[:n_stack]), stack_shape)
    return codes_spec, scale_spec
