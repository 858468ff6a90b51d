"""Prepared weights: quantize + encode static parameters *once*.

The serving hot path must not re-quantize weights per request: a static
weight's absmax scale and packed FP8 codes are functions of the
parameter alone, so they are computed once — at load / checkpoint /
engine-init time — and cached for the life of the process. ``qmatmul``
then consumes the :class:`PreparedWeight` directly:

* exact kernel: streams ``codes`` (1 byte/element of HBM traffic) and
  decodes its limbs per tile in VMEM;
* emulation / dmac fallbacks: reconstruct format-exact values from
  ``codes`` via ``decode_bits`` (cheap elementwise, no re-rounding).

``PreparedWeight`` is a registered pytree whose leaves are arrays, so a
prepared parameter tree passes through ``jax.jit`` / ``lax.scan`` like any
other: model code that scans stacked per-layer weights slices the codes /
scale planes along the leading layer axis transparently.

``prepare_weight`` keeps a process-level cache keyed by parameter
identity; ``PREP_STATS`` counts builds vs cache hits so tests (and
monitoring) can verify each weight is prepared exactly once per process.

On a multi-device mesh the planes are built **directly into their sharded
layout**: pass ``shardings`` (one :class:`jax.sharding.NamedSharding` per
plane, usually derived via :func:`repro.parallel.sharding.prepared_specs`)
and the quantize+encode computation is jitted with those
``out_shardings`` — no full replicated copy of the planes ever
materializes, and re-preparation on the same mesh is a cache hit like any
other. ``prepare_params(..., dims=..., rules=...)`` derives the plane
shardings from each weight's logical dims automatically.
"""

from __future__ import annotations

import weakref
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.formats import FPFormat, encode_bits, decode_bits, get_format
from repro.kernels.mgs_matmul import limb_decompose

from .config import QuantConfig
from .quantize import quantize_fp8

__all__ = ["PreparedWeight", "prepare_weight", "prepare_params",
           "prepare_unembed", "prepare_logits_head", "PREP_STATS",
           "clear_prepared_cache"]

# Process-level preparation accounting: ``prepared`` counts actual
# quantize+encode builds, ``cache_hits`` counts reuses. Serving must
# keep ``prepared`` constant across requests.
PREP_STATS = {"prepared": 0, "cache_hits": 0}

_CACHE: dict = {}


class PreparedWeight:
    """A weight quantized + encoded once, in kernel-ready planes.

    Array leaves (pytree children — all share any leading stack axes):

    * ``codes``: packed FP8 codes (uint8), shape (*stack, K, N) — the
      exact kernel's 1-byte/elem HBM stream, and the source for
      :meth:`values` on the emulation paths.
    * ``scale``: dequantization scale, broadcastable to (*stack, 1, N).

    The stack may span several leading axes (``stack_ndim`` — e.g.
    (layers, experts) for MoE expert weights consumed via
    ``quant.qeinsum``), and ``K`` may flatten several contracted axes
    (``k_ndim`` — e.g. (heads, head_dim) for the attention
    out-projection).

    Static aux data: ``fmt_name``, logical ``tail`` (the un-flattened
    trailing dims the consuming layer reshapes back to), ``limb_sigma``
    — the observed *weight* limb std feeding the Markov flush planner
    (``core.markov.plan_flush_period``) — and ``act_sigma``, the
    calibrated *activation* limb sigma for this weight's call site
    (``quant.calibrate``; ``None`` until a calibration pass stamps it).
    """

    def __init__(self, codes, scale, fmt_name: str,
                 tail: Tuple[int, ...], limb_sigma: Optional[float] = None,
                 act_sigma: Optional[float] = None):
        self.codes = codes
        self.scale = scale
        self.fmt_name = fmt_name
        self.tail = tuple(tail)
        self.limb_sigma = limb_sigma
        self.act_sigma = act_sigma

    @property
    def fmt(self) -> FPFormat:
        return get_format(self.fmt_name)

    @property
    def shape(self):
        return self.codes.shape

    def values(self, dtype=jnp.float32):
        """Format-exact weight values (for emulation / dmac fallbacks)."""
        return decode_bits(self.codes, self.fmt, dtype)

    def with_act_sigma(self, act_sigma: Optional[float]) -> "PreparedWeight":
        """Copy sharing the same planes, with a calibrated act sigma."""
        return PreparedWeight(self.codes, self.scale, self.fmt_name,
                              self.tail, self.limb_sigma,
                              act_sigma=act_sigma)

    def __repr__(self):
        return (f"PreparedWeight(shape={tuple(self.codes.shape)}, "
                f"fmt={self.fmt_name}, tail={self.tail}, "
                f"limb_sigma={self.limb_sigma}, "
                f"act_sigma={self.act_sigma})")


def _pw_flatten(pw: PreparedWeight):
    return ((pw.codes, pw.scale),
            (pw.fmt_name, pw.tail, pw.limb_sigma, pw.act_sigma))


def _pw_unflatten(aux, children):
    codes, scale = children
    fmt_name, tail, limb_sigma, act_sigma = aux
    return PreparedWeight(codes, scale, fmt_name, tail, limb_sigma,
                          act_sigma=act_sigma)


jax.tree_util.register_pytree_node(PreparedWeight, _pw_flatten, _pw_unflatten)


def _build(w, cfg: QuantConfig, stack_ndim: int, k_ndim: int,
           shardings=None) -> PreparedWeight:
    fmt = cfg.fmt
    w = jnp.asarray(w)
    if stack_ndim + k_ndim >= w.ndim and not (
            stack_ndim + k_ndim == w.ndim and w.ndim >= 2):
        raise ValueError(f"weight rank {w.ndim} too small for "
                         f"stack_ndim={stack_ndim} + k_ndim={k_ndim}")
    stack = tuple(int(s) for s in w.shape[:stack_ndim])
    K = int(np.prod(w.shape[stack_ndim:stack_ndim + k_ndim]))
    tail = tuple(int(s) for s in w.shape[stack_ndim + k_ndim:])
    n = int(np.prod(tail)) if tail else 1
    axis = 0 if cfg.per_channel else None
    margin = cfg.fp8_margin
    n_stack = int(np.prod(stack)) if stack else 1

    def compute(wr):
        w2 = wr.reshape(stack + (K, n)).astype(jnp.float32)

        def quantize_one(wi):
            return quantize_fp8(wi, fmt, axis=axis, margin=margin)

        if stack:  # per-slice scales (per layer, per expert, ...)
            qt = jax.vmap(quantize_one)(w2.reshape((n_stack, K, n)))
            qt = type(qt)(q=qt.q.reshape(stack + (K, n)),
                          scale=qt.scale.reshape(
                              stack + qt.scale.shape[1:]),
                          offset=qt.offset)
        else:
            qt = quantize_one(w2)
        codes = encode_bits(qt.q, fmt)
        limbs = limb_decompose(qt.q, fmt)     # (3, *stack, K, n)
        if stack:
            limbs = jnp.moveaxis(limbs, 0, len(stack))  # (*stack, 3, K, n)
        # observed limb statistics feed the Markov flush planner; the
        # limbs are not a jit output, so XLA fuses the decompose into the
        # std reduction instead of materializing a 3-byte/elem buffer.
        sigma = jnp.std(limbs.astype(jnp.float32))
        return codes, qt.scale, sigma

    # one jitted program per weight: its float/int32 temporaries are
    # fused away, so building a plane never holds more than the raw
    # weight plus the outputs (run op by op, a stacked 7B-width FFN
    # weight's temporaries alone are several GB of device memory)
    jit_kw = {}
    if shardings is not None:
        # build straight into the mesh layout: the planes come out of the
        # jit already sharded — never materialized replicated-then-moved.
        codes_sh, scale_sh = shardings
        jit_kw["out_shardings"] = (codes_sh, scale_sh, None)
    codes, scale, sigma = jax.jit(compute, **jit_kw)(w)
    PREP_STATS["prepared"] += 1
    return PreparedWeight(codes, scale, fmt.name, tuple(tail), float(sigma))


def prepare_weight(w, cfg: QuantConfig, *, stacked: bool = False,
                   stack_ndim: Optional[int] = None, k_ndim: int = 1,
                   shardings=None) -> PreparedWeight:
    """Quantize + encode ``w`` under ``cfg``, cached per process.

    Args:
      w: ``(*stack, *kdims, *tail)`` weight. Stack axes (per-layer,
        per-expert, ...) get per-slice scales so ``lax.scan`` / grouped
        ``qeinsum`` consumption matches per-slice quantization; the
        ``k_ndim`` contracted axes are flattened into the kernel's K
        (e.g. (heads, head_dim) for the attention out-projection).
      cfg: quantization config; must be an fp8 dtype.
      stacked: back-compat alias for ``stack_ndim=1``.
      stack_ndim: number of leading per-slice stack axes (overrides
        ``stacked``; e.g. 2 for (layers, experts) MoE expert weights).
      k_ndim: number of contracted axes following the stack (default 1).
      shardings: optional ``(codes, scale)`` pair of
        :class:`jax.sharding.NamedSharding` (see
        :func:`repro.parallel.sharding.prepared_specs`). When given, the
        planes are built directly into that mesh layout via jit
        ``out_shardings`` — the once-per-process build is also the
        placement, with no replicate-then-reshard step.

    Returns:
      The cached :class:`PreparedWeight`. The cache is keyed on parameter
      identity + the quantization-relevant config fields + the plane
      shardings, holding the source array only weakly — dropping the raw
      weight after preparation releases its memory. Re-preparing the same
      array is a cache hit (counted in ``PREP_STATS``, not re-built).
    """
    if not cfg.is_fp8:
        raise ValueError(f"prepare_weight requires an fp8 dtype, got "
                         f"{cfg.dtype!r}")
    if stack_ndim is None:
        stack_ndim = 1 if stacked else 0
    key = (id(w), cfg.dtype, cfg.accum, cfg.per_channel, int(stack_ndim),
           int(k_ndim), None if shardings is None else tuple(shardings))
    hit = _CACHE.get(key)
    if hit is not None and hit[0]() is w:
        PREP_STATS["cache_hits"] += 1
        return hit[1]
    pw = _build(w, cfg, stack_ndim, k_ndim, shardings)
    try:
        # weak ref: cache validity without pinning the raw weight (the
        # prepared planes replace it in the serving path)
        _CACHE[key] = (weakref.ref(w), pw)
    except TypeError:
        _CACHE[key] = (lambda w=w: w, pw)  # non-weakrefable: hold strong
    return pw


def prepare_unembed(embed, cfg: QuantConfig, *,
                    shardings=None) -> PreparedWeight:
    """Prepared unembedding view of a tied embedding table, cached.

    Tied-embedding models keep the raw ``(vocab, d_model)`` table in the
    parameter tree (the token-lookup path needs raw rows), so the logits
    head used to re-quantize the whole table on *every* prefill/decode
    step — the largest per-token re-quantization left in serving. This
    helper quantizes + encodes the **transposed** ``(d_model, vocab)``
    view once — canonical ``(K, N)`` planes the logits head's
    ``qeinsum("btd,dv->btv", ...)`` consumes directly — and caches the
    result per process keyed on the *embedding table's* identity (the
    transposed view is an internal temporary; callers never manage it).

    Args:
      embed: the raw ``(vocab, d_model)`` embedding table.
      cfg: fp8 quantization config (same contract as
        :func:`prepare_weight`).
      shardings: optional ``(codes, scale)`` NamedShardings for
        the **(d_model, vocab)-shaped view** — derive them from the
        logical dims ``("embed", "vocab")``, e.g. via
        :func:`repro.parallel.sharding.prepared_specs`.

    Returns:
      The cached :class:`PreparedWeight` of the unembedding view. Builds
      count once in ``PREP_STATS``; re-calls on the same table are cache
      hits. The codes plane costs one extra byte per table element of
      device memory — the price of never re-quantizing the head again.
    """
    if not cfg.is_fp8:
        raise ValueError(f"prepare_unembed requires an fp8 dtype, got "
                         f"{cfg.dtype!r}")
    if getattr(embed, "ndim", 0) != 2:
        raise ValueError(f"embedding table must be 2D, got shape "
                         f"{getattr(embed, 'shape', None)}")
    key = ("unembed", id(embed), cfg.dtype, cfg.accum, cfg.per_channel,
           None if shardings is None else tuple(shardings))
    hit = _CACHE.get(key)
    if hit is not None and hit[0]() is embed:
        PREP_STATS["cache_hits"] += 1
        return hit[1]
    view = jnp.swapaxes(jnp.asarray(embed), 0, 1)   # (V, d) -> (d, V)
    pw = _build(view, cfg, 0, 1, shardings)
    try:
        _CACHE[key] = (weakref.ref(embed), pw)
    except TypeError:
        _CACHE[key] = (lambda e=embed: e, pw)
    return pw


def prepare_logits_head(params, cfg: QuantConfig, *, tied: bool,
                        rules=None):
    """Return ``params`` with the logits-head weight prepared.

    Serving-side companion to :func:`prepare_params`, which leaves the
    embedding table raw (it is shared with the lookup path) and does not
    know whether the model ties its unembedding. Given that knowledge
    (``tied``, from ``ModelConfig.tie_embeddings``):

    * tied: adds an ``"unembed_prepared"`` entry — the cached
      :func:`prepare_unembed` view of ``params["embed"]`` — which
      ``models.transformer._logits`` picks up, eliminating the per-step
      re-quantization of the full table;
    * untied: replaces a raw ``params["unembed"]`` with its
      :class:`PreparedWeight` (the ``(d_model, vocab)`` layout is
      already the canonical ``(K, N)``).

    Idempotent (already-prepared trees pass through, so replica engines
    built from transferred params add nothing) and a no-op for non-MGS
    configs. ``rules`` (with the owning mesh) builds the planes directly
    into their sharded layout, exactly like :func:`prepare_params`.
    """
    if not (cfg.is_fp8 and cfg.accum in ("mgs_exact", "mgs_dmac")):
        return params

    def head_shardings(shape_dv):
        if rules is None:
            return None
        from jax.sharding import NamedSharding
        from repro.parallel.sharding import prepared_specs
        specs = prepared_specs(("embed", "vocab"), tuple(shape_dv), rules,
                               per_channel=cfg.per_channel)
        return tuple(NamedSharding(rules.mesh, s) for s in specs)

    if tied:
        embed = params.get("embed") if isinstance(params, dict) else None
        if ("unembed_prepared" in params
                or getattr(embed, "ndim", 0) != 2):
            return params
        out = dict(params)
        out["unembed_prepared"] = prepare_unembed(
            embed, cfg, shardings=head_shardings(embed.shape[::-1]))
        return out
    w = params.get("unembed") if isinstance(params, dict) else None
    if isinstance(w, PreparedWeight) or getattr(w, "ndim", 0) != 2:
        return params
    out = dict(params)
    out["unembed"] = prepare_weight(w, cfg,
                                    shardings=head_shardings(w.shape))
    return out


def clear_prepared_cache():
    _CACHE.clear()


# Weights consumed via models.linear.proj / models' qeinsum call sites,
# keyed by their parent module child name. The remaining 2D+ parameters
# (embedding tables — shared with the lookup path — and conv filters)
# stay raw arrays.
_PROJ_WEIGHTS = {
    "attn": {"wq", "wk", "wv", "wo"},
    "ffn": {"wg", "wu", "wi", "wd"},
    "moe": {"wr", "wg", "wu", "wi", "wd"},
    "ssm": {"wx", "wz", "wdt_down", "wdt_up", "wB", "wC", "wo"},
}

# Contracted-axis count per (parent, name): the attention out-projection
# flattens (heads, head_dim) into the kernel's K.
_K_NDIM = {("attn", "wo"): 2}

# Subtrees whose leaves are stacked along a leading per-layer axis
# (consumed via lax.scan / lax.map in models.transformer).
_STACKED_ROOTS = {"layers", "encoder", "cross"}

# Logical dim names that mark leading per-slice stack axes: per-layer
# scan stacks plus the per-expert axis of MoE expert weights.
_STACK_DIM_NAMES = {"layers", "groups", "sub", "experts"}


def _stack_ndim_of(path, dims, ndim: int, k_ndim: int) -> int:
    """Leading stack-axis count of one weight.

    With a logical-dims tuple the count is exact (leading dims drawn from
    ``_STACK_DIM_NAMES`` — handles (layers, experts) MoE stacks and the
    hybrid (groups, sub) nesting). Without dims, fall back to the path
    heuristic: one axis under a scanned root, plus the expert axis for
    MoE expert weights.
    """
    if isinstance(dims, tuple) and len(dims) == ndim:
        n = 0
        while n < len(dims) and dims[n] in _STACK_DIM_NAMES:
            n += 1
        return min(n, ndim - k_ndim - 1)
    n = 1 if any(p in _STACKED_ROOTS for p in path) else 0
    if len(path) >= 2 and path[-2] == "moe" and path[-1] != "wr":
        n += 1  # per-expert axis of the expert einsum weights
    return min(n, ndim - k_ndim - 1)


def prepare_params(params, cfg: QuantConfig, *, dims=None, rules=None):
    """Return ``params`` with every proj-consumed weight prepared.

    Walks the nested-dict parameter tree of ``models.transformer`` and
    replaces each matmul-consumed weight with its :class:`PreparedWeight`
    (leaving embedding tables, norms, conv filters, and biases
    untouched). Stacked subtrees (per-layer scans, per-expert MoE
    weights) get per-slice scales; the attention out-projection's
    (heads, head_dim) axes are flattened into the kernel's K. Idempotent
    and cache-backed: calling twice on the same tree builds nothing new.

    Args:
      params: nested-dict parameter tree (``models.init_params``).
      cfg: quantization config; non-MGS configs pass through untouched.
      dims: matching logical-dims tree (``init_params``'s second return /
        ``models.param_dims``). Optional but recommended — it makes the
        stack-axis inference exact for the grouped/expert layouts (MoE
        (layers, experts) stacks, hybrid (groups, sub) nesting) and is
        required for sharded builds.
      rules: :class:`repro.parallel.sharding.Rules` for the serving mesh.
        When both ``dims`` and ``rules`` are given, each weight's plane
        shardings are derived from its logical dims
        (:func:`repro.parallel.sharding.prepared_specs`) and the planes
        are built directly into the mesh layout.

    Returns:
      The parameter tree with matmul weights replaced by PreparedWeights.
    """
    if not (cfg.is_fp8 and cfg.accum in ("mgs_exact", "mgs_dmac")):
        return params
    shard = dims is not None and rules is not None
    if shard:
        from jax.sharding import NamedSharding
        from repro.parallel.sharding import prepared_specs

    def walk(node, dnode, path):
        if isinstance(node, dict):
            return {k: walk(v, dnode.get(k) if isinstance(dnode, dict)
                            else None, path + (k,))
                    for k, v in node.items()}
        if (len(path) >= 2 and path[-1] in _PROJ_WEIGHTS.get(path[-2], ())
                and getattr(node, "ndim", 0) >= 2):
            k_ndim = _K_NDIM.get((path[-2], path[-1]), 1)
            stack_ndim = _stack_ndim_of(path, dnode, node.ndim, k_ndim)
            shardings = None
            if shard and isinstance(dnode, tuple) and len(dnode) == node.ndim:
                specs = prepared_specs(dnode, node.shape, rules,
                                       stack_ndim=stack_ndim,
                                       k_ndim=k_ndim,
                                       per_channel=cfg.per_channel)
                shardings = tuple(NamedSharding(rules.mesh, s)
                                  for s in specs)
            return prepare_weight(node, cfg, stack_ndim=stack_ndim,
                                  k_ndim=k_ndim, shardings=shardings)
        return node

    return walk(params, dims, ())
