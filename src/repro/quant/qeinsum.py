"""Unified quantized-einsum dispatch — every model contraction routes here.

``qeinsum(spec, x, w, cfg)`` canonicalizes any 2-operand einsum (batched,
grouped, multi-head) into the exact kernel's ``(M, K, N)`` matmul form via
reshape/transpose planning and dispatches it through
:func:`repro.quant.qmatmul.qmatmul` — so attention out-projections, MoE
expert einsums, the logits head, and the decode-time score/value
contractions all accumulate under the same MGS numerics as the dense
projections, instead of falling back to plain ``jnp.einsum``. One dispatch
layer owning every contraction is what Sakr et al. (arXiv:1901.06588)
argue accumulator sizing needs (per-layer statistics of the *actual* dot
products) and what makes distributed serving bit-identical: the exact
kernel's integer limb accumulation cannot be reordered by GSPMD, so a
mesh that routes every matmul through it reproduces the single-device
logits bit for bit (see docs/serving.md).

Index classification (letters of the spec):

* **batch** — appears in x, w, and the output (e.g. the expert axis ``e``
  of ``gecd,edf->gecf``): the contraction is dispatched per batch slice,
  each slice quantized with its own scale (per-expert quantization).
* **k** — appears in x and w but not the output: the contracted axes,
  flattened into the kernel's K (multi-axis K such as ``(heads,
  head_dim)`` of the attention out-projection ``bthd,hdo->bto`` is
  supported).
* **m** — x and output only; **n** — w and output only: flattened into
  the kernel's M / N.

``w`` may be a :class:`repro.quant.PreparedWeight` whose planes were built
with matching stack (= batch) and K axes (``prepare_weight(stack_ndim=,
k_ndim=)``); its term must already be in canonical ``batch + k + n``
order — true for every weight layout in the model zoo.

With ``cfg.dtype == "none"`` the dispatch is a plain ``jnp.einsum`` with
fp32 accumulation (the same convention as ``qmatmul``'s unquantized dot),
so routing a call site through ``qeinsum`` never changes unquantized
numerics beyond the accumulation dtype.

``site`` names the call site (e.g. ``"moe.wg"``) for the calibration
subsystem (:mod:`repro.quant.calibrate`): under a ``calibrating()``
context the quantized activation's limb statistics are recorded per site,
and a calibrated ``cfg`` feeds each site's observed sigma into the Markov
flush planner (per-call-site flush periods instead of one global guess).
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.mgs_matmul import ACTIVATIONS
from .config import QuantConfig
from .prepared import PreparedWeight
from .qmatmul import qmatmul

__all__ = ["qeinsum", "plan_qeinsum", "QeinsumPlan"]


@dataclasses.dataclass(frozen=True)
class QeinsumPlan:
    """Reshape/transpose plan of one canonicalized contraction.

    ``batch``/``m``/``k``/``n`` are the classified index strings in
    canonical order (batch, k, n ordered as they appear in the w term; m
    as it appears in the x term). ``x_perm``/``w_perm`` transpose the
    operands to ``(batch, m, k)`` / ``(batch, k, n)`` axis order, and
    ``out_perm`` maps the canonical ``(batch, m, n)`` output back to the
    requested output term.
    """

    x_ix: str
    w_ix: str
    out_ix: str
    batch: str
    m: str
    k: str
    n: str
    x_perm: Tuple[int, ...]
    w_perm: Tuple[int, ...]
    out_perm: Tuple[int, ...]

    @property
    def canonical_w(self) -> bool:
        """True when the w term is already (batch, k, n) ordered — the
        layout a PreparedWeight's planes are stored in."""
        return self.w_ix == self.batch + self.k + self.n


def _parse(spec: str) -> Tuple[str, str, str]:
    spec = spec.replace(" ", "")
    if "..." in spec:
        raise ValueError(f"qeinsum does not support ellipsis: {spec!r}")
    if "->" not in spec:
        raise ValueError(f"qeinsum requires an explicit output: {spec!r}")
    lhs, out_ix = spec.split("->")
    terms = lhs.split(",")
    if len(terms) != 2:
        raise ValueError(f"qeinsum is 2-operand only: {spec!r}")
    x_ix, w_ix = terms
    for term in (x_ix, w_ix, out_ix):
        if len(set(term)) != len(term):
            raise ValueError(f"repeated index in term {term!r} of {spec!r}")
    return x_ix, w_ix, out_ix


def plan_qeinsum(spec: str) -> QeinsumPlan:
    """Classify a spec's indices and derive the canonicalization plan."""
    x_ix, w_ix, out_ix = _parse(spec)
    xs, ws, outs = set(x_ix), set(w_ix), set(out_ix)
    batch = "".join(i for i in w_ix if i in xs and i in outs)
    k = "".join(i for i in w_ix if i in xs and i not in outs)
    n = "".join(i for i in w_ix if i not in xs)
    m = "".join(i for i in x_ix if i not in ws)
    if not set(m) <= outs:
        raise ValueError(f"x-only indices must appear in the output "
                         f"({spec!r}: {set(m) - outs})")
    if not set(n) <= outs:
        raise ValueError(f"w-only indices must appear in the output "
                         f"({spec!r}: {set(n) - outs})")
    if outs != set(batch) | set(m) | set(n):
        raise ValueError(f"output indices must come from the operands "
                         f"({spec!r})")
    if not k:
        raise ValueError(f"no contracted index in {spec!r}")
    x_perm = tuple(x_ix.index(i) for i in batch + m + k)
    w_perm = tuple(w_ix.index(i) for i in batch + k + n)
    canonical_out = batch + m + n
    out_perm = tuple(canonical_out.index(i) for i in out_ix)
    return QeinsumPlan(x_ix=x_ix, w_ix=w_ix, out_ix=out_ix, batch=batch,
                       m=m, k=k, n=n, x_perm=x_perm, w_perm=w_perm,
                       out_perm=out_perm)


def _sizes_of(plan: QeinsumPlan, x, w,
              dims: Optional[Dict[str, int]]) -> Dict[str, int]:
    sizes: Dict[str, int] = {}

    def assign(term, shape, who):
        if len(term) != len(shape):
            raise ValueError(f"operand {who} rank {len(shape)} != term "
                             f"{term!r}")
        for i, s in zip(term, shape):
            if sizes.setdefault(i, int(s)) != int(s):
                raise ValueError(f"size mismatch for index {i!r}: "
                                 f"{sizes[i]} vs {s}")

    assign(plan.x_ix, x.shape, "x")
    if isinstance(w, PreparedWeight):
        if not plan.canonical_w:
            raise ValueError(
                f"PreparedWeight requires the w term in (batch, k, n) "
                f"order; got {plan.w_ix!r} (canonical: "
                f"{plan.batch + plan.k + plan.n!r})")
        stack = tuple(int(s) for s in w.codes.shape[:-2])
        if len(stack) != len(plan.batch):
            raise ValueError(
                f"PreparedWeight stack rank {len(stack)} != batch indices "
                f"{plan.batch!r} (prepare with stack_ndim="
                f"{len(plan.batch)})")
        assign(plan.batch, stack, "w.codes stack")
        k_flat = int(np.prod([sizes[i] for i in plan.k]))
        if k_flat != int(w.codes.shape[-2]):
            raise ValueError(f"contracted size {k_flat} != prepared K "
                             f"{int(w.codes.shape[-2])}")
        assign(plan.n, w.tail, "w.tail")
    else:
        assign(plan.w_ix, w.shape, "w")
    if dims:
        for i, s in dims.items():
            if i in sizes and sizes[i] != int(s):
                raise ValueError(f"dims[{i!r}]={s} != operand size "
                                 f"{sizes[i]}")
    return sizes


def _reshape_bias(bias, n_shape, out_ndim):
    if bias is None:
        return None
    return jnp.reshape(bias, (1,) * (out_ndim - len(n_shape))
                       + tuple(n_shape))


def qeinsum(spec: str, x, w, cfg: QuantConfig, *, dims=None,
            site: Optional[str] = None, bias=None,
            activation: str = "none", out_dtype=None):
    """Quantized 2-operand einsum under the numerics of ``cfg``.

    Args:
      spec: einsum spec with explicit output, 2 operands, no ellipsis or
        repeated indices (e.g. ``"gecd,edf->gecf"``).
      x: the activation operand (quantized per call, per batch slice).
      w: the weight operand — raw array or
        :class:`repro.quant.PreparedWeight` (the prepared term must be in
        canonical ``batch + k + n`` order).
      cfg: quantization config. ``dtype == "none"`` dispatches a plain
        fp32-accumulated ``jnp.einsum``.
      dims: optional ``{index: size}`` mapping validated against the
        operand shapes (documentation / early shape errors).
      site: call-site name for calibration statistics and per-site
        Markov flush planning (see :mod:`repro.quant.calibrate`).
      bias: optional flattened-N row added in the epilogue; requires the
        output term to end with the canonical n indices.
      activation: epilogue activation (see kernels ACTIVATIONS) — fused
        in-kernel when ``cfg.fused_exact``, applied after the output cast
        otherwise (bit-identical to the pre-fusion layer code).
      out_dtype: output dtype (default ``x.dtype``).

    Returns:
      The einsum result with MGS (or configured) accumulation numerics.
    """
    plan = plan_qeinsum(spec)
    prepared = isinstance(w, PreparedWeight)
    sizes = _sizes_of(plan, x, w, dims)
    if out_dtype is None:
        out_dtype = x.dtype
    n_shape = tuple(sizes[i] for i in plan.n)
    if (bias is not None or activation != "none") and not \
            plan.out_ix.endswith(plan.n):
        raise ValueError(f"bias/activation epilogue requires the output to "
                         f"end with the n indices {plan.n!r}: {spec!r}")

    if cfg.dtype == "none":
        if prepared:
            raise ValueError("PreparedWeight requires an fp8 QuantConfig")
        out = jnp.einsum(f"{plan.x_ix},{plan.w_ix}->{plan.out_ix}", x,
                         w.astype(x.dtype),
                         preferred_element_type=jnp.float32)
        b = _reshape_bias(bias, n_shape, out.ndim)
        if b is not None:
            out = out + b
        return ACTIVATIONS[activation](out.astype(out_dtype))

    batch_shape = tuple(sizes[i] for i in plan.batch)
    m_shape = tuple(sizes[i] for i in plan.m)
    B = int(np.prod(batch_shape)) if batch_shape else 1
    M = int(np.prod(m_shape)) if m_shape else 1
    K = int(np.prod([sizes[i] for i in plan.k]))
    N = int(np.prod(n_shape)) if n_shape else 1

    xt = x.transpose(plan.x_perm) if plan.x_perm != tuple(
        range(x.ndim)) else x
    # apply the epilogue activation inside qmatmul only on the fused exact
    # kernel; every other path applies it after the output cast, exactly
    # as the pre-qeinsum layer code did (models.linear contract).
    fuse = cfg.fused_exact
    act_in = activation if fuse else "none"

    if not plan.batch:
        x2 = xt.reshape((M, K))
        w2 = w if prepared else w.transpose(plan.w_perm).reshape((K, N))
        out2 = qmatmul(x2, w2, cfg, out_dtype=out_dtype, bias=bias,
                       activation=act_in, site=site)
    else:
        # batch dims vmap over the canonical matmul: one traced kernel
        # regardless of batch size, with per-slice quantization scales
        # (vmapping absmax reduces per slice — the same numerics as a
        # per-slice loop, verified bitwise by tests/test_qeinsum.py).
        x2 = xt.reshape((B, M, K))
        if prepared:
            scale = (w.scale.reshape((B,) + w.scale.shape[len(batch_shape):])
                     if getattr(w.scale, "ndim", 0) > 0
                     else jnp.broadcast_to(w.scale, (B,)))
            wb = PreparedWeight(
                w.codes.reshape((B,) + w.codes.shape[-2:]),
                scale, w.fmt_name, w.tail, w.limb_sigma,
                act_sigma=w.act_sigma)
        else:
            wb = w.transpose(plan.w_perm).reshape((B, K, N))
        out2 = jax.vmap(
            lambda xb, wb_: qmatmul(xb, wb_, cfg, out_dtype=out_dtype,
                                    bias=bias, activation=act_in,
                                    site=site))(x2, wb)

    out = out2.reshape(batch_shape + m_shape + n_shape)
    if plan.out_perm != tuple(range(out.ndim)):
        out = out.transpose(plan.out_perm)
    if not fuse:
        out = ACTIVATIONS[activation](out)
    return out
