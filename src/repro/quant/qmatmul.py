"""Canonical (M, K, N) quantized matmul dispatch.

Model code reaches this through :func:`repro.quant.qeinsum`, which
canonicalizes arbitrary 2-operand einsums (grouped, batched, multi-axis
K) into this function's ``(..., K) @ (K, N)`` form — so every model
contraction shares one numerics dispatch and one calibration namespace.

Given a ``QuantConfig``, ``qmatmul(x, w, cfg)`` quantizes the operands,
runs the configured numerics, and rescales:

  dtype=none                  -> plain (bf16/f32) dot, fp32 accumulation
  fp8_* + accum=wide          -> FP8 operands, fp32 accumulation (H100/TPU
                                 baseline the paper compares against)
  fp8_* + accum=mgs_exact     -> exact fixed-point accumulation
                                 (Pallas limb kernel over packed codes,
                                 scale/bias/activation epilogue
                                 in-kernel / jnp reference)
  fp8_* + accum=mgs_dmac      -> paper-faithful Fig. 8 numerics
  fp8_* + accum=swamp         -> sequential narrow accumulator (failure
                                 baseline, Fig. 3)
  int8/int5/int4 + wide       -> integer matmul, int32 accumulation
  int* + clip                 -> saturation arithmetic (framework default
                                 the paper criticizes, emulation-only)

Weights may be passed as ``quant.prepared.PreparedWeight`` — quantized +
limb-decomposed once per process (at load/engine-init time) — in which
case no weight quantization happens here: the serving hot path re-uses the
cached scale / packed codes on every call. The Markov flush
planner kicks in when ``cfg.flush_target`` is set, using the prepared
weight's observed limb statistics to lengthen the exact kernel's flush
period beyond the worst-case bound.

The heavyweight emulation paths (mgs_dmac / swamp / clip) are evaluation
tools — use them on layer-sized problems; the production TPU path is
``mgs_exact`` with the fused Pallas kernel.
"""

from __future__ import annotations

import jax.numpy as jnp

from repro.kernels import ops as kops
from repro.kernels import ref as kref
from .config import QuantConfig
from .prepared import PreparedWeight
from .quantize import quantize_fp8, quantize_int

__all__ = ["qmatmul"]


def _exact_flush_period(cfg: QuantConfig, w_sigma, x_sigma, site):
    """Flush period for the exact kernel: runtime state, plan, or None.

    Resolution order:
    1. An active ``applied_calib_state`` context carrying a flush entry
       for ``site`` — a *traced int32 scalar* flowing through the
       engine's jitted step (the hot-swap path: swapping the array
       re-plans the period with zero retraces).
    2. Host-side Markov plan when ``cfg.flush_target`` is set.
       ``x_sigma`` is the call site's observed activation limb sigma
       (calibration table, else the PreparedWeight's stamped
       ``act_sigma``); ``None`` falls back to the planner's
       uniform-limb default.
    3. ``None`` — the kernel's deterministic worst-case bound.
    """
    from .calibrate import current_calib_state
    cs = current_calib_state()
    if cs is not None and site is not None:
        fp = cs.get("flush", {}).get(site)
        if fp is not None:
            return fp
    if cfg.flush_target is None:
        return None
    from repro.core.markov import plan_flush_period
    return plan_flush_period(cfg.block_k, target_overflow=cfg.flush_target,
                             sigma_limb_x=x_sigma, sigma_limb_w=w_sigma)


def qmatmul(x, w, cfg: QuantConfig, out_dtype=None, *, bias=None,
            activation: str = "none", site: str | None = None):
    """(..., K) @ (K, N) under the quantized numerics of ``cfg``.

    ``bias`` (N,) and ``activation`` (see kernels ACTIVATIONS) form an
    optional epilogue ``activation(out + bias)`` applied after
    dequantization — fused into the exact-mode kernel when
    ``cfg.fused_exact``, a follow-up elementwise pass otherwise.

    ``site`` names the call site (e.g. ``"ffn.wg"``) for the calibration
    subsystem: under ``quant.calibrate.calibrating()`` the quantized
    activation's limb statistics are recorded per site, and a calibrated
    ``cfg`` feeds the site's observed sigma into the Markov flush
    planner (per-call-site flush periods).
    """
    if out_dtype is None:
        out_dtype = x.dtype
    prepared = isinstance(w, PreparedWeight)
    if cfg.dtype == "none":
        if prepared:
            raise ValueError("PreparedWeight requires an fp8 QuantConfig")
        out = jnp.dot(x, w, preferred_element_type=jnp.float32)
        out = kops.apply_epilogue(out, None, bias, activation)
        return out.astype(out_dtype)

    if cfg.is_fp8:
        fmt = cfg.fmt
        if prepared and w.fmt_name != fmt.name:
            raise ValueError(f"PreparedWeight format {w.fmt_name!r} != "
                             f"config format {fmt.name!r}")
        margin = cfg.fp8_margin
        qx = quantize_fp8(x, fmt, axis=-1 if cfg.per_row_act else None,
                          margin=margin)
        if cfg.accum in ("mgs_exact", "mgs_dmac"):
            from .calibrate import observe
            observe(site, qx.q, fmt)
        if prepared:
            w_scale = w.scale
        else:
            qw = quantize_fp8(w, fmt, axis=0 if cfg.per_channel else None,
                              margin=margin)
            w_scale = qw.scale
        scale = qx.scale * w_scale
        if cfg.accum in ("mgs_exact", "mgs_dmac"):
            mode = "exact" if cfg.accum == "mgs_exact" else "dmac"
            w_arg = w if prepared else qw.q
            if mode == "exact":
                x_sigma = cfg.act_sigma(site)
                if x_sigma is None and prepared:
                    x_sigma = w.act_sigma
                # per-row activation scales don't fit the fused kernel's
                # (1, N) epilogue row; rescale outside — the same f32
                # elementwise epilogue, applied after the kernel instead
                # of inside it (bit-identical either way, the fused
                # epilogue contract)
                in_kernel_epi = not cfg.per_row_act
                out = kops.mgs_matmul(
                    qx.q, w_arg, fmt, mode, use_kernel=cfg.use_kernel,
                    gate_subnormal=cfg.gate_subnormal,
                    block_m=cfg.block_m, block_n=cfg.block_n,
                    block_k=cfg.block_k,
                    flush_period=_exact_flush_period(
                        cfg, w.limb_sigma if prepared else None, x_sigma,
                        site),
                    scale=scale if in_kernel_epi else None,
                    bias=bias if in_kernel_epi else None,
                    activation=activation if in_kernel_epi else "none")
                if not in_kernel_epi:
                    out = kops.apply_epilogue(out, scale, bias, activation)
                return out.astype(out_dtype)
            out = kops.mgs_matmul(
                qx.q, w_arg, fmt, mode, use_kernel=cfg.use_kernel,
                gate_subnormal=cfg.gate_subnormal, block_m=cfg.block_m,
                block_n=cfg.block_n, block_k=cfg.block_k)
        elif cfg.accum == "wide":
            w_vals = w.values() if prepared else qw.q
            out = kref.wide_matmul_ref(qx.q, w_vals)
        elif cfg.accum == "swamp":
            w_vals = w.values() if prepared else qw.q
            lead = qx.q.shape[:-1]
            out = kref.swamp_matmul_ref(
                qx.q.reshape((-1, qx.q.shape[-1])), w_vals, fmt,
                acc_mantissa_bits=cfg.narrow_bits - 1)
            out = out.reshape(lead + (w_vals.shape[-1],))
        else:
            raise NotImplementedError(
                f"accum={cfg.accum} for fp8 (use wide/mgs_*/swamp)")
        out = kops.apply_epilogue(out * scale, None, bias, activation)
        return out.astype(out_dtype)

    if cfg.is_int:
        if prepared:
            raise ValueError("PreparedWeight requires an fp8 QuantConfig")
        bits = cfg.int_bits
        qx = quantize_int(x, min(bits, cfg.act_bits),
                          axis=-1 if cfg.per_row_act else None,
                          symmetric=True)
        qw = quantize_int(w, min(bits, cfg.weight_bits),
                          axis=0 if cfg.per_channel else None, symmetric=True)
        scale = qx.scale * qw.scale
        if cfg.accum in ("wide", "mgs_exact", "mgs_dmac"):
            # dMAC integer accumulation is exact == int32 accumulation; the
            # narrow/wide split only changes the *energy*, not the value
            # (§5.1). Stats-producing emulation lives in core.int_dmac.
            out = jnp.dot(qx.q.astype(jnp.int8) if bits <= 8 else qx.q,
                          qw.q.astype(jnp.int8) if bits <= 8 else qw.q,
                          preferred_element_type=jnp.int32)
        elif cfg.accum == "clip":
            from repro.core.int_dmac import int_dot_clip
            import jax
            lead = qx.q.shape[:-1]
            x2 = qx.q.reshape((-1, qx.q.shape[-1]))
            f = jax.vmap(jax.vmap(
                lambda xv, wv: int_dot_clip(xv, wv, cfg.narrow_bits)[0],
                in_axes=(None, 1)), in_axes=(0, None))
            out = f(x2, qw.q).reshape(lead + (w.shape[-1],))
        elif cfg.accum == "wrap":
            from repro.core.int_dmac import int_dot_wrap
            import jax
            lead = qx.q.shape[:-1]
            x2 = qx.q.reshape((-1, qx.q.shape[-1]))
            f = jax.vmap(jax.vmap(
                lambda xv, wv: int_dot_wrap(xv, wv, cfg.narrow_bits),
                in_axes=(None, 1)), in_axes=(0, None))
            out = f(x2, qw.q).reshape(lead + (w.shape[-1],))
        else:
            raise NotImplementedError(f"accum={cfg.accum} for int")
        out = kops.apply_epilogue(out.astype(jnp.float32) * scale, None, bias,
                             activation)
        return out.astype(out_dtype)

    raise ValueError(f"unhandled dtype {cfg.dtype}")
