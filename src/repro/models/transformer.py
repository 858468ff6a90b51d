"""The model zoo's unified stack: dense / MoE / sliding-window / hybrid /
SSM / encoder-decoder / VLM decoders with scan-over-layers, KV-cache
serving, and MGS-quantized linear layers throughout.

Public API (all pure functions over plain-dict param pytrees):

  init_params(cfg, key)                 -> (params, dims)
  forward(params, cfg, batch)           -> logits (teacher-forced)
  loss_fn(params, cfg, batch)           -> (loss, metrics)
  init_cache(cfg, batch, max_len)       -> (cache, cache_dims)
  prefill(params, cfg, batch, cache)    -> (last_logits, cache)
  decode_step(params, cfg, tok, cache)  -> (logits, cache)

Layer stacks are ``lax.scan`` over stacked parameters (one compiled layer
body regardless of depth); gemma3's 5:1 local:global pattern rides the
scan as a traced per-layer flag; jamba's 1-attention:7-mamba period is a
scan over *groups* with the 8 sublayers unrolled inside the group body.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.parallel.sharding import constrain
from repro.quant import (PagedKVCache, QuantizedKVCache, init_paged_kv,
                         init_quantized_kv, paged_rollback_kv, qeinsum,
                         quantize_kv)
from .attention import KVCache, attention_apply, attention_init
from .common import ParamFactory, dtype_of, grad_barrier, rms_norm
from .ffn import ffn_apply, ffn_init
from .mamba import SSMCache, mamba_apply, mamba_decode_step, mamba_init
from .moe import moe_apply, moe_init

__all__ = ["init_params", "param_dims", "forward", "loss_fn", "init_cache",
           "prefill", "decode_step", "init_paged_cache", "decode_step_paged",
           "verify_step_paged", "draft_step_paged", "rewind_slots",
           "adopt_slot", "release_slot"]


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------


def _stack_init(key, n: int, one_init):
    """vmap an init over n layer keys -> stacked params + dims w/ 'layers'."""
    keys = jax.random.split(key, n)

    def init_one(k):
        return one_init(k)[0]

    params = jax.vmap(init_one)(keys)
    _, dims = one_init(keys[0])
    dims = jax.tree.map(
        lambda d: ("layers",) + d, dims,
        is_leaf=lambda d: isinstance(d, tuple) and all(
            isinstance(s, (str, type(None))) for s in d))
    return params, dims


def _dense_layer_init(cfg: ModelConfig, moe_layer: bool):
    def init(key):
        f = ParamFactory(key, dtype_of(cfg.param_dtype))
        f.ones("ln1", (cfg.d_model,), ("embed",))
        sub = ParamFactory(key, dtype_of(cfg.param_dtype))
        attention_init(sub, cfg)
        f.child("attn", *sub.collect())
        f.ones("ln2", (cfg.d_model,), ("embed",))
        sub2 = ParamFactory(jax.random.fold_in(key, 1),
                            dtype_of(cfg.param_dtype))
        if moe_layer:
            moe_init(sub2, cfg)
            f.child("moe", *sub2.collect())
        else:
            ffn_init(sub2, cfg)
            f.child("ffn", *sub2.collect())
        return f.collect()
    return init


def _ssm_layer_init(cfg: ModelConfig):
    def init(key):
        f = ParamFactory(key, dtype_of(cfg.param_dtype))
        f.ones("ln1", (cfg.d_model,), ("embed",))
        sub = ParamFactory(key, dtype_of(cfg.param_dtype))
        mamba_init(sub, cfg)
        f.child("ssm", *sub.collect())
        return f.collect()
    return init


def _hybrid_group_init(cfg: ModelConfig):
    """One jamba period: 1 attention + (attn_every - 1) mamba sublayers,
    FFN/MoE alternating across the period (MoE on odd in-period index)."""
    per = cfg.attn_every
    n_moe = sum(1 for j in range(per) if (j % cfg.moe_every
                                          == cfg.moe_offset))
    n_ffn = per - n_moe

    def init(key):
        f = ParamFactory(key, dtype_of(cfg.param_dtype))
        f.ones("ln_mix", (per, cfg.d_model), ("sub", "embed"))
        f.ones("ln_ffn", (per, cfg.d_model), ("sub", "embed"))
        sub = ParamFactory(jax.random.fold_in(key, 1),
                           dtype_of(cfg.param_dtype))
        attention_init(sub, cfg)
        f.child("attn", *sub.collect())

        def one_mamba(k):
            g = ParamFactory(k, dtype_of(cfg.param_dtype))
            mamba_init(g, cfg)
            return g.collect()
        mp, md = _stack_init(jax.random.fold_in(key, 2), per - 1, one_mamba)
        md = jax.tree.map(lambda d: ("sub",) + d[1:], md,
                          is_leaf=_is_dims)
        f.child("ssm", mp, md)

        def one_ffn(k):
            g = ParamFactory(k, dtype_of(cfg.param_dtype))
            ffn_init(g, cfg)
            return g.collect()
        fp, fd = _stack_init(jax.random.fold_in(key, 3), n_ffn, one_ffn)
        fd = jax.tree.map(lambda d: ("sub",) + d[1:], fd, is_leaf=_is_dims)
        f.child("ffn", fp, fd)

        def one_moe(k):
            g = ParamFactory(k, dtype_of(cfg.param_dtype))
            moe_init(g, cfg)
            return g.collect()
        ep, ed = _stack_init(jax.random.fold_in(key, 4), n_moe, one_moe)
        ed = jax.tree.map(lambda d: ("sub",) + d[1:], ed, is_leaf=_is_dims)
        f.child("moe", ep, ed)
        return f.collect()
    return init


def _is_dims(d):
    return isinstance(d, tuple) and all(
        isinstance(s, (str, type(None))) for s in d)


def init_params(cfg: ModelConfig, key) -> Tuple[Dict, Dict]:
    pdt = dtype_of(cfg.param_dtype)
    f = ParamFactory(key, pdt)
    f.normal("embed", (cfg.vocab, cfg.d_model), ("vocab", "embed"),
             scale=cfg.d_model ** -0.5)
    if not cfg.tie_embeddings:
        f.normal("unembed", (cfg.d_model, cfg.vocab), ("embed", "vocab"))
    f.ones("final_norm", (cfg.d_model,), ("embed",))

    k_layers = jax.random.fold_in(key, 17)
    if cfg.is_hybrid:
        n_groups = cfg.n_layers // cfg.attn_every
        lp, ld = _stack_init(k_layers, n_groups, _hybrid_group_init(cfg))
        ld = jax.tree.map(lambda d: ("groups",) + d[1:], ld, is_leaf=_is_dims)
        f.child("layers", lp, ld)
    elif cfg.is_ssm_only:
        lp, ld = _stack_init(k_layers, cfg.n_layers, _ssm_layer_init(cfg))
        f.child("layers", lp, ld)
    else:
        moe_all = cfg.is_moe  # non-hybrid MoE archs: every layer MoE
        lp, ld = _stack_init(k_layers, cfg.n_layers,
                             _dense_layer_init(cfg, moe_all))
        f.child("layers", lp, ld)

    if cfg.encoder_layers:
        ep, ed = _stack_init(jax.random.fold_in(key, 23), cfg.encoder_layers,
                             _dense_layer_init(cfg, False))
        f.child("encoder", ep, ed)
        f.ones("encoder_norm", (cfg.d_model,), ("embed",))
        # decoder cross-attention stack
        def one_cross(k):
            g = ParamFactory(k, pdt)
            g.ones("ln", (cfg.d_model,), ("embed",))
            sub = ParamFactory(jax.random.fold_in(k, 5), pdt)
            attention_init(sub, cfg)
            g.child("attn", *sub.collect())
            return g.collect()
        cp, cd = _stack_init(jax.random.fold_in(key, 29), cfg.n_layers,
                             one_cross)
        f.child("cross", cp, cd)
    return f.collect()


def param_dims(cfg: ModelConfig) -> Dict:
    """Logical-dims tree of ``init_params(cfg, ·)`` without allocating.

    Traces the init abstractly (``jax.eval_shape``) and captures the dims
    side output — for parameters that arrive externally (checkpoint load),
    where the serving/sharding path still needs every weight's logical
    dims (e.g. to derive sharded PreparedWeight plane layouts) but
    materializing a second parameter tree would waste device memory.

    Returns:
      A nested dict mirroring the ``init_params`` parameter tree, with a
      tuple of logical dim names (or ``None``) per array leaf.
    """
    captured = {}

    def trace(key):
        params, dims = init_params(cfg, key)
        captured["dims"] = dims
        return params

    jax.eval_shape(trace, jax.random.PRNGKey(0))
    return captured["dims"]


# ---------------------------------------------------------------------------
# Layer bodies
# ---------------------------------------------------------------------------


def _dense_body(pl, x, positions, cfg: ModelConfig, is_global,
                cache: Optional[KVCache], cache_pos, cross_kv, cross_p,
                block_table=None, lengths=None, layer=None):
    """One dense/moe layer. Returns (x, new_kv, aux)."""
    h, new_kv = attention_apply(
        pl["attn"], rms_norm(x, pl["ln1"], cfg.norm_eps), cfg,
        positions=positions, is_global=is_global, cache=cache,
        cache_pos=cache_pos, block_table=block_table, lengths=lengths,
        layer=layer)
    x = constrain(x + h, ("batch", "seq", "embed_act"))
    if cross_p is not None:
        hc, _ = attention_apply(
            cross_p["attn"], rms_norm(x, cross_p["ln"], cfg.norm_eps), cfg,
            positions=positions, cross_kv=cross_kv)
        x = x + hc
    xn = rms_norm(x, pl["ln2"], cfg.norm_eps)
    if "moe" in pl:
        h, aux = moe_apply(pl["moe"], xn, cfg)
    else:
        h, aux = ffn_apply(pl["ffn"], xn, cfg), jnp.float32(0.0)
    x = constrain(x + h, ("batch", "seq", "embed_act"))
    return x, new_kv, aux


def _hybrid_group_body(pg, x, positions, cfg: ModelConfig,
                       attn_cache: Optional[KVCache], cache_pos,
                       ssm_cache: Optional[SSMCache], decode: bool):
    """One jamba period (attn + mamba sublayers, FFN/MoE alternating)."""
    per = cfg.attn_every
    aux_total = jnp.float32(0.0)
    new_attn_cache = None
    new_h, new_conv = [], []
    i_ffn = i_moe = 0
    for j in range(per):
        xn = rms_norm(x, pg["ln_mix"][j], cfg.norm_eps)
        if j == 0:
            h, new_attn_cache = attention_apply(
                pg["attn"], xn, cfg, positions=positions, cache=attn_cache,
                cache_pos=cache_pos)
        else:
            sub = jax.tree.map(lambda a, _j=j: a[_j - 1], pg["ssm"])
            if decode:
                sc = SSMCache(h=ssm_cache.h[j - 1], conv=ssm_cache.conv[j - 1])
                h, sc_new = mamba_decode_step(sub, xn, sc, cfg)
                new_h.append(sc_new.h)
                new_conv.append(sc_new.conv)
            else:
                h, sc_new = mamba_apply(sub, xn, cfg, return_state=True)
                new_h.append(sc_new.h)
                new_conv.append(sc_new.conv)
        x = x + h
        xf = rms_norm(x, pg["ln_ffn"][j], cfg.norm_eps)
        if j % cfg.moe_every == cfg.moe_offset:
            sub = jax.tree.map(lambda a, _i=i_moe: a[_i], pg["moe"])
            h, aux = moe_apply(sub, xf, cfg)
            aux_total = aux_total + aux
            i_moe += 1
        else:
            sub = jax.tree.map(lambda a, _i=i_ffn: a[_i], pg["ffn"])
            h = ffn_apply(sub, xf, cfg)
            i_ffn += 1
        x = constrain(x + h, ("batch", "seq", "embed_act"))
    new_ssm = SSMCache(h=jnp.stack(new_h), conv=jnp.stack(new_conv))
    return x, new_attn_cache, new_ssm, aux_total


def _ssm_body(pl, x, cfg: ModelConfig, cache: Optional[SSMCache],
              decode: bool):
    xn = rms_norm(x, pl["ln1"], cfg.norm_eps)
    if decode:
        h, new_cache = mamba_decode_step(pl["ssm"], xn, cache, cfg)
    else:
        h, new_cache = mamba_apply(pl["ssm"], xn, cfg, return_state=True)
    return constrain(x + h, ("batch", "seq", "embed_act")), new_cache


# ---------------------------------------------------------------------------
# Embedding / head
# ---------------------------------------------------------------------------


_KEEP_F32 = ("A_log",)  # SSM decay rates: exp() is precision-sensitive


def _cast_params(params, cfg: ModelConfig):
    """Cast weight matrices to the compute dtype ONCE, on their sharded
    layout, before any layer runs. With ZeRO-3 sharding GSPMD then
    all-gathers bf16 instead of f32 — half the per-layer collective
    traffic (EXPERIMENTS.md §Perf iteration C). Rank<=1 leaves (norms,
    biases) and precision-sensitive leaves stay f32.
    """
    cdt = dtype_of(cfg.compute_dtype)
    if dtype_of(cfg.param_dtype) == cdt:
        return params

    def cast(path, p):
        name = str(path[-1].key) if hasattr(path[-1], "key") else ""
        if p.ndim >= 2 and p.dtype == jnp.float32 and name not in _KEEP_F32:
            return p.astype(cdt)
        return p

    return jax.tree_util.tree_map_with_path(cast, params)


def _embed_tokens(params, cfg: ModelConfig, tokens, for_train: bool = False):
    cdt = dtype_of(cfg.compute_dtype)
    # One-hot path only where it wins: the SP-layout (MoE) archs whose
    # lookup-scatter gradient GSPMD materializes as full f32 (V, d)
    # buffers, and only for model-axis-divisible vocabs (otherwise the
    # (B, T, V) one-hot itself cannot shard — measured 780 GB/device on
    # internvl2's 92553 vocab; EXPERIMENTS.md §Perf G).
    if for_train and cfg.is_moe and cfg.vocab % 128 == 0:
        # One-hot matmul lookup: its transpose is a *matmul* (sharded,
        # SPMD-clean) instead of a scatter-add, which GSPMD materializes
        # as multiple full f32 (V, d) buffers (~2.5 GB each on dbrx;
        # EXPERIMENTS.md §Perf G). The one-hot is fused into the dot.
        iota = jax.lax.broadcasted_iota(jnp.int32,
                                        tokens.shape + (cfg.vocab,), 2)
        onehot = (iota == tokens[..., None]).astype(cdt)
        x = jnp.einsum("btv,vd->btd", onehot, params["embed"].astype(cdt))
    else:
        x = jnp.take(params["embed"], tokens, axis=0).astype(cdt)
    return x * jnp.asarray(np.sqrt(cfg.d_model), cdt)


def _logits(params, cfg: ModelConfig, x):
    """Unembedding through the unified quantized-einsum dispatch.

    Under an exact-MGS QuantConfig the logits head accumulates in the
    exact kernel like every other matmul — the last float contraction
    that used to all-reduce over a data-sharded embed dim, and hence the
    last source of cross-mesh float divergence (docs/serving.md).

    A serving parameter tree carries a cached PreparedWeight for the
    unembedding view (``quant.prepare_logits_head`` — the tied path
    stores it under ``"unembed_prepared"`` since the raw embed table must
    stay raw for the lookup), so no prefill/decode step re-quantizes the
    full ``(vocab, d_model)`` table."""
    pw = params.get("unembed_prepared") if isinstance(params, dict) else None
    if pw is not None:
        out = qeinsum("btd,dv->btv", x, pw, cfg.quant,
                      site="logits", out_dtype=jnp.float32)
    elif cfg.tie_embeddings:
        out = qeinsum("btd,vd->btv", x, params["embed"], cfg.quant,
                      site="logits", out_dtype=jnp.float32)
    else:
        out = qeinsum("btd,dv->btv", x, params["unembed"], cfg.quant,
                      site="logits", out_dtype=jnp.float32)
    return constrain(out, ("batch", "seq", "vocab_act"))


def _global_flags(cfg: ModelConfig):
    return jnp.asarray(
        [cfg.layer_is_global_attn(i) for i in range(cfg.n_layers)], bool)


# ---------------------------------------------------------------------------
# Forward (teacher-forced) + loss
# ---------------------------------------------------------------------------


def _encode(params, cfg: ModelConfig, audio_embeds):
    """Whisper encoder over precomputed frame embeddings (frontend stub)."""
    cdt = dtype_of(cfg.compute_dtype)
    x = audio_embeds.astype(cdt)
    B, S, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None],
                                 (B, S))

    def body(x, pl):
        h, _ = attention_apply(pl["attn"],
                               rms_norm(x, pl["ln1"], cfg.norm_eps), cfg,
                               positions=positions, causal=False)
        x = x + h
        x = x + ffn_apply(pl["ffn"], rms_norm(x, pl["ln2"], cfg.norm_eps),
                          cfg)
        return x, None

    x, _ = jax.lax.scan(body, x, params["encoder"])
    return rms_norm(x, params["encoder_norm"], cfg.norm_eps)


def forward(params, cfg: ModelConfig, batch: Dict[str, Any],
            return_features: bool = False):
    """Teacher-forced logits. batch: tokens (B,T) [+ vision_embeds /
    audio_embeds per family]. Returns (logits (B,T,V), aux_loss) — or
    (features (B,T,d), aux_loss) with ``return_features`` (used by the
    streamed cross entropy)."""
    params = _cast_params(params, cfg)
    tokens = batch["tokens"]
    B, T = tokens.shape
    x = _embed_tokens(params, cfg, tokens, for_train=True)
    prefix = 0
    if cfg.vision_prefix:
        ve = batch["vision_embeds"].astype(x.dtype)
        prefix = ve.shape[1]
        x = jnp.concatenate([ve, x], axis=1)
    x = constrain(x, ("batch", "seq", "embed_act"))
    S = x.shape[1]
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None],
                                 (B, S))

    cross_kv = None
    if cfg.encoder_layers:
        enc = _encode(params, cfg, batch["audio_embeds"])

    aux_total = jnp.float32(0.0)
    remat = cfg.remat == "layer"

    if cfg.is_hybrid:
        def gbody(carry, pg):
            x, aux = carry
            x = grad_barrier(x)  # keep saved carry bf16 (differentiable)
            x, _, _, a = _hybrid_group_body(pg, x, positions, cfg, None,
                                            None, None, decode=False)
            return (x, aux + a), None
        fn = jax.checkpoint(gbody) if remat else gbody
        (x, aux_total), _ = jax.lax.scan(fn, (x, aux_total),
                                         params["layers"])
    elif cfg.is_ssm_only:
        def sbody(x, pl):
            x = grad_barrier(x)  # keep saved carry bf16 (differentiable)
            x, _ = _ssm_body(pl, x, cfg, None, decode=False)
            return x, None
        fn = jax.checkpoint(sbody) if remat else sbody
        x, _ = jax.lax.scan(fn, x, params["layers"])
    elif cfg.encoder_layers:
        def dbody(x, xs):
            pl, pc = xs
            ck = attention_apply  # appease linters
            # cross K/V from encoder output, per decoder layer
            from .linear import proj as _proj
            ckv = KVCache(
                k=_proj(enc, pc["attn"]["wk"], cfg.quant),
                v=_proj(enc, pc["attn"]["wv"], cfg.quant))
            x, _, _ = _dense_body(pl, x, positions, cfg, True, None, None,
                                  ckv, pc)
            return x, None
        fn = jax.checkpoint(dbody) if remat else dbody
        x, _ = jax.lax.scan(fn, x, (params["layers"], params["cross"]))
    else:
        flags = _global_flags(cfg)

        def body(carry, xs):
            x, aux = carry
            x = grad_barrier(x)  # keep saved carry bf16 (differentiable)
            pl, isg = xs
            x, _, a = _dense_body(pl, x, positions, cfg, isg, None, None,
                                  None, None)
            return (x, aux + a), None
        fn = jax.checkpoint(body) if remat else body
        (x, aux_total), _ = jax.lax.scan(fn, (x, aux_total),
                                         (params["layers"], flags))

    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    if prefix:
        x = x[:, prefix:]
    if return_features:
        return x, aux_total
    return _logits(params, cfg, x), aux_total


_CE_CHUNK_THRESHOLD = 65536  # stream the CE over vocab chunks above this
_CE_VCHUNK = 16384


def _streamed_ce(x, table, labels):
    """Cross entropy without materializing (tokens, V) logits.

    Scans the (tied) embedding table in vocab chunks carrying a running
    (max, sumexp, label-logit); the chunk body is rematerialized in the
    backward pass, so peak memory is O(tokens x vchunk) instead of
    O(tokens x V) — the fix that brings gemma3-27b (V=262144) train cells
    under the HBM budget (EXPERIMENTS.md §Perf iteration B).
    Returns per-token nll, same shape as labels.
    """
    B, T, D = x.shape
    V = table.shape[0]
    n = -(-V // _CE_VCHUNK)
    pad = n * _CE_VCHUNK - V
    tpad = jnp.pad(table, ((0, pad), (0, 0)))
    chunks = tpad.reshape(n, _CE_VCHUNK, D)
    bases = jnp.arange(n, dtype=jnp.int32) * _CE_VCHUNK

    def step(carry, xs):
        m, s, ll = carry
        tc, base = xs
        logits = jnp.einsum("btd,vd->btv", x, tc.astype(x.dtype),
                            preferred_element_type=jnp.float32)
        valid = (base + jnp.arange(_CE_VCHUNK, dtype=jnp.int32)) < V
        logits = jnp.where(valid[None, None, :], logits, -jnp.inf)
        m_new = jnp.maximum(m, jnp.max(logits, axis=-1))
        alpha = jnp.exp(m - m_new)
        s = s * alpha + jnp.sum(jnp.exp(logits - m_new[..., None]), axis=-1)
        idx = labels - base
        iota = jax.lax.broadcasted_iota(jnp.int32, logits.shape, 2)
        ll = ll + jnp.sum(
            jnp.where(iota == idx[..., None], logits, 0.0), axis=-1)
        return (m_new, s, ll), None

    m0 = jnp.full((B, T), -jnp.inf, jnp.float32)
    s0 = jnp.zeros((B, T), jnp.float32)
    ll0 = jnp.zeros((B, T), jnp.float32)
    (m, s, ll), _ = jax.lax.scan(jax.checkpoint(step), (m0, s0, ll0),
                                 (chunks, bases))
    return (m + jnp.log(s)) - ll


def loss_fn(params, cfg: ModelConfig, batch):
    """Next-token cross entropy (+ MoE load-balance aux)."""
    labels = batch["labels"]
    mask = batch.get("loss_mask")
    if mask is None:
        mask = jnp.ones_like(labels, jnp.float32)

    if cfg.vocab > _CE_CHUNK_THRESHOLD and cfg.tie_embeddings:
        x, aux = forward(params, cfg, batch, return_features=True)
        nll = _streamed_ce(x, params["embed"], labels) * mask
    else:
        logits, aux = forward(params, cfg, batch)
        logits = logits.astype(jnp.float32)
        m = jnp.max(logits, axis=-1, keepdims=True)
        lse = m[..., 0] + jnp.log(jnp.sum(jnp.exp(logits - m), axis=-1))
        iota = jax.lax.broadcasted_iota(jnp.int32, logits.shape, 2)
        ll = jnp.sum(jnp.where(iota == labels[..., None], logits, 0.0),
                     axis=-1)
        nll = (lse - ll) * mask
    denom = jnp.maximum(jnp.sum(mask), 1.0)
    loss = jnp.sum(nll) / denom
    total = loss + 0.01 * aux
    return total, {"loss": loss, "aux_loss": aux,
                   "tokens": jnp.sum(mask)}


# ---------------------------------------------------------------------------
# Serving: cache init / prefill / decode
# ---------------------------------------------------------------------------


def _n_attn_layers(cfg: ModelConfig) -> int:
    if cfg.is_ssm_only:
        return 0
    if cfg.is_hybrid:
        return cfg.n_layers // cfg.attn_every
    return cfg.n_layers


def _n_ssm_layers(cfg: ModelConfig) -> int:
    if cfg.is_ssm_only:
        return cfg.n_layers
    if cfg.is_hybrid:
        return cfg.n_layers - cfg.n_layers // cfg.attn_every
    return 0


def init_cache(cfg: ModelConfig, batch: int, max_len: int,
               dtype=None):
    """Allocate the serving cache + its logical dims tree.

    K/V storage uses ``cfg.kv_cache_dtype`` (fp8_e4m3 = 1 byte/elem, the
    paper's narrow-format theme applied to cache memory); SSM conv state
    stays bf16 and the SSM recurrent state f32.

    With ``cfg.quant.kv_cache == "packed"`` (and no explicit ``dtype``
    override), the self-attention K/V planes are instead allocated as
    **packed FP8 codes** (uint8, ``quant.kvcache``) plus per-entry
    ``k_scale``/``v_scale`` float32 planes — 1 byte/element of cache,
    streamed straight into the MGS flash-decode attention kernel. The
    whisper cross-attention cache stays in ``kv_cache_dtype`` (it is
    written once at prefill and has no append path)."""
    kv_dtype = dtype if dtype is not None else dtype_of(cfg.kv_cache_dtype)
    conv_dtype = dtype if dtype is not None else jnp.bfloat16
    packed = cfg.quant.quantized_kv and dtype is None
    cache: Dict[str, Any] = {"pos": jnp.zeros((), jnp.int32)}
    dims: Dict[str, Any] = {"pos": ()}
    La = _n_attn_layers(cfg)
    if La and packed:
        # round the sequence axis up to the flash kernel's chunk
        # (quant.block_k): the decode step then streams the planes with
        # zero re-padding (an unaligned length would copy the whole
        # cache every step just to pad it). Extra positions sit beyond
        # every decode position, so the validity mask keeps them inert.
        chunk = cfg.quant.block_k
        s_alloc = -(-max_len // chunk) * chunk
        qkv = init_quantized_kv((La, batch), cfg.n_kv_heads, s_alloc,
                                cfg.head_dim)
        cache["k"] = qkv.k_codes
        cache["v"] = qkv.v_codes
        cache["k_scale"] = qkv.k_scale
        cache["v_scale"] = qkv.v_scale
        # heads before sequence (quant.kvcache layout): the decode view
        # (B*KV, S, hd) is then a reshape, never a cache-sized transpose
        d = ("layers", "batch", "kv_heads", "kv_seq", "head_dim")
        dims["k"] = d
        dims["v"] = d
        dims["k_scale"] = d[:-1]
        dims["v_scale"] = d[:-1]
    elif La:
        kv_shape = (La, batch, max_len, cfg.n_kv_heads, cfg.head_dim)
        cache["k"] = jnp.zeros(kv_shape, kv_dtype)
        cache["v"] = jnp.zeros(kv_shape, kv_dtype)
        d = ("layers", "batch", "kv_seq", "kv_heads", "head_dim")
        dims["k"] = d
        dims["v"] = d
    Lm = _n_ssm_layers(cfg)
    if Lm:
        if cfg.is_hybrid:
            G, S = cfg.n_layers // cfg.attn_every, cfg.attn_every - 1
            hshape = (G, S, batch, cfg.d_inner, cfg.ssm_state)
            cshape = (G, S, batch, cfg.d_conv - 1, cfg.d_inner)
            hd = ("groups", "sub", "batch", "inner", "ssm_state")
            cd = ("groups", "sub", "batch", "conv_k", "inner")
        else:
            hshape = (Lm, batch, cfg.d_inner, cfg.ssm_state)
            cshape = (Lm, batch, cfg.d_conv - 1, cfg.d_inner)
            hd = ("layers", "batch", "inner", "ssm_state")
            cd = ("layers", "batch", "conv_k", "inner")
        cache["ssm_h"] = jnp.zeros(hshape, jnp.float32)
        cache["ssm_conv"] = jnp.zeros(cshape, conv_dtype)
        dims["ssm_h"] = hd
        dims["ssm_conv"] = cd
    if cfg.encoder_layers:
        if packed:
            # packed cross planes: written once at prefill (quantize_kv
            # over the projected encoder K/V), streamed as 1-byte codes
            # by every decode step's cross-attention. Same chunk-aligned
            # padding as the self-attention planes; the pad tail is
            # zero-inert and masked (enc positions >= encoder_len).
            chunk = cfg.quant.block_k
            enc_pad = -(-cfg.encoder_len // chunk) * chunk
            cq = init_quantized_kv((cfg.n_layers, batch), cfg.n_kv_heads,
                                   enc_pad, cfg.head_dim)
            cache["cross_k"] = cq.k_codes
            cache["cross_v"] = cq.v_codes
            cache["cross_k_scale"] = cq.k_scale
            cache["cross_v_scale"] = cq.v_scale
            xd = ("layers", "batch", "kv_heads", "enc_seq", "head_dim")
            dims["cross_k"] = xd
            dims["cross_v"] = xd
            dims["cross_k_scale"] = xd[:-1]
            dims["cross_v_scale"] = xd[:-1]
        else:
            xshape = (cfg.n_layers, batch, cfg.encoder_len, cfg.n_kv_heads,
                      cfg.head_dim)
            cache["cross_k"] = jnp.zeros(xshape, kv_dtype)
            cache["cross_v"] = jnp.zeros(xshape, kv_dtype)
            xd = ("layers", "batch", "enc_seq", "kv_heads", "head_dim")
            dims["cross_k"] = xd
            dims["cross_v"] = xd
    return cache, dims


def _kv_stack(cache):
    """The layer-stacked attention-cache pytree for ``lax.scan``.

    Packed caches (uint8 code planes + scale planes, allocated by
    ``init_cache`` under ``quant.kv_cache == "packed"``) become a
    :class:`~repro.quant.QuantizedKVCache`; float caches a
    :class:`~repro.models.attention.KVCache`. ``lax.scan`` slices either
    NamedTuple's leaves along the leading layer axis, so the layer
    bodies receive the per-layer view directly.
    """
    if cache["k"].dtype == jnp.uint8:
        return QuantizedKVCache(cache["k"], cache["v"], cache["k_scale"],
                                cache["v_scale"])
    return KVCache(cache["k"], cache["v"])


def _kv_entries(kv) -> Dict[str, Any]:
    """Stacked cache NamedTuple -> the ``init_cache`` dict entries."""
    if isinstance(kv, QuantizedKVCache):
        return {"k": kv.k_codes, "v": kv.v_codes,
                "k_scale": kv.k_scale, "v_scale": kv.v_scale}
    return {"k": kv.k, "v": kv.v}


def prefill(params, cfg: ModelConfig, batch, cache):
    """Run the prompt through the stack, filling the cache.

    Returns (last-position logits (B, V), cache)."""
    params = _cast_params(params, cfg)
    tokens = batch["tokens"]
    B, T = tokens.shape
    x = _embed_tokens(params, cfg, tokens)
    prefix = 0
    if cfg.vision_prefix:
        ve = batch["vision_embeds"].astype(x.dtype)
        prefix = ve.shape[1]
        x = jnp.concatenate([ve, x], axis=1)
    S = x.shape[1]
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None],
                                 (B, S))
    x = constrain(x, ("batch", "seq", "embed_act"))

    if cfg.encoder_layers:
        enc = _encode(params, cfg, batch["audio_embeds"])
        from .linear import proj as _proj
        packed_cross = cache["cross_k"].dtype == jnp.uint8

        def cross_kv_one(pc):
            k = _proj(enc, pc["attn"]["wk"], cfg.quant)
            v = _proj(enc, pc["attn"]["wv"], cfg.quant)
            if not packed_cross:
                k = k.astype(cache["cross_k"].dtype)
                v = v.astype(cache["cross_v"].dtype)
            return k, v
        ck, cv = jax.lax.map(cross_kv_one, params["cross"])
        if packed_cross:
            # write-once quantization (per-entry scales, quant.kvcache):
            # prefill attends the fresh float K/V below; decode streams
            # these codes through the MGS flash kernel.
            S_pad = cache["cross_k"].shape[3]
            kc, ksc = quantize_kv(ck, cfg.quant.kv_fmt)
            vc, vsc = quantize_kv(cv, cfg.quant.kv_fmt)
            pad = ((0, 0), (0, 0), (0, 0), (0, S_pad - kc.shape[2]))
            cache = dict(
                cache,
                cross_k=jnp.pad(jnp.swapaxes(kc, 2, 3), pad + ((0, 0),)),
                cross_v=jnp.pad(jnp.swapaxes(vc, 2, 3), pad + ((0, 0),)),
                cross_k_scale=jnp.pad(jnp.swapaxes(ksc, 2, 3), pad),
                cross_v_scale=jnp.pad(jnp.swapaxes(vsc, 2, 3), pad))
        else:
            cache = dict(cache, cross_k=ck, cross_v=cv)

    new_cache = dict(cache)
    if cfg.is_hybrid:
        def gbody(x, xs):
            pg, kvl = xs
            x, akv, ssm, _ = _hybrid_group_body(
                pg, x, positions, cfg, kvl, 0, None, decode=False)
            return x, (akv, ssm.h, ssm.conv)
        x, (kvs, hs, convs) = jax.lax.scan(
            gbody, x, (params["layers"], _kv_stack(cache)))
        new_cache.update(ssm_h=hs,
                         ssm_conv=convs.astype(cache["ssm_conv"].dtype),
                         **_kv_entries(kvs))
    elif cfg.is_ssm_only:
        def sbody(x, pl):
            x, sc = _ssm_body(pl, x, cfg, None, decode=False)
            return x, (sc.h.astype(jnp.float32),
                       sc.conv)
        x, (hs, convs) = jax.lax.scan(sbody, x, params["layers"])
        new_cache.update(ssm_h=hs,
                         ssm_conv=convs.astype(cache["ssm_conv"].dtype))
    elif cfg.encoder_layers:
        def dbody(x, xs):
            pl, pc, kvl, ckl, cvl = xs
            x, akv, _ = _dense_body(pl, x, positions, cfg, True,
                                    kvl, 0, KVCache(ckl, cvl), pc)
            return x, akv
        # prefill attends the fresh (float) encoder K/V on both cache
        # layouts; the packed planes above are storage for decode only
        x, kvs = jax.lax.scan(
            dbody, x, (params["layers"], params["cross"], _kv_stack(cache),
                       ck, cv))
        new_cache.update(**_kv_entries(kvs))
    else:
        flags = _global_flags(cfg)
        def body(x, xs):
            pl, isg, kvl = xs
            x, akv, _ = _dense_body(pl, x, positions, cfg, isg,
                                    kvl, 0, None, None)
            return x, akv
        x, kvs = jax.lax.scan(
            body, x, (params["layers"], flags, _kv_stack(cache)))
        new_cache.update(**_kv_entries(kvs))

    new_cache["pos"] = jnp.asarray(S, jnp.int32)
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    last = x[:, -1:]
    return _logits(params, cfg, last)[:, 0], new_cache


def decode_step(params, cfg: ModelConfig, tokens, cache):
    """One decode step. tokens: (B, 1). Returns (logits (B, V), cache)."""
    params = _cast_params(params, cfg)
    B = tokens.shape[0]
    pos = cache["pos"]
    x = _embed_tokens(params, cfg, tokens)
    x = constrain(x, ("batch", "seq", "embed_act"))
    positions = jnp.broadcast_to(pos[None, None], (B, 1)).astype(jnp.int32)

    new_cache = dict(cache)
    if cfg.is_hybrid:
        def gbody(x, xs):
            pg, kvl, hc, cc = xs
            x, akv, ssm, _ = _hybrid_group_body(
                pg, x, positions, cfg, kvl, pos,
                SSMCache(hc, cc), decode=True)
            return x, (akv, ssm.h, ssm.conv)
        x, (kvs, hs, convs) = jax.lax.scan(
            gbody, x, (params["layers"], _kv_stack(cache),
                       cache["ssm_h"], cache["ssm_conv"]))
        new_cache.update(ssm_h=hs,
                         ssm_conv=convs.astype(cache["ssm_conv"].dtype),
                         **_kv_entries(kvs))
    elif cfg.is_ssm_only:
        def sbody(x, xs):
            pl, hc, cc = xs
            x, sc = _ssm_body(pl, x, cfg, SSMCache(hc, cc), decode=True)
            return x, (sc.h.astype(jnp.float32), sc.conv)
        x, (hs, convs) = jax.lax.scan(
            sbody, x, (params["layers"], cache["ssm_h"], cache["ssm_conv"]))
        new_cache.update(ssm_h=hs,
                         ssm_conv=convs.astype(cache["ssm_conv"].dtype))
    elif cfg.encoder_layers:
        packed_cross = cache["cross_k"].dtype == jnp.uint8
        if packed_cross:
            # decode streams the packed cross codes (written once at
            # prefill) through the MGS flash kernel per layer
            def dbody(x, xs):
                pl, pc, kvl, ckl, cvl, cks, cvs = xs
                x, akv, _ = _dense_body(
                    pl, x, positions, cfg, True, kvl, pos,
                    QuantizedKVCache(ckl, cvl, cks, cvs), pc)
                return x, akv
            x, kvs = jax.lax.scan(
                dbody, x, (params["layers"], params["cross"],
                           _kv_stack(cache), cache["cross_k"],
                           cache["cross_v"], cache["cross_k_scale"],
                           cache["cross_v_scale"]))
        else:
            def dbody(x, xs):
                pl, pc, kvl, ckl, cvl = xs
                x, akv, _ = _dense_body(pl, x, positions, cfg, True,
                                        kvl, pos, KVCache(ckl, cvl), pc)
                return x, akv
            x, kvs = jax.lax.scan(
                dbody, x, (params["layers"], params["cross"],
                           _kv_stack(cache), cache["cross_k"],
                           cache["cross_v"]))
        new_cache.update(**_kv_entries(kvs))
    else:
        flags = _global_flags(cfg)
        def body(x, xs):
            pl, isg, kvl = xs
            x, akv, _ = _dense_body(pl, x, positions, cfg, isg,
                                    kvl, pos, None, None)
            return x, akv
        x, kvs = jax.lax.scan(
            body, x, (params["layers"], flags, _kv_stack(cache)))
        new_cache.update(**_kv_entries(kvs))

    new_cache["pos"] = pos + 1
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    return _logits(params, cfg, x)[:, 0], new_cache


# ---------------------------------------------------------------------------
# Serving: paged KV pool (continuous batching)
# ---------------------------------------------------------------------------


def _require_paged_arch(cfg: ModelConfig):
    """The paged decode path covers plain dense decoder-only stacks.

    Hybrid/SSM towers carry recurrent state (not paged), encoder-decoder
    and vision archs have prefill-time side inputs, and MoE routing
    couples tokens across the batch (expert capacity + per-expert-slice
    quantization scales), which would break the continuous engine's
    traffic-invariance contract. All of them keep the dense group engine.
    """
    if (cfg.is_hybrid or cfg.is_ssm_only or cfg.encoder_layers
            or cfg.vision_prefix or cfg.is_moe):
        raise NotImplementedError(
            "paged decode supports plain dense attention-only stacks "
            "(no SSM/hybrid, encoder-decoder, vision prefix, or MoE)")
    if not cfg.quant.quantized_kv:
        raise ValueError("paged decode requires quant.kv_cache='packed' "
                         "(the pool stores packed FP8 codes)")


def init_paged_cache(cfg: ModelConfig, slots: int, max_len: int,
                     n_blocks: int):
    """Allocate the paged decode state: shared block pool + slot tables.

    Unlike :func:`init_cache` (one dense cache per batch), the paged
    cache is a single physical pool of ``n_blocks`` KV blocks (block
    size = ``cfg.quant.block_k``, the flash kernel's chunk) shared by
    ``slots`` independent decode slots. Each slot owns a row of
    ``block_table`` (logical block -> physical block, width
    ``ceil(max_len / block_k)``) and a ``pos`` entry (its next write
    position; ``pos == 0`` marks a free slot). Block 0 is the reserved
    trash block (``quant.TRASH_BLOCK``): free slots' zeroed table rows
    scatter their dead appends there, and the allocator never hands it
    out. Returns ``(cache, dims)`` like :func:`init_cache`.
    """
    _require_paged_arch(cfg)
    bs = cfg.quant.block_k
    nb = -(-max_len // bs)
    La = _n_attn_layers(cfg)
    pool = init_paged_kv((La,), n_blocks, cfg.n_kv_heads, bs, cfg.head_dim)
    cache: Dict[str, Any] = {
        "k": pool.k_codes, "v": pool.v_codes,
        "k_scale": pool.k_scale, "v_scale": pool.v_scale,
        "block_table": jnp.zeros((slots, nb), jnp.int32),
        "pos": jnp.zeros((slots,), jnp.int32),
    }
    d = ("layers", "blocks", "kv_heads", "block", "head_dim")
    dims: Dict[str, Any] = {"k": d, "v": d, "k_scale": d[:-1],
                            "v_scale": d[:-1],
                            "block_table": ("slots", "table"),
                            "pos": ("slots",)}
    return cache, dims


def _paged_kv_stack(cache) -> PagedKVCache:
    return PagedKVCache(cache["k"], cache["v"], cache["k_scale"],
                        cache["v_scale"])


def _paged_kv_entries(kv: PagedKVCache) -> Dict[str, Any]:
    return {"k": kv.k_codes, "v": kv.v_codes,
            "k_scale": kv.k_scale, "v_scale": kv.v_scale}


def _paged_layers(layers, flags, cfg: ModelConfig, x, positions, cache_pos,
                  lengths, cache):
    """Run ``layers`` over the paged pool; returns ``(x, cache)``.

    The stacked ``(La, P, KV, bs, hd)`` pool planes ride in the scan
    *carry*, never as ``xs``/``ys``: layer ``l`` appends its rows at
    ``[l, phys, :, off]`` (one in-place scatter) and its kernel reads
    layer ``l`` by tile offset (:func:`attention._paged_operands`), so
    no per-layer slice and no restacked copy of the pool is made.
    ``layers``/``flags`` may be a prefix of the stack (the draft step);
    the pool's other layers pass through untouched.
    """
    bt = cache["block_table"]

    def body(carry, xs):
        x, kv = carry
        pl, isg, layer = xs
        x, kv, _ = _dense_body(pl, x, positions, cfg, isg, kv, cache_pos,
                               None, None, block_table=bt,
                               lengths=lengths, layer=layer)
        return (x, kv), None
    ids = jnp.arange(flags.shape[0], dtype=jnp.int32)
    (x, kv), _ = jax.lax.scan(body, (x, _paged_kv_stack(cache)),
                              (layers, flags, ids))
    return x, dict(cache, **_paged_kv_entries(kv))


def adopt_slot(cache, prefill_cache, slot, phys):
    """Copy a batch-1 dense prefill cache into pool blocks; activate slot.

    ``prefill_cache`` is the packed dense cache produced by
    :func:`prefill` at batch 1 (planes ``(La, 1, KV, S, hd)`` with ``S``
    a multiple of the block size — :func:`init_cache` rounds the
    sequence axis up to ``block_k``). ``phys`` is the slot's full
    physical-block table row ``(nb,)`` int32: the first ``S // block``
    entries receive the prefill content, the remaining *allocated*
    entries are decode headroom, and unallocated tail entries must be
    ``TRASH_BLOCK``. ``slot``/``phys`` and the prefill planes are all
    traced, so one compilation serves every (bucket, slot, block
    assignment) combination — admission never recompiles.
    """
    k = cache["k"]
    La, P, KV, bs, hd = k.shape
    pk = prefill_cache["k"]
    S = pk.shape[3]
    if S % bs:
        raise ValueError(f"prefill length {S} not a multiple of block {bs}")
    ns = S // bs
    phys = phys.astype(jnp.int32)
    pb = phys[:ns]

    def blocks(plane):  # (La, 1, KV, S, ...) -> (La, ns, KV, bs, ...)
        tail = plane.shape[4:]
        p = plane.reshape((La, KV, ns, bs) + tail)
        return jnp.moveaxis(p, 2, 1)

    new = dict(cache)
    new["k"] = k.at[:, pb].set(blocks(pk))
    new["v"] = cache["v"].at[:, pb].set(blocks(prefill_cache["v"]))
    new["k_scale"] = cache["k_scale"].at[:, pb].set(
        blocks(prefill_cache["k_scale"]))
    new["v_scale"] = cache["v_scale"].at[:, pb].set(
        blocks(prefill_cache["v_scale"]))
    new["block_table"] = cache["block_table"].at[slot].set(phys)
    new["pos"] = cache["pos"].at[slot].set(
        prefill_cache["pos"].astype(jnp.int32))
    return new


def release_slot(cache, slot):
    """Free a slot: zero its table row (-> trash block) and its pos.

    Purely logical — the slot's physical blocks keep their bits until
    the allocator reassigns them and :func:`adopt_slot` overwrites them
    in full. Freeing therefore cannot perturb any co-resident slot.
    """
    new = dict(cache)
    new["block_table"] = cache["block_table"].at[slot].set(0)
    new["pos"] = cache["pos"].at[slot].set(0)
    return new


def decode_step_paged(params, cfg: ModelConfig, tokens, cache):
    """One decode step over the paged slot pool. tokens: (slots, 1).

    Returns (logits (slots, V), cache). Every slot advances through the
    same fixed-shape computation; a free slot (``pos == 0``) walks zero
    KV chunks (its attention output is exactly 0) and appends into the
    trash block, so its presence cannot change a live slot's bits —
    with ``quant.per_row_act`` the whole step is row-independent, which
    is the continuous engine's determinism contract.
    """
    _require_paged_arch(cfg)
    params = _cast_params(params, cfg)
    pos = cache["pos"]
    live = pos > 0
    lengths = jnp.where(live, pos + 1, 0)
    x = _embed_tokens(params, cfg, tokens)
    x = constrain(x, ("batch", "seq", "embed_act"))
    x, new_cache = _paged_layers(params["layers"], _global_flags(cfg), cfg,
                                 x, pos[:, None], pos, lengths, cache)
    new_cache["pos"] = jnp.where(live, pos + 1, pos)
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    return _logits(params, cfg, x)[:, 0], new_cache


# ---------------------------------------------------------------------------
# Serving: speculative decoding over the paged pool (draft -> verify ->
# rewind). The three steps compose with decode_step_paged's fixed-shape
# lifecycle: none of them advances ``pos`` except rewind_slots, which
# commits exactly the accepted prefix.
# ---------------------------------------------------------------------------


def verify_step_paged(params, cfg: ModelConfig, tokens, cache):
    """Score ``k`` candidate tokens per slot in one multi-query step.

    tokens: ``(slots, k)`` — each slot's current token followed by its
    ``k - 1`` draft proposals, occupying positions ``pos .. pos + k - 1``.
    All ``k`` K/V entries are appended through the block table (the
    admission reservation guarantees the blocks exist), then every
    (slot, token) pair attends its own causal horizon as an independent
    kernel slice — so ``logits[:, j]`` is **bit-identical** to the
    logits sequential decode would produce at position ``pos + j`` given
    the same inputs (the exact-acceptance contract, docs/serving.md).
    ``pos`` is *not* advanced: :func:`rewind_slots` commits the accepted
    prefix and physically zeroes the rejected tail.

    Returns ``(logits (slots, k, vocab), cache)``.
    """
    _require_paged_arch(cfg)
    params = _cast_params(params, cfg)
    T = tokens.shape[1]
    pos = cache["pos"]
    lengths = jnp.where(pos > 0, pos + 1, 0)
    x = _embed_tokens(params, cfg, tokens)
    x = constrain(x, ("batch", "seq", "embed_act"))
    positions = pos[:, None] + jnp.arange(T, dtype=jnp.int32)[None, :]
    x, new_cache = _paged_layers(params["layers"], _global_flags(cfg), cfg,
                                 x, positions, pos, lengths, cache)
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    return _logits(params, cfg, x), new_cache


def draft_step_paged(params, cfg: ModelConfig, tokens, cache, offset):
    """One cheap self-draft step at position ``pos + offset``.

    Runs only the first ``cfg.quant.draft_layers`` transformer layers
    (plus final norm and logits head) over sliced stacked params — the
    truncated-layer self-draft — scanning them over the full carried
    pool, whose upper layers pass through untouched. The draft's
    lower-layer K/V appends land in the shared pool at ``pos + offset``
    but are **overwritten by the verify append before any verify read**,
    so draft numerics can only change the acceptance *rate*, never an
    accepted token's bits.
    ``offset`` is traced: one compilation serves every draft position of
    a round. ``pos`` is not advanced.

    tokens: ``(slots, 1)``. Returns ``(logits (slots, vocab), cache)``.
    """
    _require_paged_arch(cfg)
    L = cfg.quant.draft_layers or cfg.n_layers
    L = min(L, cfg.n_layers)
    params = _cast_params(params, cfg)
    pos = cache["pos"]
    live = pos > 0
    offset = jnp.asarray(offset, jnp.int32)
    dpos = jnp.where(live, pos + offset, pos)
    lengths = jnp.where(live, dpos + 1, 0)
    x = _embed_tokens(params, cfg, tokens)
    x = constrain(x, ("batch", "seq", "embed_act"))
    lp = jax.tree.map(lambda a: a[:L], params["layers"])
    x, new_cache = _paged_layers(lp, _global_flags(cfg)[:L], cfg, x,
                                 dpos[:, None], dpos, lengths, cache)
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    return _logits(params, cfg, x)[:, 0], new_cache


def rewind_slots(cache, keep, max_tokens: int):
    """Commit ``keep`` verified entries per slot; zero the rejected tail.

    After :func:`verify_step_paged` appended ``k`` candidate entries at
    ``pos .. pos + k - 1`` and acceptance emitted ``keep`` tokens, the
    pool must look exactly as if sequential decode had run ``keep``
    steps: entries ``pos .. pos + keep - 1`` stay, entries
    ``pos + keep .. pos + k - 1`` are *physically zeroed*
    (:func:`repro.quant.paged_rollback_kv` — codes and scales back to
    the never-written state), and ``pos`` advances by ``keep``. Free
    slots (``pos == 0``) pass through untouched, so the engine can
    rewind after releasing finished slots.

    keep: ``(slots,)`` int32 in ``[1, max_tokens]`` for live slots
    (ignored for free ones). ``max_tokens``: static ``k`` bound.
    """
    pos = cache["pos"]
    live = pos > 0
    keep = keep.astype(jnp.int32)
    start = jnp.where(live, pos + keep, 0)
    count = jnp.where(live, max_tokens - keep, 0)
    pool = paged_rollback_kv(_paged_kv_stack(cache), cache["block_table"],
                             start, count, max_tokens)
    new_cache = dict(cache, **_paged_kv_entries(pool))
    new_cache["pos"] = jnp.where(live, pos + keep, pos)
    return new_cache
