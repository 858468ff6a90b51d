"""Plain float32 reference of the dense decoder family, in ``jax.numpy``.

It follows the configuration file and imports nothing of the program
under test. Per layer: RMSNorm, multi-/grouped-query causal attention
with rotary positions (half-split, ``rope_theta``), residual add,
RMSNorm, SwiGLU (``silu``) or tanh-GELU (``gelu``) feed-forward,
residual add; then a final RMSNorm and a logits head tied to the
embedding table, whose rows enter scaled by ``sqrt(hidden_size)``. The
weights are drawn from the seed by the same recipe as the program's
initializer (documented below), so the reference sees the same model
without taking any array the program made.

Everything runs in float32 under ``jax.default_matmul_precision
("highest")``, layer by layer (one layer's weights on the device at a
time), one sequence at a time, queries in blocks, so that it fits on a
chip once the engine is freed.

``precision="int4"`` is the control: the same forward pass with every
matmul operand fake-quantized to symmetric int4 (activations per row,
weights per tensor), the step below the FP8 operands the configuration
states.
"""

from __future__ import annotations

import functools
import math
from typing import Dict, Sequence

import jax
import jax.numpy as jnp
import numpy as np

Q_BLOCK = 512


def dims(c: dict) -> Dict[str, int]:
    d, h = c["hidden_size"], c["num_attention_heads"]
    return dict(d=d, h=h, kv=c["num_key_value_heads"], hd=d // h,
                f=c["intermediate_size"], v=c["vocab_size"],
                layers=c["num_hidden_layers"])


# --- weights from the seed --------------------------------------------------
# Recipe: key = PRNGKey(seed). The embedding is normal(split(key)[1],
# (V, d)) * d**-0.5. Layer i uses key_i = split(fold_in(key, 17), L)[i];
# its attention weights are normal draws from successive splits of key_i
# (wq, wk, wv: (d, H|KV, hd) * d**-0.5; wo: (H, hd, d) * (H hd)**-0.5) and
# its FFN weights successive splits of fold_in(key_i, 1) (wg, wu: (d, F)
# * d**-0.5, or wi for GELU; wd: (F, d) * F**-0.5). Norm gains are ones.

def _draws(key, shapes_scales):
    out = []
    for shape, scale in shapes_scales:
        key, sub = jax.random.split(key)
        out.append(jax.random.normal(sub, shape, jnp.float32) * scale)
    return out


def embed_table(c: dict, seed: int):
    n = dims(c)
    _, sub = jax.random.split(jax.random.PRNGKey(seed))
    return jax.random.normal(sub, (n["v"], n["d"]), jnp.float32) * n["d"] ** -0.5


def layer_weights(c: dict, seed: int, i: int) -> Dict[str, jnp.ndarray]:
    n = dims(c)
    d, h, kv, hd, f = n["d"], n["h"], n["kv"], n["hd"], n["f"]
    key = jax.random.split(jax.random.fold_in(jax.random.PRNGKey(seed), 17),
                           n["layers"])[i]
    wq, wk, wv, wo = _draws(key, [((d, h, hd), 1 / np.sqrt(d)),
                                  ((d, kv, hd), 1 / np.sqrt(d)),
                                  ((d, kv, hd), 1 / np.sqrt(d)),
                                  ((h, hd, d), 1 / (h * hd) ** 0.5)])
    fk = jax.random.fold_in(key, 1)
    if c["hidden_act"] == "silu":
        wg, wu, wd = _draws(fk, [((d, f), 1 / np.sqrt(d)),
                                 ((d, f), 1 / np.sqrt(d)),
                                 ((f, d), 1 / f ** 0.5)])
        ffn = {"wg": wg, "wu": wu, "wd": wd}
    else:
        wi, wd = _draws(fk, [((d, f), 1 / np.sqrt(d)), ((f, d), 1 / f ** 0.5)])
        ffn = {"wi": wi, "wd": wd}
    return {"wq": wq, "wk": wk, "wv": wv, "wo": wo, **ffn}


# --- numerics ---------------------------------------------------------------

def _q4(x, axis):
    """Symmetric int4 fake quantization (absmax over ``axis``)."""
    amax = jnp.maximum(jnp.max(jnp.abs(x), axis=axis, keepdims=True),
                       jnp.finfo(jnp.float32).tiny)
    s = amax / 7.0
    return jnp.clip(jnp.round(x / s), -8, 7) * s


def _mm(x, w, int4: bool):
    """x: (T, K) @ w: (K, N); int4 quantizes x per row, w per tensor."""
    if int4:
        x, w = _q4(x, -1), _q4(w, None)
    return x @ w


def _rms(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _rope(x, pos, theta):
    """x: (T, heads, hd); half-split rotation."""
    hd = x.shape[-1]
    half = hd // 2
    inv = 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))
    ang = pos.astype(jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer(c: dict, w, x, int4: bool):
    """One decoder layer over one sequence x: (L, d)."""
    n = dims(c)
    L = x.shape[0]
    h, kv, hd = n["h"], n["kv"], n["hd"]
    g = h // kv
    eps, theta = c["rms_norm_eps"], c["rope_theta"]
    pos = jnp.arange(L, dtype=jnp.int32)
    a = _rms(x, eps)
    q = _rope(_mm(a, w["wq"].reshape(n["d"], h * hd), int4)
              .reshape(L, h, hd), pos, theta)
    k = _rope(_mm(a, w["wk"].reshape(n["d"], kv * hd), int4)
              .reshape(L, kv, hd), pos, theta)
    v = _mm(a, w["wv"].reshape(n["d"], kv * hd), int4).reshape(L, kv, hd)
    q = q.reshape(L // Q_BLOCK, Q_BLOCK, kv, g, hd)

    def block(args):
        qb, start = args
        s = jnp.einsum("tkgh,skh->kgts", qb, k) * hd ** -0.5
        qpos = start + jnp.arange(Q_BLOCK)
        s = jnp.where(pos[None, None, None, :] <= qpos[None, None, :, None],
                      s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("kgts,skh->tkgh", p, v)

    o = jax.lax.map(block, (q, jnp.arange(L // Q_BLOCK) * Q_BLOCK))
    o = o.reshape(L, h * hd)
    x = x + _mm(o, w["wo"].reshape(h * hd, n["d"]), int4)
    a = _rms(x, eps)
    if c["hidden_act"] == "silu":
        hid = jax.nn.silu(_mm(a, w["wg"], int4)) * _mm(a, w["wu"], int4)
    else:
        hid = jax.nn.gelu(_mm(a, w["wi"], int4), approximate=True)
    return x + _mm(hid, w["wd"], int4)


@functools.partial(jax.jit, static_argnums=(0, 3))
def _run_layer(cj, w, xs, int4):
    c = dict(cj)
    return jax.lax.map(lambda x: _layer(c, w, x, int4), xs)


@functools.partial(jax.jit, static_argnums=(0,))
def _weights(cj, i, seed):
    return layer_weights(dict(cj), seed, i)


@functools.partial(jax.jit, static_argnums=(0,))
def _embed(cj, table, toks):
    c = dict(cj)
    return jnp.take(table, toks, axis=0) * math.sqrt(c["hidden_size"])


@functools.partial(jax.jit, static_argnums=(0, 5))
def _head(cj, table, x_ref, x_ctl, prog, control):
    """Per row of final hidden states: the squared error of the program's
    logits ``prog`` against the reference's, the reference's squared
    norm, and (with ``control``) the int4 head's squared error."""
    c = dict(cj)
    eps = c["rms_norm_eps"]

    def block(args):
        xr, xc, p = args
        lr = _rms(xr, eps) @ table.T
        err = jnp.sum((p - lr) ** 2, -1)
        ref = jnp.sum(lr * lr, -1)
        if not control:
            return err, ref, err
        lc = _mm(_rms(xc, eps), table.T, True)
        return err, ref, jnp.sum((lc - lr) ** 2, -1)

    r = jax.lax.map(block, (x_ref, x_ctl, prog))
    return tuple(a.reshape(-1) for a in r)


def _frozen(c: dict):
    keys = ("hidden_size", "intermediate_size", "num_hidden_layers",
            "num_attention_heads", "num_key_value_heads", "vocab_size",
            "hidden_act", "rms_norm_eps", "rope_theta")
    return tuple((k, c[k]) for k in keys)


def logit_errors(c: dict, seed: int, seqs: Sequence[np.ndarray],
                 rows: Sequence[np.ndarray], prog: np.ndarray, length: int,
                 control: bool = False) -> Dict[str, np.ndarray]:
    """Relative error of the program's logits against the reference's.

    ``seqs[j]``: the token ids the program consumed for sequence j.
    ``rows[j]``: the positions of sequence j whose logits the program
    served (the position before each served token). ``prog``: the
    program's logit rows at those positions, in order, ``(n, V)``.
    ``length``: the padded length every sequence is run at (a multiple of
    the query block, fixed per cell so that the programs compile once).
    Returns per served position ``|prog - ref| / |ref|`` (Euclidean over
    the vocabulary) and, with ``control``, the same for the int4 forward
    pass in the program's place.
    """
    if length % Q_BLOCK:
        raise ValueError(f"length {length} is not a multiple of {Q_BLOCK}")
    cj = _frozen(c)
    toks = np.zeros((len(seqs), length), np.int32)
    for j, s in enumerate(seqs):
        if len(s) > length:
            raise ValueError(f"sequence of {len(s)} tokens > {length}")
        toks[j, :len(s)] = s
    n = sum(len(r) for r in rows)
    if prog.shape[0] != n:
        raise ValueError(f"{prog.shape[0]} program rows for {n} positions")
    pad = -n % Q_BLOCK
    flat = np.concatenate([j * length + np.asarray(r, np.int64)
                           for j, r in enumerate(rows)] + [np.zeros(pad, np.int64)])
    prog = np.concatenate([prog, np.zeros((pad, prog.shape[1]), np.float32)])
    nb = (n + pad) // Q_BLOCK
    with jax.default_matmul_precision("highest"):
        table = _table(cj, jnp.int32(seed))
        x = _embed(cj, table, jnp.asarray(toks))
        xc = x
        for i in range(c["num_hidden_layers"]):
            w = _weights(cj, jnp.int32(i), jnp.int32(seed))
            x = _run_layer(cj, w, x, False)
            if control:
                xc = _run_layer(cj, w, xc, True)
            del w
        d = c["hidden_size"]
        sel = jnp.asarray(flat)
        xr = x.reshape(-1, d)[sel].reshape(nb, Q_BLOCK, d)
        xk = xc.reshape(-1, d)[sel].reshape(nb, Q_BLOCK, d)
        err, ref, ctl = (np.asarray(a)[:n] for a in _head(
            cj, table, xr, xk,
            jnp.asarray(prog).reshape(nb, Q_BLOCK, -1), control))
    out = {"program": np.sqrt(err / ref)}
    if control:
        out["control"] = np.sqrt(ctl / ref)
    return out


@functools.partial(jax.jit, static_argnums=(0,))
def _table(cj, seed):
    return embed_table(dict(cj), seed)


def forward_logits(c: dict, seed: int, toks: np.ndarray,
                   int4: bool = False) -> np.ndarray:
    """Full logits ``(N, L, V)`` of token rows ``toks`` (small sizes only;
    ``L`` a multiple of the query block)."""
    cj = _frozen(c)
    with jax.default_matmul_precision("highest"):
        table = _table(cj, jnp.int32(seed))
        x = _embed(cj, table, jnp.asarray(toks, jnp.int32))
        for i in range(c["num_hidden_layers"]):
            x = _run_layer(cj, _weights(cj, jnp.int32(i), jnp.int32(seed)),
                           x, int4)
        xn = _rms(x, c["rms_norm_eps"])
        return np.asarray(_mm(xn.reshape(-1, xn.shape[-1]), table.T, int4)
                          .reshape(x.shape[:2] + (-1,)))
