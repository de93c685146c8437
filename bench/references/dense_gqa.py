"""Plain reference of a dense GQA decoder (ChatGLM3, Yi): float32 at
``highest`` matmul precision, one layer at a time, no cache, no batching,
no kernels. It imports nothing of the program.

Per layer (pre-norm): h = x + Wo·attn(RMSNorm(x)); x' = h + W_down·(silu(
W_gate·n) * W_up·n) with n = RMSNorm(h). Attention: q, k, v projections
(plus biases where the model has them), rotary embedding on the first
``rope_dims`` dims of each head, causal softmax at scale head_dim^-0.5,
``num_heads / num_kv_heads`` query heads per KV head. Final RMSNorm, then
the unembedding.

Departure, noted: the rotary embedding rotates interleaved pairs
(2i, 2i+1) of the rotated dims, as ChatGLM does. Yi (Llama) rotates the
halves [i, i + d/2]; the two differ by a fixed permutation of the q and k
projection columns, which random weights do not see.

``quant`` computes the same thing in a lower precision, for the control:
``int8`` rounds every projection weight to int8 with one float scale per
output column (amax / 127), as weight-only int8 serving does; ``fp8``
rounds every projection weight and every projection input to float8 e4m3
with one scale per output column or per row.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * scale


def _round_cols(w, quant):
    """w [k, n] float32 through the control's precision."""
    amax = jnp.maximum(jnp.max(jnp.abs(w), axis=0, keepdims=True), 1e-30)
    if quant == "int8":
        s = amax / 127.0
        return jnp.clip(jnp.round(w / s), -127, 127) * s
    s = amax / 448.0
    return (w / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _round_rows(x):
    amax = jnp.maximum(jnp.max(jnp.abs(x), axis=-1, keepdims=True), 1e-30)
    s = amax / 448.0
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(x, w, quant):
    w = w.astype(jnp.float32)
    if quant is not None:
        w = _round_cols(w, quant)
    if quant == "fp8":
        x = _round_rows(x)
    return x @ w


def _rope(x, rope_dims, theta):
    """x [T, H, D]: rotate interleaved pairs of the first rope_dims dims."""
    t, d = x.shape[0], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, rope_dims, 2, dtype=jnp.float32)
                          / rope_dims)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv      # [T, rd/2]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., 0:rope_dims:2], x[..., 1:rope_dims:2]
    rot = jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    rot = rot.reshape(x.shape[:-1] + (rope_dims,))
    return jnp.concatenate([rot, x[..., rope_dims:]], axis=-1) \
        if rope_dims < d else rot


def _layer(x, layers, i, model, quant):
    lw = jax.tree_util.tree_map(lambda a: a[i], layers)
    f32 = lambda a: a.astype(jnp.float32)
    t = x.shape[0]
    hq, hkv, dh = model["num_heads"], model["num_kv_heads"], model["head_dim"]
    eps = model["norm_eps"]
    n = _rms(x, f32(lw["ln1"]), eps)
    q = _mm(n, lw["wq"], quant)
    k = _mm(n, lw["wk"], quant)
    v = _mm(n, lw["wv"], quant)
    if "bq" in lw:
        q, k, v = q + f32(lw["bq"]), k + f32(lw["bk"]), v + f32(lw["bv"])
    q = _rope(q.reshape(t, hq, dh), model["rope_dims"], model["rope_theta"])
    k = _rope(k.reshape(t, hkv, dh), model["rope_dims"], model["rope_theta"])
    v = v.reshape(t, hkv, dh)
    g = hq // hkv
    k = jnp.repeat(k, g, axis=1)
    v = jnp.repeat(v, g, axis=1)
    s = jnp.einsum("thd,shd->hts", q, k) * dh ** -0.5
    causal = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
    s = jnp.where(causal[None], s, -jnp.inf)
    a = jnp.einsum("hts,shd->thd", jax.nn.softmax(s, axis=-1), v)
    h = x + _mm(a.reshape(t, hq * dh), lw["wo"], quant)
    n2 = _rms(h, f32(lw["ln2"]), eps)
    ff = jax.nn.silu(_mm(n2, lw["w_gate"], quant)) * _mm(n2, lw["w_up"],
                                                         quant)
    return h + _mm(ff, lw["w_down"], quant)


@functools.partial(jax.jit, static_argnames=("model", "quant"))
def _layer_jit(x, layers, i, model, quant):
    with jax.default_matmul_precision("highest"):
        return _layer(x, layers, i, _unfreeze(model), quant)


@jax.jit
def _embed_jit(w, tokens):
    return w["embed"][tokens].astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("model", "quant"))
def _head_jit(x, w, rows, model, quant):
    with jax.default_matmul_precision("highest"):
        m = _unfreeze(model)
        h = _rms(x[rows], w["final_norm"].astype(jnp.float32), m["norm_eps"])
        return _mm(h, w["unembed"], quant)


def _freeze(model: dict):
    return tuple(sorted((k, v) for k, v in model.items()
                        if isinstance(v, (int, float, str, bool))))


def _unfreeze(model) -> dict:
    return dict(model)


def logits(w: dict, model: dict, tokens, rows,
           quant: Optional[str] = None):
    """Logits [len(rows), vocab] at positions ``rows`` of the causal
    forward over ``tokens`` [T]."""
    fm = _freeze(model)
    x = _embed_jit(w, jnp.asarray(tokens, jnp.int32))
    for i in range(model["num_layers"]):
        x = _layer_jit(x, w["layers"], jnp.int32(i), model=fm, quant=quant)
    return _head_jit(x, w, jnp.asarray(rows, jnp.int32), model=fm,
                     quant=quant)
