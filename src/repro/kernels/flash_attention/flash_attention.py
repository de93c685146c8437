"""Blockwise causal flash attention — Pallas TPU kernel (prefill path).

Online-softmax over KV blocks held in VMEM; the [T, S] score matrix never
exists in HBM. GQA is native: the KV BlockSpec index-maps head h to
h // (Hq // Hkv), so grouped query heads stream the same KV tile (one HBM
fetch serves the whole group — the bandwidth saving GQA exists for).

Grid = (B, Hq, T/bq, S/bkv); the KV axis is innermost/sequential, carrying
(acc, m, l) in VMEM scratch. Causal blocks strictly above the diagonal are
masked (real-TPU builds would early-skip them; interpret mode computes and
masks — correctness identical).

VMEM @ (bq, bkv) = (256, 512), D=128, fp32 acc:
  q 128 KiB + k,v 256 KiB + acc 128 KiB + m,l 2 KiB  ≈ 0.5 MiB.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels._tiling import ceil_mult, divisor_block, pad_to


_NEG = -1e30


def _fa_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
               nkv: int, bq: int, bkv: int, seq_q: int, seq_kv: int,
               causal: bool, scale: float):
    qi, ki = pl.program_id(2), pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0, 0].astype(jnp.float32) * scale              # [bq, d]
    k = k_ref[0, 0].astype(jnp.float32)                      # [bkv, d]
    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32)  # [bq, bkv]

    q_idx = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 0)
    kv_idx = ki * bkv + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 1)
    # causal offset: query t attends kv <= t + (seq_kv - seq_q)
    mask = kv_idx < seq_kv
    if causal:
        mask &= kv_idx <= q_idx + (seq_kv - seq_q)
    s = jnp.where(mask, s, _NEG)

    m_prev = m_ref[...]                                      # [bq, 1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)
    p = jnp.where(mask, p, 0.0)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
        p, v_ref[0, 0].astype(jnp.float32), preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(ki == nkv - 1)
    def _finish():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


def flash_attention_pallas(q: jax.Array, k: jax.Array, v: jax.Array,
                           causal: bool = True, scale: Optional[float] = None,
                           *, bq: int = 256, bkv: int = 512,
                           interpret: bool = False) -> jax.Array:
    """q [B, Hq, T, D]; k, v [B, Hkv, S, D]; Hq % Hkv == 0."""
    b, hq, t, d = q.shape
    hkv, s_len = k.shape[1], k.shape[2]
    assert hq % hkv == 0
    g = hq // hkv
    scale = scale if scale is not None else d ** -0.5
    # a query block that divides T, or — when that would be fewer than the
    # 8 rows the TPU lowering needs (a 455-token prompt) — padded query
    # rows, computed against real keys and sliced off
    bq_ = divisor_block(t, min(bq, t))
    if bq_ % 8 and bq_ != t:
        bq_ = ceil_mult(t, bq)
        q, _ = pad_to(q, bq_, 2)
    bq, tpad = bq_, q.shape[2]
    bkv = min(bkv, s_len)
    spad = (s_len + bkv - 1) // bkv * bkv
    if spad != s_len:
        pad = ((0, 0), (0, 0), (0, spad - s_len), (0, 0))
        k, v = jnp.pad(k, pad), jnp.pad(v, pad)
    grid = (b, hq, tpad // bq, spad // bkv)
    kernel = functools.partial(
        _fa_kernel, nkv=grid[3], bq=bq, bkv=bkv, seq_q=t, seq_kv=s_len,
        causal=causal, scale=scale)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda b_, h, i, j: (b_, h, i, 0)),
            pl.BlockSpec((1, 1, bkv, d), lambda b_, h, i, j, g=g: (b_, h // g, j, 0)),
            pl.BlockSpec((1, 1, bkv, d), lambda b_, h, i, j, g=g: (b_, h // g, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, d), lambda b_, h, i, j: (b_, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, d), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(q, k, v)[:, :, :t]
