"""Fused softmax-entropy Pallas TPU kernel (the early-exit confidence check).

The reference path materializes log_softmax(logits) — an extra HBM
round-trip over a [tokens, vocab] tensor (vocab up to 152k here). This
kernel streams vocab blocks through VMEM once, maintaining an
online-softmax-style running triple per row:

    m = running max
    s = sum exp(l - m)
    u = sum exp(l - m) * l

With log Z = m + log s, the entropy is H = log Z - u / s, and the kernel
emits H / log(C) (normalized to [0,1], the scale of the paper's thresholds).
Each new block's max m' rescales (s, u) by exp(m - m') — same trick flash
attention uses for the softmax denominator.

HBM traffic: read logits once, write [tokens] — vs read+write+read for the
unfused path. That is the NM-Carus thesis (compute where the data sits)
applied to the paper's own exit decision.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


_NEG = -1e30


def _entropy_kernel(x_ref, o_ref, m_ref, s_ref, u_ref, *, nv: int, vocab: int,
                    bv: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        s_ref[...] = jnp.zeros_like(s_ref)
        u_ref[...] = jnp.zeros_like(u_ref)

    x = x_ref[...].astype(jnp.float32)                       # [bm, bv]
    # mask the padded tail of the vocab axis
    col = j * bv + jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    valid = col < vocab
    x = jnp.where(valid, x, _NEG)

    m_prev = m_ref[...]                                       # [bm, 1]
    m_blk = jnp.max(x, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_blk)
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.where(valid, jnp.exp(x - m_new), 0.0)
    s_ref[...] = s_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    u_ref[...] = u_ref[...] * alpha + jnp.sum(p * jnp.where(valid, x, 0.0),
                                              axis=-1, keepdims=True)
    m_ref[...] = m_new

    @pl.when(j == nv - 1)
    def _finish():
        logz = m_ref[...] + jnp.log(s_ref[...])
        ent = logz - u_ref[...] / s_ref[...]                  # [bm, 1]
        o_ref[...] = ent / jnp.log(jnp.asarray(vocab, jnp.float32))


def entropy_pallas(logits: jax.Array, *, bm: int = 256, bv: int = 2048,
                   interpret: bool = False) -> jax.Array:
    """logits [M, V] -> normalized entropy [M] (fp32)."""
    m, v = logits.shape
    bm = min(bm, m)
    while m % bm != 0:
        bm //= 2
    bv = min(bv, _round_up(v, 128))
    vpad = _round_up(v, bv)
    if vpad != v:
        logits = jnp.pad(logits, ((0, 0), (0, vpad - v)))
    grid = (m // bm, vpad // bv)
    out = pl.pallas_call(
        functools.partial(_entropy_kernel, nv=grid[1], vocab=v, bv=bv),
        grid=grid,
        in_specs=[pl.BlockSpec((bm, bv), lambda i, j: (i, j))],
        out_specs=pl.BlockSpec((bm, 1), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((m, 1), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((bm, 1), jnp.float32),
            pltpu.VMEM((bm, 1), jnp.float32),
            pltpu.VMEM((bm, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        name="entropy_exit",
        interpret=interpret,
    )(logits)
    return out[:, 0]


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m
