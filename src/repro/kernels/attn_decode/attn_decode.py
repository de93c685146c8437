"""Block-sequential contiguous decode attention — Pallas TPU kernel.

Grid = (B, S/bs): one grid step per KV block of one sequence; online
softmax carries (m, l, acc) in VMEM scratch across the sequence axis, so
only one [Hkv, bs, D] K/V block is resident at a time — the contiguous
sibling of ``kernels/paged_attention``'s page-blocked kernel (same scratch
layout, the block index is affine instead of scalar-prefetched).
``cache_pos`` is scalar-prefetched so the validity mask costs no extra
input streaming.

VMEM per step @ bs=128, D=128, Hq=32: q 16 KiB + k,v 2x64 KiB + acc
16 KiB — far below the ~16 MiB budget; the batch axis is parallel and the
block axis sequential ("arbitrary").
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels._tiling import divisor_block

_NEG = -1e30


def _decode_kernel(cp_ref, q_ref, k_ref, v_ref, o_ref,
                   acc_ref, m_ref, l_ref, *, nb: int, bs: int, g: int,
                   scale: float, post_scale: bool):
    b, bi = pl.program_id(0), pl.program_id(1)

    @pl.when(bi == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0].astype(jnp.float32)                        # [Hq, D]
    if not post_scale:
        q = q * scale
    k = k_ref[0].astype(jnp.float32)                        # [Hkv, bs, D]
    v = v_ref[0].astype(jnp.float32)                        # [Hkv, bs, Dv]
    hkv = k.shape[0]
    # GQA as a batched matmul over KV heads ([Hkv, g, D] query groups): the
    # TPU lowering needs a non-contracting lhs dim, which "hd,hpd->hp" lacks
    s = jnp.einsum("hgd,hpd->hgp", q.reshape(hkv, g, q.shape[-1]), k,
                   preferred_element_type=jnp.float32
                   ).reshape(hkv * g, bs)                 # [Hq, bs]
    if post_scale:
        s = s * scale

    pos = bi * bs + jax.lax.broadcasted_iota(jnp.int32, (1, bs), 1)
    mask = pos <= cp_ref[b]                                 # [1, bs]
    s = jnp.where(mask, s, _NEG)

    m_prev = m_ref[...]                                     # [Hq, 1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jnp.einsum(
        "hgp,hpd->hgd", p.reshape(hkv, g, bs), v,
        preferred_element_type=jnp.float32).reshape(hkv * g, v.shape[-1])
    m_ref[...] = m_new

    @pl.when(bi == nb - 1)
    def _finish():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


def attn_decode_pallas(q: jax.Array, k: jax.Array, v: jax.Array,
                       cache_pos: jax.Array,
                       scale: Optional[float] = None,
                       q2: Optional[jax.Array] = None,
                       k2: Optional[jax.Array] = None,
                       precise: bool = False, *,
                       bs: int = 128,
                       interpret: bool = False) -> jax.Array:
    """q [B, Hq, D]; k [B, Hkv, S, D]; v [B, Hkv, S, Dv]; cache_pos [B].
    Returns fp32 [B, Hq, Dv]. ``bs`` (tunable) is the KV block length; the
    op's ``supports`` predicate only admits S % bs == 0 shapes.

    The optional second score component (``q2``/``k2`` — MLA's shared
    rotary key) is folded in by concatenation along D, exactly as in the
    paged kernel: allclose to the ref backend, not bitwise (bitwise
    identity is the REF backend's contract).
    """
    d = q.shape[-1]
    scale_ = d ** -0.5 if scale is None else scale
    if q2 is not None:
        q = jnp.concatenate([q, q2.astype(q.dtype)], axis=-1)
        k = jnp.concatenate([k, k2.astype(k.dtype)], axis=-1)
    b, hq, dcat = q.shape
    _, hkv, s_len, _ = k.shape
    dv = v.shape[-1]
    bs = divisor_block(s_len, min(bs, s_len))   # must divide: no pad pass
    nb = s_len // bs
    g = hq // hkv
    kernel = functools.partial(
        _decode_kernel, nb=nb, bs=bs, g=g, scale=scale_, post_scale=precise)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,       # cache_pos
            grid=(b, nb),
            in_specs=[
                pl.BlockSpec((1, hq, dcat), lambda bi, si, cp: (bi, 0, 0)),
                pl.BlockSpec((1, hkv, bs, dcat),
                             lambda bi, si, cp: (bi, 0, si, 0)),
                pl.BlockSpec((1, hkv, bs, dv),
                             lambda bi, si, cp: (bi, 0, si, 0)),
            ],
            out_specs=pl.BlockSpec((1, hq, dv),
                                   lambda bi, si, cp: (bi, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((hq, dv), jnp.float32),
                pltpu.VMEM((hq, 1), jnp.float32),
                pltpu.VMEM((hq, 1), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, hq, dv), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(cache_pos, q, k, v)
