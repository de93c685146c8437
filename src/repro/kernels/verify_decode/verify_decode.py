"""Multi-token verification attention — Pallas TPU kernels.

The speculative-decode verify step scores K1 = k+1 query tokens per
sequence in ONE pass over the KV cache, instead of K1 sequential decode
passes: the online-softmax state gains a query axis ([Hq, K1, ...]
scratch) and the validity mask becomes per-query — query i admits
positions ``<= cache_pos + i``, the staircase window of the i-th
sequential step. Everything else mirrors the single-token kernels:

* contiguous — grid (B, S/bs), block-sequential over the KV axis with
  ``cache_pos`` scalar-prefetched (sibling of ``kernels/attn_decode``);
* paged — grid (B, NP), one grid step per page-table entry with the page
  id scalar-prefetched so only owned pages are streamed (sibling of
  ``kernels/paged_attention``); unallocated entries (-1) stream the
  scratch page and are masked wholesale.

VMEM per step @ bs=128, D=128, Hq=32, K1=5: q 80 KiB + k,v 2x64 KiB +
acc 80 KiB — far below the ~16 MiB budget. Bitwise identity with the
sequential decode steps is the REF backend's contract; these kernels are
validated by allclose, like every Pallas kernel in the tree.
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


def _verify_kernel(cp_ref, q_ref, k_ref, v_ref, o_ref,
                   acc_ref, m_ref, l_ref, *, nb: int, bs: int, g: int,
                   k1: int, scale: float):
    b, bi = pl.program_id(0), pl.program_id(1)

    @pl.when(bi == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0].astype(jnp.float32) * scale                # [Hq, K1, D]
    k = k_ref[0].astype(jnp.float32)                        # [Hkv, bs, D]
    v = v_ref[0].astype(jnp.float32)                        # [Hkv, bs, Dv]
    kr = jnp.repeat(k, g, axis=0)                           # [Hq, bs, D]
    vr = jnp.repeat(v, g, axis=0)
    s = jnp.einsum("hqd,hpd->hqp", q, kr,
                   preferred_element_type=jnp.float32)      # [Hq, K1, bs]

    pos = bi * bs + jax.lax.broadcasted_iota(jnp.int32, (1, k1, bs), 2)
    qi = jax.lax.broadcasted_iota(jnp.int32, (1, k1, bs), 1)
    mask = pos <= cp_ref[b] + qi                            # [1, K1, bs]
    s = jnp.where(mask, s, _NEG)

    m_prev = m_ref[...]                                     # [Hq, K1, 1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jnp.einsum(
        "hqp,hpd->hqd", p, vr, preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(bi == nb - 1)
    def _finish():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


def verify_decode_pallas(q: jax.Array, k: jax.Array, v: jax.Array,
                         cache_pos: jax.Array,
                         scale: Optional[float] = None, *,
                         bs: int = 128,
                         interpret: bool = False) -> jax.Array:
    """q [B, Hq, K1, D]; k [B, Hkv, S, D]; v [B, Hkv, S, Dv]; cache_pos [B].
    Returns fp32 [B, Hq, K1, Dv]. ``bs`` (tunable) is the KV block length;
    the op's ``supports`` predicate only admits S % 8 == 0 shapes."""
    b, hq, k1, d = q.shape
    _, hkv, s_len, _ = k.shape
    dv = v.shape[-1]
    scale_ = d ** -0.5 if scale is None else scale
    bs = divisor_block(s_len, min(bs, s_len))   # must divide: no pad pass
    nb = s_len // bs
    g = hq // hkv
    kernel = functools.partial(
        _verify_kernel, nb=nb, bs=bs, g=g, k1=k1, scale=scale_)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,       # cache_pos
            grid=(b, nb),
            in_specs=[
                pl.BlockSpec((1, hq, k1, d),
                             lambda bi, si, cp: (bi, 0, 0, 0)),
                pl.BlockSpec((1, hkv, bs, d),
                             lambda bi, si, cp: (bi, 0, si, 0)),
                pl.BlockSpec((1, hkv, bs, dv),
                             lambda bi, si, cp: (bi, 0, si, 0)),
            ],
            out_specs=pl.BlockSpec((1, hq, k1, dv),
                                   lambda bi, si, cp: (bi, 0, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((hq, k1, dv), jnp.float32),
                pltpu.VMEM((hq, k1, 1), jnp.float32),
                pltpu.VMEM((hq, k1, 1), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, hq, k1, dv), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        name="verify_decode",
        interpret=interpret,
    )(cache_pos, q, k, v)


def _verify_paged_kernel(pt_ref, cp_ref, q_ref, k_ref, v_ref, o_ref,
                         acc_ref, m_ref, l_ref, *, np_: int, ps: int,
                         g: int, k1: int, scale: float):
    b, pi = pl.program_id(0), pl.program_id(1)

    @pl.when(pi == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0].astype(jnp.float32) * scale                # [Hq, K1, D]
    k = k_ref[0].astype(jnp.float32)                        # [Hkv, ps, D]
    v = v_ref[0].astype(jnp.float32)                        # [Hkv, ps, Dv]
    kr = jnp.repeat(k, g, axis=0)                           # [Hq, ps, D]
    vr = jnp.repeat(v, g, axis=0)
    s = jnp.einsum("hqd,hpd->hqp", q, kr,
                   preferred_element_type=jnp.float32)      # [Hq, K1, ps]

    pid = pt_ref[b, pi]
    pos = pi * ps + jax.lax.broadcasted_iota(jnp.int32, (1, k1, ps), 2)
    qi = jax.lax.broadcasted_iota(jnp.int32, (1, k1, ps), 1)
    mask = (pos <= cp_ref[b] + qi) & (pid >= 0)             # [1, K1, ps]
    s = jnp.where(mask, s, _NEG)

    m_prev = m_ref[...]                                     # [Hq, K1, 1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jnp.einsum(
        "hqp,hpd->hqd", p, vr, preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(pi == np_ - 1)
    def _finish():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


def verify_decode_paged_pallas(q: jax.Array, k_pages: jax.Array,
                               v_pages: jax.Array, page_table: jax.Array,
                               cache_pos: jax.Array,
                               scale: Optional[float] = None, *,
                               interpret: bool = False) -> jax.Array:
    """q [B, Hq, K1, D]; k_pages [P, Hkv, ps, D]; v_pages [P, Hkv, ps, Dv];
    page_table [B, NP] int32 (-1 = unallocated -> masked); cache_pos [B].
    Returns fp32 [B, Hq, K1, Dv]."""
    b, hq, k1, d = q.shape
    _, hkv, ps, _ = k_pages.shape
    dv = v_pages.shape[-1]
    np_ = page_table.shape[1]
    scale_ = d ** -0.5 if scale is None else scale
    g = hq // hkv
    kernel = functools.partial(
        _verify_paged_kernel, np_=np_, ps=ps, g=g, k1=k1, scale=scale_)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,       # page_table, cache_pos
            grid=(b, np_),
            in_specs=[
                pl.BlockSpec((1, hq, k1, d),
                             lambda bi, pi, pt, cp: (bi, 0, 0, 0)),
                pl.BlockSpec(
                    (1, hkv, ps, d),
                    lambda bi, pi, pt, cp: (jnp.maximum(pt[bi, pi], 0),
                                            0, 0, 0)),
                pl.BlockSpec(
                    (1, hkv, ps, dv),
                    lambda bi, pi, pt, cp: (jnp.maximum(pt[bi, pi], 0),
                                            0, 0, 0)),
            ],
            out_specs=pl.BlockSpec((1, hq, k1, dv),
                                   lambda bi, pi, pt, cp: (bi, 0, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((hq, k1, dv), jnp.float32),
                pltpu.VMEM((hq, k1, 1), jnp.float32),
                pltpu.VMEM((hq, k1, 1), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, hq, k1, dv), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        name="verify_decode_paged",
        interpret=interpret,
    )(page_table, cache_pos, q, k_pages, v_pages)
