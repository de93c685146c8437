"""Page-blocked decode attention — Pallas TPU kernel.

Grid = (B, NP): one grid step per page-table entry, the page id scalar-
prefetched (``PrefetchScalarGridSpec``) so the K/V BlockSpecs DMA exactly
the one pool page the sequence actually owns — the accelerator never
touches pages belonging to other sequences (the data-movement argument of
the paper's near-memory study, applied to KV residency). Online softmax
carries (m, l, acc) in VMEM scratch across the page axis; unallocated
entries (-1) stream the scratch page and are masked wholesale.

VMEM per step @ ps=64, D=128, Hq=32: q 16 KiB + k,v 32 KiB + acc 16 KiB —
far below the ~16 MiB budget; the page axis is sequential ("arbitrary")
and the batch axis parallel.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


_NEG = -1e30


def _paged_kernel(pt_ref, cp_ref, q_ref, k_ref, v_ref, o_ref,
                  acc_ref, m_ref, l_ref, *, np_: int, ps: int, g: int,
                  scale: float, post_scale: bool):
    b, pi = pl.program_id(0), pl.program_id(1)

    @pl.when(pi == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0].astype(jnp.float32)                        # [Hq, D]
    if not post_scale:
        q = q * scale
    k = k_ref[0].astype(jnp.float32)                        # [Hkv, ps, D]
    v = v_ref[0].astype(jnp.float32)                        # [Hkv, ps, Dv]
    hkv = k.shape[0]
    # GQA as a batched matmul over KV heads ([Hkv, g, D] query groups): the
    # TPU lowering needs a non-contracting lhs dim, which "hd,hpd->hp" lacks
    s = jnp.einsum("hgd,hpd->hgp", q.reshape(hkv, g, q.shape[-1]), k,
                   preferred_element_type=jnp.float32
                   ).reshape(hkv * g, ps)                 # [Hq, ps]
    if post_scale:
        s = s * scale

    pid = pt_ref[b, pi]
    pos = pi * ps + jax.lax.broadcasted_iota(jnp.int32, (1, ps), 1)
    mask = (pos <= cp_ref[b]) & (pid >= 0)                  # [1, ps]
    s = jnp.where(mask, s, _NEG)

    m_prev = m_ref[...]                                     # [Hq, 1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jnp.einsum(
        "hgp,hpd->hgd", p.reshape(hkv, g, ps), v,
        preferred_element_type=jnp.float32).reshape(hkv * g, v.shape[-1])
    m_ref[...] = m_new

    @pl.when(pi == np_ - 1)
    def _finish():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


def paged_attention_pallas(q: jax.Array, k_pages: jax.Array,
                           v_pages: jax.Array, page_table: jax.Array,
                           cache_pos: jax.Array,
                           scale: Optional[float] = None,
                           q2: Optional[jax.Array] = None,
                           k2_pages: Optional[jax.Array] = None,
                           precise: bool = False, *,
                           interpret: bool = False) -> jax.Array:
    """q [B, Hq, D]; k_pages [P, Hkv, ps, D]; v_pages [P, Hkv, ps, Dv];
    page_table [B, NP] int32 (-1 = unallocated -> masked); cache_pos [B].
    Returns fp32 [B, Hq, Dv].

    The optional second score component (``q2``/``k2_pages`` — MLA's shared
    rotary key) is folded in by concatenation along D: q.k' + q2.k2' ==
    [q|q2].[k|k2]' up to fp reassociation, which is fine here — bitwise
    identity with the contiguous path is the REF backend's contract, not
    this kernel's (it is validated by allclose, like every Pallas kernel).
    KNOWN COST: that concatenation materializes a pool-sized copy of the
    latent pages per call, which defeats the resident-pages-only DMA story
    for MLA; the on-TPU fix is a third scalar-prefetch-indexed input with
    its own BlockSpec and the q2.k2 dot added in-kernel (follow-up — on
    this interpret-mode container the ref backend is the measured default).
    """
    d = q.shape[-1]
    scale_ = d ** -0.5 if scale is None else scale
    if q2 is not None:
        q = jnp.concatenate([q, q2.astype(q.dtype)], axis=-1)
        k_pages = jnp.concatenate(
            [k_pages, k2_pages.astype(k_pages.dtype)], axis=-1)
    b, hq, dcat = q.shape
    p_, hkv, ps, _ = k_pages.shape
    dv = v_pages.shape[-1]
    np_ = page_table.shape[1]
    g = hq // hkv
    kernel = functools.partial(
        _paged_kernel, np_=np_, ps=ps, g=g, scale=scale_,
        post_scale=precise)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,       # page_table, cache_pos
            grid=(b, np_),
            in_specs=[
                pl.BlockSpec((1, hq, dcat), lambda bi, pi, pt, cp: (bi, 0, 0)),
                pl.BlockSpec(
                    (1, hkv, ps, dcat),
                    lambda bi, pi, pt, cp: (jnp.maximum(pt[bi, pi], 0),
                                            0, 0, 0)),
                pl.BlockSpec(
                    (1, hkv, ps, dv),
                    lambda bi, pi, pt, cp: (jnp.maximum(pt[bi, pi], 0),
                                            0, 0, 0)),
            ],
            out_specs=pl.BlockSpec((1, hq, dv),
                                   lambda bi, pi, pt, cp: (bi, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((hq, dv), jnp.float32),
                pltpu.VMEM((hq, 1), jnp.float32),
                pltpu.VMEM((hq, 1), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, hq, dv), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        name="paged_attention",
        interpret=interpret,
    )(page_table, cache_pos, q, k_pages, v_pages)
