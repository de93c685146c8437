"""Page-blocked decode attention — Pallas TPU kernel.

Grid = (B,): one grid step per slot. The K/V pools stay in HBM; inside the
step a loop runs over the slot's live blocks only — ``cdiv(pages holding
positions <= cache_pos, ppb)`` of them — each block covering ``ppb`` table
entries. A block's pages are copied one DMA per page, by the page ids of
the scalar-prefetched table, into a double-buffered VMEM block, and the
next block's copies start before the current block is computed. Entries
past the live length and entries with no page (-1) are never copied, so
the accelerator reads only pages the sequence owns (the data-movement
argument of the paper's near-memory study, applied to KV residency), and a
free slot costs one grid step with no copy and no math. Online softmax
carries (m, l, acc) in float32 VMEM scratch across the blocks.

``ppb`` follows from the shapes: about 256 positions a block, capped by
the table width and by a VMEM budget. At chatglm3-6b's shapes (pages of
16, 2 KV heads of 128, bf16) a block is 16 pages: K and V double-buffered
512 KiB, their float32 copies 512 KiB. On a v5e, blocks of 128-256
positions served mixed live lengths fastest and 512 full slots (PERF.md
records the sweep).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


_NEG = -1e30
_BLOCK_POSITIONS = 256          # positions a block aims to cover
_VMEM_BUDGET = 8 * 1024 * 1024  # K/V buffers plus their float32 copies


def _pages_per_block(ps: int, hkv: int, dk: int, dv: int, np_: int,
                     itemsize: int) -> int:
    """Pages a compute block covers: about ``_BLOCK_POSITIONS`` positions,
    no more than the table holds, and within ``_VMEM_BUDGET`` for the two
    buffered K/V blocks and the block's float32 copies."""
    ppb = max(1, min(np_, _BLOCK_POSITIONS // ps))
    per_page = ps * hkv * (dk + dv) * (2 * itemsize + 4)
    while ppb > 1 and ppb * per_page > _VMEM_BUDGET:
        ppb //= 2
    return ppb


def _paged_kernel(pt_ref, cp_ref, q_ref, k_hbm, v_hbm, o_ref,
                  k_buf, v_buf, sems, acc_ref, m_ref, l_ref, *, np_: int,
                  ps: int, ppb: int, g: int, scale: float, post_scale: bool):
    b = pl.program_id(0)
    bk = ppb * ps
    cp = cp_ref[b]
    n_pages = jnp.minimum(cp // ps + 1, np_)   # entries holding pos <= cp
    n_blocks = pl.cdiv(n_pages, ppb)

    def entries(blk):
        """Per table entry of block ``blk``: (holds a live page, in range
        without a page, pool page to copy)."""
        out = []
        for j in range(ppb):
            idx = blk * ppb + j
            pid = pt_ref[b, jnp.minimum(idx, np_ - 1)]
            in_range = idx < n_pages
            out.append((in_range & (pid >= 0), in_range & (pid < 0),
                        jnp.maximum(pid, 0)))
        return out

    def copies(j, src, slot):
        dst = pl.ds(j * ps, ps)
        return (pltpu.make_async_copy(k_hbm.at[src], k_buf.at[slot, :, dst],
                                      sems.at[0, slot]),
                pltpu.make_async_copy(v_hbm.at[src], v_buf.at[slot, :, dst],
                                      sems.at[1, slot]))

    def start(blk, slot):
        for j, (live, _, src) in enumerate(entries(blk)):
            @pl.when(live)
            def _():
                for c in copies(j, src, slot):
                    c.start()

    acc_ref[...] = jnp.zeros_like(acc_ref)
    m_ref[...] = jnp.full_like(m_ref, _NEG)
    l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0].astype(jnp.float32)                        # [Hq, D]
    if not post_scale:
        q = q * scale
    hq, d = q.shape
    hkv = hq // g
    qg = q.reshape(hkv, g, d)

    def block(blk, carry):
        slot = blk % 2

        @pl.when(blk + 1 < n_blocks)
        def _():
            start(blk + 1, 1 - slot)

        ents = entries(blk)
        n_live, hole = 0, False
        for j, (live, no_page, src) in enumerate(ents):
            @pl.when(live)
            def _():
                for c in copies(j, src, slot):
                    c.wait()

            # an entry in range without a page gets zero V rows: its lanes
            # are masked out of s, and stale buffer rows must not reach
            # p.v through 0 * NaN
            @pl.when(no_page)
            def _():
                v_buf[slot, :, j * ps:(j + 1) * ps, :] = jnp.zeros(
                    (v_buf.shape[1], ps, v_buf.shape[3]), v_buf.dtype)

            n_live = n_live + live.astype(jnp.int32)
            hole = hole | no_page

        @pl.when(n_live > 0)
        def _compute():
            base = blk * bk
            k = k_buf[slot].astype(jnp.float32)             # [Hkv, bk, D]
            v = v_buf[slot].astype(jnp.float32)             # [Hkv, bk, Dv]
            # GQA as a batched matmul over KV heads ([Hkv, g, D] query
            # groups): the TPU lowering needs a non-contracting lhs dim
            s = jnp.einsum("hgd,hpd->hgp", qg, k,
                           preferred_element_type=jnp.float32
                           ).reshape(hq, bk)                # [Hq, bk]
            if post_scale:
                s = s * scale
            lane = jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1)
            in_len = base + lane <= cp                      # [1, bk]

            def with_holes():
                ok = in_len
                for j, (_, no_page, _) in enumerate(ents):
                    ok = ok & ((lane // ps != j) | jnp.logical_not(no_page))
                return ok.astype(jnp.int32)

            # the per-entry test only where the block has an entry without
            # a page (int32: the lowering cannot return a vector of bools)
            mask = jax.lax.cond(hole, with_holes,
                                lambda: in_len.astype(jnp.int32)) > 0
            s = jnp.where(mask, s, _NEG)
            # rows past cache_pos hold junk of the page's last writer or a
            # previous block: select them out of v, not only out of p
            row = jax.lax.broadcasted_iota(jnp.int32, (1, bk, 1), 1)
            v = jnp.where(base + row <= cp, v, 0.0)

            m_prev = m_ref[...]                             # [Hq, 1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
            l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1,
                                                      keepdims=True)
            acc_ref[...] = acc_ref[...] * alpha + jnp.einsum(
                "hgp,hpd->hgd", p.reshape(hkv, g, bk), v,
                preferred_element_type=jnp.float32).reshape(hq, v.shape[-1])
            m_ref[...] = m_new

        return carry

    start(0, 0)
    jax.lax.fori_loop(0, n_blocks, block, 0)
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
    for MLA; the on-TPU fix is a third pool copied by the same page ids and
    the q2.k2 dot added in-kernel (follow-up — on this interpret-mode
    container the ref backend is the measured default).
    """
    d = q.shape[-1]
    scale_ = d ** -0.5 if scale is None else scale
    if q2 is not None:
        q = jnp.concatenate([q, q2.astype(q.dtype)], axis=-1)
        k_pages = jnp.concatenate(
            [k_pages, k2_pages.astype(k_pages.dtype)], axis=-1)
    b, hq, dcat = q.shape
    _, hkv, ps, _ = k_pages.shape
    dv = v_pages.shape[-1]
    np_ = page_table.shape[1]
    ppb = _pages_per_block(ps, hkv, dcat, dv, np_,
                           jnp.dtype(k_pages.dtype).itemsize)
    kernel = functools.partial(
        _paged_kernel, np_=np_, ps=ps, ppb=ppb, g=hq // hkv, scale=scale_,
        post_scale=precise)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,       # page_table, cache_pos
            grid=(b,),
            in_specs=[
                pl.BlockSpec((1, hq, dcat), lambda bi, pt, cp: (bi, 0, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((1, hq, dv),
                                   lambda bi, pt, cp: (bi, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, hkv, ppb * ps, dcat), k_pages.dtype),
                pltpu.VMEM((2, hkv, ppb * ps, dv), v_pages.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.VMEM((hq, dv), jnp.float32),
                pltpu.VMEM((hq, 1), jnp.float32),
                pltpu.VMEM((hq, 1), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, hq, dv), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        name="paged_attention",
        interpret=interpret,
    )(page_table, cache_pos, q, k_pages, v_pages)
