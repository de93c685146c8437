"""Jit'd wrappers + XAIF registration for paged decode attention.

The ``attn_decode_paged`` op is the decode-attention contract of the paged
KV cache: one query token per sequence against a page pool + page table
(see ``serve/engine.py`` for the pool invariants). Positional signature::

    (q [B, Hq, D], k_pages [P, Hkv, ps, D], v_pages [P, Hkv, ps, Dv],
     page_table [B, NP] i32, cache_pos [B] i32)

plus keyword-only ``scale`` / ``precise`` / ``q2``+``k2_pages`` (the MLA
absorbed-decode variant — see ref.py). Two backends:

* ``ref``    — gather-based jnp; BITWISE-identical to the contiguous decode
  paths (the paged engine's token-identity guarantee rests on it);
* ``pallas`` — page-blocked kernel, one grid step per slot looping over
  the slot's live blocks of many pages, each page DMA'd by its
  scalar-prefetched id (no gather materialization; entries past the live
  length and free slots cost no copy and no math).
"""
from __future__ import annotations

from typing import Optional

from repro.core import xaif
from repro.kernels.paged_attention import paged_attention as _k
from repro.kernels.paged_attention import ref as _ref


def paged_attention_cost(b, hq, np_, ps, d, dtype_bytes=2):
    """Decode is bandwidth-bound on the resident pages. From shapes alone
    the lengths are unknown, so this counts every slot at the full table
    width, [B, NP*ps] K and V lanes, and one [B, Hq, D] query: an upper
    bound on what the kernel reads."""
    s = np_ * ps
    flops = 4.0 * b * hq * s * d
    return {"flops": flops,
            "hbm_bytes": dtype_bytes * b * (2 * s * d + 2 * hq * d)}


@xaif.register("attn_decode_paged", "ref", cost_fn=paged_attention_cost,
               description="gather-based paged decode attention; bitwise-"
                           "identical to the contiguous decode einsums")
def paged_attention_ref_op(q, k_pages, v_pages, page_table, cache_pos,
                           scale: Optional[float] = None, q2=None,
                           k2_pages=None, precise: bool = False):
    return _ref.paged_attention_ref(q, k_pages, v_pages, page_table,
                                    cache_pos, scale, q2, k2_pages, precise)


@xaif.register("attn_decode_paged", "pallas", cost_fn=paged_attention_cost,
               description="page-blocked Pallas decode attention: one grid "
                           "step per slot over its live multi-page blocks, "
                           "page ids scalar-prefetched")
def paged_attention_pallas_op(q, k_pages, v_pages, page_table, cache_pos,
                              scale: Optional[float] = None, q2=None,
                              k2_pages=None, precise: bool = False, *,
                              interpret: bool = False):
    return _k.paged_attention_pallas(q, k_pages, v_pages, page_table,
                                     cache_pos, scale, q2, k2_pages,
                                     precise, interpret=interpret)
