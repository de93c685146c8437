"""Measured XAIF backend autotuning (ROADMAP: "Backend autotuning").

For every registered op this module enumerates the op's shape buckets
(``xaif.op_buckets``), builds one representative workload per
(op, bucket) cell, and *times every registered backend* on it — the
FEMU-style measure-then-select exploration loop, applied to the JAX
accelerator interface. The winner per cell (optionally including a sweep
over the backend's declared block-size tunables) becomes one row of a
:class:`~repro.core.xaif.DispatchPolicy`, which is

  * never slower than any static ``AccelConfig`` on a measured cell **by
    construction** — the static choice is one of the measured candidates
    and the winner is the argmin;
  * hashable and JSON-persistable: serve startup loads the policy file
    instead of re-measuring (``launch/serve.py --policy/--autotune``).

Each backend's ``cost_fn`` is reused as the *prior*: it estimates the
cell's work before anything runs, sizes the timing loop (heavy cells get
fewer iterations), and is recorded next to the measurement so reports can
show measured-vs-modelled. Backends whose ``supports`` predicate rejects
the cell, or that raise on it, are excluded from that cell only.

Off the TPU, Pallas backends run through the interpreter
(``xaif.pallas_interpret``), whose timings are meaningless as TPU
predictions — the ref/XLA backends will usually win, which is the
*correct* measured answer for that host. On a TPU the same sweep times
the compiled kernels.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import AccelConfig
from repro.core import xaif

DEFAULT_POLICY_PATH = ".xaif_policy.json"

# ---------------------------------------------------------------------------
# Representative workloads per (op, bucket) cell
# ---------------------------------------------------------------------------
#
# Sizes are deliberately modest so the sweep is a viable CI smoke step on
# CPU; ``scale`` multiplies the row/sequence extents for real measurement
# runs. Feature dims stay hardware-friendly (multiples of the VPU lane).


def _key(i: int) -> jax.Array:
    return jax.random.PRNGKey(i)


def _scaled_rows(m: int, scale: int) -> int:
    """Scale a row-cell size without crossing its shape-bucket boundary
    (<=32 / <=2048 / beyond — see xaif._rows_bucket): the scaled cell must
    still measure the bucket it is registered for."""
    if m <= 32:
        return min(m * scale, 32)
    if m <= 2048:
        return min(max(m * scale, 33), 2048)
    return m * scale


def _gemm_cell(m: int):
    def build(scale: int):
        mm, k, n = _scaled_rows(m, scale), 64 * scale, 64 * scale
        x = jax.random.normal(_key(0), (mm, k), jnp.float32)
        w = jax.random.normal(_key(1), (k, n), jnp.float32)
        return (x, w), {}
    return build


def _rmsnorm_cell(m: int):
    def build(scale: int):
        x = jax.random.normal(_key(0), (_scaled_rows(m, scale), 128 * scale),
                              jnp.float32)
        s = jnp.ones((128 * scale,), jnp.float32)
        return (x, s), {}
    return build


def _entropy_cell(m: int):
    def build(scale: int):
        lg = jax.random.normal(_key(0), (_scaled_rows(m, scale), 512 * scale),
                               jnp.float32)
        return (lg,), {}
    return build


def _attention_cell(t: int):
    def build(scale: int):
        s_len = 128 * scale
        t_len = 1 if t == 1 else t * scale
        q = jax.random.normal(_key(0), (2, 4, t_len, 32), jnp.float32)
        k = jax.random.normal(_key(1), (2, 2, s_len, 32), jnp.float32)
        v = jax.random.normal(_key(2), (2, 2, s_len, 32), jnp.float32)
        return (q, k, v), {}
    return build


def _ssm_cell(t: int, batch: int = 2, din: int = 32, n: int = 8):
    def build(scale: int):
        t_len = 1 if t == 1 else t * scale
        u = jax.random.normal(_key(0), (batch, t_len, din), jnp.float32)
        dt = jax.nn.softplus(jax.random.normal(_key(1), (batch, t_len, din),
                                               jnp.float32))
        a = -jnp.abs(jax.random.normal(_key(2), (din, n), jnp.float32))
        b = jax.random.normal(_key(3), (batch, t_len, n), jnp.float32)
        c = jax.random.normal(_key(4), (batch, t_len, n), jnp.float32)
        d = jax.random.normal(_key(5), (din,), jnp.float32)
        return (u, dt, a, b, c, d), {}
    return build


def _ssm_decode_cell(batch: int = 4, din: int = 32, n: int = 8):
    """One mamba-bucket ssm_decode cell: a single decode token's selective
    state update at [batch, din]. The bucket is keyed on rank, not size,
    so ``scale`` grows the batch axis."""
    def build(scale: int):
        b_ = batch * scale
        x = jax.random.normal(_key(0), (b_, din), jnp.float32)
        g = jax.nn.softplus(jax.random.normal(_key(1), (b_, din),
                                              jnp.float32))
        a = -jnp.abs(jax.random.normal(_key(2), (din, n), jnp.float32))
        b = jax.random.normal(_key(3), (b_, n), jnp.float32)
        c = jax.random.normal(_key(4), (b_, n), jnp.float32)
        m = jax.random.normal(_key(5), (din,), jnp.float32)
        h = jax.random.normal(_key(6), (b_, din, n), jnp.float32)
        return (x, g, a, b, c, m, h), {}
    return build


def _mlstm_decode_cell(batch: int = 4, heads: int = 4, dh: int = 16):
    """One mlstm-bucket ssm_decode cell: a single decode token's matrix-LSTM
    cell update. All operands are arrays (the state tuple is passed as two
    positional tensors plus the stabilizer) so shape collection works."""
    def build(scale: int):
        b_ = batch * scale
        qx = jax.random.normal(_key(0), (b_, heads, dh), jnp.float32)
        kx = jax.random.normal(_key(1), (b_, heads, dh), jnp.float32)
        vx = jax.random.normal(_key(2), (b_, heads, dh), jnp.float32)
        li = jax.random.normal(_key(3), (b_, heads), jnp.float32)
        lf = jax.random.normal(_key(4), (b_, heads), jnp.float32)
        m = jnp.abs(jax.random.normal(_key(5), (b_, heads), jnp.float32))
        cst = jax.random.normal(_key(6), (b_, heads, dh, dh), jnp.float32)
        nst = jax.random.normal(_key(7), (b_, heads, dh), jnp.float32)
        return (qx, kx, vx, li, lf, m, cst, nst), {}
    return build


def _attn_decode_cell(s: int, batch: int = 4, hq: int = 4, hkv: int = 2,
                      d: int = 32, mla_rope_dim: int = 0):
    """One attn_decode cell: ``batch`` sequences of staggered lengths over a
    contiguous [B, Hkv, s, D] cache. ``mla_rope_dim`` > 0 builds the MLA
    absorbed-decode call (hkv must be 1, precise fp32 post-scale, rotary
    second score component)."""
    def build(scale: int):
        q = jax.random.normal(_key(0), (batch, hq, d), jnp.float32)
        k = jax.random.normal(_key(1), (batch, hkv, s, d), jnp.float32)
        v = jax.random.normal(_key(2), (batch, hkv, s, d), jnp.float32)
        pos = (jnp.arange(batch, dtype=jnp.int32) * (s // 4) + s // 2) % s
        kwargs = {}
        if mla_rope_dim:
            assert hkv == 1
            kwargs = {
                "scale": (d + mla_rope_dim) ** -0.5,
                "q2": jax.random.normal(_key(3), (batch, hq, mla_rope_dim),
                                        jnp.float32),
                "k2": jax.random.normal(
                    _key(4), (batch, 1, s, mla_rope_dim), jnp.float32),
                "precise": True,
            }
        return (q, k, v, pos), kwargs
    return build


def _paged_attn_cell(np_pages: int, batch: int = 4, hq: int = 4,
                     hkv: int = 2, d: int = 32, ps: int = 16,
                     mla_rope_dim: int = 0):
    """One attn_decode_paged cell: ``batch`` sequences of staggered lengths
    over a pool sized for ``np_pages`` pages each (+ the scratch page).

    ``mla_rope_dim`` > 0 builds the MLA serve-time call instead: a single
    latent head (hkv must be 1), ``d``-wide latent pages, precise fp32
    post-scale and the rotary key as the q2/k2 second score component."""
    def build(scale: int):
        np_ = np_pages                    # bucket boundary is NP*ps; fixed
        pool = batch * np_ + 1
        q = jax.random.normal(_key(0), (batch, hq, d), jnp.float32)
        kp = jax.random.normal(_key(1), (pool, hkv, ps, d), jnp.float32)
        vp = jax.random.normal(_key(2), (pool, hkv, ps, d), jnp.float32)
        # slot b owns pages [1 + b*np_, 1 + (b+1)*np_), lengths staggered
        table = (1 + jnp.arange(batch)[:, None] * np_
                 + jnp.arange(np_)[None, :]).astype(jnp.int32)
        pos = (jnp.arange(batch, dtype=jnp.int32) * ps
               + ps // 2) % (np_ * ps)
        n_alloc = pos // ps + 1
        table = jnp.where(jnp.arange(np_)[None, :] < n_alloc[:, None],
                          table, -1)
        kwargs = {}
        if mla_rope_dim:
            assert hkv == 1
            kwargs = {
                "scale": (d + mla_rope_dim) ** -0.5,
                "q2": jax.random.normal(_key(3), (batch, hq, mla_rope_dim),
                                        jnp.float32),
                "k2_pages": jax.random.normal(
                    _key(4), (pool, 1, ps, mla_rope_dim), jnp.float32),
                "precise": True,
            }
        return (q, kp, vp, table, pos), kwargs
    return build


def _verify_decode_cell(s: int, batch: int = 4, hq: int = 4, hkv: int = 2,
                        d: int = 32, k1: int = 5):
    """One verify_decode cell: ``batch`` sequences scoring ``k1`` = k+1
    speculative query tokens each against a contiguous [B, Hkv, s, D]
    cache (query i admitted positions <= cache_pos + i)."""
    def build(scale: int):
        q = jax.random.normal(_key(0), (batch, hq, k1, d), jnp.float32)
        k = jax.random.normal(_key(1), (batch, hkv, s, d), jnp.float32)
        v = jax.random.normal(_key(2), (batch, hkv, s, d), jnp.float32)
        pos = (jnp.arange(batch, dtype=jnp.int32) * (s // 4)
               + s // 2) % (s - k1)
        return (q, k, v, pos), {}
    return build


def _verify_paged_cell(np_pages: int, batch: int = 4, hq: int = 4,
                       hkv: int = 2, d: int = 32, ps: int = 16,
                       k1: int = 5):
    """One verify_decode_paged cell: the paged sibling — ``k1`` query
    tokens per sequence over a page pool with staggered lengths."""
    def build(scale: int):
        np_ = np_pages                    # bucket boundary is NP*ps; fixed
        pool = batch * np_ + 1
        q = jax.random.normal(_key(0), (batch, hq, k1, d), jnp.float32)
        kp = jax.random.normal(_key(1), (pool, hkv, ps, d), jnp.float32)
        vp = jax.random.normal(_key(2), (pool, hkv, ps, d), jnp.float32)
        table = (1 + jnp.arange(batch)[:, None] * np_
                 + jnp.arange(np_)[None, :]).astype(jnp.int32)
        pos = (jnp.arange(batch, dtype=jnp.int32) * ps
               + ps // 2) % (np_ * ps - k1)
        n_alloc = (pos + k1 - 1) // ps + 1
        table = jnp.where(jnp.arange(np_)[None, :] < n_alloc[:, None],
                          table, -1)
        return (q, kp, vp, table, pos), {}
    return build


def _moe_decode_cell(e: int, batch: int = 4, k: int = 2, d: int = 64,
                     h: int = 32):
    """One moe_decode cell: ``batch`` decode tokens routed top-``k`` over
    ``e`` experts. Assignments are drawn through a real softmax top-k so
    the per-expert histogram is realistically uneven (what the sorted
    ragged dispatch actually sees). ``e`` is the bucket axis and stays
    fixed under ``scale``; the token count scales instead."""
    def build(scale: int):
        b_ = batch * scale
        x = jax.random.normal(_key(0), (b_, d), jnp.float32)
        wg = jax.random.normal(_key(1), (e, d, h), jnp.float32) * d ** -0.5
        wu = jax.random.normal(_key(2), (e, d, h), jnp.float32) * d ** -0.5
        wd = jax.random.normal(_key(3), (e, h, d), jnp.float32) * h ** -0.5
        logits = jax.random.normal(_key(4), (b_, e), jnp.float32)
        gate, idx = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), k)
        gate = gate / jnp.sum(gate, axis=-1, keepdims=True)
        return (x, idx.astype(jnp.int32), gate, wg, wu, wd), {}
    return build


# (op, bucket) -> builder(scale) -> (args, kwargs). Row classes straddle the
# xaif bucket boundaries (<=32 / <=2048 / beyond). One cell per
# (op, xaif.op_buckets(op)) entry for every BUILT-IN op; ops registered
# after the fact need a cell passed via ``autotune(cells=...)`` or they are
# reported (not silently skipped).
#
# Serving note: BOTH engines' decode attention now dispatches through XAIF
# — "attn_decode" is the contiguous slot engine's cached mixer (GQA and
# MLA absorbed decode) and "attn_decode_paged" the paged engine's — and
# MoE archs dispatch their decode FFN through "moe_decode" (the dropless
# per-token path) — so a tuned policy applies to the real serve decode
# path, alongside the row ops (gemm/rmsnorm/entropy rows_s) every
# projection / norm / exit check dispatches through, and "ssm_decode" —
# the Mamba/xLSTM single-token recurrences — so every serve-time mixer
# is now dispatch-tuned.
CELLS: Dict[Tuple[str, str], Callable] = {
    ("gemm", "rows_s"): _gemm_cell(8),
    ("gemm", "rows_m"): _gemm_cell(256),
    ("gemm", "rows_l"): _gemm_cell(2304),
    ("rmsnorm", "rows_s"): _rmsnorm_cell(8),
    ("rmsnorm", "rows_m"): _rmsnorm_cell(256),
    ("rmsnorm", "rows_l"): _rmsnorm_cell(2304),
    ("entropy_exit", "rows_s"): _entropy_cell(8),
    ("entropy_exit", "rows_m"): _entropy_cell(256),
    ("entropy_exit", "rows_l"): _entropy_cell(2304),
    ("attention", "decode"): _attention_cell(1),
    ("attention", "prefill"): _attention_cell(128),
    ("ssm_scan", "decode"): _ssm_cell(1),
    ("ssm_scan", "scan"): _ssm_cell(128),
    ("ssm_decode", "mamba"): _ssm_decode_cell(),
    ("ssm_decode", "mlstm"): _mlstm_decode_cell(),
    ("attn_decode", "kv_s"): _attn_decode_cell(128),
    ("attn_decode", "kv_l"): _attn_decode_cell(2048),
    ("attn_decode_paged", "kv_s"): _paged_attn_cell(8),     # 8*16  = 128 kv
    ("attn_decode_paged", "kv_l"): _paged_attn_cell(128),   # 128*16 = 2048
    ("verify_decode", "kv_s"): _verify_decode_cell(128),
    ("verify_decode", "kv_l"): _verify_decode_cell(2048),
    ("verify_decode_paged", "kv_s"): _verify_paged_cell(8),
    ("verify_decode_paged", "kv_l"): _verify_paged_cell(128),
    ("moe_decode", "e_s"): _moe_decode_cell(8),
    ("moe_decode", "e_l"): _moe_decode_cell(64),
}


def arch_cells(cfg, *, capacity: int = 8, bucket_len: int = 64,
               max_len: int = 256,
               page_size: int = 16) -> Dict[Tuple[str, str], Callable]:
    """Measurement cells at one ARCH's exact serve-time dims.

    The generic ``CELLS`` measure representative shape classes; a tuned
    policy for a specific deployment should measure the row-op / attention
    shapes that arch actually emits at decode (rows = slot capacity, widths
    = d_model/d_ff/vocab, the arch's head layout, its paged-KV extent).
    Returned cells OVERLAY the generic ones for the buckets they land in;
    pass them via ``autotune(arch=cfg)`` and the report records the arch
    as each overlaid cell's source (ROADMAP follow-up from PR 2).
    """
    d, dff, v = cfg.d_model, cfg.d_ff, cfg.vocab_size
    hq, hkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    rows_s = min(capacity, 32)
    rows_m = min(max(bucket_len, 33), 2048)

    def gemm(m, k, n):
        def build(scale):
            return ((jax.random.normal(_key(0), (m, k), jnp.float32),
                     jax.random.normal(_key(1), (k, n), jnp.float32)), {})
        return build

    def rows(m, n):
        def build(scale):
            return ((jax.random.normal(_key(0), (m, n), jnp.float32),
                     jnp.ones((n,), jnp.float32)), {})
        return build

    def entropy(m, n):
        def build(scale):
            return ((jax.random.normal(_key(0), (m, n), jnp.float32),), {})
        return build

    # MLA archs attend a different geometry: prefill runs dqk-wide q/k with
    # a narrower v head, decode attends the latent (one shared head,
    # lora-rank wide, rotary second component, precise fp32)
    dqk = hd if cfg.mla is None else (cfg.mla.qk_nope_head_dim
                                      + cfg.mla.qk_rope_head_dim)
    dv = hd if cfg.mla is None else cfg.mla.v_head_dim
    attn_hkv = hkv if cfg.mla is None else hq

    def attention(t, s):
        def build(scale):
            q = jax.random.normal(_key(0), (capacity, hq, t, dqk), jnp.float32)
            k = jax.random.normal(_key(1), (capacity, attn_hkv, s, dqk),
                                  jnp.float32)
            vv = jax.random.normal(_key(2), (capacity, attn_hkv, s, dv),
                                   jnp.float32)
            return (q, k, vv), {}
        return build

    cells: Dict[Tuple[str, str], Callable] = {
        # decode row ops: every projection / norm / exit check in the decode
        # step runs at [capacity, width]
        ("gemm", "rows_s"): gemm(rows_s, d, dff),
        ("gemm", "rows_m"): gemm(rows_m, d, dff),
        ("rmsnorm", "rows_s"): rows(rows_s, d),
        ("rmsnorm", "rows_m"): rows(rows_m, d),
        ("entropy_exit", "rows_s"): entropy(rows_s, v),
        ("attention", "decode"): attention(1, max_len),
        ("attention", "prefill"): attention(bucket_len, bucket_len),
    }
    np_ = -(-max_len // page_size)
    kv_extent = np_ * page_size
    kv_bucket = "kv_s" if kv_extent <= 1024 else "kv_l"
    if cfg.mla is None:
        cells[("attn_decode_paged", kv_bucket)] = _paged_attn_cell(
            np_, batch=rows_s, hq=hq, hkv=hkv, d=hd, ps=page_size)
        cells[("attn_decode", kv_bucket)] = _attn_decode_cell(
            kv_extent, batch=rows_s, hq=hq, hkv=hkv, d=hd)
        # speculative verify runs the same geometry with a K1 query axis
        # (spec decoding gates to the standard GQA path, so no MLA cell)
        cells[("verify_decode", kv_bucket)] = _verify_decode_cell(
            kv_extent, batch=rows_s, hq=hq, hkv=hkv, d=hd)
        cells[("verify_decode_paged", kv_bucket)] = _verify_paged_cell(
            np_, batch=rows_s, hq=hq, hkv=hkv, d=hd, ps=page_size)
    else:
        cells[("attn_decode_paged", kv_bucket)] = _paged_attn_cell(
            np_, batch=rows_s, hq=hq, hkv=1, d=cfg.mla.kv_lora_rank,
            ps=page_size, mla_rope_dim=cfg.mla.qk_rope_head_dim)
        cells[("attn_decode", kv_bucket)] = _attn_decode_cell(
            kv_extent, batch=rows_s, hq=hq, hkv=1, d=cfg.mla.kv_lora_rank,
            mla_rope_dim=cfg.mla.qk_rope_head_dim)
    if cfg.mamba is not None:
        from repro.models.mamba import _dims
        d_inner, _, n_state = _dims(cfg)
        cells[("ssm_scan", "decode")] = _ssm_cell(
            1, batch=rows_s, din=d_inner, n=n_state)
        cells[("ssm_scan", "scan")] = _ssm_cell(
            bucket_len, batch=1, din=d_inner, n=n_state)
        cells[("ssm_decode", "mamba")] = _ssm_decode_cell(
            batch=rows_s, din=d_inner, n=n_state)
    if getattr(cfg, "xlstm", None) is not None:
        from repro.models.xlstm import _mlstm_dims
        d_in, dh = _mlstm_dims(cfg)
        cells[("ssm_decode", "mlstm")] = _mlstm_decode_cell(
            batch=rows_s, heads=d_in // dh, dh=dh)
    if cfg.moe is not None:
        moe_bucket = "e_s" if cfg.moe.num_experts <= 16 else "e_l"
        cells[("moe_decode", moe_bucket)] = _moe_decode_cell(
            cfg.moe.num_experts, batch=rows_s, k=cfg.moe.top_k,
            d=d, h=cfg.moe.d_expert)
    return cells


def _cost_args(op: str, shapes) -> Optional[tuple]:
    """Map cell argument shapes to the op's cost_fn positional dims."""
    try:
        if op == "gemm":
            (xs, ws) = shapes[0], shapes[1]
            m = 1
            for dim in xs[:-1]:
                m *= dim
            return (m, xs[-1], ws[-1])
        if op in ("rmsnorm", "entropy_exit"):
            xs = shapes[0]
            m = 1
            for dim in xs[:-1]:
                m *= dim
            return (m, xs[-1])
        if op == "attention":
            q, k = shapes[0], shapes[1]
            return (q[0], q[1], q[2], k[2], q[3])
        if op == "attn_decode":
            q, ks = shapes[0], shapes[1]
            return (q[0], q[1], ks[2], q[2])
        if op == "attn_decode_paged":
            q, kp, pt = shapes[0], shapes[1], shapes[3]
            return (q[0], q[1], pt[1], kp[2], q[2])
        if op == "verify_decode":
            q, ks = shapes[0], shapes[1]
            return (q[0], q[1], q[2], ks[2], q[3])
        if op == "verify_decode_paged":
            q, kp, pt = shapes[0], shapes[1], shapes[3]
            return (q[0], q[1], q[2], pt[1], kp[2], q[3])
        if op == "moe_decode":
            xs, ks, wg = shapes[0], shapes[1], shapes[3]
            return (xs[0], ks[1], wg[1], wg[2], wg[0])
        if op == "ssm_scan":
            u, a = shapes[0], shapes[2]
            return (u[0], u[1], u[2], a[-1])
        if op == "ssm_decode":
            xs = shapes[0]
            if len(xs) == 2:                     # mamba: x [B, Din], a [Din, N]
                return (xs[0], xs[1], shapes[2][-1])
            return (xs[0], xs[1] * xs[2], xs[2])  # mlstm: x [B, H, dh]
    except (IndexError, TypeError):
        pass
    return None


# ---------------------------------------------------------------------------
# Measurement
# ---------------------------------------------------------------------------


def _time_call(fn, args, iters: int) -> float:
    """Best-of-``iters`` wall-clock microseconds (after one warmup that also
    pays compilation)."""
    jax.block_until_ready(fn(*args))
    best = float("inf")
    for _ in range(max(iters, 1)):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        best = min(best, time.perf_counter() - t0)
    return best * 1e6


def _tuning_variants(entry: xaif.BackendEntry,
                     tune_block_sizes: bool) -> List[Tuple[Tuple[str, int], ...]]:
    """Tuning configs to try: the backend default, plus (optionally) each
    declared tunable swept one at a time — linear, not cartesian, so the
    sweep stays O(sum of candidates)."""
    variants: List[Tuple[Tuple[str, int], ...]] = [()]
    if tune_block_sizes:
        for name, candidates in entry.tunables:
            for v in candidates:
                variants.append(((name, int(v)),))
    return variants


@dataclass
class CellReport:
    """Every measurement taken for one (op, bucket) cell."""

    op: str
    bucket: str
    # which workload produced this cell: "generic" (the CELLS table), an
    # arch name (autotune(arch=...)), or "custom" (cells= argument)
    source: str = "generic"
    # backend name -> best measured us (inf if it failed / unsupported)
    measured_us: Dict[str, float] = field(default_factory=dict)
    # backend name -> winning tuning tuple for that backend
    best_tuning: Dict[str, Tuple[Tuple[str, int], ...]] = field(
        default_factory=dict)
    skipped: List[str] = field(default_factory=list)
    prior: Optional[Dict[str, float]] = None   # cost_fn output for the cell

    def winner(self) -> Tuple[str, Tuple[Tuple[str, int], ...]]:
        name = min(self.measured_us, key=self.measured_us.get)
        return name, self.best_tuning.get(name, ())

    def us_for(self, backend: str) -> float:
        return self.measured_us.get(backend, float("inf"))


@dataclass
class AutotuneResult:
    policy: xaif.DispatchPolicy
    cells: List[CellReport]
    baseline: AccelConfig

    def persist(self, path: str = DEFAULT_POLICY_PATH) -> str:
        """Write the policy JSON (plus the measurements — including which
        arch produced each cell — which DispatchPolicy.from_json ignores
        on load)."""
        meas = [{"op": c.op, "bucket": c.bucket, "source": c.source,
                 "measured_us": c.measured_us,
                 "skipped": c.skipped, "prior": c.prior}
                for c in self.cells]
        sources = {f"{c.op}/{c.bucket}": c.source for c in self.cells}
        self.policy.save(path, measurements=meas, cell_sources=sources)
        return path


def autotune(ops: Optional[Sequence[str]] = None, *,
             iters: int = 3,
             scale: int = 1,
             tune_block_sizes: bool = False,
             baseline: Optional[AccelConfig] = None,
             default: str = "ref",
             allow_lossy: bool = False,
             arch=None,
             capacity: int = 8,
             max_len: int = 256,
             page_size: int = 16,
             cells: Optional[Dict[Tuple[str, str], Callable]] = None,
             print_fn: Optional[Callable] = None) -> AutotuneResult:
    """Measure every backend per (op, bucket) cell; return the winning
    :class:`~repro.core.xaif.DispatchPolicy` plus the full report.

    Cells come from the built-in ``CELLS`` table (every built-in op), plus
    any ``cells`` mapping {(op, bucket): build(scale) -> (args, kwargs)}
    for ops registered outside this repo; requested ops with no cell are
    reported through ``print_fn`` rather than silently untuned.

    ``arch`` (an ArchConfig) overlays :func:`arch_cells` — the arch's EXACT
    serve-time dims (decode row ops at ``capacity`` rows, its head layout,
    its paged-KV extent at ``max_len``) replace the generic shape classes
    for the buckets they land in, and each cell's report/persisted JSON
    records the arch that produced it.

    ``baseline`` (default: the all-"ref" AccelConfig) names the static
    choice each cell must at least match; its backend is always measured,
    so the winner is never slower than it on any measured cell.

    Backends registered ``lossy=True`` (on-the-fly quantization) are
    excluded unless ``allow_lossy`` — a latency win must never silently
    change model numerics; serve-time quantization stays an explicit
    RunConfig choice (``weight_quant``), not an autotune side effect.
    """
    baseline = baseline if baseline is not None else AccelConfig()
    want = set(ops) if ops else set(xaif.ops())
    say = print_fn or (lambda *_: None)
    all_cells = dict(CELLS)
    sources = {key: "generic" for key in all_cells}
    if arch is not None:
        overlay = arch_cells(arch, capacity=capacity, max_len=max_len,
                             page_size=page_size)
        all_cells.update(overlay)
        sources.update({key: arch.name for key in overlay})
    if cells:
        all_cells.update(cells)
        sources.update({key: "custom" for key in cells})
    uncovered = want - {op for (op, _) in all_cells}
    if uncovered:
        say(f"  WARNING: no measurement cells for ops {sorted(uncovered)} "
            f"— they stay on the policy default; pass cells= to tune them")
    reports: List[CellReport] = []
    rules: Dict[Tuple[str, str], xaif.DispatchRule] = {}

    for (op, bucket), build in all_cells.items():
        if op not in want:
            continue
        args, kwargs = build(scale)
        shapes = tuple(tuple(a.shape) for a in args)
        got = xaif.shape_bucket(op, shapes)
        assert got == bucket, (op, bucket, got, shapes)
        report = CellReport(op, bucket, source=sources[(op, bucket)])

        # the cost prior: estimate the cell's work before running anything,
        # and shrink the timing loop for heavy cells
        entries = xaif.entries_for(op)
        cost_fn = next((e.cost_fn for e in entries if e.cost_fn), None)
        dims = _cost_args(op, shapes)
        if cost_fn is not None and dims is not None:
            report.prior = {k: float(v) for k, v in cost_fn(*dims).items()}
        cell_iters = iters
        if report.prior and report.prior.get("flops", 0) > 1e9:
            cell_iters = max(1, iters // 2)

        must_measure = baseline.backend_for(op)
        for entry in entries:
            if entry.lossy and not allow_lossy and entry.name != must_measure:
                report.skipped.append(entry.name)
                continue
            if not entry.accepts(shapes, None) and entry.name != must_measure:
                report.skipped.append(entry.name)
                continue
            best_us, best_tuning = float("inf"), ()
            for tuning in _tuning_variants(entry, tune_block_sizes):
                kw = dict(tuning)
                kw.update(kwargs)
                if entry.takes_interpret:
                    kw["interpret"] = xaif.pallas_interpret()
                try:
                    fn = jax.jit(lambda *a, _f=entry.fn, _kw=kw: _f(*a, **_kw))
                    us = _time_call(fn, args, cell_iters)
                except Exception as e:      # noqa: BLE001 — backend can't run this cell
                    say(f"  {op}/{bucket} {entry.name}{dict(tuning)}: "
                        f"failed ({type(e).__name__})")
                    continue
                if us < best_us:
                    best_us, best_tuning = us, tuning
            if best_us < float("inf"):
                report.measured_us[entry.name] = best_us
                report.best_tuning[entry.name] = best_tuning
            else:
                report.skipped.append(entry.name)

        if not report.measured_us:
            say(f"  {op}/{bucket}: nothing measurable, cell skipped")
            continue
        name, tuning = report.winner()
        rules[(op, bucket)] = xaif.DispatchRule(name, tuning)
        say(f"  {op}/{bucket}: {name}{dict(tuning) or ''} "
            f"{report.measured_us[name]:.0f}us "
            f"(static {must_measure}: {report.us_for(must_measure):.0f}us)")
        reports.append(report)

    policy = xaif.DispatchPolicy.make(rules, default=default)
    return AutotuneResult(policy=policy, cells=reports, baseline=baseline)

