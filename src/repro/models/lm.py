"""CausalLM assembly: embed → (prefix layers + scanned super-blocks, with
early-exit heads at segment boundaries) → final norm → unembed.

Design notes (all driven by ArchConfig — DESIGN.md C1):

* **Scan over super-blocks.** Layers repeat with period P =
  len(block_pattern) (dense: 1, Jamba: 8, xLSTM: 8). Weights for each slot
  are stacked [num_superblocks, ...] and the stack is consumed by lax.scan,
  so HLO size is O(P), not O(L) — compile time and code size stay flat at
  88 layers (mistral-large). DeepSeek's first_k_dense layers are explicit.
* **Early exits split the scan.** An exit head must sit at a super-block
  boundary; the scanned region is segmented at exit layers and each segment
  is its own scan. Exit heads are RMSNorm + (shared) unembed (CALM-style).
* **Three entry points** share one parameter tree: `forward_train`
  (logits + exit logits + MoE aux), `forward_prefill` (also fills caches),
  `forward_decode` (one token against carried caches/states).
* Mixer/FFN state and cache types are per-slot pytrees stacked like the
  weights, so heterogeneous patterns (attn KV + Mamba SSM states in one
  model) scan uniformly.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig, BlockSpec
from repro.core import xaif
from repro.core.early_exit import apply_exit_head, init_exit_head
from repro.dist.sharding import constrain
from repro.models import attention as attn
from repro.models import mamba as mamba_mod
from repro.models import moe as moe_mod
from repro.models import xlstm as xlstm_mod
from repro.models.layers import (apply_mlp, dense_init, embed_init, init_mlp,
                                 init_rmsnorm, rmsnorm)

# ---------------------------------------------------------------------------
# Per-layer init / apply
# ---------------------------------------------------------------------------


def _init_layer(key, spec: BlockSpec, cfg: ArchConfig, dtype) -> Dict:
    k_mix, k_ffn = jax.random.split(key)
    p: Dict[str, Any] = {"ln1": init_rmsnorm(cfg.d_model)}
    if spec.mixer == "attn":
        p["mixer"] = (attn.init_mla(k_mix, cfg, dtype) if cfg.mla is not None
                      else attn.init_attention(k_mix, cfg, dtype))
    elif spec.mixer == "mamba":
        p["mixer"] = mamba_mod.init_mamba(k_mix, cfg, dtype)
    elif spec.mixer == "mlstm":
        p["mixer"] = xlstm_mod.init_mlstm(k_mix, cfg, dtype)
    elif spec.mixer == "slstm":
        p["mixer"] = xlstm_mod.init_slstm(k_mix, cfg, dtype)
    else:
        raise ValueError(spec.mixer)
    if spec.ffn != "none":
        p["ln2"] = init_rmsnorm(cfg.d_model)
        p["ffn"] = (moe_mod.init_moe(k_ffn, cfg, dtype) if spec.ffn == "moe"
                    else init_mlp(k_ffn, cfg.d_model, cfg.d_ff, dtype))
    return p


def _apply_layer(p, x, spec: BlockSpec, cfg: ArchConfig, policy: xaif.PolicyLike,
                 state=None, mode: str = "train", cache_pos=None,
                 page_table=None, live=None):
    """Returns (x, aux_loss, new_state). ``page_table`` [B, NP] routes
    attention decode through the paged path (state is a Paged*Cache).
    ``live`` [B] bool (decode only): slots that still matter — dead/retired
    slots are masked out of MoE routing so their stale hidden states can't
    consume expert capacity or skew the aux-loss counts."""
    h = rmsnorm(p["ln1"], x, policy, cfg.norm_eps)
    new_state = None
    if mode == "verify" and (spec.mixer != "attn" or cfg.mla is not None):
        raise ValueError(
            "speculative verify requires a non-MLA all-attention arch "
            f"(got mixer={spec.mixer!r}, mla={cfg.mla is not None})")
    if spec.mixer == "attn":
        if cfg.mla is not None:
            if mode == "decode":
                if isinstance(state, attn.PagedMLACache):
                    out, new_state = attn.apply_mla_decode_paged(
                        p["mixer"], h, cfg, policy, state, cache_pos,
                        page_table)
                else:
                    out, new_state = attn.apply_mla_decode(
                        p["mixer"], h, cfg, policy, state, cache_pos)
            else:
                out, new_state = attn.apply_mla(p["mixer"], h, cfg, policy,
                                                cache=state)
        else:
            if mode == "verify":
                if isinstance(state, attn.PagedKVCache):
                    out, new_state = attn.apply_attention_verify_paged(
                        p["mixer"], h, cfg, policy, state, cache_pos,
                        page_table)
                else:
                    out, new_state = attn.apply_attention_verify(
                        p["mixer"], h, cfg, policy, state, cache_pos)
            elif mode == "decode":
                if isinstance(state, attn.PagedKVCache):
                    out, new_state = attn.apply_attention_decode_paged(
                        p["mixer"], h, cfg, policy, state, cache_pos,
                        page_table)
                else:
                    out, new_state = attn.apply_attention_decode(
                        p["mixer"], h, cfg, policy, state, cache_pos)
            elif mode == "prefill":
                out, new_state = attn.apply_attention_prefill(
                    p["mixer"], h, cfg, policy, state)
            elif mode == "prefill_shared":
                # fork-point suffix prefill against shared paged prefix KV;
                # ``page_table`` carries the SharedPrefillCtx here
                out, new_state = attn.apply_attention_prefill_shared(
                    p["mixer"], h, cfg, policy, state, page_table)
            else:
                out = attn.apply_attention(p["mixer"], h, cfg, policy)
    elif spec.mixer == "mamba":
        fn = (mamba_mod.apply_mamba_decode if mode == "decode"
              else mamba_mod.apply_mamba)
        out, new_state = fn(p["mixer"], h, cfg, policy, state)
    elif spec.mixer == "mlstm":
        fn = (xlstm_mod.apply_mlstm_decode if mode == "decode"
              else xlstm_mod.apply_mlstm)
        out, new_state = fn(p["mixer"], h, cfg, policy, state)
    elif spec.mixer == "slstm":
        fn = (xlstm_mod.apply_slstm_decode if mode == "decode"
              else xlstm_mod.apply_slstm)
        out, new_state = fn(p["mixer"], h, cfg, policy, state)
    else:
        raise ValueError(spec.mixer)
    x = x + out
    aux = jnp.zeros((), jnp.float32)
    if spec.ffn != "none":
        h2 = rmsnorm(p["ln2"], x, policy, cfg.norm_eps)
        if spec.ffn == "moe":
            if mode == "decode" and cfg.moe.dropless_decode:
                # DROPLESS decode: per-token dispatch through the
                # ``moe_decode`` XAIF op — no capacity constant, no drops,
                # so a slot's tokens never depend on its co-batch (the
                # serve engine's composition-independence contract)
                out2, aux = moe_mod.apply_moe_decode(p["ffn"], h2, cfg,
                                                     policy, valid=live)
            else:
                groups = 1 if h2.shape[1] == 1 else None
                v2 = None if live is None else live[:, None]
                out2, aux = moe_mod.apply_moe(p["ffn"], h2, cfg, policy,
                                              groups, valid=v2)
        else:
            out2 = apply_mlp(p["ffn"], h2, policy)
        x = x + out2
    # residual stream: batch over data axes, sequence-parallel over the
    # model axis when enabled (shards the saved scan carries — the remat
    # residuals — 16x; GSPMD inserts the Megatron-SP gather/scatter pair).
    # Verify keeps the decode-style constraint: its K1 axis is a handful of
    # draft tokens, not a shardable sequence.
    sp = "sp" if (x.shape[1] > 1 and mode != "verify") else None
    x = constrain(x, "batch", sp, None)
    return x, aux, new_state


# ---------------------------------------------------------------------------
# Model init
# ---------------------------------------------------------------------------


def init_lm(key, cfg: ArchConfig) -> Dict:
    """Random parameters for ``cfg`` from ``key``. Each stacked slot weight
    is drawn layer by layer (``lax.map``), so only one layer's float32 draw
    is alive at a time: a vmapped draw held a float32 temporary the size of
    the whole stack (6.3 GB for chatglm3-6b's FFN), which does not fit
    beside the bf16 weights on one 16 GB chip. Callers may wrap this in
    ``jax.jit`` (e.g. with ``out_shardings``, see ``SlotEngine.init_params``)
    to build each device's shard in place."""
    dtype = jnp.dtype(cfg.dtype)
    keys = jax.random.split(key, 8)
    params: Dict[str, Any] = {
        "embed": embed_init(keys[0], cfg.vocab_size, cfg.d_model, dtype),
        "final_norm": init_rmsnorm(cfg.d_model),
        "unembed": dense_init(keys[1], cfg.d_model, cfg.vocab_size, dtype),
    }
    # explicit prefix layers
    if cfg.first_k_dense:
        pkeys = jax.random.split(keys[2], cfg.first_k_dense)
        params["prefix"] = [
            _init_layer(pkeys[i], cfg.layer_spec(i), cfg, dtype)
            for i in range(cfg.first_k_dense)
        ]
    # scanned slots: stacked over num_superblocks, one layer at a time
    n_sb = cfg.num_superblocks
    slots = []
    for j, spec in enumerate(cfg.block_pattern):
        slot_keys = jax.random.split(jax.random.fold_in(keys[3], j), n_sb)
        slots.append(jax.lax.map(
            lambda k, spec=spec: _init_layer(k, spec, cfg, dtype), slot_keys))
    params["slots"] = tuple(slots)
    # early-exit heads
    if cfg.early_exit is not None:
        ekeys = jax.random.split(keys[4], len(cfg.early_exit.exit_layers))
        params["exits"] = tuple(
            init_exit_head(ekeys[i], cfg.d_model, cfg.vocab_size,
                           cfg.early_exit.share_unembed, dtype)
            for i in range(len(cfg.early_exit.exit_layers)))
    return params


# ---------------------------------------------------------------------------
# Segment planning: exit layers split the scanned region
# ---------------------------------------------------------------------------


def _segments(cfg: ArchConfig) -> List[Tuple[int, int, Optional[int]]]:
    """[(sb_start, sb_end, exit_index_or_None), ...] over super-blocks."""
    n_sb = cfg.num_superblocks
    exits = []
    if cfg.early_exit is not None:
        for i, el in enumerate(cfg.early_exit.exit_layers):
            sb = (el - cfg.first_k_dense) // cfg.period
            assert 0 < sb <= n_sb and (el - cfg.first_k_dense) % cfg.period == 0, (
                f"{cfg.name}: exit layer {el} not on a super-block boundary "
                f"(first_k_dense={cfg.first_k_dense}, period={cfg.period})")
            exits.append((sb, i))
    segs: List[Tuple[int, int, Optional[int]]] = []
    prev = 0
    for sb, i in sorted(exits):
        if sb > prev:
            segs.append((prev, sb, i))
            prev = sb
        else:  # exit exactly at prev boundary (e.g. after prefix)
            segs.append((prev, prev, i))
    if prev < n_sb or not segs:
        segs.append((prev, n_sb, None))
    return segs


def _remat_wrap(fn, remat: str):
    if remat == "full":
        return jax.checkpoint(fn)
    if remat == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.checkpoint_dots)
    return fn


def _layer_at(stacked, i):
    """Super-block ``i`` of a stacked pytree (traced index, no copy of the
    rest of the stack)."""
    return jax.tree_util.tree_map(
        lambda a: jax.lax.dynamic_index_in_dim(a, i, keepdims=False), stacked)


def _set_layer(stacked, i, layer):
    return jax.tree_util.tree_map(
        lambda full, new: jax.lax.dynamic_update_index_in_dim(
            full, new.astype(full.dtype), i, 0), stacked, layer)


def _scan_segment(slots, x, sb_start, sb_end, cfg, policy, remat="nothing",
                  mode="train", states=None, cache_pos=None, page_table=None,
                  live=None):
    """Run super-blocks [sb_start, sb_end). Returns (x, aux, new_states).

    The loop indexes layer i of the FULL stacked weights (and states, which
    are updated in place: ``new_states`` is the full stack with the
    segment's layers rewritten). Slicing a segment out of the stack copied
    its weights on every call instead — 2.2 GB per FFN matrix for
    chatglm3-6b's layers 7-28 behind the exit head, which does not fit on a
    16 GB chip beside the model.
    """
    if sb_end == sb_start:
        return x, jnp.zeros((), jnp.float32), states

    def body(carry, i):
        x, aux, st = carry
        slot_params = _layer_at(slots, i)
        slot_states = None if st is None else _layer_at(st, i)
        new_states = []
        for j, spec in enumerate(cfg.block_pattern):
            x, a, ns = _apply_layer(
                slot_params[j], x, spec, cfg, policy,
                state=None if slot_states is None else slot_states[j],
                mode=mode, cache_pos=cache_pos, page_table=page_table,
                live=live)
            aux = aux + a
            new_states.append(ns)
        if st is not None:
            st = _set_layer(st, i, tuple(new_states))
        return (x, aux, st), None

    body = _remat_wrap(body, remat if mode == "train" else "nothing")
    (x, aux, states), _ = jax.lax.scan(
        body, (x, jnp.zeros((), jnp.float32), states),
        jnp.arange(sb_start, sb_end))
    return x, aux, states


# ---------------------------------------------------------------------------
# Embedding / head
# ---------------------------------------------------------------------------


def _embed(params, inputs, cfg: ArchConfig):
    """inputs: int tokens [B, T] or (frontend_stub) embeddings [B, T, d]."""
    if jnp.issubdtype(inputs.dtype, jnp.floating):
        assert cfg.frontend_stub and inputs.ndim == 3
        x = inputs.astype(jnp.dtype(cfg.dtype))
    else:
        x = jnp.take(params["embed"], inputs, axis=0)
    return constrain(x, "batch", None, None)


def _head(params, x, cfg: ArchConfig, policy: xaif.PolicyLike):
    h = rmsnorm(params["final_norm"], x, policy, cfg.norm_eps)
    logits = xaif.call("gemm", policy, h, params["unembed"])
    return constrain(logits, "batch", None, "tp")


def _exit_logits(params, x, i, cfg, policy):
    return constrain(
        apply_exit_head(params["exits"][i], x, params["unembed"], policy,
                        cfg.norm_eps),
        "batch", None, "tp")


# ---------------------------------------------------------------------------
# Forward passes
# ---------------------------------------------------------------------------


def forward_train(params, inputs, cfg: ArchConfig, policy: xaif.PolicyLike,
                  remat: str = "nothing"):
    """-> (final_logits, exit_logits tuple, aux dict)."""
    x = _embed(params, inputs, cfg)
    aux_total = jnp.zeros((), jnp.float32)
    exit_lg: List[jax.Array] = []
    exit_points = {}
    if cfg.early_exit is not None:
        exit_points = {el: i for i, el in enumerate(cfg.early_exit.exit_layers)}
    for i in range(cfg.first_k_dense):
        x, a, _ = _apply_layer(params["prefix"][i], x, cfg.layer_spec(i), cfg,
                               policy, mode="train")
        aux_total = aux_total + a
        if (i + 1) in exit_points:
            exit_lg.append(_exit_logits(params, x, exit_points[i + 1], cfg, policy))
    for sb_start, sb_end, exit_i in _segments(cfg):
        x, a, _ = _scan_segment(params["slots"], x, sb_start, sb_end, cfg,
                                policy, remat, mode="train")
        aux_total = aux_total + a
        if exit_i is not None:
            exit_lg.append(_exit_logits(params, x, exit_i, cfg, policy))
    logits = _head(params, x, cfg, policy)
    return logits, tuple(exit_lg), {"aux_loss": aux_total}


def forward_train_hidden(params, inputs, cfg: ArchConfig, policy: xaif.PolicyLike,
                         remat: str = "nothing"):
    """Like forward_train but returns the PRE-HEAD hidden states instead of
    logits: (x [B,T,d], exit_hiddens tuple, aux). Used by the chunked
    head+loss path (train_step.chunked_head_loss) so the [B,T,V] logits are
    never materialized."""
    x = _embed(params, inputs, cfg)
    aux_total = jnp.zeros((), jnp.float32)
    exit_hidden: List[jax.Array] = []
    exit_points = {}
    if cfg.early_exit is not None:
        exit_points = {el: i for i, el in enumerate(cfg.early_exit.exit_layers)}
    for i in range(cfg.first_k_dense):
        x, a, _ = _apply_layer(params["prefix"][i], x, cfg.layer_spec(i), cfg,
                               policy, mode="train")
        aux_total = aux_total + a
        if (i + 1) in exit_points:
            exit_hidden.append(x)
    for sb_start, sb_end, exit_i in _segments(cfg):
        x, a, _ = _scan_segment(params["slots"], x, sb_start, sb_end, cfg,
                                policy, remat, mode="train")
        aux_total = aux_total + a
        if exit_i is not None:
            exit_hidden.append(x)
    return x, (tuple(exit_hidden) if exit_hidden else None), \
        {"aux_loss": aux_total}


# ----- caches ----------------------------------------------------------------


class LMCache(NamedTuple):
    prefix: Tuple            # per prefix layer state (or None)
    slots: Tuple             # per slot: stacked [n_sb, ...] states
    pos: jax.Array           # [B] int32 current lengths


def _init_layer_state(spec: BlockSpec, cfg: ArchConfig, batch: int,
                      max_len: int, dtype):
    if spec.mixer == "attn":
        if cfg.mla is not None:
            return attn.init_mla_cache(cfg, batch, max_len, dtype)
        return attn.init_kv_cache(cfg, batch, max_len, dtype)
    if spec.mixer == "mamba":
        return mamba_mod.init_mamba_state(cfg, batch, dtype)
    if spec.mixer == "mlstm":
        return xlstm_mod.init_mlstm_state(cfg, batch, dtype)
    if spec.mixer == "slstm":
        return xlstm_mod.init_slstm_state(cfg, batch, dtype)
    raise ValueError(spec.mixer)


def init_cache(cfg: ArchConfig, batch: int, max_len: int) -> LMCache:
    dtype = jnp.dtype(cfg.dtype)
    prefix = tuple(
        _init_layer_state(cfg.layer_spec(i), cfg, batch, max_len, dtype)
        for i in range(cfg.first_k_dense))
    n_sb = cfg.num_superblocks
    slots = []
    for spec in cfg.block_pattern:
        one = _init_layer_state(spec, cfg, batch, max_len, dtype)
        slots.append(jax.tree_util.tree_map(
            lambda a: jnp.broadcast_to(a[None], (n_sb, *a.shape)).copy(), one))
    return LMCache(prefix=prefix, slots=tuple(slots),
                   pos=jnp.zeros((batch,), jnp.int32))


# ----- slot-indexed cache API (continuous-batching serve engine) ------------
#
# The serve scheduler treats the cache's batch dimension as SLOTS: a request
# is admitted by prefilling a batch-1 cache and writing it into a free slot
# row; retirement zeroes the row and the slot is backfilled by the next
# request. Prompt-length and batch-occupancy variation become slot STATE
# (per-slot ``pos`` lengths), never trace shape — the decode step is jitted
# once for the full capacity.


def _state_fill(state, src, slot, axis):
    if isinstance(state, (attn.KVCache, attn.MLACache)):
        return attn.fill_slot(state, src, slot, axis)
    if isinstance(state, mamba_mod.MambaState):
        return mamba_mod.fill_slot(state, src, slot, axis)
    from repro.models.layers import cache_write_row   # xLSTM et al.
    return jax.tree_util.tree_map(
        lambda d, s: cache_write_row(d, s, slot, axis), state, src)


def _state_reset(state, slot, axis):
    if isinstance(state, (attn.KVCache, attn.MLACache)):
        return attn.reset_slot(state, slot, axis)
    if isinstance(state, mamba_mod.MambaState):
        return mamba_mod.reset_slot(state, slot, axis)
    from repro.models.layers import cache_zero_row
    return jax.tree_util.tree_map(
        lambda d: cache_zero_row(d, slot, axis), state)


def fill_slot(cache: LMCache, src: LMCache, slot, length) -> LMCache:
    """Insert a batch-1 prefilled ``src`` cache into row ``slot``.

    ``length`` is the request's TRUE prompt length (≤ the src cache's
    sequence capacity when prompts are bucket-padded); it becomes the
    per-slot position so decode masks exactly the valid prefix.
    """
    new_prefix = tuple(_state_fill(c, s, slot, axis=0)
                       for c, s in zip(cache.prefix, src.prefix))
    new_slots = tuple(_state_fill(c, s, slot, axis=1)
                      for c, s in zip(cache.slots, src.slots))
    return LMCache(new_prefix, new_slots,
                   cache.pos.at[slot].set(jnp.asarray(length, jnp.int32)))


def reset_slot(cache: LMCache, slot) -> LMCache:
    """Retire row ``slot``: zero its states and length."""
    new_prefix = tuple(_state_reset(c, slot, axis=0) for c in cache.prefix)
    new_slots = tuple(_state_reset(c, slot, axis=1) for c in cache.slots)
    return LMCache(new_prefix, new_slots, cache.pos.at[slot].set(0))


def slot_lengths(cache: LMCache) -> jax.Array:
    """Per-slot current lengths [B] (prompt + generated so far)."""
    return cache.pos


# ----- paged cache API (paged KV serve engine) -------------------------------
#
# Attention KV moves from per-slot contiguous [B, ..., max_len, ...] rows to
# fixed-size PAGES: each attention layer owns a pool ([P, Hkv, ps, D] /
# [P, ps, lora]) and one [capacity, max_pages] page table (shared by all
# layers — every layer of a sequence uses the same logical page ids) maps
# slot-local page index j to the pool page holding positions
# [j*ps, (j+1)*ps). Page 0 is a reserved scratch page (dead-slot writes).
# Recurrent mixer states (Mamba conv/ssm, xLSTM) are O(1) per slot and stay
# slot-indexed. The host owns allocation (serve/paging.py): the table is
# DATA to the jitted decode step, so page churn never re-traces.


class PagedLMCache(NamedTuple):
    prefix: Tuple            # per prefix layer state (paged for attn)
    slots: Tuple             # per slot: stacked [n_sb, ...] states
    pos: jax.Array           # [B] int32 current lengths
    page_table: jax.Array    # [B, max_pages] int32; -1 = unallocated


def _init_layer_state_paged(spec: BlockSpec, cfg: ArchConfig, batch: int,
                            num_pages: int, page_size: int, dtype):
    if spec.mixer == "attn":
        if cfg.mla is not None:
            return attn.init_paged_mla_cache(cfg, num_pages, page_size, dtype)
        return attn.init_paged_kv_cache(cfg, num_pages, page_size, dtype)
    return _init_layer_state(spec, cfg, batch, 0, dtype)


def init_paged_cache(cfg: ArchConfig, batch: int, max_len: int,
                     page_size: int, num_pages: int) -> PagedLMCache:
    dtype = jnp.dtype(cfg.dtype)
    max_pages = -(-max_len // page_size)
    prefix = tuple(
        _init_layer_state_paged(cfg.layer_spec(i), cfg, batch, num_pages,
                                page_size, dtype)
        for i in range(cfg.first_k_dense))
    n_sb = cfg.num_superblocks
    slots = []
    for spec in cfg.block_pattern:
        one = _init_layer_state_paged(spec, cfg, batch, num_pages,
                                      page_size, dtype)
        slots.append(jax.tree_util.tree_map(
            lambda a: jnp.broadcast_to(a[None], (n_sb, *a.shape)).copy(), one))
    return PagedLMCache(
        prefix=prefix, slots=tuple(slots),
        pos=jnp.zeros((batch,), jnp.int32),
        page_table=jnp.full((batch, max_pages), -1, jnp.int32))


def _state_fill_paged(state, src, slot, page_ids, stacked: bool):
    if isinstance(state, (attn.PagedKVCache, attn.PagedMLACache)):
        return attn.fill_pages(state, src, page_ids, stacked)
    return _state_fill(state, src, slot, axis=1 if stacked else 0)


def fill_slot_paged(cache: PagedLMCache, src: LMCache, slot, length,
                    page_ids: jax.Array) -> PagedLMCache:
    """Admit a batch-1 contiguous prefill into row ``slot``: attention KV is
    scattered into the host-allocated ``page_ids`` (one per bucket page, in
    position order), recurrent states land in the slot row as before. The
    slot's page-table row is rewritten to exactly these pages."""
    n_pages = page_ids.shape[0]
    new_prefix = tuple(
        _state_fill_paged(c, s, slot, page_ids, stacked=False)
        for c, s in zip(cache.prefix, src.prefix))
    new_slots = tuple(
        _state_fill_paged(c, s, slot, page_ids, stacked=True)
        for c, s in zip(cache.slots, src.slots))
    row = jnp.full((cache.page_table.shape[1],), -1,
                   jnp.int32).at[:n_pages].set(page_ids.astype(jnp.int32))
    return PagedLMCache(
        new_prefix, new_slots,
        cache.pos.at[slot].set(jnp.asarray(length, jnp.int32)),
        cache.page_table.at[slot].set(row))


def free_slot_paged(cache: PagedLMCache, slot) -> PagedLMCache:
    """Retire row ``slot``: zero its length, recurrent state and page-table
    row. Pool pages keep their bytes — junk is masked at read time by the
    per-page validity test, so no zeroing pass is needed on reuse."""
    def reset_recurrent(state, stacked):
        if isinstance(state, (attn.PagedKVCache, attn.PagedMLACache)):
            return state
        return _state_reset(state, slot, axis=1 if stacked else 0)

    new_prefix = tuple(reset_recurrent(c, False) for c in cache.prefix)
    new_slots = tuple(reset_recurrent(c, True) for c in cache.slots)
    return PagedLMCache(
        new_prefix, new_slots, cache.pos.at[slot].set(0),
        cache.page_table.at[slot].set(
            jnp.full((cache.page_table.shape[1],), -1, jnp.int32)))


def forward_prefill(params, inputs, cfg: ArchConfig, policy: xaif.PolicyLike,
                    cache: LMCache, lengths: Optional[jax.Array] = None):
    """Full-sequence prefill filling caches; returns (last_logits, cache).

    ``lengths`` [B]: optional per-sequence TRUE lengths for right-padded
    inputs — logits are gathered at each sequence's last real token and the
    cache records the true length, so one trace serves a whole
    prompt-length bucket. Without it, every position is real (seed
    behavior).
    """
    x = _embed(params, inputs, cfg)
    b, t = x.shape[0], x.shape[1]
    new_prefix = []
    for i in range(cfg.first_k_dense):
        x, _, ns = _apply_layer(params["prefix"][i], x, cfg.layer_spec(i), cfg,
                                policy, state=cache.prefix[i], mode="prefill")
        new_prefix.append(ns)
    x, _, new_slots = _scan_segment(params["slots"], x, 0,
                                    cfg.num_superblocks, cfg, policy,
                                    mode="prefill", states=cache.slots)
    if lengths is None:
        last = x[:, -1:, :]
        pos = jnp.full_like(cache.pos, t)
    else:
        last = jnp.take_along_axis(
            x, (lengths - 1).astype(jnp.int32)[:, None, None], axis=1)
        pos = lengths.astype(jnp.int32)
    logits = _head(params, last, cfg, policy)
    return logits[:, 0], LMCache(tuple(new_prefix), tuple(new_slots), pos)


def copy_pages(cache: PagedLMCache, src, dst) -> PagedLMCache:
    """Copy-on-write: duplicate pool page ``src`` into ``dst`` across every
    attention layer (prefix + stacked slots). The boundary page of a
    partial prefix match is copied here so the divergent suffix prefill
    never writes a page another slot still maps."""
    new_prefix = tuple(attn.copy_page(c, src, dst) for c in cache.prefix)
    new_slots = tuple(attn.copy_page(c, src, dst, stacked=True)
                      for c in cache.slots)
    return cache._replace(prefix=new_prefix, slots=new_slots)


def gather_pages(cache: PagedLMCache, page_ids):
    """Gather pool pages ``page_ids`` out of every attention layer (prefix
    + stacked slots) — the device side of a host SWAP-OUT. Returns a
    pytree of page blocks, position-ordered along a leading page axis
    (stacked slot layers keep their superblock axis first); recurrent
    layers contribute None (their state is slot-indexed, not paged)."""
    def g(state, stacked):
        if isinstance(state, (attn.PagedKVCache, attn.PagedMLACache)):
            if stacked:
                return type(state)(*(a[:, page_ids] for a in state))
            return type(state)(*(a[page_ids] for a in state))
        return None
    return (tuple(g(c, False) for c in cache.prefix),
            tuple(g(c, True) for c in cache.slots))


def scatter_pages(cache: PagedLMCache, page_ids, blocks) -> PagedLMCache:
    """Write swapped-out page ``blocks`` (from :func:`gather_pages`) into
    pool pages ``page_ids`` — the device side of a SWAP-IN. The ids need
    not match the ids the blocks were gathered from: the resumed slot maps
    fresh pages in the same position order, so the attended bytes are
    identical. Pad ids may repeat the scratch page 0 (never validly
    read)."""
    pre_b, slo_b = blocks

    def s(state, blk, stacked):
        if isinstance(state, (attn.PagedKVCache, attn.PagedMLACache)):
            if stacked:
                return type(state)(*(a.at[:, page_ids].set(b)
                                     for a, b in zip(state, blk)))
            return type(state)(*(a.at[page_ids].set(b)
                                 for a, b in zip(state, blk)))
        return state
    return cache._replace(
        prefix=tuple(s(c, b, False)
                     for c, b in zip(cache.prefix, pre_b)),
        slots=tuple(s(c, b, True) for c, b in zip(cache.slots, slo_b)))


def forward_prefill_shared(params, inputs, cfg: ArchConfig,
                           policy: xaif.PolicyLike, cache: PagedLMCache,
                           slot, ctx: attn.SharedPrefillCtx, row_ids,
                           head: bool = True):
    """Fork-point prefill: run ONLY the unshared suffix of a prompt whose
    prefix KV is already resident in the page pools.

    ``inputs`` [1, Tsuf_bucket] holds the right-padded suffix tokens;
    ``ctx`` the shared/region page ids and absolute positions; ``row_ids``
    [max_pages] the slot's complete new page-table row (prefix ++ region,
    -1 beyond). Requires an all-attention, non-MLA arch (recurrent mixer
    states cannot resume from a page chain). Returns (first-token logits
    [1, V], cache with the slot admitted at length ``ctx.true_len``).

    ``head=False`` (chunked prefill's intermediate chunks): skip the LM
    head — only the KV writes matter — and return ``(None, cache)``."""
    x = _embed(params, inputs, cfg)
    new_prefix = []
    for i in range(cfg.first_k_dense):
        x, _, ns = _apply_layer(params["prefix"][i], x, cfg.layer_spec(i),
                                cfg, policy, state=cache.prefix[i],
                                mode="prefill_shared", page_table=ctx)
        new_prefix.append(ns)
    x, _, new_slots = _scan_segment(params["slots"], x, 0,
                                    cfg.num_superblocks, cfg, policy,
                                    mode="prefill_shared", states=cache.slots,
                                    page_table=ctx)
    new_cache = PagedLMCache(
        tuple(new_prefix), new_slots,
        cache.pos.at[slot].set(ctx.true_len.astype(jnp.int32)),
        cache.page_table.at[slot].set(jnp.asarray(row_ids, jnp.int32)))
    if not head:
        return None, new_cache
    tsuf_true = ctx.true_len - ctx.start
    last = jnp.take_along_axis(
        x, jnp.reshape(tsuf_true - 1, (1, 1, 1)).astype(jnp.int32), axis=1)
    logits = _head(params, last, cfg, policy)
    return logits[:, 0], new_cache


def forward_decode(params, tokens, cfg: ArchConfig, policy: xaif.PolicyLike,
                   cache, with_exits: bool = True, live=None):
    """One decode step. tokens [B, 1] (or [B, 1, d] embeddings).

    ``cache`` is an LMCache (contiguous per-slot KV) or a PagedLMCache
    (page-pool KV attended via the page table — same numerics, page-granular
    memory). ``live`` [B] bool (optional): the serve engine's occupied,
    not-done slots — dead slots are masked out of MoE routing. On the
    default DROPLESS decode path masking can never change a live slot's
    output (no state is shared across tokens); with
    ``MoEConfig.dropless_decode=False`` the grouped path shares one
    capacity group, so masking frees capacity dead slots were stealing —
    live outputs there depend on the mask by design.
    Returns (final_logits [B, V], exit_logits tuple, new_cache).
    """
    paged = isinstance(cache, PagedLMCache)
    page_table = cache.page_table if paged else None
    x = _embed(params, tokens, cfg)
    cache_pos = cache.pos
    exit_lg: List[jax.Array] = []
    exit_points = {}
    if with_exits and cfg.early_exit is not None:
        exit_points = {el: i for i, el in enumerate(cfg.early_exit.exit_layers)}
    new_prefix = []
    for i in range(cfg.first_k_dense):
        x, _, ns = _apply_layer(params["prefix"][i], x, cfg.layer_spec(i), cfg,
                                policy, state=cache.prefix[i], mode="decode",
                                cache_pos=cache_pos, page_table=page_table,
                                live=live)
        new_prefix.append(ns)
        if (i + 1) in exit_points:
            exit_lg.append(_exit_logits(params, x, exit_points[i + 1], cfg,
                                        policy)[:, 0])
    new_slots = cache.slots
    for sb_start, sb_end, exit_i in _segments(cfg):
        x, _, new_slots = _scan_segment(
            params["slots"], x, sb_start, sb_end, cfg, policy, mode="decode",
            states=new_slots, cache_pos=cache_pos, page_table=page_table,
            live=live)
        if exit_i is not None and (with_exits and cfg.early_exit is not None):
            exit_lg.append(_exit_logits(params, x, exit_i, cfg, policy)[:, 0])
    logits = _head(params, x, cfg, policy)[:, 0]
    if paged:
        new_cache = PagedLMCache(tuple(new_prefix), new_slots, cache.pos + 1,
                                 cache.page_table)
    else:
        new_cache = LMCache(tuple(new_prefix), new_slots, cache.pos + 1)
    return logits, tuple(exit_lg), new_cache


def forward_verify(params, tokens, cfg: ArchConfig, policy: xaif.PolicyLike,
                   cache, live=None):
    """Speculative-decode verification: score K1 = k+1 tokens per slot (the
    previous token plus k draft proposals) in ONE forward. tokens [B, K1].

    Every layer runs the multi-token verify attention (all K1 KV rows
    written at ``pos + i``, each query masked to its own staircase window),
    so logits row i is bitwise what the i-th sequential ``forward_decode``
    step would have produced — the greedy acceptance rule in the engine
    compares draft proposals against these rows directly.

    Returns (logits [B, K1, V], new_cache). ``new_cache.pos`` is UNCHANGED:
    the caller advances it by the realized accept count (rows past the
    accepted prefix hold KV for rejected tokens; they are rewritten by the
    next round before their positions can become valid). Requires an
    all-attention, non-MLA arch; early exits are not consulted (speculation
    already amortizes the full depth).
    """
    paged = isinstance(cache, PagedLMCache)
    page_table = cache.page_table if paged else None
    x = _embed(params, tokens, cfg)
    cache_pos = cache.pos
    new_prefix = []
    for i in range(cfg.first_k_dense):
        x, _, ns = _apply_layer(params["prefix"][i], x, cfg.layer_spec(i), cfg,
                                policy, state=cache.prefix[i], mode="verify",
                                cache_pos=cache_pos, page_table=page_table,
                                live=live)
        new_prefix.append(ns)
    new_slots = cache.slots
    for sb_start, sb_end, _exit_i in _segments(cfg):
        x, _, new_slots = _scan_segment(
            params["slots"], x, sb_start, sb_end, cfg, policy, mode="verify",
            states=new_slots, cache_pos=cache_pos, page_table=page_table,
            live=live)
    logits = _head(params, x, cfg, policy)                   # [B, K1, V]
    if paged:
        new_cache = PagedLMCache(tuple(new_prefix), new_slots, cache.pos,
                                 cache.page_table)
    else:
        new_cache = LMCache(tuple(new_prefix), new_slots, cache.pos)
    return logits, new_cache


def _kv_propagate_layer(p, x_exit, cfg: ArchConfig, policy, state, cache_pos):
    """CALM state propagation: fill a skipped attention layer's KV cache from
    the exit hidden state (wk/wv or latent projections only — no scores, no
    values-weighted sum, no FFN). This is the decode-side power gating
    (DESIGN.md C3): ~2 of ~8 GEMMs per skipped layer."""
    b = x_exit.shape[0]
    h = rmsnorm(p["ln1"], x_exit, policy, cfg.norm_eps)
    bidx = jnp.arange(b)
    if cfg.mla is not None:
        c_new, kr_new = attn._mla_latent(p["mixer"], h, cfg, policy,
                                         cache_pos[:, None])
        return attn.MLACache(
            state.c_kv.at[bidx, cache_pos, :].set(
                c_new[:, 0].astype(state.c_kv.dtype)),
            state.k_rope.at[bidx, cache_pos, :].set(
                kr_new[:, 0].astype(state.k_rope.dtype)))
    hkv, dh = cfg.num_kv_heads, cfg.head_dim
    mp = p["mixer"]
    k = xaif.call("gemm", policy, h, mp["wk"], bias=mp.get("bk"))
    v = xaif.call("gemm", policy, h, mp["wv"], bias=mp.get("bv"))
    k = k.reshape(b, 1, hkv, dh).transpose(0, 2, 1, 3)
    v = v.reshape(b, 1, hkv, dh).transpose(0, 2, 1, 3)
    if cfg.qk_norm:
        k = rmsnorm(mp["k_norm"], k, policy, cfg.norm_eps)
    from repro.models.layers import apply_rope, rope_dims
    rd = rope_dims(cfg)
    if rd != 0:
        k = apply_rope(k, cache_pos[:, None], cfg.rope_theta, rd)
    return attn.KVCache(
        state.k.at[bidx, :, cache_pos, :].set(k[:, :, 0, :].astype(state.k.dtype)),
        state.v.at[bidx, :, cache_pos, :].set(v[:, :, 0, :].astype(state.v.dtype)))


def forward_decode_gated(params, tokens, cfg: ArchConfig, policy: xaif.PolicyLike,
                         cache: LMCache, live: Optional[jax.Array] = None):
    """Early-exit decode with REAL compute gating (attention-only archs).

    Runs layers up to the (single) exit head, takes the entropy decision,
    and — when every LIVE sequence in the batch is confident — skips the
    remaining layers entirely via lax.cond, filling their KV caches by CALM
    state propagation so later steps stay exact. Mixed batches fall through
    to the full path (per-sequence gating needs compaction; see DESIGN.md).

    ``live`` [B] bool: slots that still matter (the slot engine's occupied,
    not-done rows). Dead slots can't veto the whole-batch skip — their
    outputs are discarded by the caller and their cache rows are either
    overwritten before becoming readable or belong to retired requests —
    and they are masked out of MoE routing like in ``forward_decode``.

    Returns (logits [B, V], exit_mask [B], new_cache).
    """
    assert cfg.early_exit is not None and len(cfg.early_exit.exit_layers) == 1
    assert all(b.mixer == "attn" for b in cfg.block_pattern), \
        "gated decode requires an attention-only arch (SSM states cannot be propagated)"
    from repro.core.early_exit import should_exit
    x = _embed(params, tokens, cfg)
    cache_pos = cache.pos
    new_prefix = []
    for i in range(cfg.first_k_dense):
        x, _, ns = _apply_layer(params["prefix"][i], x, cfg.layer_spec(i), cfg,
                                policy, state=cache.prefix[i], mode="decode",
                                cache_pos=cache_pos, live=live)
        new_prefix.append(ns)
    exit_sb = (cfg.early_exit.exit_layers[0] - cfg.first_k_dense) // cfg.period
    n_sb = cfg.num_superblocks
    # segment 1: up to the exit head
    x, _, states = _scan_segment(params["slots"], x, 0, exit_sb, cfg,
                                 policy, mode="decode", states=cache.slots,
                                 cache_pos=cache_pos, live=live)
    exit_lg = _exit_logits(params, x, 0, cfg, policy)[:, 0]
    exit_mask, _ = should_exit(exit_lg, cfg.early_exit.entropy_threshold, policy)
    gate = exit_mask if live is None else (exit_mask | ~live)

    def cont(ops):
        x_in, st = ops
        x2, _, st = _scan_segment(params["slots"], x_in, exit_sb, n_sb, cfg,
                                  policy, mode="decode", states=st,
                                  cache_pos=cache_pos, live=live)
        lg = _head(params, x2, cfg, policy)[:, 0]
        lg = jnp.where(exit_mask[:, None], exit_lg, lg)
        return lg, st

    def skip(ops):
        x_in, st = ops

        def body(st, i):
            slot_params, slot_states = _layer_at(params["slots"], i), \
                _layer_at(st, i)
            new_states = tuple(
                _kv_propagate_layer(slot_params[j], x_in, cfg, policy,
                                    slot_states[j], cache_pos)
                for j in range(cfg.period))
            return _set_layer(st, i, new_states), None

        st, _ = jax.lax.scan(body, st, jnp.arange(exit_sb, n_sb))
        return exit_lg, st

    logits, new_slots = jax.lax.cond(jnp.all(gate), skip, cont, (x, states))
    return logits, exit_mask, LMCache(tuple(new_prefix), new_slots,
                                      cache.pos + 1)


# ---------------------------------------------------------------------------
# Parameter counting (roofline MODEL_FLOPS + static characterization)
# ---------------------------------------------------------------------------


def count_params(cfg: ArchConfig, active_only: bool = False) -> int:
    import math
    shapes = jax.eval_shape(lambda k: init_lm(k, cfg),
                            jax.random.PRNGKey(0))
    total = 0
    for path, leaf in jax.tree_util.tree_flatten_with_path(shapes)[0]:
        n = math.prod(leaf.shape)
        if active_only and cfg.moe is not None:
            name = None
            for entry in reversed(path):
                key = getattr(entry, "key", getattr(entry, "name", None))
                if isinstance(key, str):
                    name = key
                    break
            in_expert = name in ("w_gate_e", "w_up_e", "w_down_e")
            if in_expert:
                n = int(n * cfg.moe.top_k / cfg.moe.num_experts)
        total += n
    return total
