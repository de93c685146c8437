"""Serving engines: the slot-based continuous-batching engine (production
path) and the legacy host-driven loop (kept for the dry-run and examples).

Continuous batching (the tentpole of this layer):

  * The cache's batch dimension is a fixed set of SLOTS (``--capacity``).
    A request is admitted by a bucketed batch-1 prefill written into a free
    slot row (``lm.fill_slot``); prompt-length and occupancy variation is
    slot STATE (per-slot ``pos``/budget/done), never trace shape.
  * Decode is ONE jitted ``lax.scan`` over the whole slot batch
    (``make_decode_chunk``): on-device greedy sampling, on-device
    ``merge_exit_logits`` early-exit selection, and on-device accumulation
    of exit-rate / gated-fraction statistics. The host sees one transfer
    per decode CHUNK (tokens + slot state + stats), never per token.
  * Early-exited work stops paying for depth through the existing gated
    path (``gated=True`` → ``forward_decode_gated``'s lax.cond skip with
    CALM KV propagation) on attention-only single-exit archs.

Paged KV (``paged=True``) replaces the per-slot contiguous ``max_len`` KV
rows with fixed-size pages — capacity becomes "tokens actually resident",
not "slots x max_len". Page-pool invariants (host side enforced by
``serve/paging.py``, device side by construction):

  * each attention layer owns a pool ``[num_pages, Hkv, ps, D]`` (MLA:
    ``[num_pages, ps, lora]``); ONE ``[capacity, max_pages]`` page table is
    shared by every layer — a sequence's logical page j maps to the same
    pool index in all of them;
  * page 0 is the reserved SCRATCH page: never allocated; appends from
    done/empty slots (whose table entry is -1) are routed there and its
    contents are never validly read;
  * live slots own disjoint page sets; a retired slot's pages return to
    the free list UNZEROED — junk is masked at read time by the per-page
    validity test (table entry >= 0) and the per-slot length, so reuse
    needs no zeroing pass;
  * the page table is DATA to the jitted decode chunk (traced shape
    ``[capacity, max_pages]``): admission, on-demand growth between chunks
    and retirement rewrite it without re-tracing;
  * admission reserves each request's worst-case page count, so the
    scheduler's on-demand growth before a chunk can never run dry.

The legacy ``generate`` remains the reference loop (tests compare the slot
engine against it token-for-token); its per-token ``float(info[k])`` host
sync is fixed — statistics stay on device until one fetch at the end.

Token identity is COMPOSITION-INDEPENDENT for every arch family: per-slot
cache positions, per-slot PRNG keys, and — since the dropless MoE decode
path (``models/moe.py`` ``apply_moe_decode`` through the ``moe_decode``
XAIF op) — per-token expert dispatch with no shared capacity group, so a
request's greedy tokens never depend on which other requests are batched
or backfilled beside it. Dead/retired slots are masked out of MoE routing
(``live`` below), so their stale hidden states can't skew the aux counts
either. (The seed's batched loop shared one expert-capacity group across
the decode batch; that caveat is gone.)
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ArchConfig, RunConfig, ShardingPolicy, get_arch
from repro.core.early_exit import gated_layer_fraction, merge_exit_logits
from repro.dist import sharding as shd
from repro.models import attention as attn
from repro.models import lm

# ---------------------------------------------------------------------------
# Jitted step builders (shared by the dry-run lowering and the legacy loop)
# ---------------------------------------------------------------------------


def make_serve_step(run: RunConfig, gated: bool = False):
    cfg, policy = run.arch, run.accel

    def serve_step(params, cache: lm.LMCache, tokens):
        """tokens [B, 1] (or [B, 1, d] embeddings for stub frontends).
        Returns (next_tokens [B], info dict, new cache)."""
        if gated:
            logits, exit_mask, new_cache = lm.forward_decode_gated(
                params, tokens, cfg, policy, cache)
            info = {"exit_rate": jnp.mean(exit_mask.astype(jnp.float32))}
        else:
            logits, exit_lgs, new_cache = lm.forward_decode(
                params, tokens, cfg, policy, cache)
            # exit_lgs is a Python list — its length is trace-static
            if cfg.early_exit is not None and len(exit_lgs) > 0:
                logits, exit_idx, info = merge_exit_logits(
                    logits, exit_lgs, cfg.early_exit, policy)
                info["gated_fraction"] = gated_layer_fraction(
                    exit_idx, cfg.early_exit.exit_layers, cfg.num_layers)
            else:
                info = {}
        next_tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return next_tok, info, new_cache

    return serve_step


def make_prefill(run: RunConfig):
    cfg, policy = run.arch, run.accel

    def prefill(params, cache: lm.LMCache, tokens):
        logits, new_cache = lm.forward_prefill(params, tokens, cfg, policy,
                                               cache)
        next_tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return next_tok, new_cache

    return prefill


_GENERATE_JIT_CACHE: Dict[tuple, tuple] = {}


def _generate_fns(run: RunConfig, gated: bool):
    """Jitted (prefill, step) cached across generate() calls — the seed
    rebuilt both closures per call, so every generation re-compiled."""
    # both AccelConfig and xaif.DispatchPolicy are hashable, so the policy
    # itself is the cache key — no manual flattening of its backend map
    key = (run.arch, run.accel, gated)
    if key not in _GENERATE_JIT_CACHE:
        _GENERATE_JIT_CACHE[key] = (
            jax.jit(make_prefill(run)),
            jax.jit(make_serve_step(run, gated=gated)))
    return _GENERATE_JIT_CACHE[key]


def generate(run: RunConfig, params, prompt, max_new_tokens: int,
             max_len: Optional[int] = None, gated: bool = False
             ) -> Tuple[jax.Array, Dict[str, float]]:
    """Greedy generation loop (host-driven, the REFERENCE path).

    Per-step statistics accumulate as device scalars and are fetched ONCE
    after the loop — the loop body never blocks on a host transfer, so
    dispatch stays async (the seed's ``float(info[k])`` per token serialized
    every step).
    """
    cfg = run.arch
    b, t = prompt.shape[0], prompt.shape[1]
    max_len = max_len or (t + max_new_tokens)
    cache = lm.init_cache(cfg, b, max_len)
    prefill, step = _generate_fns(run, gated)
    tok, cache = prefill(params, cache, prompt)
    out = [tok]
    stats: Dict[str, list] = {"exit_rate": [], "gated_fraction": []}
    for _ in range(max_new_tokens - 1):
        tok, info, cache = step(params, cache, tok[:, None])
        out.append(tok)
        for k in stats:
            if k in info:
                stats[k].append(info[k])          # device scalar, no sync
    agg = {k: (float(jnp.mean(jnp.stack(v))) if v else 0.0)
           for k, v in stats.items()}
    return jnp.stack(out, axis=1), agg


# ---------------------------------------------------------------------------
# Slot engine: continuous batching over a fixed-capacity slot batch
# ---------------------------------------------------------------------------


class DecodeState(NamedTuple):
    """Per-slot decode state + on-device statistics accumulators.

    Empty slots are born ``done``; admission (``make_prefill_slot``) flips a
    slot live, retirement is pure HOST bookkeeping (the next admission
    overwrites the row) — so backfill never re-traces or touches device
    state beyond the one prefill call.

    ``rng`` carries one PRNG key PER SLOT (raw uint32[2] rows), advanced
    only on sampled steps — the greedy default never touches it, so greedy
    numerics are unchanged leaf-for-leaf. Keys are per-slot so a request's
    sample stream depends only on its slot and admission, never on which
    other slots happen to be live (the same composition-independence
    argument as the per-slot cache positions).

    ``quarantined`` is the NaN/Inf logit guard's verdict: a live slot whose
    step logits go non-finite (a poisoned KV page, an overflowed
    activation) is frozen — its garbage token is NOT emitted, it is marked
    done — and flagged here so the host can shed exactly that request.
    Co-batched slots never read each other's state, so the quarantine is
    surgical by construction.
    """
    tokens: jax.Array        # [S] i32 — last token per slot (next step input)
    done: jax.Array          # [S] bool
    generated: jax.Array     # [S] i32 — tokens produced (incl. prefill token)
    budget: jax.Array        # [S] i32 — max_new_tokens per slot
    rng: jax.Array           # [S, 2] u32 — per-slot PRNG key (sampling)
    exit_cnt: jax.Array      # f32 — Σ over steps of early-exited live slots
    gated_layers: jax.Array  # f32 — Σ of per-slot gated layer fractions
    live_cnt: jax.Array      # f32 — Σ over steps of live slots
    quarantined: jax.Array   # [S] bool — NaN/Inf guard tripped for the slot
    # SCALAR accumulators (not per-slot: slot reuse must not lose a retired
    # request's contribution)
    realized: jax.Array      # f32 — Σ tokens actually emitted by decode chunks
    spec_prop: jax.Array     # f32 — Σ draft tokens proposed (spec decode)
    spec_acc: jax.Array      # f32 — Σ draft tokens accepted (spec decode)


def init_decode_state(capacity: int, seed: int = 0) -> DecodeState:
    z = jnp.zeros((), jnp.float32)
    base = jax.random.PRNGKey(seed)
    return DecodeState(
        tokens=jnp.zeros((capacity,), jnp.int32),
        done=jnp.ones((capacity,), bool),
        generated=jnp.zeros((capacity,), jnp.int32),
        budget=jnp.zeros((capacity,), jnp.int32),
        rng=jax.vmap(lambda i: jax.random.fold_in(base, i))(
            jnp.arange(capacity, dtype=jnp.int32)),
        exit_cnt=z, gated_layers=z, live_cnt=z,
        quarantined=jnp.zeros((capacity,), bool),
        realized=z, spec_prop=z, spec_acc=z)


@dataclasses.dataclass(frozen=True)
class SpecConfig:
    """Speculative-decoding knob for :class:`SlotEngine`.

    ``draft_arch``: registry name or :class:`ArchConfig` of the small draft
    model that proposes ``k`` tokens per live slot per round; the target
    then scores all proposals in ONE batched ``forward_verify`` and accepts
    a per-slot variable-length prefix. Greedy output is token-identical to
    plain greedy decode no matter how good the draft is — acceptance
    compares proposals against the target's own argmax rows, so draft
    quality moves THROUGHPUT only. ``share_params=True`` runs the draft
    with the target's own weights (requires ``draft_arch`` == the target
    arch): the provably-perfect-acceptance configuration benchmarks use as
    the high-acceptance reference stream."""
    draft_arch: object                   # registry name or ArchConfig
    k: int = 4                           # proposals per round
    draft_seed: int = 0                  # draft init_lm seed
    share_params: bool = False           # tied self-draft (bench reference)


def make_sampler(temperature: float, top_k: int = 0,
                 top_p: float = 1.0) -> Optional[Callable]:
    """sample(key u32[2], logits [V]) -> i32 token, or None for greedy.

    Temperature-scaled categorical sampling with optional top-k truncation
    and top-p (nucleus) truncation — applied in that order: temperature,
    top-k, then keep the smallest probability mass >= ``top_p`` (the top-1
    token always survives, so top_p -> 0 degenerates to argmax). Greedy
    (temperature 0) returns None so callers keep the exact argmax graph.
    """
    if temperature <= 0.0:
        return None

    def sample(key, logits):
        lg = logits.astype(jnp.float32) / temperature
        if top_k > 0:
            kth = jax.lax.top_k(lg, top_k)[0][-1]
            lg = jnp.where(lg < kth, -jnp.inf, lg)
        if 0.0 < top_p < 1.0:
            order = jnp.argsort(-lg)                   # descending
            sorted_lg = lg[order]
            probs = jax.nn.softmax(sorted_lg)
            # keep tokens whose PRECEDING cumulative mass is < top_p —
            # the minimal nucleus covering top_p (top-1 always kept)
            keep = (jnp.cumsum(probs) - probs) < top_p
            sorted_lg = jnp.where(keep, sorted_lg, -jnp.inf)
            choice = jax.random.categorical(key, sorted_lg)
            return order[choice].astype(jnp.int32)
        return jax.random.categorical(key, lg).astype(jnp.int32)

    return sample


def make_probs(temperature: float, top_k: int = 0,
               top_p: float = 1.0) -> Optional[Callable]:
    """probs(logits [V]) -> [V] f32, or None for greedy.

    The EXACT distribution :func:`make_sampler` draws from (temperature,
    then top-k, then nucleus truncation) as an explicit probability vector
    — speculative decoding's residual rejection rule needs p and q as
    densities, not just draws, to stay distribution-preserving."""
    if temperature <= 0.0:
        return None

    def probs(logits):
        lg = logits.astype(jnp.float32) / temperature
        if top_k > 0:
            kth = jax.lax.top_k(lg, top_k)[0][-1]
            lg = jnp.where(lg < kth, -jnp.inf, lg)
        if 0.0 < top_p < 1.0:
            order = jnp.argsort(-lg)                   # descending
            sorted_lg = lg[order]
            p = jax.nn.softmax(sorted_lg)
            keep = (jnp.cumsum(p) - p) < top_p
            sorted_lg = jnp.where(keep, sorted_lg, -jnp.inf)
            lg = jnp.full_like(lg, -jnp.inf).at[order].set(sorted_lg)
        return jax.nn.softmax(lg)

    return probs


def _admit_slot(st: DecodeState, logits0, slot, max_new,
                sampler: Optional[Callable], rng0=None, has_seed=None
                ) -> Tuple[DecodeState, jax.Array]:
    """Shared admission tail: first token (greedy or sampled with the
    slot's key) + slot-state bookkeeping. Greedy leaves ``rng`` untouched
    (``rng0``/``has_seed`` are dead arguments), so the greedy trace is
    leaf-identical to the pre-sampling engine. When a per-request seed is
    given (``has_seed``), the slot's key is REPLACED by the request's own
    key — identical seeded requests replay the same sample stream no
    matter which slot they land in."""
    if sampler is None:
        tok0 = jnp.argmax(logits0, axis=-1).astype(jnp.int32)
        rng = st.rng
    else:
        base = st.rng[slot]
        if rng0 is not None:
            base = jnp.where(has_seed, rng0, base)
        key = jax.random.fold_in(base, 0)
        tok0 = sampler(key, logits0)
        rng = st.rng.at[slot].set(jax.random.fold_in(base, 1))
    st = st._replace(
        tokens=st.tokens.at[slot].set(tok0),
        done=st.done.at[slot].set(max_new <= 1),
        generated=st.generated.at[slot].set(1),
        budget=st.budget.at[slot].set(max_new),
        rng=rng,
        quarantined=st.quarantined.at[slot].set(False))
    return st, tok0


def make_prefill_slot(run: RunConfig, bucket_len: int,
                      sampler: Optional[Callable] = None):
    """Jitted per-bucket admission: batch-1 prefill → fill_slot → slot vars.

    One trace per (arch, bucket) pair; the slot index, true length and token
    budget are traced arguments, so any request in the bucket reuses it.
    """
    cfg, policy = run.arch, run.accel

    def prefill_slot(params, cache: lm.LMCache, st: DecodeState,
                     tokens, true_len, slot, max_new, rng0, has_seed):
        slot_cache = lm.init_cache(cfg, 1, bucket_len)
        logits, slot_cache = lm.forward_prefill(
            params, tokens, cfg, policy, slot_cache,
            lengths=true_len[None])
        cache = lm.fill_slot(cache, slot_cache, slot, true_len)
        st, tok0 = _admit_slot(st, logits[0], slot, max_new, sampler,
                               rng0, has_seed)
        return cache, st, tok0

    return prefill_slot


def make_prefill_slot_paged(run: RunConfig, bucket_len: int,
                            page_size: int,
                            sampler: Optional[Callable] = None):
    """Paged admission: contiguous batch-1 prefill -> page scatter.

    The prefill compute is unchanged (a bucketed contiguous batch-1 cache);
    ``lm.fill_slot_paged`` scatters the produced KV into the host-allocated
    ``page_ids`` (traced [bucket_pages] i32 — any page assignment reuses
    the one trace per bucket)."""
    cfg, policy = run.arch, run.accel

    def prefill_slot(params, cache, st: DecodeState, tokens, true_len, slot,
                     max_new, page_ids, rng0, has_seed):
        slot_cache = lm.init_cache(cfg, 1, bucket_len)
        logits, slot_cache = lm.forward_prefill(
            params, tokens, cfg, policy, slot_cache,
            lengths=true_len[None])
        cache = lm.fill_slot_paged(cache, slot_cache, slot, true_len,
                                   page_ids)
        st, tok0 = _admit_slot(st, logits[0], slot, max_new, sampler,
                               rng0, has_seed)
        return cache, st, tok0

    return prefill_slot


def make_prefill_slot_shared(run: RunConfig, suffix_bucket: int,
                             prefix_cap: int, page_size: int,
                             sampler: Optional[Callable] = None):
    """Fork-point admission: prefill ONLY the unshared suffix of a prompt
    whose prefix KV is already resident in the page pools.

    One trace per (suffix bucket, pow2 prefix cap) pair — the matched
    length, fork offset and page ids are all traced DATA. ``tokens`` holds
    the right-padded suffix; the shared prefix is attended in place via
    ``lm.forward_prefill_shared`` (gather-only — a reader never writes a
    shared page)."""
    cfg, policy = run.arch, run.accel

    def prefill_slot(params, cache, st: DecodeState, tokens, start, n_prefix,
                     true_len, slot, max_new, prefix_ids, region_ids,
                     row_ids, rng0, has_seed):
        ctx = attn.SharedPrefillCtx(prefix_ids, region_ids, start, n_prefix,
                                    true_len)
        logits, cache = lm.forward_prefill_shared(
            params, tokens, cfg, policy, cache, slot, ctx, row_ids)
        st, tok0 = _admit_slot(st, logits[0], slot, max_new, sampler,
                               rng0, has_seed)
        return cache, st, tok0

    return prefill_slot


def make_prefill_chunk(run: RunConfig, chunk_len: int, prefix_cap: int,
                       page_size: int):
    """One INTERMEDIATE chunk of a chunked prefill: run ``chunk_len``
    prompt tokens against the slot's already-resident prefix and write
    their KV into the slot's next region pages — no admission, no LM head
    (``head=False``), no DecodeState.

    Chunk boundaries are page-aligned (the scheduler asserts
    ``chunk_len % page_size == 0``), so the resident prefix always ends
    exactly at a page boundary: ``n_prefix == start``, no COW page, and a
    zero-length prefix (the FIRST chunk) degenerates to a fully-masked
    scratch gather. One trace per (chunk_len, pow2 prefix cap)."""
    cfg, policy = run.arch, run.accel
    n_region = chunk_len // page_size

    def prefill_chunk(params, cache, tokens, start, slot, prefix_ids,
                      region_ids, row_ids):
        ctx = attn.SharedPrefillCtx(prefix_ids, region_ids, start, start,
                                    start + chunk_len)
        _, cache = lm.forward_prefill_shared(params, tokens, cfg, policy,
                                             cache, slot, ctx, row_ids,
                                             head=False)
        return cache

    prefill_chunk.n_region = n_region
    prefill_chunk.prefix_cap = prefix_cap
    return prefill_chunk


def make_decode_chunk(run: RunConfig, steps: int, gated: bool = False,
                      sampler: Optional[Callable] = None):
    """One jitted lax.scan of ``steps`` decode steps over the slot batch.

    Everything stays on device: sampling (greedy argmax, or temperature /
    top-k through the per-slot keys in ``DecodeState.rng`` when ``sampler``
    is given), early-exit merge, per-slot done/budget bookkeeping,
    statistics accumulation. Done/empty slots keep feeding their frozen
    token (their output is discarded, their cache position is pinned so the
    valid prefix never corrupts, and they are masked out of MoE routing so
    their stale hidden states can't skew the aux counts); the caller
    performs ONE host fetch of (tokens [S, steps], state) per chunk.
    """
    cfg, policy = run.arch, run.accel
    n_layers = cfg.num_layers

    def body(params, carry, _):
        cache, st = carry
        live = ~st.done
        if gated:
            logits, exit_mask, new_cache = lm.forward_decode_gated(
                params, st.tokens[:, None], cfg, policy, cache, live=live)
            exited = exit_mask
            # credit gated compute ONLY when the lax.cond skip branch
            # actually ran (all live slots confident) — otherwise the
            # full-depth path executed and nothing was saved
            skipped = jnp.all(exit_mask | ~live)
            el = cfg.early_exit.exit_layers[0]
            gated_frac = jnp.where(exit_mask & skipped,
                                   1.0 - el / n_layers, 0.0)
        else:
            logits, exit_lgs, new_cache = lm.forward_decode(
                params, st.tokens[:, None], cfg, policy, cache, live=live)
            # exit_lgs is a Python list — its length is trace-static
            if cfg.early_exit is not None and len(exit_lgs) > 0:
                logits, exit_idx, _ = merge_exit_logits(
                    logits, exit_lgs, cfg.early_exit, policy)
                bounds = jnp.asarray(
                    tuple(cfg.early_exit.exit_layers) + (n_layers,),
                    jnp.float32)
                exited = exit_idx < len(exit_lgs)
                gated_frac = 1.0 - bounds[exit_idx] / n_layers
            else:
                exited = jnp.zeros_like(st.done)
                gated_frac = jnp.zeros(st.done.shape, jnp.float32)
        if sampler is None:
            next_tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            new_rng = st.rng
        else:
            split = jax.vmap(lambda k: jax.random.split(k, 2))(st.rng)
            next_tok = jax.vmap(sampler)(split[:, 0], logits)
            new_rng = split[:, 1]
        # NaN/Inf logit guard: a live slot whose logits went non-finite
        # (poisoned KV, overflowed activation) produced a garbage token —
        # freeze it instead of emitting, mark the slot done and flag it
        # quarantined. ONLY that slot: batch elements never read each
        # other's KV, so co-batched requests are numerically untouched.
        bad = live & ~jnp.all(
            jnp.isfinite(logits.astype(jnp.float32)), axis=-1)
        ok = live & ~bad
        next_tok = jnp.where(ok, next_tok, st.tokens)
        # pin cache positions of done/empty slots (their KV write lands one
        # past the valid prefix and is overwritten before it could be read)
        new_cache = new_cache._replace(
            pos=jnp.where(live, new_cache.pos, cache.pos))
        generated = st.generated + ok.astype(jnp.int32)
        live_f = live.astype(jnp.float32)
        st = st._replace(
            tokens=next_tok,
            done=st.done | (generated >= st.budget) | bad,
            generated=generated,
            rng=new_rng,
            exit_cnt=st.exit_cnt + jnp.sum(exited.astype(jnp.float32) * live_f),
            gated_layers=st.gated_layers + jnp.sum(gated_frac * live_f),
            live_cnt=st.live_cnt + jnp.sum(live_f),
            quarantined=st.quarantined | bad,
            realized=st.realized + jnp.sum(ok.astype(jnp.float32)))
        return (new_cache, st), next_tok

    def decode_chunk(params, cache: lm.LMCache, st: DecodeState):
        (cache, st), toks = jax.lax.scan(
            functools.partial(body, params), (cache, st), None, length=steps)
        return cache, st, jnp.swapaxes(toks, 0, 1)      # [S, steps]

    return decode_chunk


def make_draft_prefill(cfg: ArchConfig, policy, bucket_len: int):
    """Per-bucket draft admission: batch-1 prefill of the FULL prompt into
    the draft's contiguous slot cache. No logits, no DecodeState — the
    round's first draft step starts from the target's last emitted token,
    so only the KV (and the slot position = true length) matter."""

    def draft_prefill(dparams, dcache: lm.LMCache, tokens, true_len, slot):
        slot_cache = lm.init_cache(cfg, 1, bucket_len)
        _, slot_cache = lm.forward_prefill(dparams, tokens, cfg, policy,
                                           slot_cache, lengths=true_len[None])
        return lm.fill_slot(dcache, slot_cache, slot, true_len)

    return draft_prefill


def make_spec_decode_chunk(run: RunConfig, draft_cfg: ArchConfig, k: int,
                           steps: int, sampler: Optional[Callable] = None,
                           probs: Optional[Callable] = None):
    """One jitted lax.scan of ``steps`` SPECULATIVE rounds over the slots.

    Each round: ``k`` sequential draft decode steps propose d_1..d_k from
    the last emitted token t_0; ONE target ``forward_verify`` over
    [t_0, d_1..d_k] writes the K1 = k+1 KV rows at pos..pos+k and yields
    logits whose row i is bitwise the i-th sequential decode step. The
    accepted prefix (plus the correction/bonus row) advances each slot by a
    VARIABLE n_real ∈ [0, k+1] positions — budget-clipped, NaN-guarded and
    position-pinned exactly like the plain chunk.

    Token identity (greedy): every emitted token is the argmax of a target
    logits row whose conditioning rows all hold already-accepted (= plain
    greedy) tokens, so the emitted stream equals plain greedy decode
    bitwise regardless of draft quality; rows past the accepted prefix hold
    rejected-draft KV and are REWRITTEN by the next round's verify before
    their positions can become valid.

    Sampling: standard residual rejection sampling — draft token d ~ q is
    accepted iff u·q(d) < p(d); the first rejection resamples from the
    residual (p − q)+, full acceptance draws the bonus token from the last
    row's p — so every emitted token is marginally ~ p (the exact
    ``make_sampler`` distribution). Keys advance along a per-slot split
    CHAIN, one link per EMITTED token, so a seeded request's stream depends
    only on its emitted prefix — placement- and chunk-boundary-independent.

    The draft keeps its own contiguous slot cache; its position row is
    re-synced to the target's every round (``draft.pos = target.pos``), so
    a swap-resumed or restored slot self-heals: stale draft KV can only
    depress the acceptance rate, never the output (see the identity
    argument above).

    Returns (cache, dcache, st, packed [S, steps*(k+1)]) — per-slot valid
    tokens left-packed in emission order, invalid lanes arbitrary (the
    scheduler reads exactly the per-slot ``generated`` delta).
    """
    cfg, policy = run.arch, run.accel
    k1 = k + 1

    def body(params, dparams, carry, _):
        cache, dcache, st = carry
        live = ~st.done
        # -- per-slot key chain: c_0 = st.rng, use_j/c_{j+1} = split(c_j).
        # use_j belongs to EMITTED position j and 3-splits into the draft
        # proposal, acceptance-uniform and residual/bonus keys; the round
        # consumes n_real links so replay is acceptance-pattern faithful.
        if sampler is not None:
            links, uses, cur = [st.rng], [], st.rng
            for _j in range(k1):
                sp = jax.vmap(lambda c: jax.random.split(c, 2))(cur)
                uses.append(sp[:, 0])
                cur = sp[:, 1]
                links.append(cur)
            chain = jnp.stack(links, axis=1)           # [S, k+2, 2]
            use = jnp.stack(uses, axis=1)              # [S, k+1, 2]
            trip = jax.vmap(jax.vmap(
                lambda c: jax.random.split(c, 3)))(use)  # [S, k+1, 3, 2]
        # -- draft: k sequential proposals from t_0 (positions re-synced to
        # the target's — the invariant holds at every round boundary and
        # self-heals one round after any restore/swap staleness). One EXTRA
        # step feeds d_k with its logits discarded: a fully-accepted round
        # (k accepts + bonus) advances the target past d_k's position, so
        # the draft must hold d_k's KV row or the NEXT round's proposals
        # would be conditioned on a never-written row.
        dc = dcache._replace(pos=cache.pos)
        cur_tok = st.tokens
        dmat, dq = [], []
        for j in range(k1):
            dlg, _, dc = lm.forward_decode(dparams, cur_tok[:, None],
                                           draft_cfg, policy, dc,
                                           with_exits=False, live=live)
            if j == k:
                break                      # KV-ingest step for d_k only
            # draft garbage can never corrupt OUTPUT (acceptance filters
            # against the target), only acceptance rate — but non-finite q
            # would poison the accept arithmetic itself, so clamp it
            dlg = dlg.astype(jnp.float32)
            dlg = jnp.where(jnp.isfinite(dlg), dlg, -1e30)
            if sampler is None:
                d = jnp.argmax(dlg, axis=-1).astype(jnp.int32)
            else:
                q = jax.vmap(probs)(dlg)               # [S, V]
                d = jax.vmap(jax.random.categorical)(
                    trip[:, j, 0], jnp.log(q)).astype(jnp.int32)
                dq.append(q)
            dmat.append(d)
            cur_tok = d
        dmat = jnp.stack(dmat, axis=1)                 # [S, k]
        # -- verify: one batched target forward over [t_0, d_1..d_k]
        vtokens = jnp.concatenate([st.tokens[:, None], dmat], axis=1)
        vlg, vcache = lm.forward_verify(params, vtokens, cfg, policy, cache,
                                        live=live)
        vlg = vlg.astype(jnp.float32)                  # [S, K1, V]
        finite = jnp.all(jnp.isfinite(vlg), axis=-1)   # [S, K1]
        if sampler is None:
            tgt = jnp.argmax(vlg, axis=-1).astype(jnp.int32)   # [S, K1]
            acc = finite[:, :k] & (dmat == tgt[:, :k])
            emit = tgt
        else:
            p = jax.vmap(jax.vmap(probs))(vlg)         # [S, K1, V]
            dq = jnp.stack(dq, axis=1)                 # [S, k, V]
            pd = jnp.take_along_axis(p[:, :k], dmat[..., None], 2)[..., 0]
            qd = jnp.take_along_axis(dq, dmat[..., None], 2)[..., 0]
            u = jax.vmap(jax.vmap(
                lambda kk: jax.random.uniform(kk)))(trip[:, :k, 1])
            # u·q(d) < p(d) ⟺ u < min(1, p/q) for u ~ U[0,1), q(d) > 0
            acc = finite[:, :k] & (u * qd < pd)
            # residual (p − q)+ at every candidate rejection row (only the
            # first rejection's draw is ever emitted); if the residual mass
            # is numerically zero, fall back to p itself
            resid = jnp.clip(p[:, :k] - dq, 0.0, None)
            rmass = jnp.sum(resid, axis=-1, keepdims=True)
            resid = jnp.where(rmass > 1e-9, resid, p[:, :k])
            corr = jax.vmap(jax.vmap(jax.random.categorical))(
                trip[:, :k, 2], jnp.log(resid)).astype(jnp.int32)
            bonus = jax.vmap(jax.random.categorical)(
                trip[:, k, 2], jnp.log(p[:, k])).astype(jnp.int32)
            emit = jnp.concatenate(
                [jnp.where(acc, dmat, corr), bonus[:, None]], axis=1)
        # -- variable-length acceptance: a consecutive accepts, then one
        # correction/bonus row (emitted only if its logits row is finite)
        a = jnp.sum(jnp.cumprod(acc.astype(jnp.int32), axis=1), axis=1)
        fin_a = jnp.take_along_axis(finite, a[:, None], axis=1)[:, 0]
        n_acc = a + fin_a.astype(jnp.int32)
        rem = st.budget - st.generated
        n_real = jnp.where(live, jnp.minimum(n_acc, rem), 0)
        bad = live & (n_acc == 0)          # row 0 non-finite: quarantine
        ok = live & ~bad
        next_tok = jnp.where(
            ok,
            jnp.take_along_axis(
                emit, jnp.maximum(n_real - 1, 0)[:, None], axis=1)[:, 0],
            st.tokens)
        # forward_verify leaves pos unchanged: advance accepted slots by
        # their realized count, pin everyone else (done slots' garbage rows
        # land past their valid prefix and are never registered or read)
        new_pos = jnp.where(ok, cache.pos + n_real, cache.pos)
        vcache = vcache._replace(pos=new_pos)
        dc = dc._replace(pos=new_pos)
        if sampler is None:
            new_rng = st.rng               # greedy never touches the keys
        else:
            new_rng = jnp.where(
                ok[:, None],
                jnp.take_along_axis(chain, n_real[:, None, None], 1)[:, 0],
                st.rng)
        generated = st.generated + n_real
        okf = ok.astype(jnp.float32)
        st = st._replace(
            tokens=next_tok,
            done=st.done | (generated >= st.budget) | bad,
            generated=generated,
            rng=new_rng,
            live_cnt=st.live_cnt + jnp.sum(live.astype(jnp.float32)),
            quarantined=st.quarantined | bad,
            realized=st.realized + jnp.sum(n_real.astype(jnp.float32)),
            spec_prop=st.spec_prop + k * jnp.sum(okf),
            spec_acc=st.spec_acc + jnp.sum(a.astype(jnp.float32) * okf))
        return (vcache, dc, st), (emit, n_real)

    def spec_decode_chunk(params, dparams, cache, dcache: lm.LMCache,
                          st: DecodeState):
        (cache, dcache, st), (emits, nreal) = jax.lax.scan(
            functools.partial(body, params, dparams), (cache, dcache, st),
            None, length=steps)
        emits = jnp.swapaxes(emits, 0, 1)              # [S, steps, K1]
        nreal = jnp.swapaxes(nreal, 0, 1)              # [S, steps]
        s = emits.shape[0]
        flat = emits.reshape(s, steps * k1)
        valid = (jnp.arange(k1, dtype=jnp.int32)[None, None, :]
                 < nreal[:, :, None]).reshape(s, steps * k1)
        # left-pack the valid tokens, preserving emission order (argsort on
        # the invalid mask is stable), so the scheduler's
        # ``toks[slot, :generated_delta]`` read stays contiguous
        order = jnp.argsort(~valid, axis=1, stable=True)
        packed = jnp.take_along_axis(flat, order, axis=1)
        return cache, dcache, st, packed               # [S, steps*(k+1)]

    return spec_decode_chunk


def _init_params(key, cfg: ArchConfig, shardings=None):
    kw = {} if shardings is None else dict(out_shardings=shardings)
    return jax.jit(functools.partial(lm.init_lm, cfg=cfg), **kw)(key)


class SlotEngine:
    """Jit lifecycle around the slot batch: one decode trace per capacity,
    one prefill trace per prompt-length bucket, donated caches.

    ``prompt_bucket``: prompts are right-padded up to the next multiple of
    this (attention-style caches mask the pad via per-slot lengths). Archs
    with recurrent mixers (Mamba/xLSTM) prefill at EXACT length — pad
    tokens would be folded into the recurrence — at the cost of one trace
    per distinct prompt length.

    ``paged``: store attention KV as fixed-size pages (``page_size``) from
    a pool of ``num_pages`` (default: the contiguous engine's worst case,
    capacity x ceil(max_len/page_size), + 1 scratch page — shrink it to
    trade worst-case headroom for admission concurrency). Token identity
    with the contiguous engine holds bitwise when page_size divides
    max_len (equal attended extents); the gated early-exit path is not yet
    page-aware.

    ``(mesh, sharding)``: the "bus topology" knob of this layer. With a
    Mesh, EVERY jitted entry point (decode chunk, per-bucket prefill,
    init_state) is built with explicit ``in_shardings``/``out_shardings``:
    params per ``dist.sharding.param_shardings`` (tp over the model axis,
    optionally fsdp), the cache per ``cache_shardings`` (slot axis over the
    data axes; page pools head-sharded per tp, page table replicated), the
    DecodeState replicated — and ``donate_argnums`` is kept, so sharded
    caches still update in place. Tracing runs under ``shard_ctx(mesh,
    sharding)`` so the model's ``constrain`` calls resolve. With NO mesh
    every helper degrades to the exact single-device behavior, and on any
    mesh shape greedy tokens are identical to the single-device engine
    (tested under forced multi-device hosts in tests/test_serving_engine.py
    / test_paged.py).

    ``temperature`` / ``top_k`` / ``sample_seed``: non-greedy sampling in
    the scan body through per-slot PRNG keys (``DecodeState.rng``).
    Greedy (temperature 0) is the default and keeps the exact argmax graph.
    """

    def __init__(self, run: RunConfig, capacity: int, max_len: int,
                 chunk: int = 8, gated: bool = False, prompt_bucket: int = 16,
                 paged: bool = False, page_size: int = 16,
                 num_pages: Optional[int] = None,
                 mesh: Optional[Mesh] = None,
                 sharding: Optional[ShardingPolicy] = None,
                 temperature: float = 0.0, top_k: int = 0,
                 top_p: float = 1.0, sample_seed: int = 0,
                 prefix_sharing: bool = False,
                 persistent_prefix_index: bool = False,
                 spec: Optional[SpecConfig] = None):
        cfg = run.arch
        if gated:
            assert (cfg.early_exit is not None
                    and len(cfg.early_exit.exit_layers) == 1
                    and all(b.mixer == "attn" for b in cfg.block_pattern)), \
                "gated decode needs an attention-only single-exit arch"
        assert not (gated and paged), \
            "gated decode is not page-aware yet (ROADMAP follow-up)"
        # the shared-prefill entry (prefix sharing AND chunked prefill ride
        # on it) needs an all-attention GQA arch: recurrent mixer states
        # cannot resume from a page chain, MLA latents are not yet
        # share-indexed, and capacity-grouped MoE prefill is
        # suffix-length dependent
        self.shared_prefill_ok = (
            all(b.mixer == "attn" for b in cfg.block_pattern)
            and cfg.mla is None and cfg.moe is None)
        if prefix_sharing:
            assert paged, "prefix sharing requires the paged engine"
            assert self.shared_prefill_ok, \
                "prefix sharing needs an all-attention GQA arch"
        if persistent_prefix_index:
            assert prefix_sharing, \
                "a persistent PrefixIndex needs prefix_sharing=True"
        self.spec = spec
        self.draft_cfg: Optional[ArchConfig] = None
        if spec is not None:
            assert not gated, \
                "speculative decoding is incompatible with gated decode " \
                "(verification amortizes the full depth — there is no " \
                "per-token exit to gate on)"
            assert spec.k >= 1, f"spec.k must be >= 1, got {spec.k}"
            dcfg = spec.draft_arch
            if isinstance(dcfg, str):
                dcfg = get_arch(dcfg)
            for c, who in ((cfg, "target"), (dcfg, "draft")):
                assert all(b.mixer == "attn" for b in c.block_pattern) \
                    and c.mla is None and c.moe is None, \
                    f"speculative decoding needs an all-attention GQA " \
                    f"{who} arch (no MLA/MoE/recurrent mixers yet)"
            assert cfg.early_exit is None, \
                "speculative decoding skips the exit merge, so an " \
                "early-exit target would change tokens — not supported"
            assert dcfg.vocab_size == cfg.vocab_size, \
                (dcfg.vocab_size, cfg.vocab_size)
            if spec.share_params:
                assert dcfg == cfg, \
                    "share_params ties the draft to the target's weights " \
                    "— the draft arch must equal the target arch"
            self.draft_cfg = dcfg
        self.spec_k = spec.k if spec is not None else 0
        self.run = run
        self.capacity = capacity
        self.max_len = max_len
        self.chunk = chunk
        self.gated = gated
        self.paged = paged
        self.page_size = page_size
        self.max_pages = -(-max_len // page_size)
        self.num_pages = (num_pages if num_pages is not None
                          else capacity * self.max_pages + 1)
        if paged:
            assert self.num_pages >= self.max_pages + 1, \
                "page pool cannot hold even one max-length request"
        self.mesh = mesh
        self.sharding = sharding if sharding is not None else run.sharding
        self.temperature = temperature
        self.top_k = top_k
        self.top_p = top_p
        self.sample_seed = sample_seed
        self.prefix_sharing = prefix_sharing
        self.persistent_prefix_index = persistent_prefix_index
        # (cache, state, alloc) parked by the last serve() call when the
        # index is persistent — the next serve() resumes the resident pool
        # (radix cache intact) instead of a fresh one. The scheduler POPS
        # it before reuse, so a stale handle can never alias a donated
        # cache.
        self.resident = None
        # optional chaos hook (serve/faults.py FaultInjector): consulted at
        # the Python entry of every jitted hot-path call — BEFORE dispatch,
        # so a raised fault never leaves a donated buffer half-consumed
        self.injector = None
        # page-granular snapshots cover attention KV only; recurrent mixer
        # states are slot-indexed (not paged), so hybrid archs snapshot the
        # full cache instead
        self._page_snapshot_ok = all(
            cfg.layer_spec(i).mixer == "attn"
            for i in range(cfg.first_k_dense)) and all(
            b.mixer == "attn" for b in cfg.block_pattern)
        self._sampler = make_sampler(temperature, top_k, top_p)
        # prefix layers inherit their mixer from the pattern, so all-attn
        # patterns are pad-safe end to end; recurrent mixers are not, and
        # neither is capacity-bounded MoE PREFILL — pad tokens would route
        # into the experts and the per-group capacity constant scales with
        # the PADDED length, so bucketing would change which tokens drop.
        # MoE archs prefill at exact length (one trace per distinct prompt
        # length), keeping every arch's prefill equal to the solo reference.
        self.pad_prompts = (all(b.mixer == "attn" for b in cfg.block_pattern)
                            and cfg.moe is None)
        self.prompt_bucket = prompt_bucket if self.pad_prompts else 1
        self.decode_traces = 0
        self.prefill_traces = 0
        self.decode_calls = 0
        # bucketed tokens pushed through prefill (shared admissions count
        # only their suffix) — proportional to prefill FLOPs, the quantity
        # the prefix-sharing benchmark reports savings on
        self.prefill_tokens = 0

        # resolved once: (params_sh, cache_sh, state_sh) or None (no mesh)
        self._shardings = self._resolve_shardings()
        # spec only: (draft_params_sh, draft_cache_sh) or None
        self._spec_shardings = self._resolve_spec_shardings()

        # draft model state (spec only): the engine OWNS the draft weights
        # and the draft's contiguous slot cache — the scheduler API is
        # unchanged, it just sees a wider token matrix per chunk
        self.draft_params = None
        self._draft_cache = None
        if spec is not None and not spec.share_params:
            self.draft_params = _init_params(
                jax.random.PRNGKey(spec.draft_seed), self.draft_cfg,
                self._spec_shardings[0] if self._spec_shardings else None)

        if spec is None:
            decode_fn = make_decode_chunk(run, chunk, gated, self._sampler)

            def counted_decode(params, cache, st):
                self.decode_traces += 1      # runs at TRACE time only
                return decode_fn(params, cache, st)

            jit_kw = {}
            if self._shardings is not None:
                params_sh, cache_sh, state_sh = self._shardings
                jit_kw = dict(
                    in_shardings=(params_sh, cache_sh, state_sh),
                    out_shardings=(cache_sh, state_sh,
                                   NamedSharding(self.mesh, P(None, None))))
            self._decode = jax.jit(self._traced(counted_decode),
                                   donate_argnums=(1, 2), **jit_kw)
        else:
            spec_fn = make_spec_decode_chunk(
                run, self.draft_cfg, spec.k, chunk, self._sampler,
                make_probs(temperature, top_k, top_p))

            def counted_spec(params, dparams, cache, dcache, st):
                self.decode_traces += 1      # runs at TRACE time only
                return spec_fn(params, dparams, cache, dcache, st)

            jit_kw = {}
            if self._shardings is not None:
                params_sh, cache_sh, state_sh = self._shardings
                dparams_sh, dcache_sh = self._spec_shardings
                jit_kw = dict(
                    in_shardings=(params_sh, dparams_sh, cache_sh,
                                  dcache_sh, state_sh),
                    out_shardings=(cache_sh, dcache_sh, state_sh,
                                   NamedSharding(self.mesh, P(None, None))))
            self._decode = jax.jit(self._traced(counted_spec),
                                   donate_argnums=(2, 3, 4), **jit_kw)
        self._prefill = {}                   # bucket_len -> jitted fn
        self._draft_prefill = {}             # bucket_len -> jitted draft fn
        self._prefill_shared = {}            # (suffix_bucket, pcap) -> fn
        self._prefill_chunk = {}             # (chunk_len, pcap) -> fn
        self._copy_page = None               # lazily jitted COW copy
        self._gather_pages = {}              # n_ids -> jitted swap-out
        self._scatter_pages = {}             # n_ids -> jitted swap-in
        self._restore_slot = None            # lazily jitted resume
        self._deactivate = None              # lazily jitted preempt kill

    # -- mesh plumbing -----------------------------------------------------

    def _traced(self, fn):
        """Install the engine's shard_ctx for the DURATION OF TRACING so
        the model's ``constrain``/``spec_for`` calls resolve against the
        engine mesh; identity with no mesh (no context -> no-ops)."""
        if self.mesh is None:
            return fn
        mesh, policy = self.mesh, self.sharding

        @functools.wraps(fn)
        def wrapped(*args):
            with shd.shard_ctx(mesh, policy):
                return fn(*args)

        return wrapped

    def _init_fn(self):
        if self.paged:
            return lambda: (
                lm.init_paged_cache(self.run.arch, self.capacity,
                                    self.max_len, self.page_size,
                                    self.num_pages),
                init_decode_state(self.capacity, self.sample_seed))
        return lambda: (
            lm.init_cache(self.run.arch, self.capacity, self.max_len),
            init_decode_state(self.capacity, self.sample_seed))

    def _resolve_shardings(self):
        if self.mesh is None:
            return None
        params_struct = jax.eval_shape(
            functools.partial(lm.init_lm, jax.random.PRNGKey(0),
                              self.run.arch))
        cache_struct, state_struct = jax.eval_shape(self._init_fn())
        with shd.shard_ctx(self.mesh, self.sharding):
            params_sh = shd.param_shardings(params_struct)
            cache_sh, state_sh = shd.serve_shardings(
                cache_struct, state_struct, self.capacity)
        return params_sh, cache_sh, state_sh

    def _resolve_spec_shardings(self):
        if self.mesh is None or self.spec is None:
            return None
        dparams_struct = jax.eval_shape(
            functools.partial(lm.init_lm, jax.random.PRNGKey(0),
                              self.draft_cfg))
        dcache_struct = jax.eval_shape(
            lambda: lm.init_cache(self.draft_cfg, self.capacity,
                                  self.max_len))
        with shd.shard_ctx(self.mesh, self.sharding):
            dparams_sh = shd.param_shardings(dparams_struct)
            dcache_sh = shd.cache_shardings(dcache_struct, self.capacity)
        return dparams_sh, dcache_sh

    def init_params(self, key):
        """Random target parameters from ``key``, built directly in the
        engine's layout: on a mesh each device only ever holds its shard,
        so a model larger than one device's memory can be served."""
        return _init_params(key, self.run.arch,
                            self._shardings[0] if self._shardings else None)

    def place_params(self, params):
        """device_put ``params`` per the engine's sharding, so repeated
        decode/prefill calls hit the jit fast path instead of re-sharding
        uncommitted host arrays every chunk. Identity with no mesh."""
        if self._shardings is None:
            return params
        return jax.device_put(params, self._shardings[0])

    # -- device state ------------------------------------------------------

    def init_state(self):
        # jitted so every leaf is a DISTINCT device buffer — eagerly built
        # zero caches can alias identical constants, which breaks donation
        # (same workaround as the trainer's init; see trainer.py)
        kw = {}
        if self._shardings is not None:
            _, cache_sh, state_sh = self._shardings
            kw = dict(out_shardings=(cache_sh, state_sh))
        if self.spec is not None:
            # fresh engine-owned draft cache rides along (separate jitted
            # init: distinct donation-safe buffers)
            dkw = {}
            if self._spec_shardings is not None:
                dkw = dict(out_shardings=self._spec_shardings[1])
            self._draft_cache = jax.jit(self._traced(
                functools.partial(lm.init_cache, self.draft_cfg,
                                  self.capacity, self.max_len)), **dkw)()
        return jax.jit(self._traced(self._init_fn()), **kw)()

    @property
    def tokens_per_chunk(self) -> int:
        """Max tokens one decode chunk can realize per slot — what the
        scheduler's page growth must cover (``chunk`` rounds × the k+1
        verify rows under speculation, plain ``chunk`` otherwise)."""
        return self.chunk * (self.spec_k + 1) if self.spec is not None \
            else self.chunk

    # -- chaos injection ---------------------------------------------------

    def _check_fault(self, site: str) -> None:
        if self.injector is not None:
            self.injector.check(site)

    # -- admission ---------------------------------------------------------

    def _bucket(self, t: int) -> int:
        b = self.prompt_bucket
        return min(-(-t // b) * b, self.max_len)

    @staticmethod
    def _seed_args(seed: Optional[int]):
        """(rng0 u32[2], has_seed bool) traced pair for a per-request
        sample seed — both are DATA, so seeded and unseeded admissions
        share one trace (and greedy traces treat them as dead args)."""
        rng0 = (jax.random.PRNGKey(seed) if seed is not None
                else jnp.zeros((2,), jnp.uint32))
        return (jnp.asarray(rng0, jnp.uint32),
                jnp.asarray(seed is not None))

    def prefill_into(self, params, cache, st, prompt, slot: int,
                     max_new: int, page_ids=None, seed: Optional[int] = None):
        """Admit one request: bucketed batch-1 prefill into ``slot``.
        prompt: 1-D int32 array/list. Paged engines additionally take the
        host-allocated ``page_ids`` (one per bucket page, position order).
        ``seed``: optional per-request sample seed (replayable sampling
        independent of slot placement; ignored by greedy engines).
        Returns (cache, st, first_token)."""
        self._check_fault("prefill")
        prompt = jnp.asarray(prompt, jnp.int32)
        t = int(prompt.shape[0])
        assert t + max_new <= self.max_len, (t, max_new, self.max_len)
        assert (page_ids is not None) == self.paged, \
            "page_ids required iff the engine is paged"
        bucket = self._bucket(t)
        if bucket not in self._prefill:
            self.prefill_traces += 1
            make = (make_prefill_slot_paged(self.run, bucket, self.page_size,
                                            self._sampler)
                    if self.paged else
                    make_prefill_slot(self.run, bucket, self._sampler))
            kw = {}
            if self._shardings is not None:
                params_sh, cache_sh, state_sh = self._shardings
                rep = NamedSharding(self.mesh, P())
                tok_sh = NamedSharding(self.mesh, P(None, None))
                in_sh = (params_sh, cache_sh, state_sh, tok_sh,
                         rep, rep, rep)
                if self.paged:
                    in_sh = in_sh + (NamedSharding(self.mesh, P(None)),)
                in_sh = in_sh + (NamedSharding(self.mesh, P(None)), rep)
                kw = dict(in_shardings=in_sh,
                          out_shardings=(cache_sh, state_sh, rep))
            self._prefill[bucket] = jax.jit(self._traced(make),
                                            donate_argnums=(1, 2), **kw)
        padded = jnp.zeros((1, bucket), jnp.int32).at[0, :t].set(prompt)
        args = (params, cache, st, padded, jnp.asarray(t, jnp.int32),
                jnp.asarray(slot, jnp.int32), jnp.asarray(max_new, jnp.int32))
        if self.paged:
            n_bucket = -(-bucket // self.page_size)
            assert page_ids.shape == (n_bucket,), (page_ids.shape, n_bucket)
            args = args + (jnp.asarray(page_ids, jnp.int32),)
        self.prefill_tokens += bucket
        out = self._prefill[bucket](*args + self._seed_args(seed))
        if self.spec is not None:
            self._admit_draft(params, padded, t, slot)
        return out

    def _admit_draft(self, params, padded, t: int, slot: int) -> None:
        """Prefill the FULL prompt into the draft's slot cache (spec only).
        Always contiguous and always full-prompt — a fork-point admission
        shares only the TARGET's KV; the draft's own cache has no radix
        index yet (ROADMAP follow-up)."""
        bucket = int(padded.shape[1])
        if bucket not in self._draft_prefill:
            make = make_draft_prefill(self.draft_cfg, self.run.accel, bucket)
            kw = {}
            if self._spec_shardings is not None:
                dparams_sh, dcache_sh = self._spec_shardings
                rep = NamedSharding(self.mesh, P())
                tok_sh = NamedSharding(self.mesh, P(None, None))
                kw = dict(in_shardings=(dparams_sh, dcache_sh, tok_sh,
                                        rep, rep),
                          out_shardings=dcache_sh)
            self._draft_prefill[bucket] = jax.jit(self._traced(make),
                                                  donate_argnums=(1,), **kw)
        dparams = params if self.spec.share_params else self.draft_params
        self._draft_cache = self._draft_prefill[bucket](
            dparams, self._draft_cache, padded,
            jnp.asarray(t, jnp.int32), jnp.asarray(slot, jnp.int32))

    # -- prefix-sharing admission ------------------------------------------

    def copy_page(self, cache, src: int, dst: int):
        """Copy-on-write: duplicate pool page ``src`` into the slot's
        exclusive page ``dst`` across every attention layer (one jitted
        donated call; traced page ids, so every COW reuses the trace)."""
        assert self.paged
        if self._copy_page is None:
            kw = {}
            if self._shardings is not None:
                _, cache_sh, _ = self._shardings
                rep = NamedSharding(self.mesh, P())
                kw = dict(in_shardings=(cache_sh, rep, rep),
                          out_shardings=cache_sh)
            self._copy_page = jax.jit(self._traced(lm.copy_pages),
                                      donate_argnums=(0,), **kw)
        return self._copy_page(cache, jnp.asarray(src, jnp.int32),
                               jnp.asarray(dst, jnp.int32))

    def prefill_into_shared(self, params, cache, st, prompt, start: int,
                            slot: int, max_new: int, prefix_ids, region_ids,
                            row, seed: Optional[int] = None):
        """Admit one request at its FORK POINT: only ``prompt[start:]`` is
        prefilled; positions [0, start) are already resident in the shared
        ``prefix_ids`` pages (plus the first ``start mod page_size`` rows
        of the COW page ``region_ids[0]``). ``row`` is the slot's complete
        host mirror page-table row. One trace per (suffix bucket, pow2
        prefix cap). Returns (cache, st, first_token)."""
        self._check_fault("prefill")
        assert self.paged and self.shared_prefill_ok
        prompt = jnp.asarray(prompt, jnp.int32)
        t = int(prompt.shape[0])
        assert 0 < start < t and t + max_new <= self.max_len
        tsuf = t - start
        suffix_bucket = self._bucket(tsuf)
        n_full = int(np.asarray(prefix_ids).shape[0])
        n_prefix = n_full * self.page_size
        assert n_prefix <= start < n_prefix + self.page_size
        pcap = 1 << max(0, n_full - 1).bit_length() if n_full > 1 else 1
        n_region_cap = -(-suffix_bucket // self.page_size) + 1
        key = (suffix_bucket, pcap)
        if key not in self._prefill_shared:
            self.prefill_traces += 1
            make = make_prefill_slot_shared(self.run, suffix_bucket, pcap,
                                            self.page_size, self._sampler)
            kw = {}
            if self._shardings is not None:
                params_sh, cache_sh, state_sh = self._shardings
                rep = NamedSharding(self.mesh, P())
                tok_sh = NamedSharding(self.mesh, P(None, None))
                vec = NamedSharding(self.mesh, P(None))
                in_sh = (params_sh, cache_sh, state_sh, tok_sh,
                         rep, rep, rep, rep, rep, vec, vec, vec,
                         vec, rep)
                kw = dict(in_shardings=in_sh,
                          out_shardings=(cache_sh, state_sh, rep))
            self._prefill_shared[key] = jax.jit(self._traced(make),
                                                donate_argnums=(1, 2), **kw)
        pids = np.full((pcap,), -1, np.int32)
        pids[:n_full] = np.asarray(prefix_ids, np.int32)
        rids = np.zeros((n_region_cap,), np.int32)      # pad -> scratch 0
        n_region = int(np.asarray(region_ids).shape[0])
        assert n_region <= n_region_cap
        rids[:n_region] = np.asarray(region_ids, np.int32)
        padded = jnp.zeros((1, suffix_bucket),
                           jnp.int32).at[0, :tsuf].set(prompt[start:])
        args = (params, cache, st, padded,
                jnp.asarray(start, jnp.int32),
                jnp.asarray(n_prefix, jnp.int32),
                jnp.asarray(t, jnp.int32),
                jnp.asarray(slot, jnp.int32),
                jnp.asarray(max_new, jnp.int32),
                jnp.asarray(pids), jnp.asarray(rids),
                jnp.asarray(row, jnp.int32))
        self.prefill_tokens += suffix_bucket
        out = self._prefill_shared[key](*args + self._seed_args(seed))
        if self.spec is not None:
            fb = self._bucket(t)
            dpadded = jnp.zeros((1, fb), jnp.int32).at[0, :t].set(prompt)
            self._admit_draft(params, dpadded, t, slot)
        return out

    # -- chunked prefill ---------------------------------------------------

    def prefill_chunk(self, params, cache, chunk_tokens, start: int,
                      slot: int, prefix_ids, region_ids, row):
        """Run ONE intermediate chunk of a chunked prefill (no admission,
        no logits): ``chunk_tokens`` (exactly C tokens, C page-aligned) are
        prefilled at absolute positions [start, start + C) against the
        slot's resident pages ``prefix_ids`` and written into the next
        ``region_ids``. ``row`` is the slot's complete mirror page-table
        row. One trace per (C, pow2 prefix cap). Returns the cache."""
        self._check_fault("prefill")
        assert self.paged and self.shared_prefill_ok
        chunk_tokens = jnp.asarray(chunk_tokens, jnp.int32)
        c_len = int(chunk_tokens.shape[0])
        assert c_len % self.page_size == 0 and start % c_len == 0, \
            (c_len, start, self.page_size)
        n_full = int(np.asarray(prefix_ids).shape[0])
        assert n_full * self.page_size == start, (n_full, start)
        assert int(np.asarray(region_ids).shape[0]) == \
            c_len // self.page_size
        pcap = 1 << max(0, n_full - 1).bit_length() if n_full > 1 else 1
        key = (c_len, pcap)
        if key not in self._prefill_chunk:
            self.prefill_traces += 1
            make = make_prefill_chunk(self.run, c_len, pcap, self.page_size)
            kw = {}
            if self._shardings is not None:
                params_sh, cache_sh, _ = self._shardings
                rep = NamedSharding(self.mesh, P())
                tok_sh = NamedSharding(self.mesh, P(None, None))
                vec = NamedSharding(self.mesh, P(None))
                kw = dict(in_shardings=(params_sh, cache_sh, tok_sh,
                                        rep, rep, vec, vec, vec),
                          out_shardings=cache_sh)
            self._prefill_chunk[key] = jax.jit(self._traced(make),
                                               donate_argnums=(1,), **kw)
        pids = np.full((pcap,), -1, np.int32)
        pids[:n_full] = np.asarray(prefix_ids, np.int32)
        self.prefill_tokens += c_len
        return self._prefill_chunk[key](
            params, cache, chunk_tokens[None],
            jnp.asarray(start, jnp.int32), jnp.asarray(slot, jnp.int32),
            jnp.asarray(pids), jnp.asarray(region_ids, jnp.int32),
            jnp.asarray(row, jnp.int32))

    # -- preemption: host swap + slot resume -------------------------------

    def _pad_pow2(self, page_ids) -> np.ndarray:
        # pad to ONE fixed shape (pow2 of the per-slot page cap) rather
        # than the next pow2 of the count: swap-outs happen mid-stream
        # under overload, where a fresh jit trace per new page count would
        # stall every in-flight decode for far longer than the extra pad
        # blocks cost to move
        ids = np.asarray(page_ids, np.int32)
        assert len(ids) <= self.max_pages, (len(ids), self.max_pages)
        cap = 1 << max(0, self.max_pages - 1).bit_length() \
            if self.max_pages > 1 else 1
        out = np.zeros((cap,), np.int32)     # pad -> scratch page 0
        out[:len(ids)] = ids
        return out

    def fetch_pages(self, cache, page_ids):
        """SWAP-OUT: gather the pool pages ``page_ids`` (position order)
        from every attention layer into one host-transferable pytree. Ids
        are padded to the next pow2 with the scratch page, so traces are
        shared across page counts; the pad blocks ride along (their bytes
        are garbage and are re-written to scratch on restore). Output
        shardings are inferred from the committed cache."""
        self._check_fault("swap")
        assert self.paged
        pids = self._pad_pow2(page_ids)
        cap = len(pids)
        if cap not in self._gather_pages:
            self._gather_pages[cap] = jax.jit(
                self._traced(lm.gather_pages))
        blocks = self._gather_pages[cap](cache, jnp.asarray(pids))
        return jax.device_get(blocks)

    def restore_pages(self, cache, page_ids, blocks):
        """SWAP-IN: write ``blocks`` (a :meth:`fetch_pages` result) into
        the FRESH pool pages ``page_ids`` — same position order, possibly
        different ids than at swap-out. Pad writes land on scratch."""
        assert self.paged
        pids = self._pad_pow2(page_ids)
        cap = len(pids)
        if cap not in self._scatter_pages:
            kw = {}
            if self._shardings is not None:
                _, cache_sh, _ = self._shardings
                kw = dict(out_shardings=cache_sh)
            self._scatter_pages[cap] = jax.jit(
                self._traced(lm.scatter_pages), donate_argnums=(0,), **kw)
        return self._scatter_pages[cap](cache, jnp.asarray(pids), blocks)

    def restore_slot(self, cache, st, slot: int, token: int, budget: int,
                     pos: int, rng_row=None):
        """Re-arm ``slot`` after a swap-in: last generated token becomes
        the next decode input, ``budget`` tokens remain, the cache position
        points at the one KV row not yet written (the last token's), and —
        when the victim was sampling — its PRNG row is restored so the
        resumed sample stream is bitwise identical."""
        if self._restore_slot is None:
            def restore(cache, st, slot, token, budget, pos, rng_row,
                        has_rng):
                st = st._replace(
                    tokens=st.tokens.at[slot].set(token),
                    done=st.done.at[slot].set(budget <= 0),
                    generated=st.generated.at[slot].set(0),
                    budget=st.budget.at[slot].set(budget),
                    rng=st.rng.at[slot].set(
                        jnp.where(has_rng, rng_row, st.rng[slot])),
                    quarantined=st.quarantined.at[slot].set(False))
                cache = cache._replace(pos=cache.pos.at[slot].set(pos))
                return cache, st
            kw = {}
            if self._shardings is not None:
                _, cache_sh, state_sh = self._shardings
                rep = NamedSharding(self.mesh, P())
                vec = NamedSharding(self.mesh, P(None))
                kw = dict(in_shardings=(cache_sh, state_sh, rep, rep, rep,
                                        rep, vec, rep),
                          out_shardings=(cache_sh, state_sh))
            self._restore_slot = jax.jit(self._traced(restore),
                                         donate_argnums=(0, 1), **kw)
        has_rng = rng_row is not None
        row = (jnp.asarray(rng_row, jnp.uint32) if has_rng
               else jnp.zeros((2,), jnp.uint32))
        return self._restore_slot(
            cache, st, jnp.asarray(slot, jnp.int32),
            jnp.asarray(token, jnp.int32), jnp.asarray(budget, jnp.int32),
            jnp.asarray(pos, jnp.int32), row, jnp.asarray(has_rng))

    def deactivate_slot(self, st, slot: int):
        """Kill a PREEMPTED slot on device: mark it done so the next decode
        chunk freezes its token, pins its cache position and masks it out
        of MoE routing. Its page-table row is cleared host-side (appends
        route to scratch); the next admission overwrites the rest."""
        if self._deactivate is None:
            def deact(st, slot):
                return st._replace(done=st.done.at[slot].set(True))
            kw = {}
            if self._shardings is not None:
                _, _, state_sh = self._shardings
                rep = NamedSharding(self.mesh, P())
                kw = dict(in_shardings=(state_sh, rep),
                          out_shardings=state_sh)
            self._deactivate = jax.jit(self._traced(deact),
                                       donate_argnums=(0,), **kw)
        return self._deactivate(st, jnp.asarray(slot, jnp.int32))

    # -- paged page-table sync ---------------------------------------------

    def set_page_table(self, cache, table) -> "lm.PagedLMCache":
        """Push the host mirror of the page table to the device cache
        (between chunks — the table is data, never trace shape). On a mesh
        the push is placed REPLICATED up front — matching the decode
        chunk's in_shardings, so a dirty table never triggers a per-chunk
        re-shard inside jit."""
        assert self.paged
        t = jnp.asarray(table, jnp.int32)
        if self.mesh is not None:
            t = jax.device_put(t, NamedSharding(self.mesh, P(None, None)))
        return cache._replace(page_table=t)

    def scrub_slot_kv(self, cache, slot: int, page_ids=None):
        """Zero a QUARANTINED slot's attention KV before its pages / row
        are recycled. Retired pages normally return to the free list
        unzeroed — junk is masked at read time — but NaN junk SURVIVES
        masking: the softmax mixes values with exactly-zero weights and
        ``0 * NaN = NaN``, so a later occupant of the page would go
        non-finite too. Rare path (one call per quarantined request)."""
        paged_types = (attn.PagedKVCache, attn.PagedMLACache)
        contig_types = (attn.KVCache, attn.MLACache)
        if self.paged:
            pids = jnp.asarray(list(page_ids or ()), jnp.int32)
            if pids.size == 0:
                return cache

            def hit(state, stacked):
                if isinstance(state, paged_types):
                    if stacked:                     # [n_sb, P, ...]
                        return type(state)(*(a.at[:, pids].set(0)
                                             for a in state))
                    return type(state)(*(a.at[pids].set(0) for a in state))
                return state
        else:
            def hit(state, stacked):
                if isinstance(state, contig_types):
                    if stacked:                     # [n_sb, B, ...]
                        return type(state)(*(a.at[:, slot].set(0)
                                             for a in state))
                    return type(state)(*(a.at[slot].set(0) for a in state))
                return state

        return cache._replace(
            prefix=tuple(hit(c, False) for c in cache.prefix),
            slots=tuple(hit(c, True) for c in cache.slots))

    # -- snapshot / restore ------------------------------------------------

    def snapshot(self, cache, st: DecodeState, alloc=None) -> dict:
        """Capture the device half of a serve stream host-side: the full
        DecodeState (per-slot rng rows included) plus the attention KV.

        Paged all-attention engines gather ONLY the allocated pool pages
        (``alloc.refcnt`` keys — slot-owned and index-retained alike),
        reusing the pow2-padded swap gather, in groups of ``max_pages`` so
        every group shares the host-swap traces. Everything else (contiguous
        rows, recurrent mixer states, hybrid paged caches) falls back to a
        full ``device_get`` of the cache. The result is pure host data —
        restorable any number of times.
        """
        state_np = jax.device_get(st)
        # the draft cache is engine-owned derived state, but sampled spec
        # streams DO depend on it (proposals feed the rejection rule), so
        # deterministic replay captures it alongside the target KV
        draft = (jax.device_get(self._draft_cache)
                 if self.spec is not None else None)
        if self.paged and alloc is not None and self._page_snapshot_ok:
            pids = sorted(alloc.refcnt)
            groups = [pids[i:i + self.max_pages]
                      for i in range(0, len(pids), self.max_pages)]
            return {"kind": "paged", "state": state_np, "draft": draft,
                    "pos": np.asarray(jax.device_get(cache.pos)),
                    "pages": [(g, self.fetch_pages(cache, g))
                              for g in groups]}
        return {"kind": "full", "state": state_np, "draft": draft,
                "cache": jax.device_get(cache)}

    def restore(self, snap: dict, alloc=None):
        """Rebuild fresh (cache, DecodeState) device buffers from a
        :meth:`snapshot` — every array the decode chunk can read is
        bitwise the captured one, so the resumed stream replays the
        uninterrupted run's tokens exactly (greedy AND sampled: the rng
        rows come back too). Compiled traces are untouched; only buffers
        are recreated, so a restore never re-traces."""
        st = self._put_state(snap["state"])
        if self.spec is not None and snap.get("draft") is not None:
            dc = jax.tree_util.tree_map(jnp.asarray, snap["draft"])
            if self._spec_shardings is not None:
                dc = jax.device_put(dc, self._spec_shardings[1])
            self._draft_cache = dc
        if snap["kind"] == "paged":
            assert alloc is not None, "paged restore needs the allocator"
            cache, _ = self.init_state()
            for group, blocks in snap["pages"]:
                cache = self.restore_pages(cache, group, blocks)
            cache = self.set_page_table(cache, alloc.table)
            pos = jnp.asarray(snap["pos"], jnp.int32)
            if self._shardings is not None:
                pos = jax.device_put(pos, self._shardings[1].pos)
            cache = cache._replace(pos=pos)
        else:
            cache = jax.tree_util.tree_map(jnp.asarray, snap["cache"])
            if self._shardings is not None:
                cache = jax.device_put(cache, self._shardings[1])
        return cache, st

    def _put_state(self, state_np) -> DecodeState:
        st = DecodeState(*(jnp.asarray(x) for x in state_np))
        if self._shardings is not None:
            st = jax.device_put(st, self._shardings[2])
        return st

    def kv_bytes(self, cache=None) -> int:
        """Total bytes of attention KV storage (pools or contiguous rows).

        Sizes are static, so with no ``cache`` the tree is built with
        ``jax.eval_shape`` — no device allocation."""
        import math
        from repro.models.attention import (KVCache, MLACache, PagedKVCache,
                                            PagedMLACache)
        if cache is None:
            cache, _ = jax.eval_shape(self._init_fn())
        total = 0
        for state in tuple(cache.prefix) + tuple(cache.slots):
            if isinstance(state, (KVCache, MLACache, PagedKVCache,
                                  PagedMLACache)):
                total += sum(math.prod(a.shape) * a.dtype.itemsize
                             for a in state)
        return total

    def set_draft_params(self, dparams):
        """Install externally-trained draft weights (e.g. a distilled
        draft).  Structure must match the engine's initialised draft tree;
        placement follows the engine mesh."""
        assert self.spec is not None and not self.spec.share_params, \
            "engine has no independent draft model"
        assert (jax.tree.structure(dparams)
                == jax.tree.structure(self.draft_params)), \
            "draft param tree does not match the configured draft arch"
        assert jax.tree.all(jax.tree.map(
            lambda a, b: a.shape == b.shape and a.dtype == b.dtype,
            dparams, self.draft_params)), \
            "draft param tree leaves do not match the configured draft arch"
        if self._spec_shardings is not None:
            self.draft_params = jax.device_put(dparams,
                                               self._spec_shardings[0])
        else:
            self.draft_params = jax.device_put(dparams)

    # -- decode ------------------------------------------------------------

    def decode(self, params, cache, st):
        """Run one jitted chunk. Returns (cache, st, tokens
        [S, tokens_per_chunk]) — per-slot valid tokens left-packed; the
        caller reads exactly the per-slot ``generated`` delta."""
        self._check_fault("decode")
        self.decode_calls += 1
        if self.spec is None:
            return self._decode(params, cache, st)
        dparams = params if self.spec.share_params else self.draft_params
        cache, self._draft_cache, st, toks = self._decode(
            params, dparams, cache, self._draft_cache, st)
        return cache, st, toks

    @staticmethod
    def stats(st: DecodeState) -> Dict[str, float]:
        """One host fetch of the on-device accumulators."""
        n = max(float(st.live_cnt), 1.0)
        prop = float(st.spec_prop)
        return {"exit_rate": float(st.exit_cnt) / n,
                "gated_fraction": float(st.gated_layers) / n,
                "decode_slot_steps": float(st.live_cnt),
                "realized_tokens": float(st.realized),
                "spec_proposed": prop,
                "spec_accepted": float(st.spec_acc),
                "spec_acceptance": float(st.spec_acc) / max(prop, 1.0)}
