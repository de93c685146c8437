"""Paged KV cache: token identity vs the contiguous engine, page-aware
admission, allocator invariants (no aliasing between live slots), the
attn_decode_paged op/backends, slot-lifecycle round-trips and the
over-long-prompt rejection regression."""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import (AccelConfig, RunConfig, SHAPES_BY_NAME,
                                get_arch)
from repro.core import xaif
from repro.dist.sharding import make_mesh
from repro.models import lm
from repro.serve.engine import SlotEngine, generate
from repro.serve.paging import PageAllocator
from repro.serve.scheduler import (ADMITTED, FULL, REJECTED, Request,
                                   SlotScheduler, serve)

ACCEL = AccelConfig()


def _run_for(cfg):
    return RunConfig(arch=cfg, shape=SHAPES_BY_NAME["decode_32k"],
                     accel=ACCEL)


def _requests(cfg, n, seed=0, max_prompt=13, max_new=8):
    rng = np.random.default_rng(seed)
    return [Request(
        rid=i,
        prompt=rng.integers(0, cfg.vocab_size,
                            (int(rng.integers(2, max_prompt)),),
                            dtype=np.int32),
        max_new_tokens=int(rng.integers(2, max_new + 1)))
        for i in range(n)]


# ---------------------------------------------------------------------------
# Token identity
# ---------------------------------------------------------------------------


def test_paged_engine_matches_host_loop_with_backfill():
    """7 mixed-length requests through 3 slots of the PAGED engine: every
    request's tokens equal a solo reference run — page churn (admission
    scatter, on-demand growth, release/reuse) must not leak into numerics."""
    cfg = get_arch("chatglm3-6b").reduced()
    run = _run_for(cfg)
    params = lm.init_lm(jax.random.PRNGKey(0), cfg)
    engine = SlotEngine(run, capacity=3, max_len=32, chunk=4, paged=True,
                        page_size=8)
    reqs = _requests(cfg, 7)
    report = serve(engine, params, reqs)
    assert engine.decode_traces == 1          # page churn never re-traces
    for r in report.requests:
        assert len(r.tokens) == r.max_new_tokens
        ref, _ = generate(run, params, jnp.asarray(r.prompt)[None],
                          max_new_tokens=r.max_new_tokens, max_len=32)
        np.testing.assert_array_equal(np.asarray(r.tokens),
                                      np.asarray(ref)[0], str(r.rid))


@pytest.mark.parametrize("arch", ["deepseek-v2-lite-16b", "jamba-v0.1-52b"])
def test_paged_engine_matches_solo_reference(arch):
    """MLA (paged latent) and hybrid attn+Mamba MoE archs: BOTH engines are
    token-identical to a SOLO run of the reference loop. Until PR 5 the solo
    loop was not a valid oracle for MoE archs (capacity sharing made decode
    composition-dependent — the old version of this test could only compare
    paged vs contiguous on the SAME stream); dropless MoE decode removed
    the carve-out."""
    cfg = get_arch(arch).reduced()
    run = _run_for(cfg)
    params = lm.init_lm(jax.random.PRNGKey(0), cfg)
    for paged in (False, True):
        engine = SlotEngine(run, capacity=2, max_len=24, chunk=3,
                            paged=paged, page_size=8)
        report = serve(engine, params, _requests(cfg, 4, seed=1,
                                                 max_prompt=10, max_new=6))
        for r in report.requests:
            ref, _ = generate(run, params, jnp.asarray(r.prompt)[None],
                              max_new_tokens=r.max_new_tokens, max_len=24)
            np.testing.assert_array_equal(np.asarray(r.tokens),
                                          np.asarray(ref)[0],
                                          f"paged={paged} rid={r.rid}")


from conftest import needs_mesh


@needs_mesh
@pytest.mark.parametrize("name,shape",
                         [("dp4", (4, 1)), ("tp4", (1, 4)),
                          ("dp2xtp2", (2, 2))])
def test_mesh_paged_engine_token_identity(name, shape):
    """The PAGED engine on a real mesh — page pools head-sharded per tp,
    page table replicated and pushed between chunks, slot axis over data —
    stays token-identical to the single-device paged engine under page
    churn (admission scatter, on-demand growth, release/reuse)."""
    from repro.configs.base import ShardingPolicy
    cfg = get_arch("chatglm3-6b").reduced()
    run = _run_for(cfg)
    params = lm.init_lm(jax.random.PRNGKey(0), cfg)
    single = SlotEngine(run, capacity=4, max_len=32, chunk=4, paged=True,
                        page_size=8)
    ref = {r.rid: r.tokens
           for r in serve(single, params, _requests(cfg, 7)).requests}
    mesh = make_mesh(shape, ("data", "model"))
    engine = SlotEngine(run, capacity=4, max_len=32, chunk=4, paged=True,
                        page_size=8, mesh=mesh,
                        sharding=ShardingPolicy(fsdp=False))
    report = serve(engine, params, _requests(cfg, 7))
    assert engine.decode_traces == 1          # page churn never re-traces
    assert {r.rid: r.tokens for r in report.requests} == ref


@needs_mesh
def test_mesh_paged_pool_sharding_applied():
    """The running mesh engine really holds its pools tp-sharded and its
    page table replicated (not just in the spec helpers)."""
    from repro.configs.base import ShardingPolicy
    cfg = get_arch("chatglm3-6b").reduced()
    run = _run_for(cfg)
    mesh = make_mesh((2, 2), ("data", "model"))
    engine = SlotEngine(run, capacity=4, max_len=32, chunk=4, paged=True,
                        page_size=8, mesh=mesh,
                        sharding=ShardingPolicy(fsdp=False))
    cache, st = engine.init_state()
    kp = cache.slots[0].k_pages                 # [n_sb, P, Hkv, ps, D]
    assert kp.sharding.spec[-3] == "model", kp.sharding
    assert all(a is None for a in cache.page_table.sharding.spec)
    table = np.full((4, engine.max_pages), -1, np.int32)
    cache = engine.set_page_table(cache, table)
    assert all(a is None for a in cache.page_table.sharding.spec)


# ---------------------------------------------------------------------------
# Page-aware admission + allocator invariants
# ---------------------------------------------------------------------------


def test_admission_is_bounded_by_free_pages():
    """With a pool that fits ~2 in-flight requests, a 4-slot engine must
    cap concurrency by PAGES yet still serve the whole stream."""
    cfg = get_arch("chatglm3-6b").reduced()
    run = _run_for(cfg)
    params = lm.init_lm(jax.random.PRNGKey(0), cfg)
    # each request reserves <= ceil((12 + 8)/8) = 3 pages; 6 usable pages
    engine = SlotEngine(run, capacity=4, max_len=32, chunk=4, paged=True,
                        page_size=8, num_pages=7)
    reqs = _requests(cfg, 6, seed=2)
    report = serve(engine, params, reqs)
    assert all(len(r.tokens) == r.max_new_tokens for r in report.requests)
    assert report.stats["max_concurrency"] <= 3
    assert report.stats["peak_pages"] <= 6
    for r in report.requests:
        ref, _ = generate(run, params, jnp.asarray(r.prompt)[None],
                          max_new_tokens=r.max_new_tokens, max_len=32)
        np.testing.assert_array_equal(np.asarray(r.tokens),
                                      np.asarray(ref)[0], str(r.rid))


def _check_alloc_invariants(alloc: PageAllocator):
    owned_all = [p for pages in alloc.owned.values() for p in pages]
    assert len(owned_all) == len(set(owned_all)), "page aliased across slots"
    assert 0 not in owned_all, "scratch page allocated"
    assert not (set(owned_all) & set(alloc.free)), "owned page also free"
    for slot, pages in alloc.owned.items():
        n = len(pages)
        assert list(alloc.table[slot, :n]) == pages
        assert (alloc.table[slot, n:] == -1).all()
    for slot in range(alloc.table.shape[0]):
        if slot not in alloc.owned:
            assert (alloc.table[slot] == -1).all()


def test_retire_backfill_never_aliases_pages():
    """Property-style churn over the live scheduler: after every admission
    and every chunk, live slots own disjoint page sets, the scratch page is
    never allocated, and the mirror rows match ownership exactly."""
    cfg = get_arch("chatglm3-6b").reduced()
    run = _run_for(cfg)
    params = lm.init_lm(jax.random.PRNGKey(0), cfg)
    engine = SlotEngine(run, capacity=3, max_len=32, chunk=2, paged=True,
                        page_size=8, num_pages=10)
    sched = SlotScheduler(engine, params)
    waiting = _requests(cfg, 8, seed=3)
    steps = 0
    while waiting or sched.busy:
        while waiting and sched.free:
            if sched.admit(waiting[0], 0.0) != ADMITTED:
                break
            waiting.pop(0)
            _check_alloc_invariants(sched.alloc)
        if sched.busy:
            sched.step_chunk(0.0)
            _check_alloc_invariants(sched.alloc)
        steps += 1
        assert steps < 200
    assert not sched.alloc.owned                    # all pages returned
    assert len(sched.alloc.free) == engine.num_pages - 1


def test_allocator_reservation_accounting():
    alloc = PageAllocator(num_pages=9, capacity=4, max_pages=4, page_size=8)
    assert alloc.available == 8
    ids = alloc.admit(0, bucket_len=16, true_len=12, max_new=12)
    assert list(ids) == [1, 2]                      # bucket pages allocated
    # reservation is the worst case ceil((12+12)/8)=3, not just the bucket
    assert alloc.available == 8 - 3
    alloc.ensure(0, last_pos=17)                    # 3rd page on demand
    assert len(alloc.owned[0]) == 3 and alloc.available == 5
    assert not alloc.can_admit(bucket_len=48, true_len=41, max_new=8)
    alloc.release(0)
    assert alloc.available == 8 and not alloc.owned


# ---------------------------------------------------------------------------
# Rejection regression (no silent truncation)
# ---------------------------------------------------------------------------


def test_admit_rejects_overlong_prompt():
    """A prompt with prompt+budget > max_len must come back REJECTED with a
    reason — never silently truncated — while the rest of the stream is
    served normally."""
    cfg = get_arch("chatglm3-6b").reduced()
    run = _run_for(cfg)
    params = lm.init_lm(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    ok = _requests(cfg, 3, seed=4)
    too_long = Request(rid=99,
                       prompt=rng.integers(0, cfg.vocab_size, (40,),
                                           dtype=np.int32),
                       max_new_tokens=8)
    engine = SlotEngine(run, capacity=2, max_len=24, chunk=4)
    report = serve(engine, params, ok + [too_long])
    assert too_long.reject_reason is not None
    assert "max_len" in too_long.reject_reason
    assert too_long.tokens == [] and too_long.t_finished is None
    assert report.rejected == [too_long]
    assert all(len(r.tokens) == r.max_new_tokens for r in report.served)


def test_admit_outcomes_direct():
    cfg = get_arch("chatglm3-6b").reduced()
    run = _run_for(cfg)
    params = lm.init_lm(jax.random.PRNGKey(0), cfg)
    engine = SlotEngine(run, capacity=1, max_len=24, chunk=4)
    sched = SlotScheduler(engine, params)
    r1, r2 = _requests(cfg, 2, seed=5)
    assert sched.admit(r1, 0.0) == ADMITTED
    assert sched.admit(r2, 0.0) == FULL             # retryable, no reason
    assert r2.reject_reason is None
    bad = Request(rid=7, prompt=np.zeros((30,), np.int32), max_new_tokens=8)
    assert sched.admit(bad, 0.0) == REJECTED


# ---------------------------------------------------------------------------
# Slot lifecycle round-trips
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("arch", ["chatglm3-6b", "xlstm-350m"])
def test_fill_reset_fill_roundtrip_equals_fresh(arch):
    """fill_slot -> reset_slot -> fill_slot must equal a single fill into a
    fresh cache, leaf for leaf (KV and recurrent states alike)."""
    cfg = get_arch(arch).reduced()
    params = lm.init_lm(jax.random.PRNGKey(0), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(1), (1, 6), 0,
                              cfg.vocab_size)
    slot_cache = lm.init_cache(cfg, 1, 8)
    _, slot_cache = lm.forward_prefill(params, toks, cfg, ACCEL, slot_cache)
    fresh = lm.fill_slot(lm.init_cache(cfg, 3, 16), slot_cache, 1, 6)
    cycled = lm.init_cache(cfg, 3, 16)
    for _ in range(2):
        cycled = lm.fill_slot(cycled, slot_cache, 1, 6)
        other = lm.fill_slot(cycled, slot_cache, 2, 6)   # neighbor churn
        cycled = lm.reset_slot(other, 2)
        cycled = lm.reset_slot(cycled, 1)
    cycled = lm.fill_slot(cycled, slot_cache, 1, 6)
    for a, b in zip(jax.tree_util.tree_leaves(fresh),
                    jax.tree_util.tree_leaves(cycled)):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))


@pytest.mark.parametrize("arch", ["chatglm3-6b", "jamba-v0.1-52b"])
def test_paged_fill_free_fill_roundtrip_equals_fresh(arch):
    """Device-side paged lifecycle: fill_slot_paged -> free_slot_paged ->
    fill_slot_paged (same pages) equals a single fill into a fresh paged
    cache — pos/table/recurrent state reset exactly, pools re-scattered."""
    cfg = get_arch(arch).reduced()
    params = lm.init_lm(jax.random.PRNGKey(0), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(1), (1, 6), 0,
                              cfg.vocab_size)
    slot_cache = lm.init_cache(cfg, 1, 8)
    _, slot_cache = lm.forward_prefill(params, toks, cfg, ACCEL, slot_cache)
    ids = jnp.asarray([2, 4], jnp.int32)
    fresh = lm.fill_slot_paged(
        lm.init_paged_cache(cfg, 2, 16, 4, 6), slot_cache, 1, 6, ids)
    cycled = lm.init_paged_cache(cfg, 2, 16, 4, 6)
    cycled = lm.fill_slot_paged(cycled, slot_cache, 1, 6, ids)
    cycled = lm.free_slot_paged(cycled, 1)
    assert int(cycled.pos[1]) == 0
    assert (np.asarray(cycled.page_table[1]) == -1).all()
    cycled = lm.fill_slot_paged(cycled, slot_cache, 1, 6, ids)
    for a, b in zip(jax.tree_util.tree_leaves(fresh),
                    jax.tree_util.tree_leaves(cycled)):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))


# ---------------------------------------------------------------------------
# The attn_decode_paged op
# ---------------------------------------------------------------------------


def _paged_fixture(key, b=3, hq=4, hkv=2, d=16, ps=8, np_=4):
    pool = b * np_ + 1
    ks = jax.random.split(key, 4)
    q = jax.random.normal(ks[0], (b, hq, d))
    kp = jax.random.normal(ks[1], (pool, hkv, ps, d))
    vp = jax.random.normal(ks[2], (pool, hkv, ps, d))
    table = (1 + jnp.arange(b)[:, None] * np_
             + jnp.arange(np_)[None, :]).astype(jnp.int32)
    pos = jnp.asarray([3, 17, 30], jnp.int32)[:b]
    table = jnp.where(jnp.arange(np_)[None, :] <= pos[:, None] // ps,
                      table, -1)
    return q, kp, vp, table, pos


def test_paged_op_registered_and_bucketed():
    assert "attn_decode_paged" in xaif.ops()
    assert set(xaif.backends_for("attn_decode_paged")) == {"ref", "pallas"}
    q, kp, vp, table, pos = _paged_fixture(jax.random.PRNGKey(0))
    shapes = tuple(tuple(a.shape) for a in (q, kp, vp, table, pos))
    assert xaif.shape_bucket("attn_decode_paged", shapes) == "kv_s"
    big = ((2, 4, 64), (257, 2, 16, 64), (257, 2, 16, 64), (2, 128), (2,))
    assert xaif.shape_bucket("attn_decode_paged", big) == "kv_l"


def test_paged_ref_matches_contiguous_decode_math():
    """The ref backend must be BITWISE identical to the contiguous decode
    einsums when the paged extent equals the contiguous S axis — the paged
    engine's token-identity guarantee rests on this."""
    q, kp, vp, table, pos = _paged_fixture(jax.random.PRNGKey(1))
    b, hq, d = q.shape
    hkv, ps = kp.shape[1], kp.shape[2]
    np_ = table.shape[1]
    s = np_ * ps
    # contiguous K/V: pages laid back to back in position order (junk where
    # the table is invalid — masked in both paths)
    ck = np.asarray(kp)[np.maximum(np.asarray(table), 0)]   # [B,NP,Hkv,ps,D]
    ck = np.moveaxis(ck, 2, 1).reshape(b, hkv, s, d)
    cv = np.asarray(vp)[np.maximum(np.asarray(table), 0)]
    cv = np.moveaxis(cv, 2, 1).reshape(b, hkv, s, d)
    g = hq // hkv
    qg = (np.asarray(q).reshape(b, hkv, g, d) * (d ** -0.5))
    logits = jnp.einsum("bhgd,bhsd->bhgs", jnp.asarray(qg, q.dtype),
                        jnp.asarray(ck, q.dtype),
                        preferred_element_type=jnp.float32)
    valid = np.arange(s)[None, :] <= np.asarray(pos)[:, None]
    logits = jnp.where(jnp.asarray(valid)[:, None, None, :], logits, -1e30)
    p = jax.nn.softmax(logits, axis=-1)
    expect = jnp.einsum("bhgs,bhsd->bhgd", p, jnp.asarray(cv, q.dtype),
                        preferred_element_type=jnp.float32
                        ).reshape(b, hq, d)
    got = xaif.call("attn_decode_paged", ACCEL, q, kp, vp, table, pos)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(expect))


def test_paged_pallas_matches_ref():
    from repro.kernels.paged_attention.paged_attention import \
        paged_attention_pallas
    from repro.kernels.paged_attention.ref import paged_attention_ref
    q, kp, vp, table, pos = _paged_fixture(jax.random.PRNGKey(2))
    ref = paged_attention_ref(q, kp, vp, table, pos)
    pal = paged_attention_pallas(q, kp, vp, table, pos, interpret=True)
    np.testing.assert_allclose(np.asarray(pal), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)
    # MLA mode: single latent head, fp32 post-scale, rotary second component
    key = jax.random.PRNGKey(3)
    ks = jax.random.split(key, 4)
    pool, ps, np_ = kp.shape[0], kp.shape[2], table.shape[1]
    lora, rd = 16, 8
    cp = jax.random.normal(ks[0], (pool, 1, ps, lora))
    krp = jax.random.normal(ks[1], (pool, 1, ps, rd))
    qa = jax.random.normal(ks[2], (3, 4, lora))
    qr = jax.random.normal(ks[3], (3, 4, rd))
    ref = paged_attention_ref(qa, cp, cp, table, pos, scale=0.2, q2=qr,
                              k2_pages=krp, precise=True)
    pal = paged_attention_pallas(qa, cp, cp, table, pos, scale=0.2, q2=qr,
                                 k2_pages=krp, precise=True, interpret=True)
    np.testing.assert_allclose(np.asarray(pal), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_paged_decode_ignores_junk_in_reused_pages():
    """Poisoning every invalid/out-of-range lane of the pools must not
    change the output — the masking contract that makes unzeroed page reuse
    safe."""
    q, kp, vp, table, pos = _paged_fixture(jax.random.PRNGKey(4))
    base = xaif.call("attn_decode_paged", ACCEL, q, kp, vp, table, pos)
    ps = kp.shape[2]
    np_ = table.shape[1]
    owned = np.zeros(kp.shape[0], bool)
    for bi in range(table.shape[0]):
        for j in range(np_):
            pid = int(table[bi, j])
            if pid >= 0:
                owned[pid] = True
    poison_k = np.asarray(kp).copy()
    poison_v = np.asarray(vp).copy()
    poison_k[~owned] = 1e9                    # unowned pages (incl. scratch)
    poison_v[~owned] = -1e9
    # positions past each sequence's length inside its own last page
    for bi in range(table.shape[0]):
        j = int(pos[bi]) // ps
        pid = int(table[bi, j])
        poison_k[pid, :, int(pos[bi]) % ps + 1:] = 1e9
        poison_v[pid, :, int(pos[bi]) % ps + 1:] = -1e9
    got = xaif.call("attn_decode_paged", ACCEL, q, jnp.asarray(poison_k),
                    jnp.asarray(poison_v), table, pos)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(base))
    # the Pallas kernel under NaN in the same lanes: p = 0 there must not
    # let 0 * NaN into the output
    from repro.kernels.paged_attention.paged_attention import \
        paged_attention_pallas
    clean = paged_attention_pallas(q, kp, vp, table, pos, interpret=True)
    poison_k[poison_k == 1e9] = np.nan
    poison_v[poison_v == -1e9] = np.nan
    pal = paged_attention_pallas(q, jnp.asarray(poison_k),
                                 jnp.asarray(poison_v), table, pos,
                                 interpret=True)
    assert np.isfinite(np.asarray(pal)).all()
    np.testing.assert_array_equal(np.asarray(pal), np.asarray(clean))


# Multi-page blocks: (cache_pos per slot, -1 for a free slot; Hq, Hkv; MLA)
# at NP = 40 pages of 16, so a block of 16 pages spans 256 positions and a
# full row runs three blocks, the last one half full.
_EDGE_PS, _EDGE_NP = 16, 40
_EDGE_CASES = {
    "block_last_position": ([255, 3], 4, 2, False),
    "next_block_first_position": ([256, 100], 4, 2, False),
    "table_last_position": ([_EDGE_NP * _EDGE_PS - 1, 511], 4, 2, False),
    "one_position": ([0, 300], 4, 2, False),
    "free_slot": ([-1, 400, -1], 4, 2, False),
    "chatglm3_heads": ([600, 17, 256], 32, 2, False),
    "mla": ([255, 256, 0], 4, 1, True),
}


def _edge_fixture(key, cache_pos, hq, hkv, mla, d=16, rope=8):
    """Pages scattered over the pool; each live slot's entries allocated a
    few pages past its cache_pos (as page growth leaves them), a free slot
    all -1 with cache_pos 0."""
    b, ps, np_ = len(cache_pos), _EDGE_PS, _EDGE_NP
    pool = b * np_ + 1
    ks = jax.random.split(key, 6)
    perm = np.asarray(jax.random.permutation(ks[0], np.arange(1, pool)))
    table = np.full((b, np_), -1, np.int32)
    for i, c in enumerate(cache_pos):
        if c >= 0:
            n = min(c // ps + 3, np_)
            table[i, :n] = perm[i * np_:i * np_ + n]
    pos = jnp.asarray(np.maximum(cache_pos, 0), jnp.int32)
    q = jax.random.normal(ks[1], (b, hq, d))
    kp = jax.random.normal(ks[2], (pool, hkv, ps, d))
    vp = kp if mla else jax.random.normal(ks[3], (pool, hkv, ps, d))
    extra = {}
    if mla:
        extra = dict(scale=0.2, q2=jax.random.normal(ks[4], (b, hq, rope)),
                     k2_pages=jax.random.normal(ks[5], (pool, 1, ps, rope)),
                     precise=True)
    return q, kp, vp, jnp.asarray(table), pos, extra


def _poison_unowned(pages, table, pos, ps):
    """NaN in every page no row maps and in every lane past a row's
    cache_pos, pages allocated ahead of it included."""
    keep = np.zeros((pages.shape[0], ps), bool)
    for row, c in zip(np.asarray(table), np.asarray(pos)):
        for j, pid in enumerate(row):
            if pid >= 0:
                keep[pid] = np.arange(j * ps, (j + 1) * ps) <= c
    return jnp.asarray(np.where(keep[:, None, :, None], np.asarray(pages),
                                np.nan))


def _pallas_jit():
    """The kernel in interpret mode under one jit, so that calls of one
    shape share a trace."""
    from repro.kernels.paged_attention.paged_attention import \
        paged_attention_pallas
    return jax.jit(functools.partial(paged_attention_pallas, interpret=True),
                   static_argnames=("scale", "precise"))


@pytest.mark.parametrize("case", list(_EDGE_CASES))
def test_paged_pallas_multi_page_blocks(case):
    """Pallas (interpret mode) against the reference where blocks of many
    pages begin and end, with NaN in every lane no live row owns."""
    from repro.kernels.paged_attention.paged_attention import \
        _pages_per_block
    from repro.kernels.paged_attention.ref import paged_attention_ref
    paged_attention_pallas = _pallas_jit()
    cache_pos, hq, hkv, mla = _EDGE_CASES[case]
    q, kp, vp, table, pos, extra = _edge_fixture(
        jax.random.PRNGKey(7), cache_pos, hq, hkv, mla)
    dk = q.shape[-1] + (extra["q2"].shape[-1] if mla else 0)
    ppb = _pages_per_block(_EDGE_PS, hkv, dk, vp.shape[-1], _EDGE_NP, 4)
    assert 1 < ppb < _EDGE_NP                  # several blocks, many pages
    live = np.asarray(cache_pos) >= 0
    ref = paged_attention_ref(q, kp, vp, table, pos, **extra)
    pal = paged_attention_pallas(q, kp, vp, table, pos, **extra)
    np.testing.assert_allclose(np.asarray(pal)[live], np.asarray(ref)[live],
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(pal)[~live], 0.0)
    if mla:
        kn = _poison_unowned(kp, table, pos, _EDGE_PS)
        extra["k2_pages"] = _poison_unowned(extra["k2_pages"], table, pos,
                                            _EDGE_PS)
        vn = kn
    else:
        kn = _poison_unowned(kp, table, pos, _EDGE_PS)
        vn = _poison_unowned(vp, table, pos, _EDGE_PS)
    got = paged_attention_pallas(q, kn, vn, table, pos, **extra)
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_array_equal(np.asarray(got), np.asarray(pal))


def test_paged_pallas_entry_without_page_is_masked():
    """An entry inside the live length that holds no page (-1) is masked
    as the reference masks it, and NaN in the page it no longer maps, or
    past cache_pos, stays out of the output."""
    from repro.kernels.paged_attention.ref import paged_attention_ref
    paged_attention_pallas = _pallas_jit()
    q, kp, vp, table, pos, _ = _edge_fixture(
        jax.random.PRNGKey(8), [400, 300], 4, 2, False)
    table = table.at[0, 3].set(-1).at[1, 17].set(-1)
    ref = paged_attention_ref(q, kp, vp, table, pos)
    pal = paged_attention_pallas(q, kp, vp, table, pos)
    np.testing.assert_allclose(np.asarray(pal), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)
    got = paged_attention_pallas(q, _poison_unowned(kp, table, pos, 16),
                                 _poison_unowned(vp, table, pos, 16), table,
                                 pos)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(pal))


# ---------------------------------------------------------------------------
# Per-arch autotune cells
# ---------------------------------------------------------------------------


def test_autotune_arch_cells_record_source(tmp_path):
    from repro.core.autotune import arch_cells, autotune
    cfg = get_arch("chatglm3-6b").reduced()
    cells = arch_cells(cfg, capacity=4, bucket_len=48, max_len=64,
                       page_size=16)
    assert ("gemm", "rows_s") in cells
    assert ("attn_decode_paged", "kv_s") in cells
    # builders must land in the bucket they claim
    for (op, bucket), build in cells.items():
        args, _ = build(1)
        shapes = tuple(tuple(a.shape) for a in args)
        assert xaif.shape_bucket(op, shapes) == bucket, (op, bucket)
    res = autotune(ops=["rmsnorm"], iters=1, arch=cfg, capacity=4)
    by_cell = {(c.op, c.bucket): c.source for c in res.cells}
    assert by_cell[("rmsnorm", "rows_s")] == cfg.name    # arch overlay
    assert by_cell[("rmsnorm", "rows_l")] == "generic"   # not overlaid
    path = str(tmp_path / "policy.json")
    res.persist(path)
    import json
    doc = json.loads(open(path).read())
    assert doc["cell_sources"]["rmsnorm/rows_s"] == cfg.name
    assert any(m["source"] == cfg.name for m in doc["measurements"])
    # the persisted policy still round-trips
    assert xaif.DispatchPolicy.load(path) == res.policy


def test_paged_engine_under_dispatch_policy():
    """The paged decode path dispatches attn_decode_paged through a
    DispatchPolicy (pallas cell included) and stays token-identical."""
    cfg = get_arch("chatglm3-6b").reduced()
    policy = xaif.DispatchPolicy.make({
        ("attn_decode_paged", "kv_s"): "pallas",
        "gemm": "ref", "rmsnorm": "ref", "attention": "ref",
        "entropy_exit": "ref"})
    run = RunConfig(arch=cfg, shape=SHAPES_BY_NAME["decode_32k"],
                    accel=policy)
    ref_run = _run_for(cfg)
    params = lm.init_lm(jax.random.PRNGKey(0), cfg)
    engine = SlotEngine(run, capacity=2, max_len=16, chunk=2, paged=True,
                        page_size=8)
    reqs = _requests(cfg, 2, seed=6, max_prompt=6, max_new=5)
    report = serve(engine, params, reqs)
    for r in report.requests:
        ref, _ = generate(ref_run, params, jnp.asarray(r.prompt)[None],
                          max_new_tokens=r.max_new_tokens, max_len=16)
        # pallas decode is allclose, not bitwise — greedy argmax can only
        # flip on exact logit ties, which random test weights don't produce
        np.testing.assert_array_equal(np.asarray(r.tokens),
                                      np.asarray(ref)[0], str(r.rid))
