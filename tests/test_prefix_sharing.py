"""Prefix-sharing KV cache: radix index, refcounted pages, COW admission.

Token identity is THE correctness bar: the sharing engine serves shared-
prefix streams with fork-point suffix prefill + copy-on-write boundary
pages, and every request's greedy tokens must equal both the no-sharing
paged engine on the same stream and a solo reference run — page reuse,
index eviction and COW copies must never leak into numerics.

The allocator/index unit tests pin the refcount invariants the serving
tests exercise only incidentally: shared pages never freed while mapped,
COW destinations never alias a live reader, pops never failing under
churn, and fill -> share -> retire -> refill behaving like a fresh fill.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import (AccelConfig, RunConfig, SHAPES_BY_NAME,
                                get_arch)
from repro.dist.sharding import make_mesh
from repro.models import lm
from repro.serve.engine import SlotEngine, generate, make_sampler
from repro.serve.paging import PageAllocator, PrefixIndex
from repro.serve.scheduler import Request, serve

from conftest import needs_mesh

ACCEL = AccelConfig()


def _run_for(cfg):
    return RunConfig(arch=cfg, shape=SHAPES_BY_NAME["decode_32k"],
                     accel=ACCEL)


def _shared_prefix_requests(cfg, n, prefix_len, seed=0, max_suffix=12,
                            max_new=8, seeds=None):
    """n requests whose prompts all open with the same prefix_len tokens."""
    rng = np.random.default_rng(seed)
    common = rng.integers(0, cfg.vocab_size, (prefix_len,), dtype=np.int32)
    out = []
    for i in range(n):
        suffix = rng.integers(0, cfg.vocab_size,
                              (int(rng.integers(1, max_suffix)),),
                              dtype=np.int32)
        out.append(Request(
            rid=i, prompt=np.concatenate([common, suffix]),
            max_new_tokens=int(rng.integers(2, max_new + 1)),
            seed=None if seeds is None else seeds[i]))
    return out


# ---------------------------------------------------------------------------
# Token identity (the tentpole bar)
# ---------------------------------------------------------------------------


def test_sharing_engine_matches_solo_and_unshared_with_backfill():
    """9 shared-prefix requests through 3 slots with backfill churn: the
    sharing engine's greedy tokens equal the no-sharing paged engine AND a
    solo reference run per request, while actually sharing (several
    fork-point admissions, fewer bucketed prefill tokens)."""
    cfg = get_arch("chatglm3-6b").reduced()
    run = _run_for(cfg)
    params = lm.init_lm(jax.random.PRNGKey(0), cfg)

    results, engines = {}, {}
    for sharing in (False, True):
        engine = SlotEngine(run, capacity=3, max_len=64, chunk=4, paged=True,
                            page_size=8, num_pages=32,
                            prefix_sharing=sharing)
        reqs = _shared_prefix_requests(cfg, 9, prefix_len=20)
        report = serve(engine, params, reqs)
        assert engine.decode_traces == 1      # sharing never re-traces decode
        results[sharing] = report
        engines[sharing] = engine

    shared = results[True]
    assert shared.stats["shared_admissions"] >= 3, shared.stats
    assert engines[True].prefill_tokens < engines[False].prefill_tokens
    for r_off, r_on in zip(results[False].requests, shared.requests):
        np.testing.assert_array_equal(np.asarray(r_off.tokens),
                                      np.asarray(r_on.tokens), str(r_on.rid))
        ref, _ = generate(run, params, jnp.asarray(r_on.prompt)[None],
                          max_new_tokens=r_on.max_new_tokens, max_len=64)
        np.testing.assert_array_equal(np.asarray(r_on.tokens),
                                      np.asarray(ref)[0], str(r_on.rid))


def test_sharing_cow_boundary_page():
    """Two prompts diverging MID-page: the second request's match ends
    inside a page (rem > 0), forcing the copy-on-write path. Tokens still
    equal the solo reference, and the COW page is the divergent slot's own
    (not the first request's boundary page)."""
    cfg = get_arch("chatglm3-6b").reduced()
    run = _run_for(cfg)
    params = lm.init_lm(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(3)
    stem = rng.integers(0, cfg.vocab_size, (13,), dtype=np.int32)  # ps=8:
    a = np.concatenate([stem, [11, 12, 13]])   # diverge at position 13,
    b = np.concatenate([stem, [21, 22, 23]])   # inside page 1 (rem=5)

    engine = SlotEngine(run, capacity=2, max_len=32, chunk=4, paged=True,
                        page_size=8, num_pages=16, prefix_sharing=True)
    reqs = [Request(rid=0, prompt=a, max_new_tokens=4),
            Request(rid=1, prompt=b, max_new_tokens=4)]
    report = serve(engine, params, reqs)
    assert report.stats["shared_admissions"] == 1      # b forked off a
    for r in report.requests:
        ref, _ = generate(run, params, jnp.asarray(r.prompt)[None],
                          max_new_tokens=r.max_new_tokens, max_len=32)
        np.testing.assert_array_equal(np.asarray(r.tokens),
                                      np.asarray(ref)[0], str(r.rid))


def test_sharing_survives_index_eviction_pressure():
    """A page pool barely above the live working set: retired chains keep
    the index populated until admission pressure evicts LRU leaves. Tokens
    must stay solo-identical through evict/reuse churn."""
    cfg = get_arch("chatglm3-6b").reduced()
    run = _run_for(cfg)
    params = lm.init_lm(jax.random.PRNGKey(0), cfg)
    engine = SlotEngine(run, capacity=2, max_len=32, chunk=4, paged=True,
                        page_size=8, num_pages=12, prefix_sharing=True)
    reqs = _shared_prefix_requests(cfg, 8, prefix_len=10, seed=5,
                                   max_suffix=8, max_new=6)
    report = serve(engine, params, reqs)
    served = [r for r in report.requests if r.reject_reason is None]
    assert len(served) == len(reqs)                   # reservation held
    for r in served:
        ref, _ = generate(run, params, jnp.asarray(r.prompt)[None],
                          max_new_tokens=r.max_new_tokens, max_len=32)
        np.testing.assert_array_equal(np.asarray(r.tokens),
                                      np.asarray(ref)[0], str(r.rid))


@needs_mesh
def test_sharing_engine_token_identity_on_mesh():
    """dp2 x tp2 mesh: the sharing engine's jitted shared-prefill/copy-page
    entries carry explicit shardings — greedy tokens equal the
    single-device sharing engine on the same stream."""
    from repro.configs.base import ShardingPolicy
    from repro.dist import sharding as shd
    cfg = get_arch("chatglm3-6b").reduced()
    run = _run_for(cfg)
    params = lm.init_lm(jax.random.PRNGKey(0), cfg)
    pol = ShardingPolicy(fsdp=False)

    outs = {}
    for mesh_on in (False, True):
        mesh = (make_mesh((2, 2), ("data", "model"))
                if mesh_on else None)
        engine = SlotEngine(run, capacity=4, max_len=64, chunk=4, paged=True,
                            page_size=8, num_pages=40, prefix_sharing=True,
                            mesh=mesh, sharding=pol if mesh else None)
        reqs = _shared_prefix_requests(cfg, 8, prefix_len=20, seed=2)
        if mesh:
            with shd.shard_ctx(mesh, pol):
                report = serve(engine, params, reqs)
        else:
            report = serve(engine, params, reqs)
        assert report.stats["shared_admissions"] >= 3
        outs[mesh_on] = {r.rid: list(r.tokens) for r in report.requests}
    assert outs[False] == outs[True]


# ---------------------------------------------------------------------------
# PrefixIndex unit tests
# ---------------------------------------------------------------------------


def _alloc(num_pages=16, capacity=4, max_pages=8, ps=4):
    return PageAllocator(num_pages, capacity, max_pages, ps, sharing=True)


def test_index_match_walks_full_pages_and_boundary():
    al = _alloc()
    chain = np.arange(10)                        # 2 full pages + 2 tokens
    ids = al.admit(0, bucket_len=12, true_len=10, max_new=2)
    al.register(chain, 0)
    assert len(al.index) == 2                    # only FULL pages indexed

    # full-page match: both pages, no boundary
    pages, boundary, rem = al.index.match(np.arange(8), cap=8)
    assert pages == [int(ids[0]), int(ids[1])] and boundary is None

    # mid-page divergence: one full page + 3 matched tokens of page 2
    probe = np.array([0, 1, 2, 3, 4, 5, 99, 98])
    pages, boundary, rem = al.index.match(probe, cap=8)
    assert pages == [int(ids[0])]
    assert boundary == int(ids[1]) and rem == 2

    # cap excludes the tail: a full-prompt match is clipped so the suffix
    # keeps >= 1 token (the scheduler calls with cap = len - 1)
    pages, boundary, rem = al.match(np.arange(8))
    assert pages == [int(ids[0])] and boundary == int(ids[1]) and rem == 3


def test_index_insert_dedup_keeps_first_resident_copy():
    al = _alloc()
    al.admit(0, bucket_len=8, true_len=8, max_new=2)
    al.admit(1, bucket_len=8, true_len=8, max_new=2)
    chain = np.arange(8)
    assert al.register(chain, 0) == 2
    assert al.register(chain, 1) == 0            # dedup: nothing new
    pages, _, _ = al.index.match(chain, cap=8)
    assert pages == al.owned[0][:2]              # first copy won


def test_index_lru_eviction_frees_only_unmapped_leaves():
    al = _alloc(num_pages=6, ps=4)               # 5 usable pages
    al.admit(0, bucket_len=4, true_len=4, max_new=1)
    al.register(np.arange(4), 0)                 # mapped AND indexed: rc=2
    al.admit(1, bucket_len=4, true_len=4, max_new=1)
    al.register(np.arange(100, 104), 1)
    reclaim_pid = al.owned[1][0]
    al.release(1)                                # index-only now: rc=1
    assert al.reclaimable == 1 and al.refcnt[reclaim_pid] == 1

    # draining the free list (3 truly free + 1 reclaimable) forces the
    # eviction path: the rc==1 leaf is evicted and reused LAST, while the
    # still-mapped page never moves
    got = [al._pop_free() for _ in range(4)]
    assert got[-1] == reclaim_pid
    assert al.owned[0][0] in al.refcnt           # mapped page survived
    assert len(al.index) == 1                    # only the mapped chain left
    with pytest.raises(AssertionError):
        al._pop_free()                           # nothing reclaimable left


def test_index_eviction_skips_interior_nodes():
    al = _alloc(num_pages=8, ps=2)
    ids = al.admit(0, bucket_len=6, true_len=6, max_new=1)
    al.register(np.arange(6), 0)                 # 3-node chain
    al.release(0)                                # all rc==1, index-only
    # leaf-first: the DEEPEST page goes first, never an interior edge
    assert al.index.evict_one(al) == int(ids[2])
    assert len(al.index) == 2
    # and the remaining chain still matches its shortened prefix
    pages, _, _ = al.index.match(np.arange(4), cap=4)
    assert pages == [int(ids[0]), int(ids[1])]


# ---------------------------------------------------------------------------
# Allocator refcount invariants (property tests)
# ---------------------------------------------------------------------------


def _check_sharing_invariants(al):
    # refcount == #mapping rows + index registration, for every page
    for pid, rc in al.refcnt.items():
        mapped = sum(p == pid for pages in al.owned.values() for p in pages)
        indexed = 1 if (al.index is not None and pid in al.index.pages) else 0
        assert rc == mapped + indexed, (pid, rc, mapped, indexed)
        assert rc >= 1 and pid != 0
    free = set(al.free)
    assert 0 not in free
    assert free.isdisjoint(al.refcnt)            # free pages hold no refs
    for slot, pages in al.owned.items():
        row = al.table[slot]
        n = len(pages)
        assert list(row[:n]) == pages and (row[n:] == -1).all()


def test_refcnt_shared_page_survives_every_release_order():
    """A page mapped by two slots and the index reaches the free list only
    after ALL THREE holders drop it — in any order."""
    import itertools
    for order in itertools.permutations(["a", "b", "idx"]):
        al = _alloc()
        al.admit(0, bucket_len=4, true_len=4, max_new=1)
        al.register(np.arange(4), 0)
        pid = al.owned[0][0]
        # slot 1 maps the same page via shared admission
        al.admit_shared(1, [pid], None, rem=0, suffix_bucket=4, true_len=8,
                        max_new=1)
        assert al.refcnt[pid] == 3
        for holder in order:
            assert pid not in al.free
            if holder == "a":
                al.release(0)
            elif holder == "b":
                al.release(1)
            else:
                node = al.index.pages[pid]
                del node.parent.children[node.edge]
                del al.index.pages[pid]
                al._release_page(pid)
            _check_sharing_invariants(al)
        assert pid in al.free and pid not in al.refcnt, order


def test_cow_region_never_aliases_a_live_reader():
    """admit_shared's region pages are freshly popped: disjoint from every
    page any other slot maps and from the matched prefix pages."""
    al = _alloc(num_pages=32, ps=4)
    al.admit(0, bucket_len=12, true_len=12, max_new=2)
    al.register(np.arange(12), 0)
    prefix, boundary, rem = al.match(np.arange(11))
    assert len(prefix) == 2 and boundary is not None and rem == 2
    pre_ids, region = al.admit_shared(1, prefix, boundary, rem=rem,
                                      suffix_bucket=4, true_len=11,
                                      max_new=2)
    live = set(al.owned[0]) | set(int(p) for p in pre_ids)
    assert live.isdisjoint(int(p) for p in region)
    assert int(boundary) not in region           # COW copies, never writes
    _check_sharing_invariants(al)


def test_fill_share_retire_refill_equals_fresh_fill():
    """Churn property: admit -> register -> shared-admit -> release all ->
    evict everything. The allocator must return to its fresh state (all
    pages free, no refs) and the next admission must behave like the
    first."""
    al = _alloc(num_pages=10, ps=4)
    fresh_free = sorted(al.free)
    al.admit(0, bucket_len=8, true_len=8, max_new=2)
    al.register(np.arange(8), 0)
    prefix, _, _ = al.match(np.arange(8, dtype=np.int64))
    al.admit_shared(1, prefix, None, rem=0, suffix_bucket=4, true_len=8,
                    max_new=2)
    _check_sharing_invariants(al)
    al.release(0)
    al.release(1)
    while al.index.evict_one(al) is not None:
        _check_sharing_invariants(al)
    assert sorted(al.free) == fresh_free and not al.refcnt
    assert len(al.index) == 0 and al.available == len(fresh_free)
    # the next admission behaves exactly like the first on a fresh
    # allocator: same reservation accounting, same row shape
    fresh = _alloc(num_pages=10, ps=4)
    ids = al.admit(2, bucket_len=8, true_len=8, max_new=2)
    fresh_ids = fresh.admit(2, bucket_len=8, true_len=8, max_new=2)
    assert len(ids) == len(fresh_ids) == 2
    assert al.available == fresh.available
    assert (al.table[2] >= 0).sum() == (fresh.table[2] >= 0).sum() == 2


def test_pops_never_fail_under_random_churn():
    """Randomized admit/shared-admit/grow/release storm, guarded only by
    can_admit/can_admit_shared: _pop_free never raises and the refcount
    invariants hold after every step."""
    rng = np.random.default_rng(0)
    al = _alloc(num_pages=14, capacity=4, max_pages=8, ps=4)
    live = {}                                    # slot -> (true_len, max_new)
    chains = {}                                  # slot -> token chain
    next_chain = 0
    for step in range(300):
        op = rng.integers(0, 3)
        if op == 0 and len(live) < 4:            # admit (maybe shared)
            slot = next(s for s in range(4) if s not in live)
            if rng.integers(0, 2) and next_chain > 0:
                chain = chains[int(rng.integers(0, next_chain)) % 4]
            else:
                chain = rng.integers(0, 50, (int(rng.integers(5, 17)),))
            chains[next_chain % 4], next_chain = chain, next_chain + 1
            t, max_new = len(chain), int(rng.integers(1, 5))
            prefix, boundary, rem = al.match(chain)
            start = len(prefix) * 4 + rem
            if prefix:
                sb = -(-(t - start) // 4) * 4
                if al.can_admit_shared(prefix, boundary, rem, sb, t,
                                       max_new):
                    al.admit_shared(slot, prefix, boundary, rem, sb, t,
                                    max_new)
                    al.register(chain, slot)     # dedups onto the prefix
                    live[slot] = (t, max_new)
            elif al.can_admit(-(-t // 4) * 4, t, max_new):
                al.admit(slot, -(-t // 4) * 4, t, max_new)
                al.register(chain, slot)
                live[slot] = (t, max_new)
        elif op == 1 and live:                   # grow to the worst case
            slot = int(rng.choice(sorted(live)))
            t, max_new = live[slot]
            al.ensure(slot, t + max_new - 1)
        elif op == 2 and live:                   # retire
            slot = int(rng.choice(sorted(live)))
            al.release(slot)
            del live[slot]
        _check_sharing_invariants(al)
    assert al.peak_pages <= al.num_pages - 1


def test_can_admit_shared_excludes_pinned_prefix_pages():
    """The matched prefix pages must not fund their own region allocation:
    retaining them at admission makes them unevictable, so an availability
    check that counts them as reclaimable overpromises and _pop_free
    asserts. Repro: 3-page pool, retired chain indexes pages for 8 tokens
    (rc 1), one truly free page, shared admission needing 2 region pages."""
    al = _alloc(num_pages=4, ps=4)               # 3 usable pages
    al.admit(0, bucket_len=8, true_len=8, max_new=0)
    al.register(np.arange(8), 0)
    al.release(0)                                # 2 index-only pages, 1 free
    assert len(al.free) == 1 and al.reclaimable == 2
    prompt = np.concatenate([np.arange(8), np.arange(50, 58)])
    prefix, boundary, rem = al.match(prompt)
    assert len(prefix) == 2 and boundary is None and rem == 0
    # needs 2 region pages but pinning the 2 matched pages leaves only the
    # single free page available — must refuse, not crash later
    assert not al.can_admit_shared(prefix, boundary, rem=0, suffix_bucket=8,
                                   true_len=16, max_new=0)
    # a region that fits the one truly free page is admissible
    assert al.can_admit_shared(prefix, boundary, rem=0, suffix_bucket=4,
                               true_len=12, max_new=0)
    al.admit_shared(1, prefix, boundary, rem=0, suffix_bucket=4,
                    true_len=12, max_new=0)
    _check_sharing_invariants(al)


def test_reclaimable_counts_only_transitively_evictable_pages():
    """An index-only interior node above a dedup-shadowed, slot-mapped
    descendant is NOT reclaimable: evict_one only frees refcount-1 leaves,
    so it can never reach the ancestors while the descendant's page stays
    mapped — counting them would overpromise availability."""
    al = _alloc(num_pages=8, ps=4)
    al.admit(0, bucket_len=8, true_len=8, max_new=0)
    al.register(np.arange(8), 0)                 # nodes X,Y hold slot 0's pages
    al.release(0)                                # X,Y refcount 1 (index-only)
    al.admit(1, bucket_len=12, true_len=12, max_new=0)
    al.register(np.arange(12), 1)                # X,Y dedup'd (slot 1 maps its
                                                 # own duplicates); new leaf Z
                                                 # holds slot 1's page (rc 2)
    # X and Y are rc 1 but sit above the unevictable leaf Z
    assert al.reclaimable == 0
    assert al.index.evict_one(al) is None
    # admission must see only the truly free pages
    free_now = len(al.free)
    assert not al.can_admit(bucket_len=4 * (free_now + 1),
                            true_len=4 * (free_now + 1), max_new=0)
    al.release(1)                                # Z drops to rc 1: the whole
    assert al.reclaimable == 3                   # chain is evictable again


def test_shared_admission_falls_back_to_standard_path():
    """Bucket rounding can make the shared reservation LARGER than the
    standard one (rem + bucket(t - start) > bucket(t)): when the shared
    region cannot be reserved, the scheduler must fall through to standard
    admission instead of reporting FULL forever — otherwise the request
    starves in a small pool even though it fits."""
    cfg = get_arch("chatglm3-6b").reduced()
    run = _run_for(cfg)
    params = lm.init_lm(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(11)
    stem = rng.integers(0, cfg.vocab_size, (8,), dtype=np.int32)
    fork = np.concatenate([stem[:5],
                           rng.integers(0, cfg.vocab_size, (3,),
                                        dtype=np.int32)])
    # 4 usable pages, ps=4, prompt_bucket=8: the forked prompt (t=8, match
    # ends at 5) would need 3 region pages on top of 2 pinned index pages
    # (7 > pool) while the standard path needs only 3 total
    engine = SlotEngine(run, capacity=1, max_len=16, chunk=4, paged=True,
                        page_size=4, num_pages=5, prompt_bucket=8,
                        prefix_sharing=True)
    reqs = [Request(rid=0, prompt=stem, max_new_tokens=2),
            Request(rid=1, prompt=fork, max_new_tokens=2)]
    report = serve(engine, params, reqs)
    assert len(report.served) == 2               # nobody starves
    assert report.stats["shared_admissions"] == 0
    for r in report.served:
        ref, _ = generate(run, params, jnp.asarray(r.prompt)[None],
                          max_new_tokens=r.max_new_tokens, max_len=16)
        np.testing.assert_array_equal(np.asarray(r.tokens),
                                      np.asarray(ref)[0], str(r.rid))


def test_allocator_reduces_to_unshared_arithmetic_when_sharing_off():
    """sharing=False: no index, every refcount exactly 1, and available
    matches the PR 3 free-minus-outstanding arithmetic."""
    al = PageAllocator(9, 4, 4, 8, sharing=False)
    assert al.index is None and al.available == 8
    al.admit(0, bucket_len=16, true_len=12, max_new=12)
    assert all(rc == 1 for rc in al.refcnt.values())
    assert al.available == 8 - 3                 # reserved 3, owns 2
    al.release(0)
    assert al.available == 8 and not al.refcnt


# ---------------------------------------------------------------------------
# Satellites: top-p sampling + per-request seeds
# ---------------------------------------------------------------------------


def test_top_p_sampler_properties():
    key = jax.random.PRNGKey(7)
    logits = jnp.asarray(np.random.default_rng(1).normal(size=(64,)) * 3,
                         jnp.float32)
    # deterministic per key
    s = make_sampler(1.0, top_p=0.9)
    assert int(s(key, logits)) == int(s(key, logits))
    # top_p -> tiny degenerates to argmax (top-1 always survives)
    s_tiny = make_sampler(1.0, top_p=1e-6)
    assert int(s_tiny(key, logits)) == int(jnp.argmax(logits))
    # the nucleus really truncates: every draw lands inside the top-p set
    probs = np.asarray(jax.nn.softmax(logits))
    order = np.argsort(-probs)
    keep = (np.cumsum(probs[order]) - probs[order]) < 0.5
    nucleus = set(order[keep].tolist())
    s_half = make_sampler(1.0, top_p=0.5)
    draws = {int(s_half(jax.random.PRNGKey(i), logits)) for i in range(50)}
    assert draws <= nucleus and len(draws) > 1
    # greedy stays greedy: no sampler at temperature 0 regardless of top_p
    assert make_sampler(0.0, top_p=0.5) is None


def test_greedy_engine_unchanged_by_top_p_and_seeds():
    """Greedy regression: top_p and per-request seeds are dead arguments —
    the greedy engine's tokens are bit-identical with and without them."""
    cfg = get_arch("chatglm3-6b").reduced()
    run = _run_for(cfg)
    params = lm.init_lm(jax.random.PRNGKey(0), cfg)
    outs = {}
    for top_p, seeds in ((1.0, None), (0.5, [11, 22, 33, 44])):
        engine = SlotEngine(run, capacity=2, max_len=32, chunk=4, paged=True,
                            page_size=8, temperature=0.0, top_p=top_p)
        rng = np.random.default_rng(4)
        reqs = [Request(rid=i,
                        prompt=rng.integers(0, cfg.vocab_size, (6,),
                                            dtype=np.int32),
                        max_new_tokens=5,
                        seed=None if seeds is None else seeds[i])
                for i in range(4)]
        report = serve(engine, params, reqs)
        outs[top_p] = {r.rid: list(r.tokens) for r in report.requests}
    assert outs[1.0] == outs[0.5]


def test_per_request_seed_replays_across_slot_placements():
    """Sampled decode: a seeded request draws the SAME tokens whether it
    lands on slot 0 of an otherwise-empty engine or backfills into a busy
    one — the per-request key replaces the slot-position key. Unseeded
    requests still vary with placement (the slot key is position-bound)."""
    cfg = get_arch("chatglm3-6b").reduced()
    run = _run_for(cfg)
    params = lm.init_lm(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(9)
    target_prompt = rng.integers(0, cfg.vocab_size, (6,), dtype=np.int32)

    def run_stream(decoys, sample_seed):
        engine = SlotEngine(run, capacity=2, max_len=32, chunk=4,
                            paged=True, page_size=8,
                            temperature=0.8, top_k=8, top_p=0.95,
                            sample_seed=sample_seed)
        reqs = [Request(rid=i,
                        prompt=rng.integers(0, cfg.vocab_size, (4,),
                                            dtype=np.int32),
                        max_new_tokens=6) for i in range(decoys)]
        reqs.append(Request(rid=99, prompt=target_prompt,
                            max_new_tokens=6, seed=1234))
        report = serve(engine, params, reqs)
        return next(list(r.tokens) for r in report.requests if r.rid == 99)

    # different decoy loads AND different engine base seeds: the seeded
    # request replays identically in every placement
    a = run_stream(decoys=0, sample_seed=0)
    b = run_stream(decoys=3, sample_seed=0)
    c = run_stream(decoys=1, sample_seed=77)
    assert a == b == c
    assert len(a) == 6
