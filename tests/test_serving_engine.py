"""Continuous-batching serve engine: slot cache API, token-identity against
the reference host loop, trace stability, and the no-host-transfer contract
of the jitted decode chunk."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import (AccelConfig, RunConfig, SHAPES_BY_NAME,
                                get_arch)
from repro.dist.sharding import make_mesh
from repro.models import lm
from repro.serve.engine import SlotEngine, generate
from repro.serve.scheduler import Request, poisson_requests, serve

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


def _reference_tokens(run, params, req, max_len):
    toks, _ = generate(run, params, jnp.asarray(req.prompt)[None],
                       max_new_tokens=req.max_new_tokens, max_len=max_len)
    return np.asarray(toks)[0]


# ---------------------------------------------------------------------------
# Slot cache API
# ---------------------------------------------------------------------------


def test_fill_and_reset_slot():
    cfg = get_arch("chatglm3-6b").reduced()
    params = lm.init_lm(jax.random.PRNGKey(0), cfg)
    cache = lm.init_cache(cfg, 3, 16)
    toks = jax.random.randint(jax.random.PRNGKey(1), (1, 5), 0,
                              cfg.vocab_size)
    slot_cache = lm.init_cache(cfg, 1, 8)
    _, slot_cache = lm.forward_prefill(params, toks, cfg, ACCEL, slot_cache)
    cache = lm.fill_slot(cache, slot_cache, slot=1, length=5)
    assert list(np.asarray(lm.slot_lengths(cache))) == [0, 5, 0]
    k = np.asarray(cache.slots[0].k, np.float32)   # [n_sb, B, Hkv, S, D]
    assert np.abs(k[:, 1, :, :5, :]).max() > 0     # filled row, valid prefix
    assert np.abs(k[:, 0]).max() == 0              # other rows untouched
    assert np.abs(k[:, 2]).max() == 0
    cache = lm.reset_slot(cache, 1)
    assert list(np.asarray(lm.slot_lengths(cache))) == [0, 0, 0]
    assert np.abs(np.asarray(cache.slots[0].k, np.float32)).max() == 0


def test_fill_slot_recurrent_state():
    cfg = get_arch("xlstm-350m").reduced()
    params = lm.init_lm(jax.random.PRNGKey(0), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(1), (1, 6), 0,
                              cfg.vocab_size)
    slot_cache = lm.init_cache(cfg, 1, 6)
    _, slot_cache = lm.forward_prefill(params, toks, cfg, ACCEL, slot_cache)
    cache = lm.init_cache(cfg, 2, 16)
    cache = lm.fill_slot(cache, slot_cache, slot=0, length=6)
    src = jax.tree_util.tree_leaves(slot_cache.slots)
    dst = jax.tree_util.tree_leaves(cache.slots)
    for s, d in zip(src, dst):
        np.testing.assert_array_equal(np.asarray(s[:, 0], np.float32),
                                      np.asarray(d[:, 0], np.float32))
        assert np.abs(np.asarray(d[:, 1], np.float32)).max() == 0


# ---------------------------------------------------------------------------
# Token identity vs the reference host loop
# ---------------------------------------------------------------------------


def test_slot_engine_matches_host_loop_with_backfill():
    """7 requests with mixed prompt lengths/budgets through 3 slots: every
    request's tokens must equal a solo run of the reference loop on a fixed
    seed (admission order, bucketed prefill and backfill must not leak into
    the numerics)."""
    cfg = get_arch("chatglm3-6b").reduced()
    run = _run_for(cfg)
    params = lm.init_lm(jax.random.PRNGKey(0), cfg)
    engine = SlotEngine(run, capacity=3, max_len=32, chunk=4)
    reqs = _requests(cfg, 7)
    report = serve(engine, params, reqs)
    for r in report.requests:
        assert len(r.tokens) == r.max_new_tokens
        np.testing.assert_array_equal(
            np.asarray(r.tokens),
            _reference_tokens(run, params, r, max_len=32), str(r.rid))


MOE_ARCHS = ("qwen3-moe-30b-a3b", "deepseek-v2-lite-16b")


@pytest.mark.parametrize("arch", MOE_ARCHS)
def test_moe_slot_engine_matches_host_loop_with_backfill(arch):
    """Dropless MoE decode (PR 5): MoE archs join the token-identity
    matrix. Under backfill churn every request's tokens equal a solo run of
    the reference loop — the capacity-sharing carve-out documented since
    PR 1 is gone (decode dispatches the per-token ``moe_decode`` op; the
    engine prefills MoE archs at exact length, since capacity-bounded
    prefill is not pad-safe)."""
    cfg = get_arch(arch).reduced()
    run = _run_for(cfg)
    params = lm.init_lm(jax.random.PRNGKey(0), cfg)
    engine = SlotEngine(run, capacity=3, max_len=32, chunk=4)
    reqs = _requests(cfg, 6)
    report = serve(engine, params, reqs)
    assert engine.decode_traces == 1
    for r in report.requests:
        assert len(r.tokens) == r.max_new_tokens
        np.testing.assert_array_equal(
            np.asarray(r.tokens),
            _reference_tokens(run, params, r, max_len=32), str(r.rid))


def test_slot_engine_matches_host_loop_static_batch_hybrid():
    """Hybrid attn+Mamba(+MoE) arch with a STATIC slot composition equals
    the seed's batched loop exactly (with dropless MoE decode the batched
    loop itself dispatches per-token, so batched and slot decode agree
    bit for bit)."""
    cfg = get_arch("jamba-v0.1-52b").reduced()
    run = _run_for(cfg)
    params = lm.init_lm(jax.random.PRNGKey(0), cfg)
    b, t, new = 3, 6, 5
    prompt = jax.random.randint(jax.random.PRNGKey(1), (b, t), 0,
                                cfg.vocab_size)
    ref, _ = generate(run, params, prompt, max_new_tokens=new, max_len=24)
    engine = SlotEngine(run, capacity=b, max_len=24, chunk=2)
    reqs = [Request(rid=i, prompt=np.asarray(prompt[i]), max_new_tokens=new)
            for i in range(b)]
    report = serve(engine, params, reqs)
    got = np.stack([r.tokens for r in
                    sorted(report.requests, key=lambda r: r.rid)])
    np.testing.assert_array_equal(got, np.asarray(ref))


def test_slot_engine_gated_matches_reference():
    cfg = get_arch("yi-9b").reduced()
    cfg = dataclasses.replace(cfg, early_exit=dataclasses.replace(
        cfg.early_exit, entropy_threshold=2.0))      # always exit
    run = _run_for(cfg)
    params = lm.init_lm(jax.random.PRNGKey(0), cfg)
    engine = SlotEngine(run, capacity=2, max_len=24, chunk=3, gated=True)
    reqs = _requests(cfg, 4, seed=3, max_prompt=9, max_new=6)
    report = serve(engine, params, reqs)
    for r in report.requests:
        toks, _ = generate(run, params, jnp.asarray(r.prompt)[None],
                           max_new_tokens=r.max_new_tokens, max_len=24,
                           gated=True)
        np.testing.assert_array_equal(np.asarray(r.tokens),
                                      np.asarray(toks)[0], str(r.rid))
    assert report.stats["exit_rate"] == 1.0


# ---------------------------------------------------------------------------
# Engine contracts: trace stability, on-device stats, no per-token transfers
# ---------------------------------------------------------------------------


def test_decode_compiles_once_despite_occupancy_churn():
    """Prompt-length variation, admissions and backfill are slot STATE: the
    decode chunk must trace exactly once for the whole stream."""
    cfg = get_arch("chatglm3-6b").reduced()
    run = _run_for(cfg)
    params = lm.init_lm(jax.random.PRNGKey(0), cfg)
    engine = SlotEngine(run, capacity=2, max_len=32, chunk=4)
    serve(engine, params, _requests(cfg, 5, seed=1))
    assert engine.decode_traces == 1
    assert engine.decode_calls >= 3                  # several chunks ran
    # bucketed prefill: few traces despite many distinct prompt lengths
    assert engine.prefill_traces <= 2


def test_decode_chunk_no_host_transfers():
    """The jitted decode chunk performs NO device-to-host transfer: sampling,
    early-exit merge and statistics all stay on device (the host fetches
    once per chunk, after the call). Verified with jax's transfer guard
    around the dispatch + donated-cache execution."""
    cfg = get_arch("chatglm3-6b").reduced()
    run = _run_for(cfg)
    params = lm.init_lm(jax.random.PRNGKey(0), cfg)
    engine = SlotEngine(run, capacity=2, max_len=24, chunk=4)
    cache, st = engine.init_state()
    cache, st, _ = engine.prefill_into(params, cache, st,
                                       np.arange(5, dtype=np.int32), 0, 12)
    cache, st, toks = engine.decode(params, cache, st)   # warm (compiles)
    with jax.transfer_guard_device_to_host("disallow"):
        cache, st, toks = engine.decode(params, cache, st)
        cache, st, toks = engine.decode(params, cache, st)
    # single fetch per request batch: the on-device accumulators come back
    # as plain floats in one stats() call
    stats = SlotEngine.stats(st)
    assert stats["decode_slot_steps"] > 0


def test_slot_engine_exit_rate_threshold_response():
    """The slot engine's on-device exit statistics respond to the entropy
    threshold exactly like the legacy engine's per-step metrics."""
    base = get_arch("chatglm3-6b").reduced()
    rates = {}
    for th in (0.0, 1.1):
        cfg = dataclasses.replace(base, early_exit=dataclasses.replace(
            base.early_exit, entropy_threshold=th))
        run = _run_for(cfg)
        params = lm.init_lm(jax.random.PRNGKey(0), cfg)
        engine = SlotEngine(run, capacity=2, max_len=24, chunk=4)
        report = serve(engine, params, _requests(cfg, 3, seed=2))
        rates[th] = report.stats["exit_rate"]
    assert rates[0.0] == 0.0 and rates[1.1] == 1.0


def test_gated_decode_live_mask_controls_skip():
    """Dead slots must not veto the whole-batch skip, and an unconfident
    LIVE slot must force the full path."""
    cfg = get_arch("yi-9b").reduced()
    cfg = dataclasses.replace(cfg, early_exit=dataclasses.replace(
        cfg.early_exit, entropy_threshold=-1.0))     # nobody is confident
    params = lm.init_lm(jax.random.PRNGKey(0), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0,
                              cfg.vocab_size)
    cache = lm.init_cache(cfg, 2, 16)
    _, cache = lm.forward_prefill(params, toks, cfg, ACCEL, cache)
    step = toks[:, :1]
    full_lg, _, _ = lm.forward_decode(params, step, cfg, ACCEL, cache,
                                      with_exits=False)
    # one live unconfident slot -> cont branch: final-head logits
    lg_live, mask, _ = lm.forward_decode_gated(
        params, step, cfg, ACCEL, cache, live=jnp.asarray([False, True]))
    assert not bool(jnp.any(mask))
    np.testing.assert_allclose(np.asarray(lg_live), np.asarray(full_lg),
                               rtol=2e-3, atol=2e-3)
    # all slots dead -> skip branch runs despite zero confidence: the
    # returned logits are the EXIT head's, not the final head's
    lg_dead, _, _ = lm.forward_decode_gated(
        params, step, cfg, ACCEL, cache, live=jnp.asarray([False, False]))
    assert not np.allclose(np.asarray(lg_dead, np.float32),
                           np.asarray(full_lg, np.float32), atol=1e-3)


def test_cache_shardings_slot_batch_axis():
    """Stacked slot states shard the BATCH axis (axis 1), never the [n_sb]
    stack axis — even when n_sb happens to equal the batch size."""
    from jax.sharding import PartitionSpec as P
    from repro.configs.base import ShardingPolicy
    from repro.dist import sharding as shd
    cfg = get_arch("yi-9b").reduced()          # n_sb == 2
    batch = cfg.num_superblocks                # force the size collision
    cache = jax.eval_shape(lambda: lm.init_cache(cfg, batch, 16))
    mesh = make_mesh((1, 1), ("data", "model"))
    with mesh, shd.shard_ctx(mesh, ShardingPolicy()):
        sh = shd.cache_shardings(cache, batch)
    k_spec = sh.slots[0].k.spec                # [n_sb, B, Hkv, S, D]
    assert k_spec[0] is None and k_spec[1] == "data", k_spec
    assert sh.pos.spec == P("data")
    prefix_free = jax.tree_util.tree_leaves(sh.prefix)
    assert all(s.spec[0] == "data" for s in prefix_free) or not prefix_free


# ---------------------------------------------------------------------------
# Mesh-aware serving: token identity on forced multi-device hosts
# ---------------------------------------------------------------------------

from conftest import needs_mesh  # noqa: E402

MESHES = (("dp4", (4, 1)), ("tp4", (1, 4)), ("dp2xtp2", (2, 2)))


def _serve_policy():
    from repro.configs.base import ShardingPolicy
    return ShardingPolicy(fsdp=False)   # serve layout: tp + replicated-dp


@needs_mesh
@pytest.mark.parametrize("name,shape", MESHES)
def test_mesh_engine_token_identity_with_backfill(name, shape):
    """7 mixed-length requests through 4 slots on a real mesh: every jitted
    entry runs with explicit in/out shardings, yet the emitted tokens are
    identical to the single-device engine under backfill churn, with one
    decode trace (tp4: head counts that don't divide simply replicate)."""
    cfg = get_arch("chatglm3-6b").reduced()
    run = _run_for(cfg)
    params = lm.init_lm(jax.random.PRNGKey(0), cfg)
    single = SlotEngine(run, capacity=4, max_len=32, chunk=4)
    ref = serve(single, params, _requests(cfg, 7))
    ref_toks = {r.rid: r.tokens for r in ref.requests}
    mesh = make_mesh(shape, ("data", "model"))
    engine = SlotEngine(run, capacity=4, max_len=32, chunk=4,
                        mesh=mesh, sharding=_serve_policy())
    report = serve(engine, params, _requests(cfg, 7))
    assert engine.decode_traces == 1
    assert {r.rid: r.tokens for r in report.requests} == ref_toks


@needs_mesh
def test_mesh_moe_engine_token_identity_with_backfill():
    """MoE arch on a dp2xtp2 mesh: expert weights shard E over the model
    axis (the ``ep`` rules), decode dispatches the dropless ``moe_decode``
    op — and greedy tokens stay identical to the single-device engine under
    backfill churn (the same identity bar as PR 4, now covering MoE)."""
    cfg = get_arch("qwen3-moe-30b-a3b").reduced()
    run = _run_for(cfg)
    params = lm.init_lm(jax.random.PRNGKey(0), cfg)
    single = SlotEngine(run, capacity=4, max_len=32, chunk=4)
    ref = serve(single, params, _requests(cfg, 7))
    ref_toks = {r.rid: r.tokens for r in ref.requests}
    mesh = make_mesh((2, 2), ("data", "model"))
    engine = SlotEngine(run, capacity=4, max_len=32, chunk=4,
                        mesh=mesh, sharding=_serve_policy())
    report = serve(engine, params, _requests(cfg, 7))
    assert engine.decode_traces == 1
    assert {r.rid: r.tokens for r in report.requests} == ref_toks


@needs_mesh
def test_mesh_decode_caches_donated():
    """Sharded caches are still donated: after a decode chunk the previous
    cache's buffers are invalidated (updated in place, not copied)."""
    cfg = get_arch("chatglm3-6b").reduced()
    run = _run_for(cfg)
    params = lm.init_lm(jax.random.PRNGKey(0), cfg)
    mesh = make_mesh((2, 2), ("data", "model"))
    engine = SlotEngine(run, capacity=4, max_len=24, chunk=4,
                        mesh=mesh, sharding=_serve_policy())
    placed = engine.place_params(params)
    cache, st = engine.init_state()
    new_cache, new_st, _ = engine.decode(placed, cache, st)
    assert cache.pos.is_deleted() and cache.slots[0].k.is_deleted()
    assert not new_cache.pos.is_deleted()


# ---------------------------------------------------------------------------
# Non-greedy sampling through per-slot PRNG keys
# ---------------------------------------------------------------------------


def test_sampled_decode_deterministic_and_distinct_from_greedy():
    """temperature/top-k sampling draws through DecodeState.rng: the same
    seed reproduces the stream exactly; a sampled stream differs from the
    greedy one; greedy engines keep rng untouched."""
    cfg = get_arch("chatglm3-6b").reduced()
    run = _run_for(cfg)
    params = lm.init_lm(jax.random.PRNGKey(0), cfg)

    def run_stream(**kw):
        engine = SlotEngine(run, capacity=2, max_len=32, chunk=4, **kw)
        report = serve(engine, params, _requests(cfg, 5, seed=8))
        return {r.rid: r.tokens for r in report.requests}

    a = run_stream(temperature=0.9, top_k=16, sample_seed=7)
    b = run_stream(temperature=0.9, top_k=16, sample_seed=7)
    assert a == b, "sampling must be deterministic for a fixed seed"
    c = run_stream(temperature=0.9, top_k=16, sample_seed=8)
    greedy = run_stream()
    assert a != greedy
    assert a != c, "different seeds should diverge on this workload"
    # every request still produced exactly its budget
    assert all(len(v) > 0 for v in a.values())


def test_contiguous_engine_under_dispatch_policy_pallas_decode():
    """The contiguous decode path now dispatches the ``attn_decode`` XAIF
    op (ROADMAP follow-up: only the paged path did), so a DispatchPolicy
    can route the serve decode mixer to the pallas backend — and stays
    token-identical (argmax only flips on exact logit ties, which random
    test weights don't produce)."""
    from repro.core import xaif
    cfg = get_arch("chatglm3-6b").reduced()
    policy = xaif.DispatchPolicy.make({
        ("attn_decode", "kv_s"): "pallas",
        "gemm": "ref", "rmsnorm": "ref", "attention": "ref",
        "entropy_exit": "ref"})
    run = RunConfig(arch=cfg, shape=SHAPES_BY_NAME["decode_32k"],
                    accel=policy)
    ref_run = _run_for(cfg)
    params = lm.init_lm(jax.random.PRNGKey(0), cfg)
    engine = SlotEngine(run, capacity=2, max_len=16, chunk=2)
    reqs = _requests(cfg, 3, seed=6, max_prompt=6, max_new=5)
    report = serve(engine, params, reqs)
    for r in report.requests:
        np.testing.assert_array_equal(
            np.asarray(r.tokens),
            _reference_tokens(ref_run, params, r, max_len=16), str(r.rid))


def test_greedy_engine_leaves_rng_untouched():
    """The greedy default must not perturb the PRNG leaf — its trace is
    leaf-identical to the pre-sampling engine."""
    cfg = get_arch("chatglm3-6b").reduced()
    run = _run_for(cfg)
    params = lm.init_lm(jax.random.PRNGKey(0), cfg)
    engine = SlotEngine(run, capacity=2, max_len=24, chunk=4)
    cache, st = engine.init_state()
    rng0 = np.asarray(st.rng).copy()
    cache, st, _ = engine.prefill_into(params, cache, st,
                                       np.arange(5, dtype=np.int32), 0, 8)
    cache, st, _ = engine.decode(params, cache, st)
    np.testing.assert_array_equal(np.asarray(st.rng), rng0)


# ---------------------------------------------------------------------------
# Invalid flag combinations: CLI-time validation + engine-level guard
# ---------------------------------------------------------------------------


def test_launch_serve_rejects_paged_gated_at_parse_time(monkeypatch, capsys):
    """``--paged --gated`` must die in argparse with an actionable message,
    not on a bare assert deep inside SlotEngine after the model is built."""
    from repro.launch import serve as serve_launch
    monkeypatch.setattr("sys.argv", ["serve", "--arch", "yi-9b", "--reduced",
                                     "--paged", "--gated"])
    with pytest.raises(SystemExit) as ei:
        serve_launch.main()
    assert ei.value.code == 2                     # argparse error exit
    err = capsys.readouterr().err
    assert "page-aware" in err and "--gated" in err


def test_engine_still_guards_gated_paged_direct_construction():
    """The engine-level assert stays as the last line of defense for direct
    construction (the CLI check is a convenience, not the invariant)."""
    cfg = get_arch("yi-9b").reduced()
    run = _run_for(cfg)
    with pytest.raises(AssertionError, match="page-aware"):
        SlotEngine(run, capacity=2, max_len=24, chunk=2, gated=True,
                   paged=True)


def test_poisson_stream_serves_all_requests():
    cfg = get_arch("chatglm3-6b").reduced()
    run = _run_for(cfg)
    params = lm.init_lm(jax.random.PRNGKey(0), cfg)
    reqs = poisson_requests(num=6, rate_hz=50.0, prompt_lens=(2, 10),
                            max_new_tokens=4, vocab_size=cfg.vocab_size,
                            seed=0)
    engine = SlotEngine(run, capacity=2, max_len=24, chunk=4)
    report = serve(engine, params, reqs, realtime=True)
    assert all(r.t_finished is not None for r in report.requests)
    assert all(len(r.tokens) == r.max_new_tokens for r in report.requests)
    lat = report.latency_percentiles()
    assert lat["p99"] >= lat["p50"] > 0
    assert report.tokens_per_s > 0
