"""Speculative decoding: greedy token identity with the plain engine across
every cache layout (contiguous / paged / prefix-sharing / mesh) under
backfill churn, the tied-params acceptance==1.0 pin, residual rejection
sampling (seeded determinism, placement independence, distribution
preservation), engine construction gates, and CLI parse-time validation.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import (AccelConfig, RunConfig, SHAPES_BY_NAME,
                                get_arch)
from repro.dist.sharding import make_mesh
from repro.models import lm
from repro.serve.engine import SlotEngine, SpecConfig
from repro.serve.scheduler import Request, serve

ACCEL = AccelConfig()


def _run_for(cfg):
    return RunConfig(arch=cfg, shape=SHAPES_BY_NAME["decode_32k"],
                     accel=ACCEL)


def _cfg(arch="chatglm3-6b"):
    # the reduced archs carry early-exit heads; speculative verification
    # skips the exit merge, so BOTH the spec target and the plain reference
    # run with the exits stripped (identical logits -> comparable tokens)
    return dataclasses.replace(get_arch(arch).reduced(), early_exit=None)


def _draft_of(cfg):
    return dataclasses.replace(cfg, name=cfg.name + "-draft1l",
                               num_layers=1,
                               block_pattern=cfg.block_pattern[:1])


def _requests(cfg, n, seed=0, max_prompt=13, max_new=10, seeds=False):
    rng = np.random.default_rng(seed)
    return [Request(
        rid=i,
        prompt=rng.integers(0, cfg.vocab_size,
                            (int(rng.integers(2, max_prompt)),),
                            dtype=np.int32),
        max_new_tokens=int(rng.integers(2, max_new + 1)),
        seed=int(rng.integers(0, 2**31)) if seeds else None)
        for i in range(n)]


def _toks(report):
    return {r.rid: r.tokens for r in report.requests}


@pytest.fixture(scope="module")
def world():
    cfg = _cfg()
    run = _run_for(cfg)
    params = lm.init_lm(jax.random.PRNGKey(0), cfg)
    plain = SlotEngine(run, capacity=3, max_len=32, chunk=4)
    ref = _toks(serve(plain, params, _requests(cfg, 7)))
    return cfg, run, params, ref


TIED = dict(k=3, share_params=True)


# ---------------------------------------------------------------------------
# Greedy token identity under backfill churn (7 requests through 3 slots)
# ---------------------------------------------------------------------------


def test_greedy_identity_contiguous_tied(world):
    cfg, run, params, ref = world
    eng = SlotEngine(run, capacity=3, max_len=32, chunk=2,
                     spec=SpecConfig(draft_arch=cfg, **TIED))
    rep = serve(eng, params, _requests(cfg, 7))
    assert _toks(rep) == ref
    assert eng.decode_traces == 1, "spec decode chunk retraced"
    # identical draft/target logits: every proposal must be accepted
    assert rep.stats["spec_acceptance"] == 1.0, rep.stats
    assert rep.stats["spec_proposed"] > 0


def test_greedy_identity_paged_tied(world):
    cfg, run, params, ref = world
    eng = SlotEngine(run, capacity=3, max_len=32, chunk=2, paged=True,
                     page_size=8, spec=SpecConfig(draft_arch=cfg, **TIED))
    rep = serve(eng, params, _requests(cfg, 7))
    assert _toks(rep) == ref
    assert eng.decode_traces == 1


def test_greedy_identity_prefix_sharing_tied(world):
    cfg, run, params, _ = world
    base = (np.arange(10, dtype=np.int32) * 17 + 3) % cfg.vocab_size
    rng = np.random.default_rng(3)

    def shared():
        rng2 = np.random.default_rng(3)
        return [Request(rid=i, prompt=np.concatenate(
            [base, rng2.integers(0, cfg.vocab_size,
                                 (int(rng2.integers(3, 8)),),
                                 dtype=np.int32)]),
            max_new_tokens=int(rng2.integers(3, 8))) for i in range(6)]

    del rng
    plain = SlotEngine(run, capacity=3, max_len=48, chunk=4, paged=True,
                       page_size=8)
    ref = _toks(serve(plain, params, shared()))
    eng = SlotEngine(run, capacity=3, max_len=48, chunk=2, paged=True,
                     page_size=8, prefix_sharing=True,
                     spec=SpecConfig(draft_arch=cfg, **TIED))
    rep = serve(eng, params, shared())
    assert _toks(rep) == ref
    assert rep.stats["shared_admissions"] >= 3, rep.stats


def test_greedy_identity_independent_draft(world):
    """A randomly-initialised 1-layer draft proposes garbage (acceptance
    near 0) — tokens must STILL be identical to plain greedy; speculation
    may only change speed, never output."""
    cfg, run, params, ref = world
    eng = SlotEngine(run, capacity=3, max_len=32, chunk=2,
                     spec=SpecConfig(draft_arch=_draft_of(cfg), k=3))
    rep = serve(eng, params, _requests(cfg, 7))
    assert _toks(rep) == ref
    assert rep.stats["spec_acceptance"] < 1.0


def test_realized_tokens_match_emitted(world):
    """The realized-token accumulator (throughput accounting) must equal
    the tokens the scheduler actually kept, per the whole run."""
    cfg, run, params, _ = world
    eng = SlotEngine(run, capacity=3, max_len=32, chunk=2,
                     spec=SpecConfig(draft_arch=cfg, **TIED))
    rep = serve(eng, params, _requests(cfg, 7))
    emitted = sum(len(r.tokens) for r in rep.requests)
    # prefill produces each request's first token; decode realizes the rest
    assert rep.stats["realized_tokens"] == emitted - len(rep.requests)


# ---------------------------------------------------------------------------
# Mesh: forced-4-device host
# ---------------------------------------------------------------------------

from conftest import needs_mesh  # noqa: E402


@needs_mesh
@pytest.mark.parametrize("name,shape", [("dp2xtp2", (2, 2)),
                                        ("tp4", (1, 4))])
def test_mesh_spec_token_identity_with_backfill(world, name, shape):
    from repro.configs.base import ShardingPolicy
    cfg, run, params, ref = world
    mesh = make_mesh(shape, ("data", "model"))
    eng = SlotEngine(run, capacity=3, max_len=32, chunk=2,
                     mesh=mesh, sharding=ShardingPolicy(fsdp=False),
                     spec=SpecConfig(draft_arch=cfg, **TIED))
    rep = serve(eng, params, _requests(cfg, 7))
    assert _toks(rep) == ref
    assert eng.decode_traces == 1
    assert rep.stats["spec_acceptance"] == 1.0, rep.stats


@needs_mesh
def test_mesh_spec_independent_draft_identity(world):
    """Draft params live on the mesh too (own shardings); identity holds
    with a low-acceptance draft."""
    from repro.configs.base import ShardingPolicy
    cfg, run, params, ref = world
    mesh = make_mesh((2, 2), ("data", "model"))
    eng = SlotEngine(run, capacity=3, max_len=32, chunk=2,
                     mesh=mesh, sharding=ShardingPolicy(fsdp=False),
                     spec=SpecConfig(draft_arch=_draft_of(cfg), k=2))
    rep = serve(eng, params, _requests(cfg, 7))
    assert _toks(rep) == ref


# ---------------------------------------------------------------------------
# Residual rejection sampling
# ---------------------------------------------------------------------------


def test_sampled_tied_acceptance_is_one(world):
    """p == q makes min(1, p/q) == 1 for every draw: a single rejection
    under tied params means the rejection test compares misaligned rows."""
    cfg, run, params, _ = world
    eng = SlotEngine(run, capacity=2, max_len=32, chunk=2, temperature=0.9,
                     top_k=16, sample_seed=11,
                     spec=SpecConfig(draft_arch=cfg, **TIED))
    rep = serve(eng, params, _requests(cfg, 5, seed=8))
    assert rep.stats["spec_acceptance"] == 1.0, rep.stats


def test_sampled_deterministic_per_seed(world):
    cfg, run, params, _ = world

    def run_once():
        eng = SlotEngine(run, capacity=2, max_len=32, chunk=2,
                         temperature=0.8, top_k=12, sample_seed=7,
                         spec=SpecConfig(draft_arch=_draft_of(cfg), k=2))
        return _toks(serve(eng, params, _requests(cfg, 5, seed=8)))

    a, b = run_once(), run_once()
    assert a == b
    assert all(len(v) > 0 for v in a.values())


def test_sampled_placement_independent(world):
    """Per-request seeds pin each request's sample stream to the REQUEST:
    serving the same seeded workload through engines with different
    capacities (different slot placement, admission order, backfill churn,
    per-chunk accept overshoot) must emit identical tokens — the rng chain
    consumes one link per ACCEPTED token, not per speculative round."""
    cfg, run, params, _ = world
    reqs = lambda: _requests(cfg, 6, seed=9, seeds=True)  # noqa: E731
    out = {}
    for cap in (2, 4):
        eng = SlotEngine(run, capacity=cap, max_len=32, chunk=2,
                         temperature=0.9, top_k=8, sample_seed=0,
                         spec=SpecConfig(draft_arch=_draft_of(cfg), k=3))
        out[cap] = _toks(serve(eng, params, reqs()))
    assert out[2] == out[4], \
        "seeded sampling depends on slot placement under speculation"


def test_sampled_low_temperature_collapses_to_greedy(world):
    """As temperature -> 0 the target distribution collapses onto argmax;
    a DISTRIBUTION-PRESERVING sampler must then emit the plain greedy
    tokens even with a disagreeing draft — any residual-rejection bias
    toward the draft's proposals shows up here immediately."""
    cfg, run, params, ref = world
    eng = SlotEngine(run, capacity=3, max_len=32, chunk=2,
                     temperature=0.001, sample_seed=3,
                     spec=SpecConfig(draft_arch=_draft_of(cfg), k=3))
    rep = serve(eng, params, _requests(cfg, 7))
    assert _toks(rep) == ref


def test_sampled_distribution_matches_plain_sampling(world):
    """Empirical check on a fixed context: 240 seeded single-decode-token
    requests through the plain sampled engine vs the spec engine (draft
    that DISAGREES with the target), top_k=2 so the support is tiny. The
    two second-token marginals must agree within sampling noise — residual
    rejection preserves the target distribution, it does not tilt toward
    the draft's proposals."""
    cfg, run, params, _ = world
    prompt = (np.arange(6, dtype=np.int32) * 11 + 5) % cfg.vocab_size

    def reqs():
        rng = np.random.default_rng(123)
        return [Request(rid=i, prompt=prompt.copy(), max_new_tokens=2,
                        seed=int(rng.integers(0, 2**31)))
                for i in range(240)]

    counts = {}
    for tag, spec in (("plain", None),
                      ("spec", SpecConfig(draft_arch=_draft_of(cfg), k=2))):
        eng = SlotEngine(run, capacity=8, max_len=16, chunk=2,
                         temperature=1.0, top_k=2, sample_seed=0, spec=spec)
        rep = serve(eng, params, reqs())
        pairs = [tuple(r.tokens[:2]) for r in rep.requests]
        c = {}
        for p in pairs:
            c[p] = c.get(p, 0) + 1
        counts[tag] = {k: v / len(pairs) for k, v in c.items()}
    support = set(counts["plain"]) | set(counts["spec"])
    tv = 0.5 * sum(abs(counts["plain"].get(s, 0.0)
                       - counts["spec"].get(s, 0.0)) for s in support)
    assert tv < 0.12, (tv, counts)


# ---------------------------------------------------------------------------
# Engine construction gates
# ---------------------------------------------------------------------------


def test_engine_rejects_bad_spec_configs(world):
    cfg, run, params, _ = world
    with pytest.raises(AssertionError, match="spec.k"):
        SlotEngine(run, capacity=2, max_len=24, chunk=2,
                   spec=SpecConfig(draft_arch=cfg, k=0))
    with pytest.raises(AssertionError, match="share_params"):
        SlotEngine(run, capacity=2, max_len=24, chunk=2,
                   spec=SpecConfig(draft_arch=_draft_of(cfg), k=2,
                                   share_params=True))
    moe = get_arch("qwen3-moe-30b-a3b").reduced()
    with pytest.raises(AssertionError, match="all-attention"):
        SlotEngine(run, capacity=2, max_len=24, chunk=2,
                   spec=SpecConfig(draft_arch=moe, k=2))
    exits = get_arch("chatglm3-6b").reduced()   # carries early-exit heads
    run_exits = _run_for(exits)
    with pytest.raises(AssertionError, match="early-exit"):
        SlotEngine(run_exits, capacity=2, max_len=24, chunk=2,
                   spec=SpecConfig(draft_arch=exits, k=2,
                                   share_params=True))
    with pytest.raises(AssertionError, match="gated"):
        SlotEngine(run_exits, capacity=2, max_len=24, chunk=2, gated=True,
                   spec=SpecConfig(draft_arch=cfg, k=2))


def test_set_draft_params_validates(world):
    cfg, run, params, _ = world
    eng = SlotEngine(run, capacity=2, max_len=24, chunk=2,
                     spec=SpecConfig(draft_arch=_draft_of(cfg), k=2))
    fresh = lm.init_lm(jax.random.PRNGKey(9), _draft_of(cfg))
    eng.set_draft_params(fresh)                  # matching tree: accepted
    with pytest.raises(AssertionError, match="tree"):
        eng.set_draft_params(params)             # target tree: rejected
    tied = SlotEngine(run, capacity=2, max_len=24, chunk=2,
                      spec=SpecConfig(draft_arch=cfg, **TIED))
    with pytest.raises(AssertionError, match="independent"):
        tied.set_draft_params(fresh)


# ---------------------------------------------------------------------------
# CLI parse-time validation (launch/serve.py)
# ---------------------------------------------------------------------------


def _cli(monkeypatch, argv):
    from repro.launch import serve as serve_launch
    monkeypatch.setattr("sys.argv", ["serve", "--reduced"] + argv)
    with pytest.raises(SystemExit) as ei:
        serve_launch.main()
    return ei.value.code


@pytest.mark.parametrize("argv,needle", [
    (["--spec-k", "3"], "--draft"),
    (["--draft", "yi-9b", "--spec-k", "0"], ">= 1"),
    (["--draft", "no-such-arch"], "not a known arch"),
    (["--draft", "yi-9b", "--gated"], "--gated"),
    (["--draft", "yi-9b", "--threshold", "0.5"], "--threshold"),
    (["--draft", "yi-9b", "--prefill-chunk", "16"], "--prefill-chunk"),
])
def test_launch_serve_rejects_bad_spec_flags(monkeypatch, capsys, argv,
                                             needle):
    code = _cli(monkeypatch, ["--arch", "yi-9b"] + argv)
    assert code == 2                              # argparse error exit
    assert needle in capsys.readouterr().err


@pytest.mark.parametrize("argv,needle", [
    (["--arch", "yi-9b", "--draft", "qwen3-moe-30b-a3b"],
     "all-attention"),
])
def test_launch_serve_rejects_incompatible_draft_arch(monkeypatch, capsys,
                                                      argv, needle):
    code = _cli(monkeypatch, argv)
    assert code == 2
    assert needle in capsys.readouterr().err
