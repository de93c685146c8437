"""dist/sharding.py helpers in isolation: no-context identity, the
non-dividing-axis drop, dp_over_model folding, and cache_shardings /
serve_shardings over both LMCache and PagedLMCache structures.

The multi-device cases need a forced multi-device host
(XLA_FLAGS=--xla_force_host_platform_device_count=4 — the CI mesh smoke
job provides it); on a plain single-device run they skip.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs.base import ShardingPolicy, get_arch
from repro.dist import sharding as shd
from repro.models import lm

from conftest import needs_mesh as needs4


# ---------------------------------------------------------------------------
# No context installed: every helper is an identity / trivial spec
# ---------------------------------------------------------------------------


def test_no_ctx_constrain_is_identity():
    assert shd.current_ctx() is None
    x = jnp.arange(6.0).reshape(2, 3)
    assert shd.constrain(x, "batch", "tp") is x         # same object
    assert shd.spec_for((2, 3), "batch", "tp") == P(None, None)


def test_no_ctx_param_shardings_asserts():
    with pytest.raises(AssertionError):
        shd.param_shardings({"w": jnp.zeros((2, 2))})
    with pytest.raises(AssertionError):
        shd.cache_shardings({"k": jnp.zeros((2, 2))}, 2)


# ---------------------------------------------------------------------------
# Axis resolution on a real mesh
# ---------------------------------------------------------------------------


@needs4
def test_non_dividing_axis_is_dropped():
    """An axis that would not divide a dim is dropped (replicated), never
    padded — the predictable-layout contract."""
    mesh = shd.make_mesh((2, 2), ("data", "model"))
    with mesh, shd.shard_ctx(mesh, ShardingPolicy()):
        assert shd.spec_for((4, 8), "batch", "tp") == P("data", "model")
        assert shd.spec_for((3, 8), "batch", "tp") == P(None, "model")
        assert shd.spec_for((4, 7), "batch", "tp") == P("data", None)


@needs4
def test_dp_over_model_folds_model_into_batch():
    """dp_over_model: the model axis joins the data axes for ``batch`` and
    tp/sp/ep resolve to nothing (small-model serving mode)."""
    mesh = shd.make_mesh((2, 2), ("data", "model"))
    with mesh, shd.shard_ctx(mesh, ShardingPolicy(dp_over_model=True)) as ctx:
        assert ctx.axis("batch") == ("data", "model")
        assert ctx.axis("tp") is None and ctx.axis("ep") is None
        assert shd.spec_for((8, 4), "batch", None) == P(("data", "model"),
                                                        None)
        # batch of 2 does not divide the folded 4-way axis -> dropped
        assert shd.spec_for((2, 4), "batch", None) == P(None, None)


# ---------------------------------------------------------------------------
# cache_shardings: LMCache vs PagedLMCache structures
# ---------------------------------------------------------------------------


@needs4
def test_cache_shardings_lmcache_slot_axis():
    cfg = get_arch("chatglm3-6b").reduced()
    cache = jax.eval_shape(lambda: lm.init_cache(cfg, 4, 16))
    mesh = shd.make_mesh((2, 2), ("data", "model"))
    with mesh, shd.shard_ctx(mesh, ShardingPolicy()):
        sh = shd.cache_shardings(cache, 4)
    # stacked slot states: batch at axis 1 over data, stack axis free
    k_spec = sh.slots[0].k.spec
    assert k_spec[0] is None and k_spec[1] == "data", k_spec
    assert sh.pos.spec == P("data")


@needs4
def test_cache_shardings_paged_pools_and_table():
    """Paged pools shard the capacity-agnostic HEAD dim over tp (GQA),
    MLA latent pools stay replicated, the page table is replicated, and
    recurrent (hybrid) slot states keep the slot axis over data."""
    mesh = shd.make_mesh((2, 2), ("data", "model"))
    # GQA arch: reduced chatglm3 has num_kv_heads divisible by tp=2
    cfg = get_arch("chatglm3-6b").reduced()
    cache = jax.eval_shape(
        lambda: lm.init_paged_cache(cfg, 4, 32, 8, 9))
    with mesh, shd.shard_ctx(mesh, ShardingPolicy()):
        sh = shd.cache_shardings(cache, 4)
    kp_spec = sh.slots[0].k_pages.spec          # [n_sb, P, Hkv, ps, D]
    assert kp_spec[-3] == "model" and kp_spec[1] is None, kp_spec
    assert sh.page_table.spec == P(None, None)
    assert sh.pos.spec == P("data")

    # MLA arch: latent pools replicated (single shared head)
    cfg = get_arch("deepseek-v2-lite-16b").reduced()
    cache = jax.eval_shape(
        lambda: lm.init_paged_cache(cfg, 4, 32, 8, 9))
    with mesh, shd.shard_ctx(mesh, ShardingPolicy()):
        sh = shd.cache_shardings(cache, 4)
    c_spec = sh.slots[0].c_kv_pages.spec
    assert all(a is None for a in c_spec), c_spec

    # hybrid arch: recurrent slot states still shard the slot axis
    cfg = get_arch("jamba-v0.1-52b").reduced()
    cache = jax.eval_shape(
        lambda: lm.init_paged_cache(cfg, 4, 32, 8, 9))
    with mesh, shd.shard_ctx(mesh, ShardingPolicy()):
        sh = shd.cache_shardings(cache, 4)
    recurrent = [s for slot in sh.slots
                 for s in jax.tree_util.tree_leaves(slot)
                 if len(s.spec) and s.spec[1] == "data"]
    assert recurrent, "hybrid recurrent states lost their slot sharding"


@needs4
def test_cache_shardings_paged_nondividing_heads_replicate():
    """tp=4 over 2 KV heads does not divide: the pool head axis drops to
    replicated instead of erroring."""
    cfg = get_arch("chatglm3-6b").reduced()
    cache = jax.eval_shape(lambda: lm.init_paged_cache(cfg, 4, 32, 8, 9))
    mesh = shd.make_mesh((1, 4), ("data", "model"))
    with mesh, shd.shard_ctx(mesh, ShardingPolicy()):
        sh = shd.cache_shardings(cache, 4)
    if cfg.num_kv_heads % 4 != 0:
        assert all(a is None for a in sh.slots[0].k_pages.spec)


@needs4
def test_serve_shardings_state_replicated():
    from repro.serve.engine import init_decode_state
    cfg = get_arch("chatglm3-6b").reduced()
    cache, state = jax.eval_shape(
        lambda: (lm.init_cache(cfg, 4, 16), init_decode_state(4)))
    mesh = shd.make_mesh((2, 2), ("data", "model"))
    with mesh, shd.shard_ctx(mesh, ShardingPolicy()):
        cache_sh, state_sh = shd.serve_shardings(cache, state, 4)
    for s in jax.tree_util.tree_leaves(
            state_sh, is_leaf=lambda x: hasattr(x, "spec")):
        assert all(a is None for a in s.spec), s.spec
    assert cache_sh.pos.spec == P("data")


# ---------------------------------------------------------------------------
# place_params round-trip (engine plumbing over param_shardings)
# ---------------------------------------------------------------------------


@needs4
def test_engine_place_params_commits_shardings():
    from repro.configs.base import (AccelConfig, RunConfig, SHAPES_BY_NAME)
    from repro.serve.engine import SlotEngine
    cfg = get_arch("chatglm3-6b").reduced()
    run = RunConfig(arch=cfg, shape=SHAPES_BY_NAME["decode_32k"],
                    accel=AccelConfig())
    params = lm.init_lm(jax.random.PRNGKey(0), cfg)
    mesh = shd.make_mesh((2, 2), ("data", "model"))
    engine = SlotEngine(run, capacity=4, max_len=16, mesh=mesh,
                        sharding=ShardingPolicy(fsdp=False))
    placed = engine.place_params(params)
    # a tp-sharded weight really is distributed over the model axis
    wq = placed["slots"][0]["mixer"]["wq"]
    assert wq.sharding.spec[-1] == "model", wq.sharding
    np.testing.assert_array_equal(
        np.asarray(wq, np.float32),
        np.asarray(params["slots"][0]["mixer"]["wq"], np.float32))
