"""Beyond-paper optimizations: chunked head+CE and int8 weight-quantized
serving must be numerically sound and structurally transparent."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import (AccelConfig, RunConfig, SHAPES_BY_NAME,
                                get_arch)
from repro.dist.sharding import make_mesh
from repro.models import lm
from repro.train.train_step import make_train_step


def test_chunked_head_loss_bit_exact():
    cfg = get_arch("yi-9b").reduced()
    run = RunConfig(arch=cfg, shape=SHAPES_BY_NAME["train_4k"],
                    accel=AccelConfig(), remat="nothing")
    init_a, step_a = make_train_step(run)
    _, step_b = make_train_step(run, loss_chunk=8)
    state = init_a(jax.random.PRNGKey(0))
    x = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0, cfg.vocab_size)
    y = jax.random.randint(jax.random.PRNGKey(2), (2, 32), 0, cfg.vocab_size)
    sa, ma = jax.jit(step_a)(state, {"inputs": x, "labels": y})
    sb, mb = jax.jit(step_b)(state, {"inputs": x, "labels": y})
    np.testing.assert_allclose(float(ma["loss"]), float(mb["loss"]),
                               rtol=1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(sa.params),
                    jax.tree_util.tree_leaves(sb.params)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=1e-4, atol=1e-5)


def test_wq8_serving_accuracy_and_structure():
    from repro.serve.quantize import WeightQ, quantize_weights_int8
    cfg = get_arch("yi-9b").reduced()
    params = lm.init_lm(jax.random.PRNGKey(0), cfg)
    qp = quantize_weights_int8(params)
    # structure: attention weights quantized, norms untouched
    assert isinstance(qp["slots"][0]["mixer"]["wq"], WeightQ)
    assert qp["slots"][0]["mixer"]["wq"].q.dtype == jnp.int8
    assert not isinstance(qp["slots"][0]["ln1"]["scale"], WeightQ)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0,
                              cfg.vocab_size)
    ref, _, _ = lm.forward_train(params, toks, cfg, AccelConfig())
    out, _, _ = lm.forward_train(qp, toks, cfg, AccelConfig())
    rel = float(jnp.linalg.norm((ref - out).astype(jnp.float32))
                / jnp.linalg.norm(ref.astype(jnp.float32)))
    assert rel < 0.05, rel
    # decode path stays finite and cache-consistent
    cache = lm.init_cache(cfg, 2, 32)
    _, cache = lm.forward_prefill(qp, toks, cfg, AccelConfig(), cache)
    lg, _, cache = lm.forward_decode(qp, toks[:, :1], cfg, AccelConfig(),
                                     cache)
    assert bool(jnp.all(jnp.isfinite(lg.astype(jnp.float32))))


def test_wq8_pallas_int8_consumes_prequantized():
    from repro.serve.quantize import quantize_weights_int8
    cfg = get_arch("yi-9b").reduced()
    params = lm.init_lm(jax.random.PRNGKey(0), cfg)
    qp = quantize_weights_int8(params)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0,
                              cfg.vocab_size)
    acc8 = AccelConfig(backends={"gemm": "pallas_int8"})
    out8, _, _ = lm.forward_train(qp, toks, cfg, acc8)
    ref, _, _ = lm.forward_train(params, toks, cfg, AccelConfig())
    rel = float(jnp.linalg.norm((ref - out8).astype(jnp.float32))
                / jnp.linalg.norm(ref.astype(jnp.float32)))
    assert rel < 0.1, rel


def test_wq8_sharding_rules_inherit():
    """Quantized leaves inherit the parent weight's partition spec."""
    from jax.sharding import PartitionSpec as P
    from repro.configs.base import ShardingPolicy
    from repro.dist import sharding as shd
    from repro.serve.quantize import quantize_weights_int8
    cfg = get_arch("yi-9b").reduced()
    params = jax.eval_shape(lambda: quantize_weights_int8(
        lm.init_lm(jax.random.PRNGKey(0), cfg)))
    mesh = make_mesh((1, 1), ("data", "model"))
    with mesh, shd.shard_ctx(mesh, ShardingPolicy()):
        specs = shd.param_pspecs(params)
    wq_spec = specs["slots"][0]["mixer"]["wq"]
    # q: [n_sb, d, H*dh] gets (None, fsdp, tp); scale [n_sb, 1, H*dh] tp-last
    assert wq_spec.q[-1] in ("model", ("model",))
    assert wq_spec.scale[-1] in ("model", ("model",))
