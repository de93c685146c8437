"""XAIF v2 dispatch: hashable policies usable as jit static args, shape
buckets, per-bucket backend + tuning selection, JSON round-trips, backend
equivalence across every shape bucket, and the measured autotuner."""
import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import AccelConfig, RunConfig, SHAPES_BY_NAME, get_arch
from repro.core import xaif
from repro.core.autotune import CELLS, autotune


# ---------------------------------------------------------------------------
# Hashability — policies as jit static arguments (regression: the seed's
# AccelConfig held a raw dict, so hash() raised)
# ---------------------------------------------------------------------------


def test_accel_config_hashable_from_dict():
    a = AccelConfig(backends={"gemm": "pallas", "attention": "blockwise"})
    b = AccelConfig(backends={"attention": "blockwise", "gemm": "pallas"})
    assert hash(a) == hash(b) and a == b       # order-insensitive normal form
    assert {a: 1}[b] == 1
    assert a.backend_for("gemm") == "pallas"
    assert a.backend_for("rmsnorm") == "ref"   # unlisted ops fall back


def test_policies_work_as_jit_static_args():
    traces = []

    def fn(x, w, policy):
        traces.append(1)
        return xaif.call("gemm", policy, x, w)

    f = jax.jit(fn, static_argnums=2)
    x, w = jnp.ones((4, 8)), jnp.ones((8, 8))
    f(x, w, AccelConfig())
    f(x, w, AccelConfig())                      # equal config: cache hit
    assert len(traces) == 1
    f(x, w, AccelConfig(backends={"gemm": "pallas"}))
    assert len(traces) == 2
    pol = xaif.DispatchPolicy.make({("gemm", "rows_s"): "ref"})
    f(x, w, pol)
    f(x, w, xaif.DispatchPolicy.make({("gemm", "rows_s"): "ref"}))
    assert len(traces) == 3                     # DispatchPolicy hashes too


# ---------------------------------------------------------------------------
# Shape buckets + per-bucket selection
# ---------------------------------------------------------------------------


def test_shape_bucket_classes():
    assert xaif.shape_bucket("gemm", ((8, 64), (64, 64))) == "rows_s"
    assert xaif.shape_bucket("gemm", ((4, 64, 64), (64, 64))) == "rows_m"
    assert xaif.shape_bucket("gemm", ((4096, 64), (64, 64))) == "rows_l"
    assert xaif.shape_bucket("attention",
                             ((2, 4, 1, 32), (2, 2, 64, 32))) == "decode"
    assert xaif.shape_bucket("attention",
                             ((2, 4, 64, 32), (2, 2, 64, 32))) == "prefill"
    assert xaif.shape_bucket("ssm_scan", ((2, 1, 16),)) == "decode"
    assert xaif.shape_bucket("ssm_scan", ((2, 128, 16),)) == "scan"
    assert xaif.shape_bucket("gemm", ()) == xaif.WILDCARD   # malformed


def test_policy_selects_backend_and_tuning_per_bucket():
    """A throwaway op registered with tunables shows the policy routing
    decode-shaped calls and prefill-shaped calls to different backends with
    the declared tuning injected (explicit kwargs win)."""
    seen = []

    @xaif.register("_test_probe", "alpha", tunables={"blk": (16, 32)})
    def _alpha(x, *, blk=16):
        seen.append(("alpha", blk))
        return x

    @xaif.register("_test_probe", "beta", tunables={"blk": (64,)})
    def _beta(x, *, blk=64):
        seen.append(("beta", blk))
        return x

    pol = xaif.DispatchPolicy.make({
        ("_test_probe", "rows_s"): ("alpha", {"blk": 32}),
        ("_test_probe", "rows_m"): "beta",
    })
    xaif.call("_test_probe", pol, jnp.ones((4, 8)))      # rows_s
    xaif.call("_test_probe", pol, jnp.ones((256, 8)))    # rows_m
    xaif.call("_test_probe", pol, jnp.ones((4, 8)), blk=7)  # explicit kwarg
    assert seen == [("alpha", 32), ("beta", 64), ("alpha", 7)]
    # unknown bucket falls back to the wildcard then the default backend
    assert pol.rule_for("_test_probe", "rows_l").backend == "ref"


def test_supports_predicate_falls_back():
    """MLA-style v head dim != q head dim: the fused attention kernel
    declares it unsupported; the policy falls back to the default backend
    instead of crashing."""
    q = jnp.ones((1, 2, 4, 16))
    k = jnp.ones((1, 2, 8, 16))
    v = jnp.ones((1, 2, 8, 8))                 # dv != d
    pol = xaif.DispatchPolicy.make({("attention", "prefill"): "pallas"})
    out = xaif.call("attention", pol, q, k, v)
    ref = xaif.call("attention", AccelConfig(), q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref))
    entry = xaif.resolve("attention", pol, (q.shape, k.shape, v.shape))
    assert entry.name == "ref"
    # supported shapes resolve to the requested backend
    v_ok = jnp.ones((1, 2, 8, 16))
    entry = xaif.resolve("attention", pol, (q.shape, k.shape, v_ok.shape))
    assert entry.name == "pallas"


def test_accel_config_path_unchanged():
    """v1 dispatch (static string map) still resolves and raises on
    unknown backends — the registry contract of the seed."""
    with pytest.raises(KeyError):
        xaif.resolve("gemm", AccelConfig(backends={"gemm": "nope"}))
    assert xaif.resolve("gemm", AccelConfig()).name == "ref"


# ---------------------------------------------------------------------------
# JSON round-trip
# ---------------------------------------------------------------------------


def test_policy_json_roundtrip_lossless():
    pol = xaif.DispatchPolicy.make(
        {("gemm", "rows_s"): ("pallas", {"bm": 64, "bk": 256}),
         ("gemm", "rows_l"): "pallas_int8",
         ("attention", "decode"): "blockwise",
         "rmsnorm": "pallas"},
        default="ref")
    doc = pol.to_json()
    back = xaif.DispatchPolicy.from_json(doc)
    assert back == pol
    assert back.to_json() == doc               # fixpoint
    assert hash(back) == hash(pol)
    # extra metadata (e.g. autotune measurements) is ignored on load
    with_meta = pol.to_json(measurements=[{"op": "gemm", "us": 1.0}])
    assert xaif.DispatchPolicy.from_json(with_meta) == pol


def test_policy_file_cannot_set_interpret_mode():
    """Interpret mode follows the platform only: a policy file that still
    carries the retired ``interpret`` key (or smuggles it in as tuning)
    changes nothing, and off the TPU the Pallas backend runs interpreted."""
    doc = json.dumps({"version": 2, "interpret": False, "default": "ref",
                      "rules": [{"op": "rmsnorm", "bucket": "*",
                                 "backend": "pallas",
                                 "tuning": {"interpret": 0}}]})
    pol = xaif.DispatchPolicy.from_json(doc)
    assert "interpret" not in json.loads(pol.to_json())
    x = jax.random.normal(jax.random.PRNGKey(0), (8, 128))
    scale = jnp.ones((128,), jnp.float32)
    assert xaif.pallas_interpret() == (jax.default_backend() != "tpu")
    out = xaif.call("rmsnorm", pol, x, scale)
    ref = xaif.call("rmsnorm", AccelConfig(), x, scale)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# Dispatch equivalence: every backend, every shape bucket
# ---------------------------------------------------------------------------


def _norm_rel(a, b):
    a = np.asarray(a, np.float32).ravel()
    b = np.asarray(b, np.float32).ravel()
    return np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-6)


@pytest.mark.parametrize("op,bucket",
                         [k for k in CELLS if k[1] != "rows_l"])
def test_all_backends_equivalent_per_bucket(op, bucket):
    """For every op and shape bucket, every registered backend that
    supports the cell produces the same answer as the ref backend (int8
    within quantization error)."""
    args, kwargs = CELLS[(op, bucket)](1)
    shapes = tuple(tuple(a.shape) for a in args)
    ref_entry = xaif.get_entry(op, "ref")
    ref_out = ref_entry.fn(*args, **kwargs)
    for entry in xaif.entries_for(op):
        if entry.name == "ref":
            continue
        if not entry.accepts(shapes, None):
            continue
        kw = dict(kwargs)
        if entry.takes_interpret:
            kw["interpret"] = True
        out = entry.fn(*args, **kw)
        flat_o = jax.tree_util.tree_leaves(out)
        flat_r = jax.tree_util.tree_leaves(ref_out)
        tol = 0.02 if "int8" in entry.name else 2e-4
        for o, r in zip(flat_o, flat_r):
            assert _norm_rel(o, r) < tol, (op, bucket, entry.name)


# ---------------------------------------------------------------------------
# Autotune
# ---------------------------------------------------------------------------


def test_autotune_never_slower_than_static_and_persists(tmp_path):
    static = AccelConfig()
    res = autotune(ops=["rmsnorm", "attention"], iters=1, baseline=static)
    assert res.cells, "nothing measured"
    for cell in res.cells:
        winner, _ = cell.winner()
        assert cell.us_for(winner) <= cell.us_for(
            static.backend_for(cell.op)), (cell.op, cell.bucket)
        # the winning rule is what the policy dispatches for that cell
        assert res.policy.rule_for(cell.op, cell.bucket).backend == winner
    path = tmp_path / "policy.json"
    res.persist(str(path))
    loaded = xaif.DispatchPolicy.load(str(path))
    assert loaded == res.policy


def test_autotune_cells_stay_in_bucket_under_scale():
    """Scaled measurement cells must still land in the bucket they are
    registered for (regression: scale=5 used to push rows_s cells into
    rows_m and trip the sweep's consistency assert)."""
    for scale in (1, 5, 16):
        for (op, bucket), build in CELLS.items():
            args, _ = build(scale)
            shapes = tuple(tuple(a.shape) for a in args)
            assert xaif.shape_bucket(op, shapes) == bucket, (op, bucket,
                                                            scale, shapes)


def test_autotune_excludes_lossy_backends_by_default():
    """pallas_int8 trades accuracy for speed: it must never win a cell
    unless explicitly allowed, so autotuned policies keep exact numerics."""
    assert xaif.get_entry("gemm", "pallas_int8").lossy
    res = autotune(ops=["gemm"], iters=1)
    for cell in res.cells:
        assert "pallas_int8" not in cell.measured_us
        assert "pallas_int8" in cell.skipped
    for _, _, rule in res.policy.rules:
        assert rule.backend != "pallas_int8"


def test_supports_fallback_skips_rejecting_default():
    """If the policy's default backend itself rejects the shapes, the
    fallback chain continues to a backend that accepts them instead of
    running the kernel on shapes it declared illegal."""
    q = jnp.ones((1, 2, 4, 16))
    k = jnp.ones((1, 2, 8, 16))
    v = jnp.ones((1, 2, 8, 8))                 # dv != d: pallas rejects
    pol = xaif.DispatchPolicy.make({("attention", "prefill"): "pallas"},
                                   default="pallas")
    entry = xaif.resolve("attention", pol, (q.shape, k.shape, v.shape))
    assert entry.name == "ref"
    out = xaif.call("attention", pol, q, k, v)
    ref = xaif.call("attention", AccelConfig(), q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref))


def test_fallback_prefers_non_lossy():
    """When neither the rule backend nor default/ref accept the shapes,
    the last-resort fallback picks a non-lossy accepting backend before a
    lossy one."""
    rejecting = lambda shapes, dtype: False

    @xaif.register("_test_fb", "picky", supports=rejecting)
    def _picky(x):
        return x

    @xaif.register("_test_fb", "fast_lossy", lossy=True)
    def _fl(x):
        return x * 0 + 1

    @xaif.register("_test_fb", "exact")
    def _exact(x):
        return x

    pol = xaif.DispatchPolicy.make({"_test_fb": "picky"}, default="picky")
    entry = xaif.resolve("_test_fb", pol, ((4, 4),))
    assert entry.name == "exact"
    out = xaif.call("_test_fb", pol, jnp.zeros((4, 4)))
    np.testing.assert_array_equal(np.asarray(out), 0)   # not the lossy one


def test_autotune_warns_on_ops_without_cells():
    """An op registered outside the built-in cell table is reported, not
    silently left untuned; a caller-provided cell covers it."""
    @xaif.register("_test_nocell", "only")
    def _only(x):
        return x

    msgs = []
    res = autotune(ops=["_test_nocell"], iters=1, print_fn=msgs.append)
    assert not res.cells
    assert any("_test_nocell" in m and "WARNING" in m for m in msgs)
    cell = {("_test_nocell", "rows_s"):
            lambda scale: ((jnp.ones((8, 16)),), {})}
    res = autotune(ops=["_test_nocell"], iters=1, cells=cell)
    assert [(c.op, c.bucket) for c in res.cells] == [("_test_nocell",
                                                      "rows_s")]
    assert res.policy.backend_for("_test_nocell", "rows_s") == "only"


def test_autotune_tunes_block_sizes():
    res = autotune(ops=["rmsnorm"], iters=1, tune_block_sizes=True)
    # the sweep ran and produced a policy with rules for every bucket
    assert {b for _, b, _ in res.policy.rules} == {"rows_s", "rows_m",
                                                   "rows_l"}


def test_serving_token_identity_under_dispatch_policy():
    """The slot engine and the legacy host loop stay token-identical when
    both dispatch through an autotuned-style DispatchPolicy (per-bucket
    backends, including a non-ref decode pick)."""
    from repro.models import lm
    from repro.serve.engine import SlotEngine, generate
    from repro.serve.scheduler import Request, serve

    cfg = get_arch("chatglm3-6b").reduced()
    pol = xaif.DispatchPolicy.make({("attention", "decode"): "blockwise",
                                    ("attention", "prefill"): "blockwise"})
    run = RunConfig(arch=cfg, shape=SHAPES_BY_NAME["decode_32k"], accel=pol)
    params = lm.init_lm(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    reqs = [Request(rid=i,
                    prompt=rng.integers(0, cfg.vocab_size, (4 + 2 * i,),
                                        dtype=np.int32),
                    max_new_tokens=5) for i in range(4)]
    engine = SlotEngine(run, capacity=2, max_len=32, chunk=3)
    report = serve(engine, params, reqs)
    for r in report.requests:
        ref, _ = generate(run, params, jnp.asarray(r.prompt)[None],
                          max_new_tokens=r.max_new_tokens, max_len=32)
        np.testing.assert_array_equal(np.asarray(r.tokens),
                                      np.asarray(ref)[0], str(r.rid))
