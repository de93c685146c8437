"""repro.analysis: lint rule engine (seeded violations per rule, taint
pruning, scope resolution, suppression), registry auditor (seeded
missing-ref op, policy resolution, lossy exclusion), HEAD-clean gates,
pinned regressions for the violations the linter surfaced on HEAD, and
the analyze CLI's exit-code contract."""
import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis.lint import lint_file, lint_tree
from repro.analysis.registry_audit import audit_registry
from repro.core import xaif

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SRC_TREE = os.path.join(REPO, "src", "repro")


def _lint(src, relpath="src/repro/serve/fake.py"):
    return lint_file(relpath, src=textwrap.dedent(src), relpath=relpath)


def _rules(findings):
    return [f.rule for f in findings]


# ---------------------------------------------------------------------------
# Lint: each rule fires on a seeded violation
# ---------------------------------------------------------------------------


def test_tracer_leak_int_cast_caught():
    fs = _lint("""
        import jax
        def body(x):
            return int(x) + 1
        y = jax.jit(body)
    """)
    assert "XH101" in _rules(fs), fs


def test_tracer_leak_item_caught():
    fs = _lint("""
        import jax
        def body(x):
            return x.item()
        y = jax.jit(body)
    """)
    assert "XH102" in _rules(fs), fs


def test_tracer_leak_if_caught_through_scan_and_partial():
    # the canonical engine shape: a body handed to lax.scan via partial
    fs = _lint("""
        import functools, jax
        def body(params, carry, _):
            if carry > 0:
                carry = carry - 1
            return carry, None
        def chunk(params, carry, steps):
            return jax.lax.scan(functools.partial(body, params),
                                carry, None, length=steps)
    """)
    assert "XH103" in _rules(fs), fs


def test_taint_pruned_for_static_attrs_and_none_checks():
    fs = _lint("""
        import jax
        def body(x, mask):
            if x.shape[0] > 4:          # static: shapes are trace-time
                x = x * 2
            if mask is not None:        # static: identity check
                x = x + 1
            if len(x.shape) == 3:       # static: len of static
                x = x - 1
            return x
        y = jax.jit(body)
    """)
    assert fs == [], fs


def test_closure_vars_are_static():
    # cfg/sampler-style factory closures are baked into the trace
    fs = _lint("""
        import jax
        def make(cfg, sampler):
            def body(x):
                if cfg.gated:
                    x = x * 2
                if sampler is None:
                    x = x + 1
                return x
            return jax.jit(body)
    """)
    assert fs == [], fs


def test_scope_resolution_local_closure_does_not_alias_method():
    # regression: SlotEngine.restore_slot jits a LOCAL def restore();
    # the host-side method restore() must not become a jit region
    fs = _lint("""
        import jax
        class Engine:
            def restore_slot(self, cache, st):
                def restore(cache, st):
                    return cache, st
                self._restore = jax.jit(restore, donate_argnums=(0, 1))
            def restore(self, snap):
                if snap["kind"] == "paged":     # host code: fine
                    return jax.device_put(snap["cache"])
                return snap["cache"]
    """)
    assert fs == [], fs


def test_dtype_drift_caught_and_scoped():
    bad = """
        import jax.numpy as jnp
        def mask(s):
            return jnp.arange(s)
    """
    assert _rules(_lint(bad, "src/repro/kernels/foo/ref.py")) == ["XH201"]
    assert _rules(_lint(bad, "src/repro/serve/foo.py")) == ["XH201"]
    # models/ has benign default-dtype sites: out of scope by design
    assert _lint(bad, "src/repro/models/foo.py") == []
    good = """
        import jax.numpy as jnp
        def mask(s):
            return jnp.arange(s, dtype=jnp.int32)
    """
    assert _lint(good, "src/repro/kernels/foo/ref.py") == []


def test_host_sync_in_jit_region_caught():
    fs = _lint("""
        import jax, numpy as np
        def body(x):
            return np.asarray(x).sum()
        y = jax.jit(body)
    """)
    assert "XH301" in _rules(fs), fs


def test_xaif_bypass_caught_and_tiling_exempt():
    bad = """
        from repro.kernels.rmsnorm.ref import rmsnorm_ref
    """
    assert _rules(_lint(bad, "src/repro/models/foo.py")) == ["XH401"]
    # kernels importing kernels is the implementation layer: fine
    assert _lint(bad, "src/repro/kernels/foo/ops.py") == []
    exempt = """
        from repro.kernels._tiling import divisor_block
    """
    assert _lint(exempt, "src/repro/serve/foo.py") == []


def test_missing_donation_caught():
    bad = """
        import jax
        def step(params, cache, st):
            return cache, st
        f = jax.jit(step)
    """
    assert _rules(_lint(bad)) == ["XH501"]
    good = """
        import jax
        def step(params, cache, st):
            return cache, st
        f = jax.jit(step, donate_argnums=(1, 2))
    """
    assert _lint(good) == []
    # a jit that only READS the cache has nothing to donate
    read_only = """
        import jax
        def peek(params, cache):
            return params
        f = jax.jit(peek)
    """
    assert _lint(read_only) == []


def test_inline_and_file_suppression():
    inline = """
        import jax.numpy as jnp
        def mask(s):
            return jnp.arange(s)  # analysis: disable=XH201
    """
    assert _lint(inline, "src/repro/kernels/foo/ref.py") == []
    whole = """
        # analysis: disable-file=XH201
        import jax.numpy as jnp
        def mask(s):
            return jnp.arange(s)
        def mask2(s):
            return jnp.zeros((s,))
    """
    assert _lint(whole, "src/repro/kernels/foo/ref.py") == []
    wrong_id = """
        import jax.numpy as jnp
        def mask(s):
            return jnp.arange(s)  # analysis: disable=XH999
    """
    assert _rules(_lint(wrong_id, "src/repro/kernels/foo/ref.py")) \
        == ["XH201"]


# ---------------------------------------------------------------------------
# HEAD-clean gates
# ---------------------------------------------------------------------------


def test_head_tree_is_lint_clean():
    fs = lint_tree(SRC_TREE)
    assert fs == [], "\n".join(str(f) for f in fs)


def test_head_registry_is_clean():
    fs = audit_registry()
    assert fs == [], "\n".join(str(f) for f in fs)


# ---------------------------------------------------------------------------
# Registry auditor: seeded violations
# ---------------------------------------------------------------------------


def _fake_backend(x):
    return x


def test_missing_ref_backend_caught():
    xaif._ensure_builtin_backends()
    key = ("fakeop_analysis", "pallas")
    xaif._REGISTRY[key] = xaif.BackendEntry(
        op=key[0], name=key[1], fn=_fake_backend)
    try:
        fs = audit_registry(archs=())
        assert any(f.rule == "XR101" and "fakeop_analysis" in f.path
                   for f in fs), fs
        # its default row buckets have no measurement cells either
        assert any(f.rule == "XR105" for f in fs), fs
    finally:
        del xaif._REGISTRY[key]
    assert audit_registry() == []


def test_dishonest_tunables_caught():
    xaif._ensure_builtin_backends()
    key = ("fakeop_analysis", "ref")
    xaif._REGISTRY[key] = xaif.BackendEntry(
        op=key[0], name=key[1], fn=_fake_backend,
        cost_fn=lambda *a: {}, tunables=(("bm", (128,)), ("nope", (1,))))
    try:
        fs = audit_registry(archs=())
        assert any(f.rule == "XR102" and "nope" in f.message
                   for f in fs), fs
    finally:
        del xaif._REGISTRY[key]


def test_policy_audit_catches_stale_and_lossy(tmp_path):
    xaif._ensure_builtin_backends()
    # a backend that no longer exists, a bucket the op can't emit, an
    # undeclared tuning kwarg — all must surface
    policy = xaif.DispatchPolicy.make({
        ("gemm", "rows_s"): "definitely_not_registered",
        ("rmsnorm", "bogus_bucket"): "ref",
    })
    p = tmp_path / "stale.json"
    policy.save(str(p))
    fs = audit_registry(policy_paths=[str(p)], archs=())
    assert sum(1 for f in fs if f.rule == "XR107") >= 2, fs

    # a lossy backend selected without the allow_lossy marker
    key = ("gemm", "lossy_test_backend")
    xaif._REGISTRY[key] = xaif.BackendEntry(
        op="gemm", name="lossy_test_backend", fn=_fake_backend,
        cost_fn=lambda *a: {}, lossy=True)
    try:
        lp = tmp_path / "lossy.json"
        xaif.DispatchPolicy.make(
            {("gemm", "rows_s"): "lossy_test_backend"}).save(str(lp))
        fs = audit_registry(policy_paths=[str(lp)], archs=())
        assert any(f.rule == "XR108" for f in fs), fs
        # the same policy with the explicit marker is legal
        xaif.DispatchPolicy.make(
            {("gemm", "rows_s"): "lossy_test_backend"}).save(
                str(lp), allow_lossy=True)
        fs = audit_registry(policy_paths=[str(lp)], archs=())
        assert not any(f.rule == "XR108" for f in fs), fs
    finally:
        del xaif._REGISTRY[key]


def test_persisted_autotune_policy_passes_audit(tmp_path):
    from repro.core.autotune import autotune
    res = autotune(ops=["rmsnorm"], iters=1)
    path = str(tmp_path / "policy.json")
    res.persist(path)
    fs = audit_registry(policy_paths=[path], archs=())
    assert fs == [], fs


# ---------------------------------------------------------------------------
# Pinned regressions for the violations the linter surfaced on HEAD
# ---------------------------------------------------------------------------


def test_attn_decode_ref_mask_dtype_pinned_under_x64():
    # HEAD fix: jnp.arange(s) without dtype followed the x64 flag; the
    # masks (and with them the trace cache keys) must not
    from repro.kernels.attn_decode.ref import attn_decode_ref
    from repro.kernels.paged_attention.ref import paged_attention_ref

    q = jax.random.normal(jax.random.PRNGKey(0), (2, 4, 8), jnp.float32)
    k = jax.random.normal(jax.random.PRNGKey(1), (2, 2, 16, 8), jnp.float32)
    v = jax.random.normal(jax.random.PRNGKey(2), (2, 2, 16, 8), jnp.float32)
    pos = jnp.array([3, 9], jnp.int32)
    base = attn_decode_ref(q, k, v, pos)
    with jax.enable_x64(True):
        wide = attn_decode_ref(q, k, v, pos)
    assert base.dtype == wide.dtype == jnp.float32
    np.testing.assert_array_equal(np.asarray(base), np.asarray(wide))

    kp = k.reshape(4, 2, 8, 8).swapaxes(0, 0)       # [P, Hkv, ps, D]
    vp = v.reshape(4, 2, 8, 8)
    table = jnp.array([[0, 1], [2, 3]], jnp.int32)
    base = paged_attention_ref(q, kp, vp, table, pos)
    with jax.enable_x64(True):
        wide = paged_attention_ref(q, kp, vp, table, pos)
    np.testing.assert_array_equal(np.asarray(base), np.asarray(wide))


def test_cnn_encoder_routes_rmsnorm_through_xaif():
    # HEAD fix: _encoder_layer imported rmsnorm_ref directly, bypassing
    # dispatch. Pin: the xaif route is bitwise the ref oracle, and a
    # policy override actually reaches the layer.
    from repro.configs.base import AccelConfig
    from repro.kernels.rmsnorm.ref import rmsnorm_ref
    from repro.models.cnn import SeizureTransformerConfig, _encoder_layer

    cfg = SeizureTransformerConfig(window=64, patch=16, in_channels=1,
                                   d_model=32, d_ff=64, num_heads=4,
                                   num_layers=1, num_classes=2)
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (2, 4, 32), jnp.float32)
    p = {"ln1": jnp.ones((32,)) * 1.5, "ln2": jnp.ones((32,)) * 0.5,
         "wq": jnp.eye(32), "wk": jnp.eye(32), "wv": jnp.eye(32),
         "wo": jnp.eye(32) * 0.1, "w1": jnp.ones((32, 64)) * 0.01,
         "w2": jnp.ones((64, 32)) * 0.01}
    out = _encoder_layer(p, x, cfg, AccelConfig())

    calls = []
    orig = xaif.call
    def spy(op, policy, *a, **kw):
        calls.append(op)
        return orig(op, policy, *a, **kw)
    xaif.call = spy
    try:
        out2 = _encoder_layer(p, x, cfg, AccelConfig())
    finally:
        xaif.call = orig
    assert calls.count("rmsnorm") == 2, calls
    np.testing.assert_array_equal(np.asarray(out), np.asarray(out2))
    # the ref backend is the oracle the old direct call used
    h = rmsnorm_ref(x, p["ln1"])
    h_x = orig("rmsnorm", AccelConfig(), x, p["ln1"])
    np.testing.assert_array_equal(np.asarray(h), np.asarray(h_x))


# ---------------------------------------------------------------------------
# CLI exit-code contract
# ---------------------------------------------------------------------------


def _run_cli(*args, cwd=REPO):
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    return subprocess.run(
        [sys.executable, "-m", "repro.launch.analyze", *args],
        capture_output=True, text=True, env=env, cwd=cwd)


def test_cli_exits_nonzero_on_seeded_violation(tmp_path):
    bad = tmp_path / "bad.py"
    bad.write_text(textwrap.dedent("""
        import jax
        def body(x):
            return int(x)
        f = jax.jit(body)
    """))
    out_json = tmp_path / "findings.json"
    r = _run_cli("--lint", "--paths", str(bad), "--json", str(out_json))
    assert r.returncode != 0, r.stdout + r.stderr
    doc = json.loads(out_json.read_text())
    assert any(f["rule"] == "XH101" for f in doc["findings"]), doc


def test_cli_exits_zero_on_clean_file(tmp_path):
    good = tmp_path / "good.py"
    good.write_text("import jax\nf = jax.jit(lambda x: x + 1)\n")
    out_json = tmp_path / "findings.json"
    r = _run_cli("--lint", "--paths", str(good), "--json", str(out_json))
    assert r.returncode == 0, r.stdout + r.stderr
    assert json.loads(out_json.read_text())["findings"] == []
