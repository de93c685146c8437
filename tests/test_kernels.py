"""Per-kernel validation: Pallas (interpret=True) vs the pure-jnp ref.py
oracle, swept over shapes and dtypes per the deliverable spec."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.entropy_exit import ops as ee_ops, ref as ee_ref
from repro.kernels.flash_attention import ops as fa_ops, ref as fa_ref
from repro.kernels.gemm import ops as gemm_ops, ref as gemm_ref
from repro.kernels.rmsnorm import ops as rn_ops, ref as rn_ref
from repro.kernels.ssm_scan import ops as ss_ops, ref as ss_ref


# ---------------------------------------------------------------------------
# gemm
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("m,k,n", [(128, 128, 128), (256, 512, 384),
                                   (200, 300, 260), (64, 1000, 130),
                                   (1, 256, 512)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("act", ["none", "gelu", "silu", "relu"])
def test_gemm_pallas_matches_ref(m, k, n, dtype, act):
    kx, kw, kb = jax.random.split(jax.random.PRNGKey(m * 7 + n), 3)
    x = jax.random.normal(kx, (m, k), dtype)
    w = jax.random.normal(kw, (k, n), dtype)
    b = jax.random.normal(kb, (n,), dtype)
    ref = gemm_ref.gemm_ref(x, w, b, act)
    out = gemm_ops.gemm_pallas_op(x, w, b, act, interpret=True)
    tol = 1e-4 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=tol, atol=tol * 10)


@pytest.mark.parametrize("m,k,n", [(128, 256, 128), (200, 300, 260)])
def test_gemm_int8_matches_int8_ref(m, k, n):
    kx, kw = jax.random.split(jax.random.PRNGKey(0))
    x = jax.random.normal(kx, (m, k), jnp.float32)
    w = jax.random.normal(kw, (k, n), jnp.float32)
    out = gemm_ops.gemm_int8_pallas_op(x, w, None, "none", interpret=True)
    xq, xs = gemm_ref.quantize_int8(x, -1)
    wq, ws = gemm_ref.quantize_int8(w, 0)
    ref = gemm_ref.gemm_int8_ref(xq, wq, xs, ws, None, "none", jnp.float32)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-4)


def test_gemm_int8_close_to_fp():
    """The NM-Carus integer path stays within quantization error of fp."""
    x = jax.random.normal(jax.random.PRNGKey(1), (256, 512), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(2), (512, 256), jnp.float32)
    out8 = gemm_ops.gemm_int8_pallas_op(x, w, None, "none", interpret=True)
    ref = x @ w
    rel = np.linalg.norm(np.asarray(out8) - np.asarray(ref)) / \
        np.linalg.norm(np.asarray(ref))
    assert rel < 0.02, rel


def test_gemm_leading_dims():
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 3, 100), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(4), (100, 50), jnp.float32)
    out = gemm_ops.gemm_pallas_op(x, w, interpret=True)
    assert out.shape == (2, 3, 50)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(gemm_ref.gemm_ref(x, w)),
                               rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# rmsnorm
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("shape", [(4, 128), (33, 512), (2, 7, 384),
                                   (1, 1024), (256, 4096)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rmsnorm_matches_ref(shape, dtype):
    x = jax.random.normal(jax.random.PRNGKey(0), shape, dtype)
    s = jax.random.normal(jax.random.PRNGKey(1), (shape[-1],), jnp.float32)
    out = rn_ops.rmsnorm_pallas_op(x, s, interpret=True)
    ref = rn_ref.rmsnorm_ref(x, s)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=tol, atol=tol)


# ---------------------------------------------------------------------------
# entropy_exit
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("rows,vocab", [(4, 128), (37, 5001), (16, 65536),
                                        (256, 2048), (3, 151936)])
def test_entropy_matches_ref(rows, vocab):
    lg = jax.random.normal(jax.random.PRNGKey(rows), (rows, vocab),
                           jnp.float32) * 3.0
    out = ee_ops.entropy_pallas_op(lg, interpret=True)
    ref = ee_ref.entropy_ref(lg)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)
    assert np.all((np.asarray(out) >= 0) & (np.asarray(out) <= 1.0 + 1e-6))


def test_entropy_extremes():
    # one-hot logits -> entropy ~ 0; uniform -> entropy ~ 1
    v = 512
    onehot = jnp.full((2, v), -30.0).at[:, 3].set(30.0)
    uniform = jnp.zeros((2, v))
    lo = ee_ops.entropy_pallas_op(onehot, interpret=True)
    hi = ee_ops.entropy_pallas_op(uniform, interpret=True)
    assert np.all(np.asarray(lo) < 1e-5)
    np.testing.assert_allclose(np.asarray(hi), 1.0, rtol=1e-6)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("b,hq,hkv,t,d", [(2, 8, 2, 128, 64), (1, 4, 4, 64, 32),
                                          (2, 16, 8, 256, 64), (1, 2, 1, 96, 128),
                                          (1, 4, 2, 37, 32)])   # odd T: padded q
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_matches_ref(b, hq, hkv, t, d, dtype):
    ks = jax.random.split(jax.random.PRNGKey(b * hq + t), 3)
    q = jax.random.normal(ks[0], (b, hq, t, d), dtype)
    k = jax.random.normal(ks[1], (b, hkv, t, d), dtype)
    v = jax.random.normal(ks[2], (b, hkv, t, d), dtype)
    out = fa_ops.attention_pallas_op(q, k, v, True, interpret=True,
                                     bq=64, bkv=64)
    ref = fa_ref.attention_ref(q, k, v, True)
    tol = 2e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=tol, atol=tol * 10)


def test_blockwise_attention_matches_ref():
    ks = jax.random.split(jax.random.PRNGKey(9), 3)
    q = jax.random.normal(ks[0], (2, 8, 256, 64), jnp.float32)
    k = jax.random.normal(ks[1], (2, 2, 256, 64), jnp.float32)
    v = jax.random.normal(ks[2], (2, 2, 256, 64), jnp.float32)
    out = fa_ops.attention_blockwise_op(q, k, v, True, bq=64, bkv=128)
    ref = fa_ref.attention_ref(q, k, v, True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_attention_cross_lengths():
    """seq_kv > seq_q (prefill continuation) causal offset correctness."""
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    q = jax.random.normal(ks[0], (1, 2, 64, 32), jnp.float32)
    k = jax.random.normal(ks[1], (1, 2, 128, 32), jnp.float32)
    v = jax.random.normal(ks[2], (1, 2, 128, 32), jnp.float32)
    out = fa_ops.attention_pallas_op(q, k, v, True, interpret=True,
                                     bq=32, bkv=32)
    ref = fa_ref.attention_ref(q, k, v, True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-4)


# ---------------------------------------------------------------------------
# ssm scan
# ---------------------------------------------------------------------------

def _ssm_inputs(b, t, din, n, key):
    ks = jax.random.split(key, 6)
    u = jax.random.normal(ks[0], (b, t, din), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, t, din)))
    a = -jnp.exp(jax.random.normal(ks[2], (din, n)))
    bb = jax.random.normal(ks[3], (b, t, n))
    cc = jax.random.normal(ks[4], (b, t, n))
    dd = jax.random.normal(ks[5], (din,))
    return u, dt, a, bb, cc, dd


@pytest.mark.parametrize("b,t,din,n", [(2, 64, 32, 8), (1, 96, 64, 16),
                                       (3, 128, 16, 4)])
def test_ssm_pallas_matches_ref(b, t, din, n):
    u, dt, a, bb, cc, dd = _ssm_inputs(b, t, din, n, jax.random.PRNGKey(t))
    y1, h1 = ss_ops.ssm_pallas_op(u, dt, a, bb, cc, dd, interpret=True,
                                  bt=32, bd=16)
    y2, h2 = ss_ref.selective_scan_ref(u, dt, a, bb, cc, dd)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), rtol=1e-4,
                               atol=1e-4)
    np.testing.assert_allclose(np.asarray(h1), np.asarray(h2), rtol=1e-4,
                               atol=1e-4)


@pytest.mark.parametrize("b,t,din,n", [(2, 64, 32, 8), (1, 128, 64, 16)])
def test_ssm_assoc_matches_ref(b, t, din, n):
    u, dt, a, bb, cc, dd = _ssm_inputs(b, t, din, n, jax.random.PRNGKey(t + 1))
    y1, h1 = ss_ops.ssm_assoc_op(u, dt, a, bb, cc, dd, chunk=32)
    y2, h2 = ss_ref.selective_scan_ref(u, dt, a, bb, cc, dd)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), rtol=1e-4,
                               atol=1e-4)
    np.testing.assert_allclose(np.asarray(h1), np.asarray(h2), rtol=1e-4,
                               atol=1e-4)


def test_ssm_initial_state_chaining():
    """Running [0:T/2] then [T/2:T] with carried state == full run."""
    u, dt, a, bb, cc, dd = _ssm_inputs(2, 64, 32, 8, jax.random.PRNGKey(3))
    y_full, h_full = ss_ref.selective_scan_ref(u, dt, a, bb, cc, dd)
    h = None
    ys = []
    for sl in (slice(0, 32), slice(32, 64)):
        y, h = ss_ops.ssm_pallas_op(u[:, sl], dt[:, sl], a, bb[:, sl],
                                    cc[:, sl], dd, h0=h, interpret=True,
                                    bt=16, bd=16)
        ys.append(y)
    np.testing.assert_allclose(np.asarray(jnp.concatenate(ys, 1)),
                               np.asarray(y_full), rtol=1e-4, atol=1e-4)
