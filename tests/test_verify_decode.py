"""Multi-token verify attention (speculative decoding's verification op).

The contract the engine's greedy token identity rests on: ref backends are
BITWISE-identical to K1 sequential single-token decode steps (contiguous
and paged), the Pallas backends match the refs numerically in interpret
mode, and the op participates in XAIF dispatch (kv_s/kv_l buckets, tunable
block size, autotune cells).
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import xaif
from repro.kernels.attn_decode.ref import attn_decode_ref
from repro.kernels.paged_attention.ref import paged_attention_ref
from repro.kernels.verify_decode import ops as vd_ops
from repro.kernels.verify_decode import ref as vd_ref


def _contig(seed, b=3, hq=4, hkv=2, s=64, d=16, k1=4):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    q = jax.random.normal(ks[0], (b, hq, k1, d))
    k = jax.random.normal(ks[1], (b, hkv, s, d))
    v = jax.random.normal(ks[2], (b, hkv, s, d))
    # staggered positions, leaving room for all K1 rows
    pos = (jnp.arange(b, dtype=jnp.int32) * 7 + 3) % (s - k1)
    return q, k, v, pos


def _paged(seed, b=3, hq=4, hkv=2, np_=4, ps=8, d=16, k1=4):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    pool = b * np_ + 1
    q = jax.random.normal(ks[0], (b, hq, k1, d))
    kp = jax.random.normal(ks[1], (pool, hkv, ps, d))
    vp = jax.random.normal(ks[2], (pool, hkv, ps, d))
    table = (1 + jnp.arange(b)[:, None] * np_
             + jnp.arange(np_)[None, :]).astype(jnp.int32)
    pos = (jnp.arange(b, dtype=jnp.int32) * ps + 3) % (np_ * ps - k1)
    # unallocated tail entries are -1, exactly like the live mirror table
    n_alloc = (pos + k1 - 1) // ps + 1
    table = jnp.where(jnp.arange(np_)[None, :] < n_alloc[:, None],
                      table, -1)
    return q, kp, vp, table, pos


# ---------------------------------------------------------------------------
# ref == K1 sequential decode steps, bitwise
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("k1", [1, 3, 5])
@pytest.mark.parametrize("seed", [0, 7])
def test_ref_bitwise_equals_sequential_decode(seed, k1):
    q, k, v, pos = _contig(seed, k1=k1)
    out = vd_ref.verify_decode_ref(q, k, v, pos)
    seq = jnp.stack([attn_decode_ref(q[:, :, i, :], k, v, pos + i)
                     for i in range(k1)], axis=2)
    assert np.array_equal(np.asarray(out), np.asarray(seq)), \
        "verify ref must be BITWISE identical to sequential decode"


@pytest.mark.parametrize("k1", [1, 3, 5])
@pytest.mark.parametrize("seed", [0, 7])
def test_paged_ref_bitwise_equals_sequential_paged_decode(seed, k1):
    q, kp, vp, table, pos = _paged(seed, k1=k1)
    out = vd_ref.verify_decode_paged_ref(q, kp, vp, table, pos)
    seq = jnp.stack(
        [paged_attention_ref(q[:, :, i, :], kp, vp, table, pos + i)
         for i in range(k1)], axis=2)
    assert np.array_equal(np.asarray(out), np.asarray(seq)), \
        "paged verify ref must be BITWISE identical to sequential decode"


def test_ref_staircase_causality():
    """Perturbing the KV row at cache_pos + i must change query i but NOT
    queries < i (each query sees only its own prefix)."""
    q, k, v, pos = _contig(0, b=1, k1=4)
    base = np.asarray(vd_ref.verify_decode_ref(q, k, v, pos))
    p = int(pos[0])
    for i in range(1, 4):
        k2 = k.at[:, :, p + i, :].add(3.0)
        v2 = v.at[:, :, p + i, :].add(1.0)
        out = np.asarray(vd_ref.verify_decode_ref(q, k2, v2, pos))
        assert np.array_equal(out[:, :, :i], base[:, :, :i]), i
        assert not np.array_equal(out[:, :, i], base[:, :, i]), i


# ---------------------------------------------------------------------------
# pallas (interpret) vs ref
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("s,k1", [(64, 4), (128, 2), (64, 1), (96, 5)])
def test_pallas_matches_ref(s, k1):
    q, k, v, pos = _contig(1, s=s, k1=k1)
    ref = vd_ops.verify_decode_ref_op(q, k, v, pos)
    for bs in (32, 64):
        out = vd_ops.verify_decode_pallas_op(q, k, v, pos, bs=bs,
                                             interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("np_,ps,k1", [(4, 8, 4), (8, 16, 3), (3, 8, 1)])
def test_paged_pallas_matches_ref(np_, ps, k1):
    q, kp, vp, table, pos = _paged(2, np_=np_, ps=ps, k1=k1)
    ref = vd_ops.verify_decode_paged_ref_op(q, kp, vp, table, pos)
    out = vd_ops.verify_decode_paged_pallas_op(q, kp, vp, table, pos,
                                               interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# dispatch: buckets, policy routing, autotune cells
# ---------------------------------------------------------------------------


def test_shape_buckets_follow_decode():
    q, k, v, pos = _contig(0, s=64)
    shapes = tuple(a.shape for a in (q, k, v, pos))
    assert xaif.shape_bucket("verify_decode", shapes) == "kv_s"
    q, k, v, pos = _contig(0, s=2048)
    shapes = tuple(a.shape for a in (q, k, v, pos))
    assert xaif.shape_bucket("verify_decode", shapes) == "kv_l"
    q, kp, vp, table, pos = _paged(0, np_=4, ps=8)
    shapes = tuple(a.shape for a in (q, kp, vp, table, pos))
    assert xaif.shape_bucket("verify_decode_paged", shapes) == "kv_s"


def test_policy_routes_verify_backend():
    q, k, v, pos = _contig(3, s=64, k1=3)
    ref_pol = xaif.DispatchPolicy.make({("verify_decode", "kv_s"): "ref"})
    pal_pol = xaif.DispatchPolicy.make(
        {("verify_decode", "kv_s"): ("pallas", {"bs": 32})})
    a = xaif.call("verify_decode", ref_pol, q, k, v, pos)
    b = xaif.call("verify_decode", pal_pol, q, k, v, pos)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=1e-5, atol=1e-5)


def test_autotune_cells_registered():
    from repro.core.autotune import CELLS
    for key in (("verify_decode", "kv_s"), ("verify_decode", "kv_l"),
                ("verify_decode_paged", "kv_s"),
                ("verify_decode_paged", "kv_l")):
        assert key in CELLS, key
        args, kwargs = CELLS[key](1)
        out = xaif.call(key[0], xaif.DispatchPolicy.make(
            {key: "ref"}), *args, **kwargs)
        assert np.all(np.isfinite(np.asarray(out)))
