"""Kernel microbenchmarks (§IV analogue — the accelerator integration).

Two things are reported per XAIF op:
  * wall-clock of the REF backend on this CPU host (the only real timing
    this container can produce; Pallas kernels run in interpret mode, whose
    timing is meaningless, so they are validated for correctness and
    costed analytically);
  * the HBM-byte model of ref vs fused kernel (the NM-Carus data-movement
    argument): fused kernels make one pass where the unfused path makes
    2-3 — the ratio is the structural speedup the roofline credits.

``tuned_vs_static()`` additionally runs the measured autotuner
(core/autotune.py) and reports, per (op, shape-bucket) cell, the tuned
DispatchPolicy's backend against the static AccelConfig default — the
tuned pick is never slower on any measured cell (it is the argmin of a
candidate set that includes the static default).
"""
from __future__ import annotations

import json
import time
from typing import Dict, List

import jax
import jax.numpy as jnp

from repro.configs.base import AccelConfig
from repro.core import xaif

BENCH_JSON = "BENCH_kernels.json"


def _time(fn, *args, iters=5) -> float:
    fn(*args)[0].block_until_ready() if isinstance(fn(*args), tuple) else \
        jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(iters):
        jax.block_until_ready(fn(*args))
    return (time.perf_counter() - t0) / iters * 1e6


def bench() -> List[Dict]:
    rows = []
    key = jax.random.PRNGKey(0)
    ref = AccelConfig()

    # gemm: fused does 1 HBM round-trip; unfused matmul+bias+act does 3
    m, k, n = 1024, 1024, 1024
    x = jax.random.normal(key, (m, k), jnp.float32)
    w = jax.random.normal(jax.random.fold_in(key, 1), (k, n), jnp.float32)
    b = jnp.zeros((n,))
    f = jax.jit(lambda x, w, b: xaif.call("gemm", ref, x, w, bias=b,
                                          activation="gelu"))
    us = _time(f, x, w, b)
    bytes_unfused = 4 * (m * k + k * n + 3 * m * n * 2)
    bytes_fused = 4 * (m * k + k * n + m * n)
    rows.append({"name": "gemm_bias_gelu_1024", "us_per_call_ref": us,
                 "hbm_bytes_ref": bytes_unfused,
                 "hbm_bytes_fused": bytes_fused,
                 "fusion_byte_ratio": bytes_unfused / bytes_fused})

    # entropy: ref materializes log_softmax (3 passes); kernel streams (1)
    rows_, v = 4096, 65536
    lg = jax.random.normal(key, (rows_, v), jnp.float32)
    f = jax.jit(lambda l: xaif.call("entropy_exit", ref, l))
    us = _time(f, lg)
    rows.append({"name": "entropy_exit_4096x65536", "us_per_call_ref": us,
                 "hbm_bytes_ref": 4 * rows_ * v * 3,
                 "hbm_bytes_fused": 4 * rows_ * v,
                 "fusion_byte_ratio": 3.0})

    # rmsnorm
    x = jax.random.normal(key, (8192, 4096), jnp.float32)
    s = jnp.ones((4096,))
    f = jax.jit(lambda x, s: xaif.call("rmsnorm", ref, x, s))
    us = _time(f, x, s)
    rows.append({"name": "rmsnorm_8192x4096", "us_per_call_ref": us,
                 "hbm_bytes_ref": 4 * 8192 * 4096 * 3,
                 "hbm_bytes_fused": 4 * 8192 * 4096 * 2,
                 "fusion_byte_ratio": 1.5})

    # attention blockwise vs materialized
    q = jax.random.normal(key, (1, 8, 1024, 64), jnp.bfloat16)
    kk = jax.random.normal(jax.random.fold_in(key, 2), (1, 2, 1024, 64),
                           jnp.bfloat16)
    vv = jax.random.normal(jax.random.fold_in(key, 3), (1, 2, 1024, 64),
                           jnp.bfloat16)
    f = jax.jit(lambda q, k, v: xaif.call("attention", ref, q, k, v))
    us = _time(f, q, kk, vv)
    blockwise = AccelConfig(backends={"attention": "blockwise"})
    f2 = jax.jit(lambda q, k, v: xaif.call("attention", blockwise, q, k, v))
    us2 = _time(f2, q, kk, vv)
    rows.append({"name": "attention_ref_vs_blockwise_1k", "us_per_call_ref": us,
                 "us_per_call_blockwise": us2,
                 "scores_bytes_materialized": 4 * 8 * 1024 * 1024,
                 "scores_bytes_blockwise": 4 * 8 * 1024 * 128})

    # paged decode attention. The timing is the REF backend, which gathers
    # the FULL page-table extent (invalid entries fetch the scratch page) —
    # like every row here, the byte ratio is the ANALYTIC structural saving
    # of the fused path, not a property of the measured ref: the Pallas
    # backend's per-page BlockSpec DMA is what touches only RESIDENT pages,
    # while any contiguous/gather decode streams B * max_len lanes per
    # token regardless of actual lengths
    b_, hkv_, ps_, np_, d_ = 8, 2, 16, 64, 64          # max_len 1024
    pool = b_ * np_ + 1
    kp = jax.random.normal(key, (pool, hkv_, ps_, d_), jnp.bfloat16)
    vp = jax.random.normal(jax.random.fold_in(key, 4), (pool, hkv_, ps_, d_),
                           jnp.bfloat16)
    qd = jax.random.normal(jax.random.fold_in(key, 5), (b_, 8, d_),
                           jnp.bfloat16)
    table = (1 + jnp.arange(b_)[:, None] * np_
             + jnp.arange(np_)[None, :]).astype(jnp.int32)
    pos = (jnp.arange(b_, dtype=jnp.int32) * 97) % (np_ * ps_)
    table = jnp.where(jnp.arange(np_)[None, :] <= pos[:, None] // ps_,
                      table, -1)
    f = jax.jit(lambda *a: xaif.call("attn_decode_paged", ref, *a))
    us = _time(f, qd, kp, vp, table, pos)
    resident = int(jnp.sum(pos // ps_ + 1)) * ps_
    full = b_ * np_ * ps_
    rows.append({"name": "attn_decode_paged_1k", "us_per_call_ref": us,
                 "kv_lanes_ref_full_extent": full,
                 "kv_lanes_pallas_resident": resident,
                 "residency_byte_ratio_analytic": full / max(resident, 1)})
    return rows


def tuned_vs_static(iters: int = 3, scale: int = 1) -> List[Dict]:
    """One row per measured (op, bucket) cell: tuned policy vs the static
    AccelConfig default, from the same measurement sweep."""
    from repro.core.autotune import autotune

    static = AccelConfig()
    result = autotune(iters=iters, scale=scale, baseline=static)
    rows = []
    for cell in result.cells:
        tuned_backend, tuning = cell.winner()
        static_backend = static.backend_for(cell.op)
        tuned_us = cell.us_for(tuned_backend)
        static_us = cell.us_for(static_backend)
        rows.append({
            "op": cell.op, "bucket": cell.bucket,
            "static_backend": static_backend, "static_us": static_us,
            "tuned_backend": tuned_backend, "tuned_tuning": dict(tuning),
            "tuned_us": tuned_us,
            "speedup": static_us / tuned_us if tuned_us else float("inf"),
            "not_slower": tuned_us <= static_us,
        })
    return rows


def main(json_path: str = BENCH_JSON):
    rows = bench()
    for r in rows:
        print(r)
    print("--- autotuned DispatchPolicy vs static AccelConfig ---")
    cells = tuned_vs_static()
    for r in cells:
        print(r)
    assert all(r["not_slower"] for r in cells), \
        "tuned policy slower than static default on a measured cell"
    print(f"tuned policy not slower on all {len(cells)} measured cells")
    if json_path:
        with open(json_path, "w") as f:
            json.dump({"bench": "kernels", "micro": rows,
                       "tuned_vs_static": cells},
                      f, indent=2, sort_keys=True, default=str)
        print(f"wrote {json_path}")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
