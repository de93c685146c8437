"""Compile the main-path Pallas kernels for a TPU v5e chip without one.

Each kernel is lowered through its ``ops.py`` wrapper with
``interpret=False`` at published widths — chatglm3-6b for the dense path
(B=8 decode rows, 512-token prefill, 1024-token KV in pages of 16),
qwen3-moe-30b-a3b for ``moe_decode`` and xlstm-350m for the mLSTM
``ssm_decode`` cell — and compiled by the TPU compiler for one device of a
described ``v5e:2x2`` topology. That catches what interpret mode cannot:
tiling the lowering refuses and scoped VMEM a kernel overruns. Nothing
runs, so nothing here is a timing.

The topology is described inside a module fixture (never at import time):
only one process may load the TPU library, and every test worker imports
this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

B, D, FF, V, HQ, HKV, DH, L, PS = 8, 4096, 13696, 65024, 32, 2, 128, 1024, 16
NP = L // PS
POOL = B * NP + 1


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                  # noqa: BLE001 — no TPU compiler
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """One device of the topology, with the persistent compilation cache
    off: a compile for a described chip is written to the cache but cannot
    be read back without one."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _gemm(act="none"):
    from repro.kernels.gemm.ops import gemm_pallas_op
    return lambda x, w: gemm_pallas_op(x, w, activation=act, interpret=False)


def _cases():
    from repro.kernels.attn_decode.ops import attn_decode_pallas_op
    from repro.kernels.entropy_exit.ops import entropy_pallas_op
    from repro.kernels.flash_attention.ops import attention_pallas_op
    from repro.kernels.moe_decode.ops import moe_decode_pallas_op
    from repro.kernels.paged_attention.ops import paged_attention_pallas_op
    from repro.kernels.rmsnorm.ops import rmsnorm_pallas_op
    from repro.kernels.ssm_decode.ops import ssm_decode_pallas_op
    from repro.kernels.verify_decode.ops import (
        verify_decode_paged_pallas_op, verify_decode_pallas_op)
    bf, f32, i32 = jnp.bfloat16, jnp.float32, jnp.int32
    kv = ((B, HKV, L, DH), bf)
    pages = ((POOL, HKV, PS, DH), bf)
    table, pos = ((B, NP), i32), ((B,), i32)
    cell = ((B, 4, 512), f32)                 # xlstm-350m: 4 heads of 512
    gate = ((B, 4), f32)
    return {
        "gemm_ffn_decode": (_gemm("silu"), [((B, D), bf), ((D, FF), bf)]),
        "gemm_head_decode": (_gemm(), [((B, D), bf), ((D, V), bf)]),
        "gemm_down_prefill": (_gemm(), [((1, 512, FF), bf), ((FF, D), bf)]),
        "rmsnorm_prefill": (
            lambda x, s: rmsnorm_pallas_op(x, s, interpret=False),
            [((1, 512, D), bf), ((D,), f32)]),
        "rmsnorm_odd_rows": (
            lambda x, s: rmsnorm_pallas_op(x, s, interpret=False),
            [((1, 455, D), bf), ((D,), f32)]),
        "flash_attention_prefill": (
            lambda q, k, v: attention_pallas_op(q, k, v, True,
                                                interpret=False),
            [((1, HQ, 512, DH), bf), ((1, HKV, 512, DH), bf),
             ((1, HKV, 512, DH), bf)]),
        "flash_attention_odd_len": (
            lambda q, k, v: attention_pallas_op(q, k, v, True,
                                                interpret=False),
            [((1, HQ, 455, DH), bf), ((1, HKV, 455, DH), bf),
             ((1, HKV, 455, DH), bf)]),
        "attn_decode": (
            lambda q, k, v, p: attn_decode_pallas_op(q, k, v, p,
                                                     interpret=False),
            [((B, HQ, DH), bf), kv, kv, pos]),
        "attn_decode_paged": (
            lambda q, k, v, t, p: paged_attention_pallas_op(
                q, k, v, t, p, interpret=False),
            [((B, HQ, DH), bf), pages, pages, table, pos]),
        "verify_decode": (
            lambda q, k, v, p: verify_decode_pallas_op(q, k, v, p,
                                                       interpret=False),
            [((B, HQ, 5, DH), bf), kv, kv, pos]),
        "verify_decode_paged": (
            lambda q, k, v, t, p: verify_decode_paged_pallas_op(
                q, k, v, t, p, interpret=False),
            [((B, HQ, 5, DH), bf), pages, pages, table, pos]),
        "entropy_exit": (
            lambda lg: entropy_pallas_op(lg, interpret=False),
            [((B, 1, V), bf)]),
        "moe_decode_qwen3": (
            lambda *a: moe_decode_pallas_op(*a, interpret=False),
            [((B, 2048), bf), ((B, 8), i32), ((B, 8), f32),
             ((128, 2048, 768), bf), ((128, 2048, 768), bf),
             ((128, 768, 2048), bf)]),
        "ssm_decode_mlstm_xlstm": (
            lambda *a: ssm_decode_pallas_op(*a, interpret=False),
            [cell, cell, cell, gate, gate, gate,
             ((B, 4, 512, 512), f32), cell]),
    }


@pytest.mark.parametrize("name", [
    "gemm_ffn_decode", "gemm_head_decode", "gemm_down_prefill",
    "rmsnorm_prefill", "rmsnorm_odd_rows", "flash_attention_prefill",
    "flash_attention_odd_len", "attn_decode", "attn_decode_paged",
    "verify_decode", "verify_decode_paged", "entropy_exit",
    "moe_decode_qwen3", "ssm_decode_mlstm_xlstm"])
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, specs = _cases()[name]
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in specs]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
