"""Fused GEMM Pallas TPU kernel — the NM-Carus adaptation (DESIGN.md C4).

NM-Carus puts a vector unit inside the SRAM bank so operands never cross the
bus. The TPU-native equivalent: stream HBM tiles into VMEM once, keep the
fp32/int32 accumulator resident in VMEM scratch across the K grid axis, and
fuse bias + activation (+ dequant for the int8 path) before the single
write-back. One HBM round-trip instead of (matmul, bias, activation) three.

Tiling: (bm x bk) @ (bk x bn) MXU tiles; defaults are multiples of 128 to
match the 128x128 systolic array. Grid = (M/bm, N/bn, K/bk) with the K axis
innermost so the accumulator stays hot in VMEM (sequential TPU grid order).
VMEM working set = bm*bk + bk*bn + bm*bn(fp32 acc) + bm*bn(out)
             =  128k + 128k + 512k + 256k  ≈ 1 MiB at (128,128,512) bf16.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.gemm.ref import ACTIVATIONS


def _gemm_kernel(x_ref, w_ref, b_ref, o_ref, acc_ref, *, nk: int, activation: str,
                 has_bias: bool):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(x_ref[...], w_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(2) == nk - 1)
    def _finish():
        out = acc_ref[...]
        if has_bias:
            out = out + b_ref[...].astype(jnp.float32)
        out = ACTIVATIONS[activation](out)
        o_ref[...] = out.astype(o_ref.dtype)


def gemm_pallas(x: jax.Array, w: jax.Array, bias: Optional[jax.Array] = None,
                activation: str = "none", *, bm: int = 128, bn: int = 128,
                bk: int = 512, interpret: bool = False) -> jax.Array:
    """x [M, K] @ w [K, N] with fused bias/activation. M, N, K must be
    divisible by the block sizes (ops.py pads otherwise)."""
    m, k = x.shape
    k2, n = w.shape
    assert k == k2, (x.shape, w.shape)
    bm, bn, bk = min(bm, m), min(bn, n), min(bk, k)
    assert m % bm == 0 and n % bn == 0 and k % bk == 0, (m, n, k, bm, bn, bk)
    grid = (m // bm, n // bn, k // bk)
    has_bias = bias is not None
    in_specs = [
        pl.BlockSpec((bm, bk), lambda i, j, l: (i, l)),
        pl.BlockSpec((bk, bn), lambda i, j, l: (l, j)),
    ]
    args = [x, w]
    if has_bias:
        in_specs.append(pl.BlockSpec((1, bn), lambda i, j, l: (0, j)))
        args.append(bias.reshape(1, n))
    else:
        in_specs.append(pl.BlockSpec((1, bn), lambda i, j, l: (0, j)))
        args.append(jnp.zeros((1, n), x.dtype))
    kernel = functools.partial(_gemm_kernel, nk=grid[2], activation=activation,
                               has_bias=has_bias)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, l: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(*args)


def _gemm_int8_kernel(x_ref, w_ref, xs_ref, ws_ref, b_ref, o_ref, acc_ref, *,
                      nk: int, activation: str, has_bias: bool):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(x_ref[...].astype(jnp.int32),
                            w_ref[...].astype(jnp.int32),
                            preferred_element_type=jnp.int32)

    @pl.when(pl.program_id(2) == nk - 1)
    def _finish():
        out = acc_ref[...].astype(jnp.float32)
        out = out * xs_ref[...].astype(jnp.float32)           # [bm, 1]
        out = out * ws_ref[...].astype(jnp.float32)           # [1, bn]
        if has_bias:
            out = out + b_ref[...].astype(jnp.float32)
        out = ACTIVATIONS[activation](out)
        o_ref[...] = out.astype(o_ref.dtype)


def gemm_int8_pallas(xq: jax.Array, wq: jax.Array, x_scale: jax.Array,
                     w_scale: jax.Array, bias: Optional[jax.Array] = None,
                     activation: str = "none", *, bm: int = 128, bn: int = 128,
                     bk: int = 512, out_dtype=jnp.bfloat16,
                     interpret: bool = False) -> jax.Array:
    """Integer GEMM, int32 accumulate, fused dequant+bias+activation.
    xq [M, K] int8, wq [K, N] int8, x_scale [M, 1] f32, w_scale [1, N] f32."""
    m, k = xq.shape
    _, n = wq.shape
    bm, bn, bk = min(bm, m), min(bn, n), min(bk, k)
    assert m % bm == 0 and n % bn == 0 and k % bk == 0, (m, n, k, bm, bn, bk)
    grid = (m // bm, n // bn, k // bk)
    has_bias = bias is not None
    b = bias.reshape(1, n) if has_bias else jnp.zeros((1, n), jnp.float32)
    kernel = functools.partial(_gemm_int8_kernel, nk=grid[2],
                               activation=activation, has_bias=has_bias)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, l: (i, l)),
            pl.BlockSpec((bk, bn), lambda i, j, l: (l, j)),
            pl.BlockSpec((bm, 1), lambda i, j, l: (i, 0)),
            pl.BlockSpec((1, bn), lambda i, j, l: (0, j)),
            pl.BlockSpec((1, bn), lambda i, j, l: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, l: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(xq, wq, x_scale, w_scale, b)
