"""Chunked selective-scan Pallas TPU kernel (Mamba mixer hot loop).

The recurrence is sequential in T but embarrassingly parallel in (B, Din).
Tiling: grid = (B, Din/bd, T/bt) with the T axis innermost/sequential; the
SSM state h [bd, N] lives in VMEM scratch and is carried across T-chunks
(never touching HBM — the GPU implementation's "keep state in SRAM" insight,
which on TPU becomes state-resident-in-VMEM). Inside a chunk the time loop
runs over VMEM-resident tiles.

VMEM @ (bt, bd, N) = (128, 256, 16) fp32:
  u,dt,y 3*128 KiB + b,c 2*8 KiB + A 16 KiB + h 16 KiB  ≈ 0.45 MiB.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssm_kernel(u_ref, dt_ref, a_ref, b_ref, c_ref, d_ref, h0_ref,
                y_ref, hout_ref, h_ref, *, bt: int, nt: int, has_h0: bool):
    ti = pl.program_id(2)

    @pl.when(ti == 0)
    def _init():
        if has_h0:
            h_ref[...] = h0_ref[0].astype(jnp.float32)
        else:
            h_ref[...] = jnp.zeros_like(h_ref)

    u = u_ref[0].astype(jnp.float32)          # [bt, bd]
    dt = dt_ref[0].astype(jnp.float32)        # [bt, bd]
    a = a_ref[...].astype(jnp.float32)        # [bd, N]
    b = b_ref[0].astype(jnp.float32)          # [bt, N]
    c = c_ref[0].astype(jnp.float32)          # [bt, N]
    d = d_ref[...].astype(jnp.float32)        # [1, bd]

    def step(t, carry):
        h, ys = carry
        da = jnp.exp(dt[t][:, None] * a)                  # [bd, N]
        db = (dt[t] * u[t])[:, None] * b[t][None, :]      # [bd, N]
        h = da * h + db
        y = jnp.sum(h * c[t][None, :], axis=-1)           # [bd]
        ys = jax.lax.dynamic_update_slice(ys, y[None, :], (t, 0))
        return h, ys

    h0 = h_ref[...]
    ys0 = jnp.zeros((bt, u.shape[1]), jnp.float32)
    h_fin, ys = jax.lax.fori_loop(0, bt, step, (h0, ys0))
    h_ref[...] = h_fin
    y_ref[0] = (ys + d * u).astype(y_ref.dtype)

    @pl.when(ti == nt - 1)
    def _finish():
        hout_ref[0] = h_ref[...].astype(hout_ref.dtype)


def selective_scan_pallas(u, dt, a, b, c, d, h0=None, *, bt: int = 128,
                          bd: int = 256, interpret: bool = False):
    """Same contract as ref.selective_scan_ref. T % bt == 0 required
    (ops.py pads); Din % bd handled by shrinking bd."""
    bsz, t, din = u.shape
    n = a.shape[-1]
    bt = min(bt, t)
    while t % bt:
        bt //= 2
    bd = min(bd, din)
    while din % bd:
        bd //= 2
    grid = (bsz, din // bd, t // bt)
    has_h0 = h0 is not None
    if h0 is None:
        h0 = jnp.zeros((bsz, din, n), jnp.float32)
    kernel = functools.partial(_ssm_kernel, bt=bt, nt=grid[2], has_h0=has_h0)
    y, h_fin = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bt, bd), lambda b_, di, ti: (b_, ti, di)),   # u
            pl.BlockSpec((1, bt, bd), lambda b_, di, ti: (b_, ti, di)),   # dt
            pl.BlockSpec((bd, n), lambda b_, di, ti: (di, 0)),            # a
            pl.BlockSpec((1, bt, n), lambda b_, di, ti: (b_, ti, 0)),     # b
            pl.BlockSpec((1, bt, n), lambda b_, di, ti: (b_, ti, 0)),     # c
            pl.BlockSpec((1, bd), lambda b_, di, ti: (0, di)),            # d
            pl.BlockSpec((1, bd, n), lambda b_, di, ti: (b_, di, 0)),     # h0
        ],
        out_specs=[
            pl.BlockSpec((1, bt, bd), lambda b_, di, ti: (b_, ti, di)),
            pl.BlockSpec((1, bd, n), lambda b_, di, ti: (b_, di, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bsz, t, din), u.dtype),
            jax.ShapeDtypeStruct((bsz, din, n), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((bd, n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        name="selective_scan",
        interpret=interpret,
    )(u, dt, a, b, c, d.reshape(1, din), h0)
    return y, h_fin
