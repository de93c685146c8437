"""Pallas TPU kernels for the single-token recurrent decode steps.

Both cells are pure VPU work (elementwise + small reductions, no MXU):
the win over XLA is fusing the whole state update into one VMEM-resident
pass so the [B, Din, N] / [B, H, dh, dh] state is read and written exactly
once per token.

* Mamba: grid (B, Din/bd) — each program owns a [bd, N] state tile.
  VMEM @ bd=256, N=16 fp32: state in+out 2*16 KiB + operands ~4 KiB.
* mLSTM: grid (B, H) — each program owns one head's [dh, dh] cell state
  (1 MiB fp32 at xlstm-350m's dh = 512).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _mamba_kernel(x_ref, g_ref, a_ref, b_ref, c_ref, m_ref, h_ref,
                  y_ref, hout_ref):
    x = x_ref[0].astype(jnp.float32)          # [bd]
    g = g_ref[0].astype(jnp.float32)          # [bd]
    a = a_ref[...].astype(jnp.float32)        # [bd, N]
    b = b_ref[0].astype(jnp.float32)          # [N]
    c = c_ref[0].astype(jnp.float32)          # [N]
    m = m_ref[0].astype(jnp.float32)          # [bd]
    h = h_ref[0].astype(jnp.float32)          # [bd, N]
    da = jnp.exp(g[:, None] * a)
    db = (g * x)[:, None] * b[None, :]
    h_new = da * h + db
    y = jnp.sum(h_new * c[None, :], axis=-1) + m * x
    y_ref[0] = y.astype(y_ref.dtype)
    hout_ref[0] = h_new.astype(hout_ref.dtype)


def mamba_decode_pallas(x, g, a, b, c, m, h, *, bd: int = 256,
                        interpret: bool = False):
    bsz, din = x.shape
    n = a.shape[-1]
    bd = min(bd, din)
    while din % bd:
        bd //= 2
    grid = (bsz, din // bd)
    y, h_new = pl.pallas_call(
        _mamba_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bd), lambda b_, di: (b_, di)),       # x
            pl.BlockSpec((1, bd), lambda b_, di: (b_, di)),       # g
            pl.BlockSpec((bd, n), lambda b_, di: (di, 0)),        # a
            pl.BlockSpec((1, n), lambda b_, di: (b_, 0)),         # b
            pl.BlockSpec((1, n), lambda b_, di: (b_, 0)),         # c
            pl.BlockSpec((1, bd), lambda b_, di: (0, di)),        # m (d_skip)
            pl.BlockSpec((1, bd, n), lambda b_, di: (b_, di, 0)),  # h
        ],
        out_specs=[
            pl.BlockSpec((1, bd), lambda b_, di: (b_, di)),
            pl.BlockSpec((1, bd, n), lambda b_, di: (b_, di, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bsz, din), jnp.float32),
            jax.ShapeDtypeStruct((bsz, din, n), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
        ),
        name="mamba_decode",
        interpret=interpret,
    )(x, g, a, b, c, m.reshape(1, din), h)
    return y, h_new


def _mlstm_kernel(q_ref, k_ref, v_ref, li_ref, lf_ref, m_ref, c_ref, n_ref,
                  hout_ref, cout_ref, nout_ref, mout_ref):
    # one (batch row, head): q/k/n arrive as [dh, 1] columns and v as a
    # [1, dh] row, so the cell update and read-out are pure broadcasts
    q = q_ref[0, 0].astype(jnp.float32)       # [dh, 1]
    k = k_ref[0, 0].astype(jnp.float32)       # [dh, 1]
    v = v_ref[0, 0].astype(jnp.float32)       # [1, dh]
    li = li_ref[0, 0].astype(jnp.float32)     # [1, 1]
    lf = lf_ref[0, 0].astype(jnp.float32)
    m = m_ref[0, 0].astype(jnp.float32)
    cst = c_ref[0, 0].astype(jnp.float32)     # [dh, dh]
    nst = n_ref[0, 0].astype(jnp.float32)     # [dh, 1]
    m_new = jnp.maximum(lf + m, li)
    fw = jnp.exp(lf + m - m_new)
    iw = jnp.exp(li - m_new)
    c_new = fw * cst + iw * (k * v)
    n_new = fw * nst + iw * k
    h_num = jnp.sum(q * c_new, axis=0, keepdims=True)      # [1, dh]
    denom = jnp.maximum(jnp.abs(jnp.sum(q * n_new, axis=0, keepdims=True)),
                        jnp.exp(-m_new))                    # [1, 1]
    hout_ref[0, 0] = (h_num / denom).astype(hout_ref.dtype)
    cout_ref[0, 0] = c_new.astype(cout_ref.dtype)
    nout_ref[0, 0] = n_new.astype(nout_ref.dtype)
    mout_ref[0, 0] = m_new.astype(mout_ref.dtype)


def mlstm_decode_pallas(x, g, a, b, c, m, h, n, *, interpret: bool = False):
    """Grid (B, H): one head's [dh, dh] cell per program. Whole-row
    [H, dh, dh] blocks overran the 16 MiB scoped VMEM at xlstm-350m widths
    (dh = 512: 4 MiB per cell, in and out, double-buffered)."""
    bsz, hh, dh = x.shape
    col = lambda t: t.reshape(bsz, hh, dh, 1)   # noqa: E731
    row = lambda t: t.reshape(bsz, hh, 1, dh)   # noqa: E731
    one = lambda t: t.reshape(bsz, hh, 1, 1)    # noqa: E731
    spec = lambda r, c_: pl.BlockSpec((1, 1, r, c_),  # noqa: E731
                                      lambda b_, h_: (b_, h_, 0, 0))
    cspec, rspec, sspec = spec(dh, 1), spec(1, dh), spec(1, 1)
    cell = spec(dh, dh)
    f32 = jnp.float32
    h_out, c_new, n_new, m_new = pl.pallas_call(
        _mlstm_kernel,
        grid=(bsz, hh),
        in_specs=[cspec, cspec, rspec, sspec, sspec, sspec, cell, cspec],
        out_specs=[rspec, cell, cspec, sspec],
        out_shape=[
            jax.ShapeDtypeStruct((bsz, hh, 1, dh), f32),
            jax.ShapeDtypeStruct((bsz, hh, dh, dh), f32),
            jax.ShapeDtypeStruct((bsz, hh, dh, 1), f32),
            jax.ShapeDtypeStruct((bsz, hh, 1, 1), f32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
        ),
        name="mlstm_decode",
        interpret=interpret,
    )(col(x), col(g), row(a), one(b), one(c), one(m), h, col(n))
    return (h_out.reshape(bsz, hh, dh),
            (c_new, n_new.reshape(bsz, hh, dh), m_new.reshape(bsz, hh)))
