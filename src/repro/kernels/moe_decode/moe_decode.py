"""Sort-by-expert ragged MoE decode dispatch — Pallas TPU kernel.

The wrapper groups the B*K (token, expert) assignments by expert at trace
time (argsort + rank-within-run — the same sort-based ranking the capacity
path uses), pads each expert's run up to the block size ``bt`` and lays the
padded runs back to back. Each grid step then computes ONE [bt, d] row
block against ONE expert's SwiGLU panels: the block's expert id is
scalar-prefetched (``PrefetchScalarGridSpec``) and indexes the weight
BlockSpecs directly, so the accelerator only DMAs panels of experts that
actually received tokens this step — the resident-data-only story the
paged-attention kernel tells for KV pages, applied to expert weights.
Unused tail blocks (expert id -1) write zeros and are never gathered back.

Worst-case block count is static — ceil(B*K/bt) + E (every expert's run
padded) — so the grid never re-traces as routing shifts between steps.

Each grid step also takes one ``bh``-wide slice of the expert hidden dim
and accumulates its down projection in an fp32 VMEM scratch, so VMEM per
step @ bt=8, bh=256, d=2048 (qwen3 full scale, bf16) is x 32 KiB + 3 weight
panel slices 3 MiB, double-buffered 6 MiB — whole [d, h] panels (18 MiB
double-buffered) overran the 16 MiB scoped-VMEM limit. ``bt`` trades
dispatch padding against grid steps; it has no effect on the panels.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels._tiling import divisor_block, sorted_run_ranks


def _moe_kernel(be_ref, x_ref, wg_ref, wu_ref, wd_ref, o_ref, acc_ref, *,
                nh: int):
    i, j = pl.program_id(0), pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # one [bh] slice of the expert hidden dim: the SwiGLU is elementwise in
    # h, so the down projection sums exactly over the h tiles
    xb = x_ref[...]                                           # [bt, d]
    gact = jnp.dot(xb, wg_ref[0], preferred_element_type=jnp.float32)
    up = jnp.dot(xb, wu_ref[0], preferred_element_type=jnp.float32)
    hidden = (jax.nn.silu(gact) * up).astype(wd_ref.dtype)   # [bt, bh]
    acc_ref[...] += jnp.dot(hidden, wd_ref[0],
                            preferred_element_type=jnp.float32)

    @pl.when(j == nh - 1)
    def _finish():
        o_ref[...] = jnp.where(be_ref[i] >= 0, acc_ref[...], 0.0)


def moe_decode_pallas(x: jax.Array, expert_idx: jax.Array, gate: jax.Array,
                      w_gate: jax.Array, w_up: jax.Array, w_down: jax.Array,
                      *, bt: int = 8, bh: int = 256,
                      interpret: bool = False) -> jax.Array:
    """x [B, d]; expert_idx [B, K] i32; gate [B, K] f32;
    w_gate/w_up [E, d, h]; w_down [E, h, d]. Returns fp32 [B, d].

    ``bh`` tiles the expert hidden dim (a power-of-two divisor of h).

    Bitwise identity with the capacity path is the REF backend's contract,
    not this kernel's — like every Pallas kernel here it is validated by
    allclose (weight-dtype MXU operands with fp32 accumulation).
    """
    b, d = x.shape
    k = expert_idx.shape[1]
    e, _, h = w_gate.shape
    bk = b * k

    # ---- trace-time ragged layout: sort assignments by expert --------------
    flat_e = expert_idx.reshape(-1).astype(jnp.int32)          # [BK]
    tok = jnp.arange(bk, dtype=jnp.int32) // k                 # source token
    order = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[order]
    rank = sorted_run_ranks(sorted_e)                          # rank in run
    counts = jnp.zeros((e,), jnp.int32).at[flat_e].add(1)
    padded = -(-counts // bt) * bt                             # run -> blocks
    bounds = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), jnp.cumsum(padded)])
    offsets = bounds[:-1]                                      # [E] run start
    nb = -(-bk // bt) + e                                      # static worst case
    dest = offsets[sorted_e] + rank                            # padded slot
    x_pad = jnp.zeros((nb * bt, d), x.dtype).at[dest].set(x[tok[order]])

    # per-block expert id: the expert whose padded run covers the block
    starts = jnp.arange(nb, dtype=jnp.int32) * bt
    blk_e = (jnp.searchsorted(offsets, starts, side="right")
             .astype(jnp.int32) - 1)
    blk_e = jnp.where(starts < bounds[1:][jnp.maximum(blk_e, 0)], blk_e, -1)

    bh = divisor_block(h, bh)
    nh = h // bh
    out_pad = pl.pallas_call(
        functools.partial(_moe_kernel, nh=nh),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,          # blk_e
            grid=(nb, nh),
            in_specs=[
                pl.BlockSpec((bt, d), lambda i, j, be: (i, 0)),
                pl.BlockSpec((1, d, bh),
                             lambda i, j, be: (jnp.maximum(be[i], 0), 0, j)),
                pl.BlockSpec((1, d, bh),
                             lambda i, j, be: (jnp.maximum(be[i], 0), 0, j)),
                pl.BlockSpec((1, bh, d),
                             lambda i, j, be: (jnp.maximum(be[i], 0), j, 0)),
            ],
            out_specs=pl.BlockSpec((bt, d), lambda i, j, be: (i, 0)),
            scratch_shapes=[pltpu.VMEM((bt, d), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((nb * bt, d), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        name="moe_decode",
        interpret=interpret,
    )(blk_e, x_pad, w_gate, w_up, w_down)

    # ---- combine: gather back to assignment order, gate-weighted sum over k
    outk = jnp.zeros((bk, d), jnp.float32).at[order].set(out_pad[dest])
    return jnp.einsum("bk,bkd->bd", gate.astype(jnp.float32),
                      outk.reshape(b, k, d))
