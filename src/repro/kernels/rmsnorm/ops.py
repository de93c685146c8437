"""Jit'd wrapper + XAIF registration for fused RMSNorm."""
from __future__ import annotations

from repro.core import xaif
from repro.kernels._tiling import ceil_mult, divisor_block, pad_to
from repro.kernels.rmsnorm import ref as _ref
from repro.kernels.rmsnorm import rmsnorm as _k


def rmsnorm_cost(m, d, dtype_bytes=2):
    return {"flops": 4.0 * m * d, "hbm_bytes": 2.0 * dtype_bytes * m * d}


@xaif.register("rmsnorm", "ref", cost_fn=rmsnorm_cost,
               description="pure-jnp RMSNorm")
def rmsnorm_ref_op(x, scale, eps: float = 1e-5):
    return _ref.rmsnorm_ref(x, scale, eps)


@xaif.register("rmsnorm", "pallas", cost_fn=rmsnorm_cost,
               description="fused single-pass VMEM RMSNorm",
               tunables={"bm": (64, 128, 256, 512)})
def rmsnorm_pallas_op(x, scale, eps: float = 1e-5, *, interpret: bool = False,
                      bm: int = 256):
    lead = x.shape[:-1]
    d = x.shape[-1]
    x2 = x.reshape(-1, d)
    m = x2.shape[0]
    # prefer a block that divides the rows exactly; the TPU lowering needs a
    # multiple of 8 rows (or all of them), so odd counts (a 455-token
    # prompt) are padded instead — rows are independent, pad rows dropped
    bm_ = divisor_block(m, bm)
    if bm_ % 8 and bm_ != m:
        bm_ = ceil_mult(m, bm)
        x2, _ = pad_to(x2, bm_, 0)
    out = _k.rmsnorm_pallas(x2, scale, eps, bm=bm_, interpret=interpret)
    return out[:m].reshape(*lead, d)
