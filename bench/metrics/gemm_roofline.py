"""gemm_roofline (kernels): the Pallas gemm calls inside the traced decode
chunks — the least time each call could take at its operand shapes
(``flops.gemm``: the larger of FLOPs over peak and bytes over bandwidth),
summed, over their summed device time (device trace). Nothing to read
where no Pallas gemm ran."""
from bench import devmetrics, flops


def read(ctx):
    evs = devmetrics.kernel_events(ctx, "gemm")
    if not evs or ctx["peaks"] is None:
        return None
    pk = ctx["peaks"]
    bound = sum(flops.roofline_s(
        flops.gemm(x[0], x[1], w[1], bias=True), pk["bf16_flops"],
        pk["hbm_bytes_per_s"]) for _, (x, w, _b), _ in evs)
    spent = sum((e.end - e.start) * 1e-9 for e, _, _ in evs)
    return 100.0 * bound / spent
