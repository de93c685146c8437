"""decode_mfu_pct (model step): model FLOPs of the traced decode steps
(2 x projection parameters per live token, plus attention over each live
context; ``flops.decode_step``) over decode device time x chips x the
chip's peak bf16 FLOP/s (device trace)."""
from bench import devmetrics


def read(ctx):
    t = devmetrics.decode_device_s(ctx)
    if t is None or ctx["peaks"] is None:
        return None
    f = devmetrics.decode_cost(ctx)["flops"]
    return 100.0 * f / (t * ctx["chips"] * ctx["peaks"]["bf16_flops"])
