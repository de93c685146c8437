"""decode_hbm_pct (model step): the bytes the traced decode steps must
read per device (the weights once a step, each live slot's K/V, one
embedding row a token; ``flops.decode_step``) over the decode programs'
device time, as a share of the chip's peak HBM bandwidth (device trace)."""
from bench import devmetrics


def read(ctx):
    t = devmetrics.decode_device_s(ctx)
    if t is None or ctx["peaks"] is None:
        return None
    per_device = devmetrics.decode_cost(ctx)["bytes"] / ctx["chips"]
    return 100.0 * per_device / t / ctx["peaks"]["hbm_bytes_per_s"]
