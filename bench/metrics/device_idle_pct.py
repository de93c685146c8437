"""device_idle_pct (device): share of the traced slice in which the
device ran no operation, averaged over the chips (device trace)."""


def read(ctx):
    red = ctx.get("reduced")
    if red is None or red["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - red["busy_s"] / red["window_s"])
