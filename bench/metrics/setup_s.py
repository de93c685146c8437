"""setup_s: seconds from process start to the measured window's start:
loading, weights, compiling or reading the compile cache, warm-up and the
stream's ramp (host clock)."""


def read(ctx):
    return ctx["setup_s"]
