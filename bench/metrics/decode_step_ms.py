"""decode_step_ms (model step): device time of one decode step — the
decode program's time inside each traced ``serve.chunk`` span (paired with
its ``ServeReport.chunks`` record by the span's ``chunk`` id), over the
chunk's steps; the median over the traced chunks, averaged over devices
(device trace)."""
from bench import progtrace


def read(ctx):
    return progtrace.step_ms(ctx)
