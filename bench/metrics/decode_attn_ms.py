"""decode_attn_ms (kernels): device time per decode step of the leaf ops
whose HLO ``op_name`` lies under ``xaif.attn_decode_paged.*`` (the paged
decode attention, whichever backend ran it), inside the traced decode
chunks; the median over the chunks, averaged over devices (device
trace)."""
from bench import progtrace


def read(ctx):
    return progtrace.step_ms(ctx, "attn_decode_paged")
