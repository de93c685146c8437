"""decode_gemm_ms (kernels): device time per decode step of the leaf ops
whose HLO ``op_name`` lies under ``xaif.gemm.*`` (every projection and the
unembedding, whichever backend ran them), inside the traced decode chunks;
the median over the chunks, averaged over devices (device trace)."""
from bench import progtrace


def read(ctx):
    return progtrace.step_ms(ctx, "gemm")
