"""prefill_ms (engine): median host time of one admission that admitted,
from the call into ``SlotScheduler.admit`` to its first token on the host
(program span: ``bench.admit``)."""
from bench import stats


def read(ctx):
    lo, hi = ctx["window"]
    t = [b - a for a, b, res, _ in ctx["recorder"].admits
         if res == "admitted" and lo <= a < hi]
    return 1e3 * stats.median(t) if t else None
