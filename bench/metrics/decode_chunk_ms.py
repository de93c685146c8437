"""decode_chunk_ms (engine): median host time of one
``SlotScheduler.step_chunk`` in the window — page growth, the jitted decode
chunk, its one host fetch, retirement (program span: ``bench.chunk``)."""
from bench import stats


def read(ctx):
    lo, hi = ctx["window"]
    t = [b - a for a, b, _, _ in ctx["recorder"].chunks if lo <= a < hi]
    return 1e3 * stats.median(t) if t else None
