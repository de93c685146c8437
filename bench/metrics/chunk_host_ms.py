"""chunk_host_ms (engine): median over the window's decode chunks of the
host's time outside its wait for the device — ``t_end - t_start -
(t_fetched - t_dispatched)`` of each ``ServeReport.chunks`` record: page
growth, the dispatch, retirement (program span)."""
from bench import progtrace, stats


def read(ctx):
    t = progtrace.host_self_s(ctx)
    return 1e3 * stats.median(t) if t else None
