"""queue_p95_ms (scheduler): 95th percentile over the requests due in the
window of the wait from due time to admission (``t_admitted - arrival``);
a request never admitted waited forever (host clock)."""
from bench import stats


def read(ctx):
    waits = [stats.INF if r.t_admitted is None else r.t_admitted - r.arrival
             for r in ctx["requests"]]
    return 1e3 * stats.percentile(waits, 95)
