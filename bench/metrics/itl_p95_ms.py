"""itl_p95_ms: 95th percentile, over every output token of the requests
due in the window, of the scheduler's chunk-granular inter-token gap
(``Request.itl``: a chunk's wall gap spread over the tokens it produced);
host clock."""
from bench import stats


def read(ctx):
    return stats.itl_p95_ms(ctx["requests"])
