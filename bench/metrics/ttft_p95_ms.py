"""ttft_p95_ms: 95th percentile of time to first token over every request
due in the window, timed from its due time; a failed or refused request
counts as infinitely late (host clock)."""
from bench import stats


def read(ctx):
    return stats.ttft_p95_ms(ctx["requests"])
