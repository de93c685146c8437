"""slo_met_pct: share of the requests due in the window whose TTFT is
within the mix's ``slo.ttft_ms`` and whose own p95 inter-token gap is
within ``slo.itl_ms``; failed requests missed (host clock)."""
from bench import stats


def read(ctx):
    slo = ctx["mix"]["slo"]
    return stats.slo_met_pct(ctx["requests"], slo["ttft_ms"], slo["itl_ms"])
