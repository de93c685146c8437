"""queue_decode_p95_ms (scheduler): 95th percentile over the requests due
in the window of ``Request.wait_decode_s``, the part of the wait from due
time to admission in which a decode chunk held the scheduler; a request
never admitted waited forever (program counter)."""
from bench import progtrace, stats


def read(ctx):
    waits = progtrace.wait_decode_s(ctx)
    return 1e3 * stats.percentile(waits, 95) if waits else None
