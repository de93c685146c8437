"""collective_pct (device, several chips): share of the traced decode
programs' device time spent in collective operations (all-reduce,
all-gather, ...), averaged over the chips (device trace)."""
from bench import devmetrics, trace_reduce


def read(ctx):
    t = devmetrics.decode_device_s(ctx)
    if t is None or ctx["chips"] < 2:
        return None
    coll = 0.0
    for _, sp in devmetrics.traced_chunks(ctx):
        for dev, mods in zip(ctx["trace"].devices,
                             devmetrics.chunk_modules(ctx, sp)):
            for e in trace_reduce.ops_in(ctx["trace"], mods, dev):
                if trace_reduce.COLLECTIVE.search(e.name):
                    coll += (e.end - e.start) * 1e-9
    return 100.0 * coll / len(ctx["trace"].devices) / t
