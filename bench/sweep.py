"""Find a cell's knee once, by a sweep on the chip: the cell's traffic mix
served open loop at each of several rates, in one process (weights drawn
and programs compiled once).

    python3 bench/sweep.py --workload <cell> --rates 0.5,2,4,6,8 \\
        [--seconds 30] [--seed N] [--out FILE]

The first rate is the unloaded point: the TTFT and ITL limits are
``--slo-scale`` times its p95s. For every rate one JSON line: requests,
p95 TTFT and ITL, tokens per second, the share that met both limits, the
p95 queue wait, and the backlog trend (median queue wait of the last
quarter of arrivals over the first quarter's). The knee is the highest
rate at which 90% meet the limits and the backlog does not grow; the
sweep stops at the first rate past it, and its last line gives the knee,
the rate 4/5 of it and the limits.
"""
import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=4_000_000_007)
    ap.add_argument("--slo-scale", type=float, default=3.0)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--out")
    args = ap.parse_args(argv)

    import numpy as np
    from bench import harness, spans, stats, traffic
    log = lambda s: print(s, file=sys.stderr, flush=True)
    b = harness.Bench(args.workload, args.rehearse, not args.rehearse,
                      log=log)
    _, params = b.draw(args.seed)
    rows, limits = [], None
    for rate in [float(x) for x in args.rates.split(",")]:
        b.mix = dict(b.mix, rate_hz=rate)
        sched = b.schedule(args.seed, args.seconds)
        b.warm(params, sched, args.seed)
        rec = spans.Recorder()
        reqs, _, compiles, _ = b.stream(params, sched, rec)
        lo, hi = traffic.window(b.mix, args.seconds)
        win = stats.due_in(reqs, lo, hi)
        ttft, itl = stats.ttft_p95_ms(win), stats.itl_p95_ms(win)
        if limits is None:
            limits = (args.slo_scale * ttft, args.slo_scale * itl)
        q = [r.t_admitted - r.arrival for r in win
             if r.t_admitted is not None]
        k = max(len(q) // 4, 1)
        firsts = [r.t_first_token for r in reqs if r.t_first_token]
        row = {"rate": rate, "requests": len(win), "ttft_p95_ms": ttft,
               "itl_p95_ms": itl,
               "out_tok_s": stats.delivered(
                   firsts, [(c[1], c[2]) for c in rec.chunks], lo, hi)
               / (hi - lo),
               "slo_met_pct": stats.slo_met_pct(win, *limits),
               "queue_p95_ms": 1e3 * stats.percentile(q, 95) if q else None,
               "backlog_trend": float(np.median(q[-k:])
                                      / max(np.median(q[:k]), 1e-3))
               if q else None,
               "prefill_ms": 1e3 * stats.median(
                   [t1 - t0 for t0, t1, res, _ in rec.admits
                    if res == "admitted"]),
               "decode_chunk_ms": 1e3 * stats.median(
                   [c[1] - c[0] for c in rec.chunks]),
               "window_compiles": compiles,
               "limits_ms": list(limits)}
        print(json.dumps(row), flush=True)
        rows.append(row)
        b.free()
        if not sustained(row):
            break                       # past the knee: higher rates wait
    ok = [r["rate"] for r in rows if sustained(r)]
    knee = max(ok) if ok else None
    summary = {"knee": knee, "rate_hz": knee and round(0.8 * knee, 3),
               "slo": {"ttft_ms": float(round(limits[0])),
                       "itl_ms": float(round(limits[1]))}}
    print(json.dumps(summary), flush=True)
    if args.out:
        with open(args.out, "w") as f:
            json.dump({"rows": rows, "summary": summary}, f, indent=1)
    return 0


def sustained(row: dict) -> bool:
    """90% of the requests met the limits and the backlog did not grow."""
    return (row["slo_met_pct"] >= 90.0
            and (row["backlog_trend"] or 0.0) < 2.0)


if __name__ == "__main__":
    sys.exit(main())
