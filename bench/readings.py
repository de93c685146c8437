"""Readings behind the limits of ``correct``: the program's widest gap over
many seeds (sound runs), and the control's over some, in one process.

    python3 bench/readings.py --workload <cell> --seeds 1,2,3 \\
        --control-seeds 1,2,3 --quant int8,fp8 [--seconds 10] [--out FILE]

Each seed draws its own weights and traffic, serves a short stream at the
cell's own load (the ramp and ``--seconds``), and compares the same sample
a benchmark run compares. For a control seed the same positions are also
read through the reference in each lower precision of ``--quant``
(``check.py``) and judged by the cell's own limits, as a run's served
tokens are. One JSON line per seed, and a summary.
"""
import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def _ints(s):
    return [int(x) for x in s.split(",") if x]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=_ints, required=True)
    ap.add_argument("--control-seeds", type=_ints, default=[])
    ap.add_argument("--quant", default="fp8",
                    help="comma-separated: int8, fp8")
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--out")
    args = ap.parse_args(argv)

    from bench import harness, spans
    log = lambda s: print(s, file=sys.stderr, flush=True)
    b = harness.Bench(args.workload, args.rehearse, not args.rehearse,
                      log=log)
    rows = []
    for seed in sorted(set(args.seeds) | set(args.control_seeds)):
        t0 = time.perf_counter()
        weights, params = b.draw(seed)
        sched = b.schedule(seed, args.seconds)
        b.warm(params, sched, seed)
        reqs, _, compiles, _ = b.stream(params, sched, spans.Recorder())
        b.free()
        del params
        gaps = b.compare(weights, reqs, seed)
        checks = b.checks(reqs, gaps)
        row = {"seed": seed, "program_gap": float(gaps.max()),
               "tokens": int(gaps.size),
               "lost": checks["lost_requests"]["value"],
               "window_compiles": compiles,
               "correct": harness._passes(checks)}
        if seed in args.control_seeds:
            for q in args.quant.split(","):
                ctrl = b.compare(weights, reqs, seed, quant=q)
                row[f"control_gap_{q}"] = float(ctrl.max())
                # the control put in the program's place, judged as a run is
                row[f"control_correct_{q}"] = harness._passes(
                    b.checks(reqs, ctrl))
        row["seconds"] = round(time.perf_counter() - t0, 1)
        print(json.dumps(row), flush=True)
        rows.append(row)
        del weights
    prog = [r["program_gap"] for r in rows if r["seed"] in args.seeds]
    summary = {"workload": args.workload, "lower": max(prog),
               "program_gaps": prog}
    for q in args.quant.split(","):
        ctrl = [r[f"control_gap_{q}"] for r in rows
                if f"control_gap_{q}" in r]
        summary[f"upper_{q}"] = min(ctrl) if ctrl else None
        summary[f"control_gaps_{q}"] = ctrl
    print(json.dumps(summary), flush=True)
    if args.out:
        with open(args.out, "w") as f:
            json.dump({"rows": rows, "summary": summary}, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
