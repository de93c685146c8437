"""Run one cell of BENCHMARK.json once and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Needs the chips the cell asks for: with no accelerator, or too few chips,
it exits non-zero and prints no result. ``--rehearse`` runs the same
harness at tiny widths on whatever JAX finds (the CPU here), and still
exits non-zero without a result: a rehearsal measures nothing.

The last line of standard output is one JSON object (``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1``
``breakdown``, then ``check``: each compared number beside its limit); the
compared numbers are also the last lines of standard error.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny widths on any platform; prints no result")
    args = ap.parse_args(argv)

    from bench import harness
    try:
        out = harness.run(args.workload, args.seed, args.seconds,
                          bool(args.trace), rehearse=args.rehearse,
                          require_chip=not args.rehearse,
                          t_process=T_PROCESS,
                          log=lambda s: print(s, file=sys.stderr,
                                              flush=True))
    except harness.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    print(f"[window] compiles inside the stream: {out['_window_compiles']}",
          file=sys.stderr)
    if args.rehearse:
        print("bench: rehearsal finished; a rehearsal prints no result",
              file=sys.stderr)
        harness.print_checks(out)
        return 4
    if out["_missing"]:
        print(f"bench: the traced run read none of {out['_missing']}, "
              f"which {args.workload} declares", file=sys.stderr)
        harness.print_checks(out)
        return 5
    print(harness.result_line(out), flush=True)
    harness.print_checks(out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
