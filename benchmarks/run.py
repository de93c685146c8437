"""Benchmark driver — one section per paper table/figure.

  fig2   static characterization (area/leakage analogue)      §VI-A
  fig3   runtime speedup/energy table                          §VI-B
  sweep  early-exit training sweep at the paper's op points    §V
  kernels XAIF op microbench (ref timing + fusion byte model)  §IV
  roofline  aggregated dry-run roofline table (if cells exist)

Prints ``name,us_per_call,derived`` CSV rows per the harness contract, plus
JSON detail to benchmarks/out/.
"""
from __future__ import annotations

import json
import os
import sys
import time

OUT_DIR = os.path.join(os.path.dirname(__file__), "out")


def _emit(name: str, us: float, derived):
    print(f"{name},{us:.2f},{derived}")


def run_fig2():
    from benchmarks.static_characterization import table
    t0 = time.perf_counter()
    t = table()
    us = (time.perf_counter() - t0) * 1e6
    for arch, row in t.items():
        _emit(f"fig2_static/{arch}", us / len(t),
              f"total_GB_per_chip={row['total_bytes_per_chip']/1e9:.3f};"
              f"floor_frac={row['floor_fraction']:.3f}")
    return t


def run_fig3(exit_rates=None, label="fig3_runtime"):
    from benchmarks.runtime_improvements import fig3_table
    t0 = time.perf_counter()
    t = fig3_table(exit_rates)
    us = (time.perf_counter() - t0) * 1e6
    for kind, row in t.items():
        for cfgn in ("cpu_early_exit", "nm_offload", "nm_offload_early_exit"):
            r = row[cfgn]
            _emit(f"{label}/{kind}/{cfgn}", us / 6,
                  f"speedup={r['speedup']:.2f}x(paper={r.get('paper_speedup')});"
                  f"energy={r['energy_gain']:.2f}x(paper={r.get('paper_energy_gain')})")
    return t


def run_sweep(steps: int):
    from benchmarks.early_exit_sweep import paper_operating_points
    t0 = time.perf_counter()
    pts = paper_operating_points(steps=steps)
    us = (time.perf_counter() - t0) * 1e6
    for kind, r in pts.items():
        _emit(f"sweep_operating_point/{kind}", us / 2,
              f"exit_rate={r['exit_rate']:.2f};f1_full={r['f1_full']:.3f};"
              f"f1_ee={r['f1_early_exit']:.3f}")
    return pts


def run_kernels():
    from benchmarks.kernel_bench import bench
    rows = bench()
    for r in rows:
        _emit(f"kernel/{r['name']}", r.get("us_per_call_ref", 0.0),
              f"fusion_byte_ratio={r.get('fusion_byte_ratio', '')}")
    return rows


def run_roofline():
    dr_dir = os.path.join(os.path.dirname(__file__), "..", "experiments",
                          "dryrun")
    if not os.path.isdir(dr_dir):
        return {}
    out = {}
    for f in sorted(os.listdir(dr_dir)):
        if not f.endswith(".json"):
            continue
        d = json.load(open(os.path.join(dr_dir, f)))
        if d.get("status") != "ok" or "roofline" not in d:
            continue
        r = d["roofline"]
        key = f"{r['arch']}/{r['shape']}/{r['mesh']}"
        out[key] = r
        _emit(f"roofline/{key}", d.get("compile_s", 0) * 1e6,
              f"dom={r['dominant']};frac={r['roofline_fraction']:.4f};"
              f"useful={r['useful_flops_ratio']:.3f}")
    return out


def main() -> None:
    os.makedirs(OUT_DIR, exist_ok=True)
    fast = "--fast" in sys.argv
    results = {}
    results["fig2_static"] = run_fig2()
    # the early-exit training sweep is cached (it is the slow part)
    cached = os.path.join(OUT_DIR, "sweep.json")
    rates = None
    if os.path.exists(cached):
        sweep = json.load(open(cached))
        for kind, r in sweep.items():
            _emit(f"sweep_operating_point/{kind}(cached)", 0.0,
                  f"exit_rate={r['exit_rate']:.2f};f1_full={r['f1_full']:.3f};"
                  f"f1_ee={r['f1_early_exit']:.3f}")
        rates = {k: v["exit_rate"] for k, v in sweep.items()}
        results["sweep"] = sweep
    elif not fast:
        sweep = run_sweep(steps=200)
        results["sweep"] = sweep
        json.dump(sweep, open(cached, "w"), indent=2)
        rates = {k: v["exit_rate"] for k, v in sweep.items()}
    # PRIMARY: the paper's measured exit rates (its energy argument);
    # secondary: rates measured on our synthetic task (EXPERIMENTS.md §Paper)
    results["fig3_runtime_paper_rates"] = run_fig3(
        None, label="fig3_runtime_paper_rates")
    if rates is not None:
        results["fig3_runtime_measured_rates"] = run_fig3(
            rates, label="fig3_runtime_measured_rates")
    results["kernels"] = run_kernels()
    results["roofline"] = run_roofline()
    json.dump(results, open(os.path.join(OUT_DIR, "results.json"), "w"),
              indent=2, default=float)


if __name__ == '__main__':
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
