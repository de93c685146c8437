"""Chaos benchmark: completion rate, recovery latency and survivor token
identity for the fault-tolerant serving supervisor.

One mixed-length greedy stream is served fault-free through the paged
engine to get the reference tokens, then re-served under
``serve_resilient`` while the :class:`repro.serve.faults.FaultInjector`
fires:

  * ``site_*``  — one scheduled fault at every injection site (prefill,
    decode, page_alloc, swap, backend): the kill-and-resume matrix at
    benchmark scale;
  * ``rate_*``  — seeded Bernoulli faults on the decode site at a sweep of
    per-chunk fault rates (bounded by ``max_faults`` so a hostile rate
    cannot starve the stream);
  * ``breaker`` — a raising dispatched backend absorbed by the
    ``core/xaif.py`` circuit breaker (ref fallback, zero restarts).

Per row: completion rate, restarts, faults fired, mean/max recovery
latency (snapshot-restore wall time) and the fraction of requests whose
tokens are bitwise identical to the fault-free run. Acceptance bars
(asserted): EVERY row completes 100% of requests with 100% token
identity, and the breaker row recovers with zero restarts.

Emits ``chaos/<row>,us_per_call,derived`` CSV rows and merges a ``chaos``
section into ``BENCH_serving.json`` (read-modify-write: the serving
benchmark's tables are preserved).

    PYTHONPATH=src python -m benchmarks.chaos_bench [--arch chatglm3-6b]
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import time
from typing import Dict, List

import jax
import numpy as np

BENCH_JSON = "BENCH_serving.json"

SITES_BENCH = ("prefill", "decode", "page_alloc", "swap")
RATES = (0.02, 0.05, 0.10)


def _requests(cfg, num: int, seed: int = 0) -> List:
    from repro.serve.scheduler import Request
    rng = np.random.default_rng(seed)
    out = []
    for i in range(num):
        t = int(rng.integers(4, 25))
        out.append(Request(
            rid=i,
            prompt=rng.integers(0, cfg.vocab_size, (t,), dtype=np.int32),
            max_new_tokens=int(rng.integers(6, 17))))
    return out


def _row(rep, ref_toks, inj, t_wall: float) -> Dict:
    identical = sum(1 for r in rep.served
                    if list(r.tokens) == ref_toks[r.rid])
    return {
        "completion_rate": rep.completion_rate,
        "served": len(rep.served),
        "identical_tokens": identical,
        "token_identity": identical / max(len(rep.requests), 1),
        "restarts": int(rep.stats.get("restarts", 0)),
        "faults_injected": int(inj.fired) if inj is not None else 0,
        "recovery_s_mean": rep.stats.get("recovery_s_mean", 0.0),
        "recovery_s_max": rep.stats.get("recovery_s_max", 0.0),
        "wall_s": t_wall,
        "tok_per_s": rep.tokens_per_s,
    }


def chaos_table(arch: str, num_requests: int = 24) -> Dict[str, Dict]:
    from repro.configs.base import (AccelConfig, RunConfig, SHAPES_BY_NAME,
                                    get_arch)
    from repro.core import xaif
    from repro.models import lm
    from repro.serve.engine import SlotEngine
    from repro.serve.faults import FaultInjector, register_chaos_backends
    from repro.serve.resilient import serve_resilient
    from repro.serve.scheduler import serve

    cfg = get_arch(arch).reduced()
    run = RunConfig(arch=cfg, shape=SHAPES_BY_NAME["decode_32k"],
                    accel=AccelConfig())
    params = lm.init_lm(jax.random.PRNGKey(0), cfg)
    engine = SlotEngine(run, capacity=4, max_len=64, chunk=4,
                        paged=True, page_size=8)

    # fault-free reference: first run compiles, second is the timed
    # baseline AND the token oracle every chaos row is compared against
    serve(engine, params, _requests(cfg, num_requests))
    t0 = time.perf_counter()
    ref = serve(engine, params, _requests(cfg, num_requests))
    base_wall = time.perf_counter() - t0
    assert not ref.rejected
    ref_toks = {r.rid: list(r.tokens) for r in ref.served}

    table: Dict[str, Dict] = {
        "baseline": {"completion_rate": 1.0, "token_identity": 1.0,
                     "served": len(ref.served), "identical_tokens":
                     len(ref.served), "restarts": 0, "faults_injected": 0,
                     "recovery_s_mean": 0.0, "recovery_s_max": 0.0,
                     "wall_s": base_wall, "tok_per_s": ref.tokens_per_s}}

    # one scheduled fault per site
    for site in SITES_BENCH:
        inj = FaultInjector(schedule={site: [1]})
        t0 = time.perf_counter()
        rep = serve_resilient(engine, params, _requests(cfg, num_requests),
                              snapshot_every=2, injector=inj)
        table[f"site_{site}"] = _row(rep, ref_toks, inj,
                                     time.perf_counter() - t0)

    # Bernoulli rate sweep on the decode site (bounded total faults)
    for rate in RATES:
        inj = FaultInjector(rates={"decode": rate}, seed=0, max_faults=6)
        t0 = time.perf_counter()
        rep = serve_resilient(engine, params, _requests(cfg, num_requests),
                              snapshot_every=2, max_restarts=16,
                              injector=inj)
        table[f"rate_{rate:g}"] = _row(rep, ref_toks, inj,
                                       time.perf_counter() - t0)

    # circuit breaker: raising dispatched backend, ref fallback, 0 restarts
    register_chaos_backends()
    chaos_run = dataclasses.replace(
        run, accel=xaif.DispatchPolicy.make({"rmsnorm": "chaos"}))
    ref_run = dataclasses.replace(run, accel=xaif.DispatchPolicy.make({}))
    ref_b = serve(SlotEngine(ref_run, capacity=4, max_len=64, chunk=4,
                             paged=True, page_size=8),
                  params, _requests(cfg, num_requests))
    ref_b_toks = {r.rid: list(r.tokens) for r in ref_b.served}
    eng_b = SlotEngine(chaos_run, capacity=4, max_len=64, chunk=4,
                       paged=True, page_size=8)
    inj = FaultInjector(schedule={"backend": [0]})
    breaker = xaif.CircuitBreaker()
    t0 = time.perf_counter()
    rep = serve_resilient(eng_b, params, _requests(cfg, num_requests),
                          injector=inj, breaker=breaker)
    table["breaker"] = _row(rep, ref_b_toks, inj, time.perf_counter() - t0)
    table["breaker"]["breaker_trips"] = breaker.trips
    return table


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="chatglm3-6b")
    ap.add_argument("--requests", type=int, default=24)
    ap.add_argument("--json", default=BENCH_JSON,
                    help="merge the chaos table into this JSON ('' to skip)")
    args = ap.parse_args()

    table = chaos_table(args.arch, num_requests=args.requests)
    for name, r in table.items():
        print(f"chaos/{name},{r['wall_s']*1e6:.2f},"
              f"completion={r['completion_rate']:.2f};"
              f"identity={r['token_identity']:.2f};"
              f"restarts={r['restarts']};"
              f"faults={r['faults_injected']};"
              f"recovery_ms_max={r['recovery_s_max']*1e3:.1f}")

    # acceptance bars: zero lost requests, zero divergent survivors
    for name, r in table.items():
        assert r["completion_rate"] == 1.0, \
            f"{name}: completion {r['completion_rate']:.2f} < 1.0"
        assert r["token_identity"] == 1.0, \
            f"{name}: only {r['identical_tokens']}/{r['served']} " \
            "token-identical to the fault-free run"
    faulted = [n for n, r in table.items() if r["faults_injected"]]
    assert len(faulted) >= len(SITES_BENCH) + 1, faulted
    assert any(n.startswith("rate_") for n in faulted), \
        f"Bernoulli sweep never fired: {faulted}"
    assert table["breaker"]["restarts"] == 0 \
        and table["breaker"]["breaker_trips"] >= 1, table["breaker"]
    n_rec = sum(1 for r in table.values() if r["recovery_s_max"] > 0)
    print(f"chaos: {len(table) - 1} fault configurations, 100% completion, "
          f"100% token identity, {n_rec} with measured recoveries")

    if args.json:
        doc = {}
        if os.path.exists(args.json):
            with open(args.json) as f:
                doc = json.load(f)
        doc["chaos"] = table
        with open(args.json, "w") as f:
            json.dump(doc, f, indent=2, sort_keys=True, default=str)
        print(f"wrote chaos section -> {args.json}")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
