"""One run of one cell: set-up, the measured window, the metrics and the
check of what the window served.

1. The compile cache: ``$JAX_COMPILATION_CACHE_DIR``, else ``.jax_cache/``
   at the checkout root (a fixed path: the path is part of the key).
2. The weights, drawn on the device from the seed in one jitted call.
3. Warm-up through ``serve()`` of exactly the programs the cell's traffic
   reaches: each prompt bucket and the decode chunk.
4. One open-loop stream through ``serve(engine, params, requests,
   realtime=True)``: a ramp, then the window ``[ramp, ramp + seconds)``.
   Requests are timed from when they were due.
5. The metrics: end-to-end ones with ``--trace 0``; per-layer ones with
   ``--trace 1``, which profiles a slice of the window's middle.
6. ``correct``: the served tokens of a sample against the plain reference
   (``check.py``), once the program's state is freed.
"""
from __future__ import annotations

import gc
import json
import os
import shutil
import sys
import time
from pathlib import Path
from typing import List, Optional

import numpy as np

from bench import check, devmetrics, model as bm, spans, spec, stats
from bench import trace_reduce, traffic

TRACE_SLICE_S = 2.0


class NoChip(RuntimeError):
    """No accelerator, or fewer chips than the cell asks for."""


def enable_compile_cache(root: Path) -> str:
    import jax
    # every program, however small, is kept: admission compiles a small
    # one per prompt length, and reading it back is cheaper than compiling
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(root / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def device_info(chips: int, require_chip: bool) -> dict:
    import jax
    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    if require_chip:
        if info["platform"] != "tpu":
            raise NoChip(f"no accelerator: JAX found {info}")
        if info["count"] < chips:
            raise NoChip(f"the cell needs {chips} chips, JAX found "
                         f"{info['count']}")
    return info


def _requests(reqs: List[traffic.Req], first_rid: int = 0):
    from repro.serve.scheduler import Request
    return [Request(rid=first_rid + i, prompt=r.prompt,
                    max_new_tokens=r.max_new, arrival=r.arrival)
            for i, r in enumerate(reqs)]


def _serve(engine, params, reqs, mesh, realtime: bool):
    import contextlib
    from repro.dist import sharding as shd
    from repro.serve.scheduler import serve
    ctx = (shd.shard_ctx(mesh, engine.sharding) if mesh is not None
           else contextlib.nullcontext())
    with ctx:
        return serve(engine, params, reqs, realtime=realtime)


def warm_up(engine, params, mesh, sched: List[traffic.Req], vocab: int,
            seed: int) -> int:
    """Compile (or read from the cache) every program the schedule
    reaches, through ``serve()``: each prompt bucket and the decode chunk.
    Returns the warm-up requests served."""
    rng = traffic.rng_for(seed ^ 0xA11CE)
    new = engine.chunk + 1                       # one decode chunk too
    buckets = sorted({engine._bucket(len(r.prompt)) for r in sched})
    first = [traffic.Req(rng.integers(0, vocab, (b,), dtype=np.int32), new,
                         0.0) for b in buckets]
    return len(_serve(engine, params, _requests(first, 10**9), mesh,
                      False).requests)


def warm_host_ops(engine, sched: List[traffic.Req]) -> None:
    """Admission pads each prompt to its bucket with an eager
    ``.at[].set``: a program that depends on the prompt's length. Run it
    once for every length the schedule holds, so none compiles in the
    window."""
    import jax.numpy as jnp
    for t in sorted({len(r.prompt) for r in sched}):
        jnp.zeros((1, engine._bucket(t)), jnp.int32).at[0, :t].set(
            jnp.asarray(np.zeros((t,), np.int32))).block_until_ready()


class Bench:
    """One cell's system under test, set up once: its configuration, the
    engine (and mesh) it states, the devices and their peaks."""

    def __init__(self, workload: str, rehearse: bool = False,
                 require_chip: bool = True, root: Path = spec.ROOT,
                 log=print):
        self.log = log
        self.cell = spec.load_cell(workload, root)
        self.config = (bm.rehearsal(self.cell.config) if rehearse
                       else self.cell.config)
        cache_dir = enable_compile_cache(root)
        self.clock = spans.CompileClock()
        import jax
        self.info = device_info(self.cell.chips, require_chip)
        self.peaks = spec.peaks(self.info["kind"], root) if require_chip \
            else None
        self.devices = jax.devices()[:self.cell.chips]
        log(f"[device] platform={self.info['platform']} kind="
            f"{self.info['kind']} count={self.info['count']} using="
            f"{len(self.devices)} cache={cache_dir}")
        self.model = self.config["model"]
        self.cfg = bm.arch_config(self.model, self.cell.config["name"])
        self.engine, self.mesh = bm.build_engine(self.config, self.cfg,
                                                 self.devices)
        self.mix = self.cell.traffic
        if rehearse:
            self.mix = traffic.rehearsal_mix(
                self.mix, self.engine.max_len
                / self.cell.config["engine"]["max_len"])

    def draw(self, seed: int):
        """(weights, program params) from ``seed``, on the devices."""
        import jax
        w = bm.draw_weights(self.model, seed,
                            bm.program_shardings(self.engine))
        bm.check_layout(w, self.cfg)
        params = bm.to_program(w)
        jax.block_until_ready(params)
        return w, params

    def schedule(self, seed: int, seconds: float) -> List[traffic.Req]:
        return traffic.generate(self.mix, seed, seconds,
                                self.model["vocab_size"],
                                self.engine.max_len)

    def warm(self, params, sched: List[traffic.Req], seed: int) -> None:
        eng = self.engine
        c0, t0 = self.clock.compiles, time.perf_counter()
        n = warm_up(eng, params, self.mesh, sched, self.model["vocab_size"],
                    seed)
        t1 = time.perf_counter()
        warm_host_ops(eng, sched)
        self.log(f"[setup] warm-up served {n} requests in {t1 - t0:.1f} s,"
                 f" host ops {time.perf_counter() - t1:.1f} s; "
                 f"{self.clock.compiles - c0} "
                 f"compiles, {self.clock.cache_hits} cache hits so far; "
                 f"traces decode={eng.decode_traces} "
                 f"prefill={eng.prefill_traces}")

    def stream(self, params, sched: List[traffic.Req], rec: spans.Recorder):
        """Serve ``sched`` open loop through ``serve()``; returns (the
        requests, the report, compiles inside the stream, its start)."""
        reqs = _requests(sched)
        gc.collect()
        c0 = self.clock.compiles
        t_serve = time.perf_counter()
        with rec.instrument(self.engine):
            report = _serve(self.engine, params, reqs, self.mesh, True)
        compiles = self.clock.compiles - c0
        self.log(f"[window] {len(reqs)} requests, stream "
                 f"{report.wall_s:.1f} s; compiles inside the stream: "
                 f"{compiles}")
        return reqs, report, compiles, t_serve

    def peak_bytes(self) -> int:
        return int(max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                       for d in self.devices))

    def free(self) -> None:
        """Drop the program's KV pool and slot state."""
        self.engine.resident = None
        gc.collect()

    def compare(self, weights, reqs, seed: int, quant: Optional[str] = None
                ) -> np.ndarray:
        lim = self.config["correct"]
        picked = check.sample(reqs, seed, lim["sample_tokens"],
                              lim["sample_requests"])
        return check.gaps(weights, self.config, picked, self.engine.max_len,
                          quant)

    def checks(self, reqs, gaps: np.ndarray) -> dict:
        lim = self.config["correct"]
        lost = sum(1 for r in reqs if r.reject_reason is not None
                   or len(r.tokens) != r.max_new_tokens)
        return {"max_gap": {"value": float(gaps.max()) if gaps.size
                            else None, "limit": lim["max_gap"]},
                "compared_tokens": {"value": int(gaps.size),
                                    "limit": lim["min_compared"]},
                "lost_requests": {"value": lost, "limit": 0}}


def _passes(checks: dict) -> bool:
    c = checks
    return (c["compared_tokens"]["value"] >= c["compared_tokens"]["limit"]
            and c["max_gap"]["value"] is not None
            and c["max_gap"]["value"] <= c["max_gap"]["limit"]
            and c["lost_requests"]["value"] == 0)


def run(workload: str, seed: int, seconds: float, trace: bool,
        rehearse: bool = False, require_chip: bool = True,
        root: Path = spec.ROOT, t_process: Optional[float] = None,
        log=print) -> dict:
    """One run of ``workload``; returns the result object (see
    ``result_line``). ``rehearse`` swaps in the rehearsal widths."""
    t_process = time.perf_counter() if t_process is None else t_process
    b = Bench(workload, rehearse, require_chip, root, log)
    weights, params = b.draw(seed)
    log(f"[setup] weights drawn {time.perf_counter() - t_process:.1f} s "
        f"after start")
    sched = b.schedule(seed, seconds)
    w_start, w_end = traffic.window(b.mix, seconds)
    b.warm(params, sched, seed)

    rec = spans.Recorder()
    trace_dir = None
    if trace:
        trace_dir = str(root / ".bench_trace" / workload)
        shutil.rmtree(trace_dir, ignore_errors=True)
        rec.trace_at = w_start + max(seconds / 2 - TRACE_SLICE_S / 2, 0.0)
        rec.trace_s = TRACE_SLICE_S
        rec.trace_dir = trace_dir
    reqs, report, compiles, t_serve = b.stream(params, sched, rec)
    setup_s = t_serve + w_start - t_process
    peak = b.peak_bytes()
    hlo = None
    if trace and b.engine.resident is not None:
        cache, st, _ = b.engine.resident
        hlo = b.engine._decode.lower(params, cache, st).compile().as_text()
        Path(trace_dir).mkdir(parents=True, exist_ok=True)
        (Path(trace_dir) / "decode.hlo.txt").write_text(hlo)
    b.free()

    window = stats.due_in(reqs, w_start, w_end)
    ctx = dict(cell=b.cell, config=b.config, model=b.model, mix=b.mix,
               requests=window, all_requests=reqs, report=report,
               recorder=rec, window=(w_start, w_end), seconds=seconds,
               peaks=b.peaks, chips=len(b.devices), engine=b.engine,
               setup_s=setup_s, hlo=hlo)
    if trace and rec.traced is not None:
        tr = trace_reduce.load(trace_reduce.find_xplane(trace_dir))
        ctx["trace"] = tr
        ctx["reduced"] = trace_reduce.reduce(tr)
    metrics = {}
    for m in (b.cell.per_layer if trace else b.cell.end_to_end):
        v = spec.metric_reader(m.name, root)(ctx)
        if v is not None:
            metrics[m.name] = {"value": float(v), "unit": m.unit}

    t_chk = time.perf_counter()
    gaps = b.compare(weights, reqs, seed)
    checks = b.checks(reqs, gaps)
    log(f"[check] {gaps.size} tokens in {time.perf_counter() - t_chk:.1f} s")

    out = {"correct": _passes(checks), "attempted": len(window),
           "failed": sum(1 for r in window if stats.failed(r)),
           "metrics": metrics,
           "device": {"platform": b.info["platform"],
                      "kind": b.info["kind"], "count": len(b.devices),
                      "memory_peak_bytes": peak}}
    if trace and "reduced" in ctx:
        red = ctx["reduced"]
        out["device"].update(busy_s=red["busy_s"], window_s=red["window_s"])
        kinds = devmetrics.kernels(hlo)
        label = lambda n: (f"{n} ({kinds[n][0]} "
                           f"{'x'.join(map(str, kinds[n][1][0]))})"
                           if n in kinds else n)
        out["breakdown"] = {
            "device_ops": sorted(([label(k), v]
                                  for k, v in red["ops"].items()),
                                 key=lambda kv: -kv[1])[:10],
            "idle_gaps": [[k, v] for k, v in red["gaps"][:10]]}
    out["check"] = checks
    out["_window_compiles"] = compiles
    # a per-layer metric the cell declares and the traced run could not
    # read: on the chip that is a fault of the reduction, not a silence
    out["_missing"] = ([m.name for m in b.cell.per_layer
                        if m.name not in metrics]
                       if trace and require_chip else [])
    if out["_missing"]:
        log(f"[trace] what the traced run found: {trace_summary(ctx)}")
    return out


def trace_summary(ctx: dict) -> dict:
    """What a traced run had to read, to find why a metric read nothing."""
    tr, rec = ctx.get("trace"), ctx["recorder"]
    spans_by = {}
    for s in (tr.spans if tr is not None else []):
        spans_by[s.name] = spans_by.get(s.name, 0) + 1
    return {"traced": rec.traced, "chunks_recorded": len(rec.chunks),
            "chunks_traced": len(devmetrics.traced_chunks(ctx)),
            "spans": spans_by,
            "devices": [(d.name, len(d.ops), len(d.modules))
                        for d in (tr.devices if tr is not None else [])],
            "hlo_kernels": len(devmetrics.kernels(ctx.get("hlo")))}


def result_line(out: dict) -> str:
    """The last line of standard output: the contract's keys, with the
    compared numbers last."""
    keys = ["correct", "attempted", "failed", "metrics", "device",
            "breakdown", "check"]
    return json.dumps({k: out[k] for k in keys if k in out})


def print_checks(out: dict) -> None:
    for name, c in out["check"].items():
        print(f"check {name} {c['value']} limit {c['limit']}",
              file=sys.stderr)
