"""Chip smoke: serve a full-width model on the TPU through the normal
serving path (``SlotEngine`` -> ``serve()``), in one process.

    python chip_smoke.py                # one chip: chatglm3-6b, 4 phases
    python chip_smoke.py --four-chips   # four chips: yi-9b on a dp1 x tp4 mesh
    JAX_PLATFORMS=cpu python chip_smoke.py --reduced   # CPU rehearsal

One chip serves chatglm3-6b at its published widths and full depth (28
layers, d_model 4096, 32 query / 2 KV heads of 128, d_ff 13696, vocabulary
65024, exit head after layer 7) with random weights from ``--seed``:

1. device   — platform, device kind and count;
2. ref      — the paged engine (capacity 8, page 16, max_len 1024) serves 8
              seeded requests (prompts 128-512 tokens, two prefill buckets,
              32 new tokens each) under the all-``ref`` policy; the tokens
              are checked against the plain ``lm.forward_train`` logits;
3. pallas   — the same requests with gemm, rmsnorm, attention,
              attn_decode_paged and entropy_exit on their compiled Pallas
              kernels; ``xaif.resolve`` must return the Pallas backend for
              every (op, bucket) dispatched, and one prompt's last-position
              prefill logits must match the ref policy's;
4. gated    — the paper's entropy-gated early exit on the contiguous engine
              (``gated=True``) under the Pallas policy, at the published
              threshold and at 1.0, where every token must exit.

``--four-chips`` runs only yi-9b at full width (17.66 GB in bf16: no single
chip holds it) through ``SlotEngine(mesh=dp1 x tp4)``, checked against
``lm.forward_train`` of the same parameters on the same mesh.

Every timing printed is a smoke timing, not a benchmark. The script exits
non-zero when a phase fails, when the device is not a TPU (after running
the phases under ``--reduced``, before them otherwise), and when run
outside the repository. On success its last line is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import copy
import dataclasses
import json
import os
import sys
import time
import traceback

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

from repro.launch.compile_cache import enable_compile_cache  # noqa: E402

PALLAS_OPS = ("gemm", "rmsnorm", "attention", "attn_decode",
              "attn_decode_paged", "entropy_exit")
# Relative L2 gap allowed between two policies' prefill logits. Both run
# the bf16 model; the Pallas kernels accumulate in f32 like XLA but in a
# different order (bk-blocked GEMMs, online-softmax attention), so each of
# the 28 layers re-rounds its bf16 activations differently — a few bf16
# ulps (2^-8 relative) compounding over the depth, i.e. about 1e-2.
LOGITS_RTOL = 5e-2
# A decode step may differ from the full-sequence reference only where the
# reference's top-1/top-2 margin is within this multiple of the measured
# gap between the engine's prefill logits and the reference's at the same
# position (bf16 path noise: paged KV, per-step attention, other fusions),
# floored at one bf16 ulp of the largest logit.
NEAR_TIE = 4.0


class CompileClock:
    """Seconds JAX spends tracing, lowering and compiling (or reading the
    persistent cache), from its own monitoring events."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax.monitoring as mon
        self.seconds = 0.0
        self.cache_hits = 0
        mon.register_event_duration_secs_listener(self._duration)
        mon.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event in self.EVENTS:
            self.seconds += secs

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def _requests(cfg, num, lens, new_tokens, seed):
    import numpy as np
    from repro.serve.scheduler import poisson_requests
    return poisson_requests(num=num, rate_hz=np.inf, prompt_lens=lens,
                            max_new_tokens=new_tokens,
                            vocab_size=cfg.vocab_size, seed=seed)


def _serve(engine, params, requests, mesh=None):
    import contextlib
    from repro.dist import sharding as shd
    from repro.serve.scheduler import serve
    reqs = copy.deepcopy(requests)
    ctx = (shd.shard_ctx(mesh, engine.sharding) if mesh is not None
           else contextlib.nullcontext())
    with ctx:
        report = serve(engine, params, reqs)
    assert report.completion_rate == 1.0, \
        [r.reject_reason for r in report.rejected]
    for r in reqs:
        assert len(r.tokens) == r.max_new_tokens, (r.rid, len(r.tokens))
        assert all(0 <= t < engine.run.arch.vocab_size for t in r.tokens)
    print(f"  served {len(reqs)} requests, {report.decode_tokens} decode "
          f"tokens in {report.wall_s:.2f} s (smoke timing, not a benchmark);"
          f" traces: decode={engine.decode_traces} "
          f"prefill={engine.prefill_traces}")
    return report, reqs


def _prefill_logits(params, cfg, policy, prompt, mesh=None):
    """Last-position logits of ``prompt`` through the serving prefill."""
    import jax
    import jax.numpy as jnp
    from repro.dist import sharding as shd
    from repro.models import lm

    def f(p, toks):
        return lm.forward_prefill(p, toks, cfg, policy,
                                  lm.init_cache(cfg, 1, toks.shape[1]))[0][0]
    return _on_mesh(jax.jit(f), mesh, shd)(params, jnp.asarray(prompt)[None])


def _on_mesh(fn, mesh, shd):
    if mesh is None:
        return fn
    from repro.launch.serve import SERVE_POLICY

    def wrapped(*a):
        with shd.shard_ctx(mesh, SERVE_POLICY):
            return fn(*a)
    return wrapped


def _check_against_reference(params, cfg, reqs, mesh=None):
    """Teacher-forced check of served tokens against the plain
    ``lm.forward_train`` logits of the same parameters: the first token
    comes from the prefill's final head, later ones through the same
    early-exit merge the decode chunk applies. Every greedy token must be
    the reference's argmax, except at near-ties (see NEAR_TIE)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.configs.base import AccelConfig
    from repro.core.early_exit import merge_exit_logits
    from repro.dist import sharding as shd
    from repro.models import lm

    policy = AccelConfig()

    def reference(p, toks):
        final, exits, _ = lm.forward_train(p, toks, cfg, policy)
        merged = final
        if cfg.early_exit is not None:
            merged = merge_exit_logits(final, exits, cfg.early_exit,
                                       policy)[0]
        return final[0], merged[0]

    # causal attention: right padding never changes earlier positions, so
    # one padded length (one compile) serves every request
    width = max(len(r.prompt) + len(r.tokens) for r in reqs)
    width = -(-width // 128) * 128
    train = _on_mesh(jax.jit(reference), mesh, shd)
    r0 = reqs[0]
    ref0 = np.asarray(train(params, jnp.zeros((1, width), jnp.int32).at[
        0, :len(r0.prompt)].set(jnp.asarray(r0.prompt)))[0], np.float32)
    pre0 = np.asarray(_prefill_logits(params, cfg, policy, r0.prompt, mesh),
                      np.float32)
    assert np.isfinite(pre0).all() and np.isfinite(ref0).all()
    # floor: one bf16 ulp at the largest logit (the logits are bf16)
    noise = max(float(np.max(np.abs(pre0 - ref0[len(r0.prompt) - 1]))),
                2.0 ** -8 * float(np.max(np.abs(ref0))))
    counted = agree = 0
    for r in reqs:
        seq = np.concatenate([r.prompt, np.asarray(r.tokens[:-1], np.int32)])
        toks = jnp.zeros((1, width), jnp.int32).at[0, :len(seq)].set(seq)
        final, merged = (np.asarray(a, np.float32)[len(r.prompt) - 1:len(seq)]
                         for a in train(params, toks))
        lg = np.concatenate([final[:1], merged[1:]])
        top2 = np.sort(lg, axis=-1)[:, -2:]
        decided = (top2[:, 1] - top2[:, 0]) > NEAR_TIE * noise
        match = np.argmax(lg, axis=-1) == np.asarray(r.tokens)
        assert match[decided].all(), (
            f"request {r.rid}: served tokens differ from the reference "
            f"argmax at decided positions {np.where(decided & ~match)[0]}")
        counted += int(decided.sum())
        agree += int(match.sum())
    total = sum(len(r.tokens) for r in reqs)
    print(f"  vs lm.forward_train: prefill-logit gap (floored) {noise:.4g}; "
          f"{agree}/{total} tokens are the reference argmax; all "
          f"{counted} positions with a top-2 margin > {NEAR_TIE:g}x the gap "
          f"agree")


def _check_pallas_dispatch(policy, cfg, capacity, max_len, page_size,
                           buckets, paged):
    """xaif.resolve must answer "pallas" for every (op, shape) the phase
    dispatches — a silent fallback to ref would hide the device."""
    from repro.core import xaif
    assert xaif.active_breaker() is None, "a circuit breaker is installed"
    d, hq, hkv, dh = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, \
        cfg.head_dim
    b, npg = capacity, -(-max_len // page_size)
    shapes = []
    for rows in [(b, 1)] + [(1, t) for t in buckets]:
        for k, n in ((d, hq * dh), (d, hkv * dh), (hq * dh, d), (d, cfg.d_ff),
                     (cfg.d_ff, d), (d, cfg.vocab_size)):
            shapes.append(("gemm", (rows + (k,), (k, n))))
        shapes.append(("rmsnorm", (rows + (d,), (d,))))
    for t in buckets:
        shapes.append(("attention", ((1, hq, t, dh), (1, hkv, t, dh),
                                     (1, hkv, t, dh))))
    if paged:
        shapes.append(("attn_decode_paged", (
            (b, hq, dh), (b * npg + 1, hkv, page_size, dh),
            (b * npg + 1, hkv, page_size, dh), (b, npg), (b,))))
    else:
        shapes.append(("attn_decode", ((b, hq, dh), (b, hkv, max_len, dh),
                                       (b, hkv, max_len, dh), (b,))))
        shapes.append(("entropy_exit", ((b, cfg.vocab_size),)))
    for op, s in shapes:
        got = xaif.resolve(op, policy, s).name
        assert got == "pallas", (op, xaif.shape_bucket(op, s), s, got)
    cells = sorted({(op, xaif.shape_bucket(op, s)) for op, s in shapes})
    print(f"  pallas on every dispatched cell: "
          + ", ".join(f"{o}/{k}" for o, k in cells))


def one_chip_phases(args, st):
    import jax
    import numpy as np
    from repro.configs.base import AccelConfig, RunConfig, SHAPES_BY_NAME
    from repro.core import xaif
    from repro.serve.engine import SlotEngine

    cfg = st["cfg"]
    capacity, max_len, page_size, bucket = 8, 1024, 16, 256
    requests = _requests(cfg, 8, (128, 512), 32, args.seed)
    pallas = xaif.DispatchPolicy.make({op: "pallas" for op in PALLAS_OPS})

    def engine(policy, **kw):
        run = RunConfig(arch=cfg, shape=SHAPES_BY_NAME["decode_32k"],
                        accel=policy)
        return SlotEngine(run, capacity=capacity, max_len=max_len,
                          prompt_bucket=bucket, page_size=page_size, **kw)

    def ref():
        eng = engine(AccelConfig(), paged=True)
        st["params"] = eng.init_params(jax.random.PRNGKey(args.seed))
        nbytes = sum(a.size * a.dtype.itemsize
                     for a in jax.tree_util.tree_leaves(st["params"]))
        print(f"  parameter bytes: {nbytes} ({nbytes / 1e9:.2f} GB)")
        _, st["ref_reqs"] = _serve(eng, st["params"], requests)
        _check_against_reference(st["params"], cfg, st["ref_reqs"])

    def pallas_phase():
        prompt = requests[0].prompt        # also prefilled unbucketed below
        _check_pallas_dispatch(pallas, cfg, capacity, max_len, page_size,
                               (256, 512, len(prompt)), paged=True)
        _, reqs = _serve(engine(pallas, paged=True), st["params"], requests)
        same = sum(a == b for r, q in zip(reqs, st["ref_reqs"])
                   for a, b in zip(r.tokens, q.tokens))
        total = sum(len(r.tokens) for r in reqs)
        print(f"  greedy-token agreement with the ref phase: {same}/{total}")
        a = np.asarray(_prefill_logits(st["params"], cfg, AccelConfig(),
                                       prompt), np.float32)
        b = np.asarray(_prefill_logits(st["params"], cfg, pallas, prompt),
                       np.float32)
        assert np.isfinite(b).all()
        rel = float(np.linalg.norm(a - b) / np.linalg.norm(a))
        print(f"  prefill logits, pallas vs ref: relative L2 {rel:.4g} "
              f"(limit {LOGITS_RTOL:g}), same argmax: "
              f"{int(np.argmax(a)) == int(np.argmax(b))}")
        assert rel <= LOGITS_RTOL, rel

    def gated():
        _check_pallas_dispatch(pallas, cfg, capacity, max_len, page_size,
                               (256, 512), paged=False)
        # the published threshold, then 1.0: normalized entropy is below 1
        # for any non-uniform distribution, so every token exits and the
        # lax.cond skip branch (KV propagation only) serves the stream
        for threshold in (cfg.early_exit.entropy_threshold, 1.0):
            ee = dataclasses.replace(cfg.early_exit,
                                     entropy_threshold=threshold)
            run = RunConfig(arch=dataclasses.replace(cfg, early_exit=ee),
                            shape=SHAPES_BY_NAME["decode_32k"], accel=pallas)
            eng = SlotEngine(run, capacity=capacity, max_len=max_len,
                             prompt_bucket=bucket, gated=True)
            report, _ = _serve(eng, st["params"], requests)
            rate = report.stats["exit_rate"]
            print(f"  threshold {threshold}: exit rate {rate:.4f}, gated "
                  f"fraction {report.stats['gated_fraction']:.4f}")
        assert rate == 1.0, rate

    return [("ref", ref), ("pallas", pallas_phase), ("gated", gated)]


def four_chip_phases(args, st):
    import jax
    from repro.configs.base import AccelConfig, RunConfig, SHAPES_BY_NAME
    from repro.dist import sharding as shd
    from repro.launch.serve import SERVE_POLICY
    from repro.serve.engine import SlotEngine

    cfg = st["cfg"]

    def mesh_phase():
        mesh = shd.make_mesh((1, 4), ("data", "model"))
        run = RunConfig(arch=cfg, shape=SHAPES_BY_NAME["decode_32k"],
                        accel=AccelConfig())
        eng = SlotEngine(run, capacity=4, max_len=512, paged=True,
                         page_size=16, prompt_bucket=256, mesh=mesh,
                         sharding=SERVE_POLICY)
        params = eng.init_params(jax.random.PRNGKey(args.seed))
        nbytes = sum(a.size * a.dtype.itemsize
                     for a in jax.tree_util.tree_leaves(params))
        print(f"  parameter bytes: {nbytes} ({nbytes / 1e9:.2f} GB) over "
              f"{mesh.devices.size} devices")
        _, reqs = _serve(eng, params, _requests(cfg, 4, (128, 256), 16,
                                                args.seed), mesh)
        _check_against_reference(params, cfg, reqs, mesh)

    return [("mesh dp1 x tp4", mesh_phase)]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="serve yi-9b on a dp1 x tp4 mesh of four chips")
    ap.add_argument("--reduced", action="store_true",
                    help="CPU rehearsal at ArchConfig.reduced() widths "
                         "(still exits non-zero off the TPU)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    cache_dir = enable_compile_cache()
    clock = CompileClock()
    import jax
    from repro.configs.base import get_arch

    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    need = 4 if args.four_chips else 1
    print(f"[device] platform={device['platform']} kind={device['kind']} "
          f"count={device['count']} compile_cache={cache_dir}")
    on_tpu = device["platform"] == "tpu"
    if not on_tpu and not args.reduced:
        print("chip smoke needs a TPU (run with --reduced to rehearse on "
              "this platform)", file=sys.stderr)
        return 1
    if device["count"] < need:
        print(f"chip smoke needs {need} devices, found {device['count']}",
              file=sys.stderr)
        return 1

    cfg = get_arch("yi-9b" if args.four_chips else "chatglm3-6b")
    st = {"cfg": cfg.reduced() if args.reduced else cfg}
    print(f"[model] {st['cfg'].name}: {st['cfg'].num_layers} layers, "
          f"d_model {st['cfg'].d_model}, heads {st['cfg'].num_heads}/"
          f"{st['cfg'].num_kv_heads}x{st['cfg'].head_dim}, d_ff "
          f"{st['cfg'].d_ff}, vocab {st['cfg'].vocab_size}"
          + (" (reduced)" if args.reduced else ""))
    phases = (four_chip_phases if args.four_chips else one_chip_phases)(
        args, st)
    failed = []
    for name, fn in phases:
        print(f"[{name}]", flush=True)
        c0, h0, t0 = clock.seconds, clock.cache_hits, time.time()
        try:
            fn()
        except Exception:                   # noqa: BLE001 — report, go on
            traceback.print_exc()
            failed.append(name)
        stats = devs[0].memory_stats() or {}
        peak = stats.get("peak_bytes_in_use")
        print(f"  [{name}] {'FAILED' if name in failed else 'ok'}: wall "
              f"{time.time() - t0:.1f} s, compile {clock.seconds - c0:.1f} s "
              f"({clock.cache_hits - h0} persistent-cache hits) — smoke "
              f"timings, not a benchmark; peak_bytes_in_use "
              f"{peak if peak is not None else 'not reported'}", flush=True)
    if failed:
        print(f"chip smoke FAILED: {', '.join(failed)}", file=sys.stderr)
        return 1
    if not on_tpu:
        print("rehearsal passed; no TPU, so no result", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
