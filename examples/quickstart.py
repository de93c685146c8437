"""Quickstart: build an assigned architecture at reduced size, train it a few
steps with the early-exit loss, then decode with entropy-gated early exit —
first through the legacy host loop, then through the continuous-batching
PAGED slot engine (the production serving path) running under an autotuned
shape-aware dispatch policy.

Serving in one paragraph: ``SlotEngine(run, capacity=S, max_len=L,
paged=True)`` owns a fixed batch of S SLOTS whose attention KV lives in
fixed-size pages from a shared pool — a request holds only the pages its
tokens occupy, so admission is bounded by free PAGES (tokens actually
resident), not slots x max_len. ``serve(engine, params, requests)`` admits
each request into a free slot (one bucketed batch-1 prefill scattered into
host-allocated pages), decodes ALL occupied slots in jitted lax.scan chunks
(greedy sampling, early-exit merge and statistics on device — one host
transfer per chunk; pages grow on demand between chunks), and backfills
retired slots — returning their pages to the pool — without re-compiling.
Decode is token-identical to the contiguous engine. ``repro.launch.serve``
wraps the same path in a Poisson request-stream simulator (--paged).

MoE archs serve COMPOSITION-INDEPENDENTLY: decode dispatches each token's
top-k expert GEMMs through the ``moe_decode`` XAIF op (dropless — no
shared expert-capacity group, so a request's tokens never depend on which
other requests are batched or backfilled beside it), dead/retired slots
are masked out of routing entirely (no capacity theft, no aux-count skew),
and the engine prefills MoE prompts at exact length (capacity-bounded
prefill is not pad-safe). Every token-identity guarantee below therefore
covers qwen3-moe / deepseek-v2 / jamba too.

PREFIX SHARING (``SlotEngine(..., paged=True, prefix_sharing=True)``):
prompts that open with tokens already resident in the page pool — system
prompts, few-shot preambles, multi-turn prefixes — are radix-matched
against retired and live requests' KV page chains; matched full pages are
mapped (refcounted) into the new request's page-table row, a partially
matched boundary page is copied (copy-on-write) and prefill runs only
from the fork point. Same greedy tokens, a fraction of the prefill FLOPs
and resident pages (``repro.launch.serve --paged --prefix-sharing
--shared-prefix-len 40`` demos it end to end).

SPECULATIVE DECODING (``SlotEngine(..., spec=SpecConfig(draft_arch=dcfg,
k=3))``): a small DRAFT model proposes k tokens per live slot per chunk,
and the target verifies all k+1 positions in ONE batched forward through
the ``verify_decode`` op (row i bitwise equal to the i-th sequential
decode step) — so each target pass can realize up to k+1 tokens instead
of one. Greedy speculative decode is token-identical to plain greedy on
every layout above (contiguous / paged / prefix-sharing / mesh); sampled
requests go through residual rejection sampling, which preserves the
target distribution on a pinned per-request stream. Acceptance is the
economics: the serving benchmark distils a 1-layer draft onto an 8-layer
target's own rollouts and measures ~0.88 acceptance at k=3, for 1.99x /
1.21x / 1.31x decode tok/s over the best plain engine at batch 1 / 2 / 4
(BENCH_serving.json, ``spec_decode`` section). From the CLI:
``repro.launch.serve --draft yi-9b --spec-k 3`` (prints the acceptance
rate in the epilogue).

Serve on a MESH: pass ``SlotEngine(..., mesh=make_mesh((dp, tp),
("data", "model")), sharding=ShardingPolicy(fsdp=False))`` (``make_mesh``
from ``repro.dist.sharding``: every axis Auto) — every jitted
entry point is built with explicit in/out shardings (params tp-sharded,
the cache's slot axis over the data axes, page pools head-sharded, MoE
expert stacks E-over-model) and greedy tokens stay identical to the
single-device engine. From the CLI:

    XLA_FLAGS=--xla_force_host_platform_device_count=4 \
        PYTHONPATH=src python -m repro.launch.serve --arch yi-9b \
        --reduced --mesh dp=2,model=2 [--temperature 0.8 --top-k 40]

    PYTHONPATH=src python examples/quickstart.py [--arch yi-9b] [--steps 30]
"""
import argparse
import dataclasses

import jax
import jax.numpy as jnp

from repro.configs.base import (AccelConfig, RunConfig, SHAPES_BY_NAME,
                                get_arch)
from repro.serve.engine import generate
from repro.train.trainer import train


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="yi-9b")
    ap.add_argument("--steps", type=int, default=30)
    args = ap.parse_args()

    cfg = get_arch(args.arch).reduced()
    print(f"arch={cfg.name} (reduced): {cfg.num_layers}L d={cfg.d_model} "
          f"params={sum(p.size for p in jax.tree_util.tree_leaves(jax.eval_shape(lambda: __import__('repro.models.lm', fromlist=['lm']).init_lm(jax.random.PRNGKey(0), cfg))))}")
    run = RunConfig(arch=cfg, shape=SHAPES_BY_NAME["train_4k"],
                    accel=AccelConfig(), remat="nothing", learning_rate=1e-3)

    # --- train a few steps -------------------------------------------------
    history = train(run, num_steps=args.steps, batch_override=8,
                    seq_override=64, log_every=10)
    print(f"loss: {history['loss'][0]:.3f} -> {history['loss'][-1]:.3f}")

    # --- early-exit generation (legacy host loop) --------------------------
    from repro.models import lm
    params = lm.init_lm(jax.random.PRNGKey(0), cfg)
    prompt = jax.random.randint(jax.random.PRNGKey(1), (4, 8), 0,
                                cfg.vocab_size)
    tokens, stats = generate(run, params, prompt, max_new_tokens=8)
    print(f"generated {tokens.shape} tokens; exit stats: {stats}")

    # --- autotune the XAIF dispatch policy ---------------------------------
    # Measure every registered backend per (op, shape-bucket) cell and keep
    # the winner; the resulting DispatchPolicy is hashable (a jit static
    # arg) and JSON-persistable, so a serve launch can load it instead of
    # re-measuring (repro.launch.serve --policy / --autotune). On this CPU
    # host the ref/XLA backends usually win — that IS the measured answer;
    # on a real TPU the same sweep selects the fused Pallas kernels.
    from repro.core.autotune import autotune
    tuned = autotune(ops=["attention", "rmsnorm"], iters=2)
    for cell in tuned.cells:
        backend, tuning = cell.winner()
        print(f"autotune {cell.op}/{cell.bucket}: {backend} "
              f"{dict(tuning) or ''}")
    run = dataclasses.replace(run, accel=tuned.policy)

    # --- continuous-batching PAGED slot engine -----------------------------
    import numpy as np
    from repro.serve.engine import SlotEngine
    from repro.serve.scheduler import Request, serve

    engine = SlotEngine(run, capacity=2, max_len=32, chunk=4,
                        paged=True, page_size=8)
    requests = [Request(rid=i, prompt=np.asarray(prompt[i]),
                        max_new_tokens=8) for i in range(4)]
    report = serve(engine, params, requests)   # 4 requests through 2 slots
    lat = report.latency_percentiles()
    print(f"paged slot engine: {report.decode_tokens} tokens at "
          f"{report.tokens_per_s:.0f} tok/s "
          f"(p50 {lat['p50']*1e3:.0f}ms, p99 {lat['p99']*1e3:.0f}ms); "
          f"decode traces={engine.decode_traces}, "
          f"peak pages {int(report.stats['peak_pages'])}")

    # --- prefix sharing: system-prompt style workloads ---------------------
    # Every prompt below opens with the same 24-token prefix (think: one
    # system prompt, many user turns). With prefix_sharing=True the engine
    # radix-matches each new prompt against KV pages already resident,
    # maps the matched pages into the request's page-table row (refcounted,
    # copy-on-write at the fork page) and prefills ONLY the unshared
    # suffix. Greedy tokens are identical to the unshared engine; the
    # prefix is computed once instead of once per request.
    shared_engine = SlotEngine(run, capacity=2, max_len=64, chunk=4,
                               paged=True, page_size=8, num_pages=32,
                               prefix_sharing=True)
    system = np.asarray(jax.random.randint(
        jax.random.PRNGKey(2), (24,), 0, cfg.vocab_size), np.int32)
    turns = [Request(rid=i,
                     prompt=np.concatenate([system, np.asarray(prompt[i])]),
                     max_new_tokens=8) for i in range(4)]
    shared_report = serve(shared_engine, params, turns)
    print(f"prefix sharing: {int(shared_report.stats['shared_admissions'])}"
          f"/4 admissions forked off resident pages, "
          f"{int(shared_report.stats['shared_tokens'])} prompt tokens "
          f"reused, prefill pushed {shared_engine.prefill_tokens} bucketed "
          f"tokens, peak pages {int(shared_report.stats['peak_pages'])}")

    # --- speculative decoding: draft proposals, batched verification -------
    # A draft model proposes k tokens per slot per chunk; the target scores
    # all k+1 positions in one verify pass and keeps the longest accepted
    # prefix (+1 bonus token from its own distribution). Tied params
    # (share_params=True) make the draft byte-identical to the target, so
    # every proposal verifies — acceptance is exactly 1.0 and the engine
    # realizes (k+1) tokens per chunk step. The real win comes from a CHEAP
    # draft: the serving bench distils a 1-layer draft (~0.88 acceptance,
    # 1.99x tok/s at batch 1 vs plain). Early-exit heads are incompatible
    # with verification, so this demo strips them from the target arch.
    from repro.serve.engine import SpecConfig

    spec_cfg = dataclasses.replace(cfg, early_exit=None)
    spec_run = dataclasses.replace(run, arch=spec_cfg)
    spec_params = lm.init_lm(jax.random.PRNGKey(0), spec_cfg)
    plain_engine = SlotEngine(spec_run, capacity=2, max_len=32, chunk=4)
    spec_engine = SlotEngine(spec_run, capacity=2, max_len=32, chunk=2,
                             spec=SpecConfig(draft_arch=spec_cfg, k=3,
                                             share_params=True))
    def spec_requests():
        return [Request(rid=i, prompt=np.asarray(prompt[i]),
                        max_new_tokens=8) for i in range(4)]
    ref_toks = {r.rid: list(r.tokens)
                for r in serve(plain_engine, spec_params,
                               spec_requests()).served}
    sp = serve(spec_engine, spec_params, spec_requests())
    assert all(list(r.tokens) == ref_toks[r.rid] for r in sp.served)
    print(f"speculative decoding (tied draft, k=3): acceptance "
          f"{sp.stats['spec_acceptance']:.0%} "
          f"({int(sp.stats['spec_accepted'])}/"
          f"{int(sp.stats['spec_proposed'])} proposals), "
          f"{int(sp.stats['realized_tokens'])} tokens realized over "
          f"{spec_engine.decode_calls} chunks, tokens identical to plain "
          f"greedy")

    # --- overload control: priorities + preemption -------------------------
    # Pass an OverloadConfig to serve() and the stream routes through the
    # priority-aware preemptive scheduler instead of the FIFO reject-only
    # one. Admission is OPTIMISTIC: a request books pages for its prompt
    # bucket only, not its worst case, so a pool far smaller than
    # capacity * max_pages still admits everyone. When decode growth does
    # exhaust the pool, the lowest-priority / most-page-hungry occupant is
    # preempted — its KV pages swap to a host pool — and it resumes later
    # with bitwise-identical tokens. First run an uncontended reference,
    # then the same workload through a pool less than half the worst case.
    from repro.serve.overload import OverloadConfig

    def overload_requests():
        return [Request(rid=i, prompt=np.asarray(prompt[i % 4]),
                        max_new_tokens=40, priority=i % 3)
                for i in range(6)]

    roomy = SlotEngine(run, capacity=2, max_len=64, chunk=4,
                       paged=True, page_size=8)
    ref = {r.rid: list(r.tokens)
           for r in serve(roomy, params, overload_requests()).served}
    tight = SlotEngine(run, capacity=2, max_len=64, chunk=4,
                       paged=True, page_size=8, num_pages=10)
    ov = serve(tight, params, overload_requests(),
               overload=OverloadConfig(mode="preempt"))
    hi = ov.ttft_percentiles(min_priority=2)
    assert all(list(r.tokens) == ref[r.rid] for r in ov.served)
    print(f"overload control: {len(ov.served)}/6 served through a "
          f"10-page pool (worst case 17), "
          f"{int(ov.stats['preemptions'])} preemptions / "
          f"{int(ov.stats['swap_resumes'])} swap resumes, tokens identical "
          f"to the uncontended run; hi-pri p99 TTFT {hi['p99']*1e3:.0f}ms")

    # --- fault tolerance: chaos injection + snapshot/restore replay --------
    # serve_resilient() wraps the same stream in a restart supervisor:
    # every few chunks it snapshots the engine (DecodeState + allocated KV
    # pages + allocator + queue + per-request progress), and ANY crash out
    # of a serve step — here a deterministic FaultInjector killing the 2nd
    # decode chunk — restores the snapshot and replays. Replay is exact:
    # the survivors' tokens are bitwise identical to a fault-free run,
    # greedy and seeded sampling alike. The same injector reaches every
    # hot-path site (prefill / decode / page_alloc / swap / backend), and
    # `repro.launch.serve --inject-fault site=decode,chunk=3` runs this as
    # a CLI smoke. Runtime guards ride along: a NaN/Inf logit quarantines
    # only the poisoned slot (reject_reason "nan-quarantined: ...";
    # co-batched requests unaffected), --watchdog-ms bounds chunk wall
    # time, and a core.xaif.CircuitBreaker degrades a raising dispatched
    # backend to "ref" for that (op, bucket) cell instead of crashing the
    # stream at all.
    from repro.serve.faults import FaultInjector
    from repro.serve.resilient import serve_resilient

    chaos_engine = SlotEngine(run, capacity=2, max_len=64, chunk=4,
                              paged=True, page_size=8)
    def chaos_requests():
        return [Request(rid=i, prompt=np.asarray(prompt[i % 4]),
                        max_new_tokens=12) for i in range(4)]
    ref = {r.rid: list(r.tokens)
           for r in serve(chaos_engine, params, chaos_requests()).served}
    inj = FaultInjector(schedule={"decode": [1]})
    rep = serve_resilient(chaos_engine, params, chaos_requests(),
                          snapshot_every=2, injector=inj)
    assert rep.completion_rate == 1.0
    assert all(list(r.tokens) == ref[r.rid] for r in rep.served)
    print(f"fault tolerance: decode chunk killed and replayed — "
          f"{int(rep.stats['restarts'])} restart, "
          f"{int(rep.stats['faults_injected'])} injected fault, recovery "
          f"{rep.stats['recovery_s_max']*1e3:.0f}ms, 4/4 served, tokens "
          f"identical to the fault-free run")

    # --- contract analyzer: lint + registry audit + trace audit ------------
    # Everything above leans on contracts that used to live only in prose:
    # no tracer leaks or host syncs inside jitted regions, explicit dtypes
    # in kernels/serve, models dispatch through xaif (never import kernels
    # directly), jitted cache-updaters donate, every op keeps a ref
    # backend, persisted policies resolve, and the decode chunk traces
    # exactly ONCE per engine no matter how the stream churns. repro.analysis
    # machine-checks all of it (CONTRACTS.md lists every rule) and CI runs
    #   PYTHONPATH=src python -m repro.launch.analyze \
    #       --lint --registry --trace-audit --json findings.json
    # as a required gate (exit status == number of findings). A documented
    # lint exception is suppressed inline with `# analysis: disable=RULE`.
    from repro.analysis import audit_registry, lint_file

    leaky = ("import jax, jax.numpy as jnp\n"
             "@jax.jit\n"
             "def f(x):\n"
             "    return jnp.zeros(int(x.sum()))\n")
    findings = lint_file("demo.py", src=leaky)
    assert any(f.rule == "XH101" for f in findings)  # tracer concretized
    assert audit_registry() == []                    # registry honest on HEAD
    print(f"analysis: seeded tracer leak caught ({findings[0].rule} "
          f"line {findings[0].line}); XAIF registry audit clean")


if __name__ == "__main__":
    main()
