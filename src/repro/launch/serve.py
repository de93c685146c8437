"""Serving launcher: request-stream simulator over the continuous-batching
slot engine.

Generates an open-loop Poisson arrival stream of ``--requests`` requests
with mixed prompt lengths, serves it on ``--capacity`` slots, and reports
decode throughput plus per-request latency percentiles (p50/p99):

    PYTHONPATH=src python -m repro.launch.serve --arch chatglm3-6b \
        --requests 32 --capacity 8 --rate 4 [--gated] [--threshold 0.9]

The arch is served at its published widths and depth, with random weights
from a seed; ``--reduced`` swaps in the tiny same-family config
(``ArchConfig.reduced()``) that a CPU host can run.

``--rate 0`` disables arrival pacing (closed-loop: every request is ready
at t=0 — the pure-throughput configuration the benchmarks use).

``--paged`` serves through the paged KV engine: attention KV lives in
fixed-size pages (``--page-size``) from a pool of ``--num-pages`` and
admission is by free pages, so short requests stop reserving worst-case
``--max-len`` rows. Shrink ``--num-pages`` below the contiguous worst case
(capacity x max_len / page_size) to trade headroom for concurrency.
``--paged --gated`` is rejected at argument-parsing time (the gated
early-exit decode path is not page-aware yet).

``--mesh dp=2,model=2`` serves the slot batch on a real device mesh: the
engine jits every entry point with explicit in/out shardings (params tp
over the model axis, the cache's slot dim over the data axes, page pools
head-sharded, DecodeState + page table replicated) and the request-stream
simulator runs under the matching ``shard_ctx``. Greedy tokens are
identical to the single-device engine on any mesh shape. On a CPU host,
force virtual devices first:

    XLA_FLAGS=--xla_force_host_platform_device_count=4 \
        PYTHONPATH=src python -m repro.launch.serve --arch chatglm3-6b \
        --reduced --mesh dp=2,model=2

``--paged --prefix-sharing`` turns on the radix prefix index: prompts are
matched against KV page chains left resident by earlier requests, matched
full pages are mapped into the new request's page-table row (refcounted,
copy-on-write at the fork page) and only the unshared suffix is prefilled.
``--shared-prefix-len N`` prepends a common N-token prefix to every prompt
to exercise it. Greedy tokens are identical with sharing on or off.

``--preemption`` / ``--priority N`` / ``--prefill-chunk C`` route the
stream through the overload-control scheduler (``serve/overload.py``):
optimistic page admission whose growth preempts (host-swaps or
re-prefills) the lowest-priority victim instead of rejecting new work,
N aged priority classes, per-request TTFT shedding (``--slo-ttft-ms``),
and long prompts admitted as C-token prefill chunks interleaved with
decode. Invalid combinations (``--preemption`` without ``--paged``, a
chunk size off the page grid, a recurrent arch with ``--prefill-chunk``)
die at argument parsing with an actionable message.

``--draft ARCH --spec-k N`` turns on speculative decoding: the draft arch
(reduced along with the target under ``--reduced``) proposes N tokens per
live slot per round and the target model verifies all of them in ONE
batched ``verify_decode`` forward; accepted prefixes advance multiple
positions per chunk and KV pages grow by the accepted count. Greedy
tokens are identical to plain decode; sampled requests go through residual rejection sampling (distribution-
preserving). Rejected at parse time: unknown draft arch, ``--spec-k``
below 1, vocab mismatch, recurrent/MLA/MoE archs on either side, and
``--gated``/``--threshold``/``--prefill-chunk`` combos (verification
needs full-model logits; chunked admission never fills the draft KV).
The serve epilogue prints the measured draft-acceptance rate.

``--temperature`` / ``--top-k`` / ``--top-p`` switch the scan body from
greedy argmax to temperature / top-k / nucleus sampling through per-slot
PRNG keys (``--sample-seed`` makes streams reproducible; a per-request
``Request.seed`` overrides the slot key for placement-independent replay).

Backend selection: by default the static all-"ref" AccelConfig. Pass
``--policy PATH`` to serve under a persisted shape-aware DispatchPolicy
(produced by ``repro.core.autotune``), or ``--autotune`` to run the
measured sweep at startup — at THIS arch's exact serve-time dims (row ops
at the slot capacity, its head layout, its paged-KV extent; the policy
JSON records the arch per cell) — persisting to ``--policy``'s path
(default ``.xaif_policy.json``). A policy file is read only when
``--policy`` names it. Pallas backends run compiled on a TPU and through
the interpreter elsewhere (``xaif.pallas_interpret``).
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses

import jax
import numpy as np

from repro.configs.base import (AccelConfig, RunConfig, SHAPES_BY_NAME,
                                ShardingPolicy, get_arch, list_archs)
from repro.core import autotune as autotune_mod
from repro.core import xaif
from repro.dist import sharding as shd
from repro.launch.compile_cache import enable_compile_cache
from repro.serve.engine import SlotEngine
from repro.serve.overload import OverloadConfig
from repro.serve.scheduler import poisson_requests, serve

# serve-time layout: weights tp-sharded over the model axis and REPLICATED
# over data (fsdp is a training-time memory lever; at decode it would force
# a per-layer weight all-gather), cache slot dim over the data axes
SERVE_POLICY = ShardingPolicy(fsdp=False)


def parse_mesh(spec: str):
    """``dp=2,model=2`` (aliases: dp/data, tp/model) -> Mesh("data","model").
    """
    sizes = {"data": 1, "model": 1}
    alias = {"dp": "data", "data": "data", "tp": "model", "model": "model"}
    for part in spec.split(","):
        k, v = part.split("=")
        sizes[alias[k.strip()]] = int(v)
    need = sizes["data"] * sizes["model"]
    if need > jax.device_count():
        raise SystemExit(
            f"--mesh {spec} needs {need} devices but only "
            f"{jax.device_count()} are visible (on CPU prepend "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={need})")
    return shd.make_mesh((sizes["data"], sizes["model"]), ("data", "model"))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=list_archs())
    ap.add_argument("--reduced", action="store_true",
                    help="reduced same-family config (CPU-runnable) instead "
                         "of the published widths")
    ap.add_argument("--requests", type=int, default=32)
    ap.add_argument("--capacity", type=int, default=8)
    ap.add_argument("--rate", type=float, default=0.0,
                    help="mean arrivals/s (Poisson); 0 = all at t=0")
    ap.add_argument("--prompt-len-min", type=int, default=4)
    ap.add_argument("--prompt-len-max", type=int, default=24)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--max-len", type=int, default=64)
    ap.add_argument("--chunk", type=int, default=8,
                    help="decode steps per jitted scan chunk")
    ap.add_argument("--threshold", type=float, default=None)
    ap.add_argument("--gated", action="store_true")
    ap.add_argument("--paged", action="store_true",
                    help="paged KV cache: page-pool storage + page-aware "
                         "admission (capacity = tokens, not slots x max_len)")
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--num-pages", type=int, default=0,
                    help="page pool size (0 = contiguous worst case)")
    ap.add_argument("--mesh", default="",
                    help="serve on a device mesh, e.g. dp=2,model=2 "
                         "(aliases dp/data, tp/model); greedy tokens stay "
                         "identical to the single-device engine")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="sampling temperature (0 = greedy argmax)")
    ap.add_argument("--top-k", type=int, default=0,
                    help="top-k truncation for sampled decode (0 = full)")
    ap.add_argument("--top-p", type=float, default=1.0,
                    help="nucleus (top-p) truncation for sampled decode "
                         "(1.0 = full distribution)")
    ap.add_argument("--prefix-sharing", action="store_true",
                    help="radix-match prompts against resident KV page "
                         "chains; matched prefixes are mapped (refcounted, "
                         "copy-on-write boundary) and only the unshared "
                         "suffix is prefilled (requires --paged)")
    ap.add_argument("--shared-prefix-len", type=int, default=0,
                    help="give every request a common prompt prefix of "
                         "this many tokens (demo workload for "
                         "--prefix-sharing)")
    ap.add_argument("--preemption", action="store_true",
                    help="overload control: optimistic page admission with "
                         "priority-aware preemption — victims are host-"
                         "swapped or re-prefilled instead of new arrivals "
                         "being rejected (requires --paged)")
    ap.add_argument("--priority", type=int, default=0, metavar="N",
                    help="number of priority classes: each request draws a "
                         "priority in [0, N) (higher = sooner; aged so "
                         "nothing starves). N <= 1 keeps a single class. "
                         "Routed through the overload scheduler")
    ap.add_argument("--prefill-chunk", type=int, default=0, metavar="C",
                    help="chunked prefill: admit long prompts as C-token "
                         "prefill chunks interleaved with decode chunks, "
                         "bounding the stall a long prompt inflicts on "
                         "running requests (requires --paged; C must be a "
                         "multiple of --page-size)")
    ap.add_argument("--slo-ttft-ms", type=float, default=0.0,
                    help="stamp every request with this first-token SLO; "
                         "the overload scheduler sheds queued requests the "
                         "moment the SLO is already missed (0 = off)")
    ap.add_argument("--sample-seed", type=int, default=0,
                    help="base seed of the per-slot sampling PRNG keys")
    ap.add_argument("--draft", default="", metavar="ARCH",
                    help="speculative decoding: run this arch as the "
                         "draft model — k proposals per live slot per "
                         "round, verified in ONE batched target forward; "
                         "greedy tokens stay identical to plain decode")
    ap.add_argument("--spec-k", type=int, default=None, metavar="N",
                    help="draft proposals per speculative round "
                         "(requires --draft; default 4)")
    ap.add_argument("--inject-fault", default="", metavar="SPEC",
                    help="chaos smoke: SPEC is site=<name>,chunk=<n> — "
                         "inject one deterministic fault at the n-th call "
                         "of that site (sites: prefill, decode, page_alloc, "
                         "swap, backend), serve under the restart "
                         "supervisor, and assert 100%% completion with "
                         "tokens identical to a fault-free reference run")
    ap.add_argument("--watchdog-ms", type=float, default=0.0,
                    help="per-chunk watchdog budget for the restart "
                         "supervisor: a chunk slower than this wall-clock "
                         "bound is treated as a crash and replayed from "
                         "the latest snapshot (0 = off)")
    ap.add_argument("--snapshot-every", type=int, default=4,
                    help="supervisor snapshot cadence in decode chunks")
    ap.add_argument("--max-restarts", type=int, default=8,
                    help="supervisor restart budget before giving up")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--policy", default=None,
                    help="serve under this persisted DispatchPolicy JSON "
                         "(with --autotune: where to persist it, default "
                         f"{autotune_mod.DEFAULT_POLICY_PATH})")
    ap.add_argument("--autotune", action="store_true",
                    help="run the measured backend sweep at startup — at "
                         "this arch's exact serve-time dims — and persist "
                         "the winning policy to --policy")
    args = ap.parse_args()
    enable_compile_cache()

    # invalid flag combinations die HERE with an actionable message, not on
    # an assert deep inside SlotEngine after the model has been built
    if args.paged and args.gated:
        ap.error("--paged cannot be combined with --gated: the gated "
                 "early-exit decode path is not page-aware yet (ROADMAP.md "
                 "follow-up) — drop one of the two flags")
    if args.prefix_sharing and not args.paged:
        ap.error("--prefix-sharing requires --paged: shared prefixes are "
                 "mapped as refcounted KV pages, which only exist in the "
                 "paged engine")
    if args.prefix_sharing and args.gated:
        ap.error("--prefix-sharing cannot be combined with --gated "
                 "(implied by --paged being incompatible with --gated)")
    if args.preemption and not args.paged:
        ap.error("--preemption requires --paged: optimistic admission and "
                 "the host-swap pool operate on KV pages — add --paged")
    if args.prefill_chunk:
        if not args.paged:
            ap.error("--prefill-chunk requires --paged: chunk KV is "
                     "written page-by-page into the pool — add --paged")
        if args.prefill_chunk % args.page_size != 0:
            ap.error(f"--prefill-chunk {args.prefill_chunk} must be a "
                     f"multiple of --page-size {args.page_size}: chunk "
                     f"boundaries must land on page boundaries")
    if args.priority < 0:
        ap.error("--priority must be >= 0 (number of priority classes)")
    spec_k = args.spec_k
    if spec_k is not None and not args.draft:
        ap.error("--spec-k requires --draft: k counts DRAFT proposals per "
                 "speculative round — name the draft arch")
    if spec_k is not None and spec_k < 1:
        ap.error(f"--spec-k must be >= 1 (got {spec_k}): each round "
                 "proposes at least one draft token")
    if args.draft:
        spec_k = spec_k or 4
        if args.draft not in list_archs():
            ap.error(f"--draft {args.draft!r} is not a known arch "
                     f"(choices: {', '.join(list_archs())})")
        if args.gated:
            ap.error("--draft cannot be combined with --gated: batched "
                     "verification scores all k+1 positions with the FULL "
                     "model — the entropy-gated early-exit decode path has "
                     "no verify equivalent, so spec decode disables "
                     "early exit entirely")
        if args.threshold is not None:
            ap.error("--draft cannot be combined with --threshold: "
                     "speculative serving strips the target's early-exit "
                     "heads (verification must score every position with "
                     "full-model logits), so an exit threshold would be "
                     "silently ignored — drop one of the two flags")
        if args.prefill_chunk:
            ap.error("--draft cannot be combined with --prefill-chunk: "
                     "chunked admission writes target KV page-by-page and "
                     "never prefills the draft's slot cache — the draft "
                     "would propose from uninitialized rows")
    fault_spec = None
    if args.inject_fault:
        from repro.serve.faults import SITES
        kv = {}
        for part in args.inject_fault.split(","):
            if "=" not in part:
                ap.error(f"--inject-fault {args.inject_fault!r}: expected "
                         f"site=<name>,chunk=<n> (got segment {part!r})")
            k, v = part.split("=", 1)
            kv[k.strip()] = v.strip()
        site, chunk = kv.pop("site", None), kv.pop("chunk", None)
        if kv:
            ap.error(f"--inject-fault: unknown key(s) {sorted(kv)}; the "
                     f"spec is site=<name>,chunk=<n>")
        if site not in SITES:
            ap.error(f"--inject-fault site must be one of {SITES} "
                     f"(got {site!r})")
        try:
            chunk = int(chunk)
        except (TypeError, ValueError):
            ap.error(f"--inject-fault chunk must be an integer >= 0 "
                     f"(got {chunk!r})")
        if chunk < 0:
            ap.error(f"--inject-fault chunk must be >= 0 (got {chunk})")
        if site in ("page_alloc", "swap") and not args.paged:
            ap.error(f"--inject-fault site={site} requires --paged: that "
                     f"site only exists on the paged KV path")
        fault_spec = (site, chunk)
    if args.watchdog_ms < 0:
        ap.error("--watchdog-ms must be >= 0 (0 = off)")
    if args.snapshot_every < 1:
        ap.error("--snapshot-every must be >= 1")
    if args.max_restarts < 0:
        ap.error("--max-restarts must be >= 0")
    resilient = fault_spec is not None or args.watchdog_ms > 0
    if resilient and (args.preemption or args.priority > 1
                      or args.prefill_chunk or args.slo_ttft_ms > 0):
        ap.error("--inject-fault/--watchdog-ms run the restart supervisor, "
                 "which drives the base FIFO scheduler only — drop the "
                 "overload flags (--preemption/--priority/--prefill-chunk/"
                 "--slo-ttft-ms)")

    def arch(name):
        c = get_arch(name)
        return c.reduced() if args.reduced else c

    if args.autotune:
        path = args.policy or autotune_mod.DEFAULT_POLICY_PATH
        print(f"autotuning XAIF backends at {args.arch} serve dims -> {path}")
        result = autotune_mod.autotune(
            iters=2, arch=arch(args.arch), capacity=args.capacity,
            max_len=args.max_len, page_size=args.page_size, print_fn=print)
        result.persist(path)
        policy = result.policy
    elif args.policy:
        policy = xaif.DispatchPolicy.load(args.policy)
        print(f"loaded dispatch policy from {args.policy} "
              f"({len(policy.rules)} rules)")
    else:
        policy = AccelConfig()

    if fault_spec is not None and fault_spec[0] == "backend":
        from repro.serve.faults import register_chaos_backends
        register_chaos_backends()
        # route a hot row op through the chaos backend (= ref + injected
        # trace-time faults) so the dispatched-backend site actually fires
        policy = xaif.DispatchPolicy.make({"rmsnorm": "chaos"})

    cfg = arch(args.arch)
    if args.threshold is not None and cfg.early_exit is not None:
        cfg = dataclasses.replace(cfg, early_exit=dataclasses.replace(
            cfg.early_exit, entropy_threshold=args.threshold))
    gated = args.gated and all(b.mixer == "attn" for b in cfg.block_pattern)

    if args.prefill_chunk and not (
            all(b.mixer == "attn" for b in cfg.block_pattern)
            and cfg.mla is None and cfg.moe is None):
        ap.error(f"--prefill-chunk needs an all-attention GQA arch (chunks "
                 f"ride on the shared-prefill entry); {args.arch} has "
                 f"recurrent/MLA/MoE blocks")

    spec = None
    if args.draft:
        from repro.serve.engine import SpecConfig
        draft_cfg = arch(args.draft)
        for role, c, name in (("target", cfg, args.arch),
                              ("draft", draft_cfg, args.draft)):
            if not (all(b.mixer == "attn" for b in c.block_pattern)
                    and c.mla is None and c.moe is None):
                ap.error(f"--draft needs all-attention GQA archs on both "
                         f"sides (verify_decode scatters plain KV rows); "
                         f"the {role} arch {name} has recurrent/MLA/MoE "
                         f"blocks")
        if draft_cfg.vocab_size != cfg.vocab_size:
            ap.error(f"--draft {args.draft} has vocab_size "
                     f"{draft_cfg.vocab_size} but target {args.arch} has "
                     f"{cfg.vocab_size}: rejection sampling needs the two "
                     f"distributions over the SAME token alphabet")
        if draft_cfg.early_exit is not None:
            draft_cfg = dataclasses.replace(draft_cfg, early_exit=None)
        if cfg.early_exit is not None:
            # verification must score all k+1 positions with full-model
            # logits; the exit merge has no verify equivalent — serve the
            # target without the exit heads
            cfg = dataclasses.replace(cfg, early_exit=None)
            print(f"spec decode: early-exit heads of {args.arch} disabled "
                  f"for serving (verification uses full-model logits)")
        spec = SpecConfig(draft_arch=draft_cfg, k=spec_k)

    overload = None
    if (args.preemption or args.priority > 1 or args.prefill_chunk
            or args.slo_ttft_ms > 0):
        overload = OverloadConfig(
            mode="preempt" if args.preemption else "reject",
            prefill_chunk=args.prefill_chunk)

    assert (args.shared_prefix_len + args.prompt_len_max + args.new_tokens
            <= args.max_len), "--max-len must fit prompt + generation"
    prio_spec = None
    if args.priority > 1:
        vals = np.arange(args.priority)
        prio_spec = (vals, np.full(args.priority, 1.0 / args.priority))
    requests = poisson_requests(
        num=args.requests,
        rate_hz=(args.rate if args.rate > 0 else np.inf),
        prompt_lens=(args.prompt_len_min, args.prompt_len_max),
        max_new_tokens=args.new_tokens,
        vocab_size=cfg.vocab_size, seed=args.seed,
        priorities=prio_spec,
        slo_ttft_ms=args.slo_ttft_ms if args.slo_ttft_ms > 0 else None)
    if args.shared_prefix_len > 0:
        # demo workload for prefix sharing: every prompt opens with the
        # same system-prompt-style prefix, unique suffix after it
        common = np.random.default_rng(args.seed + 1).integers(
            0, cfg.vocab_size, (args.shared_prefix_len,), dtype=np.int32)
        for r in requests:
            r.prompt = np.concatenate([common, r.prompt])

    mesh = parse_mesh(args.mesh) if args.mesh else None
    run = RunConfig(arch=cfg, shape=SHAPES_BY_NAME["decode_32k"],
                    accel=policy)
    engine = SlotEngine(run, capacity=args.capacity, max_len=args.max_len,
                        chunk=args.chunk, gated=gated, paged=args.paged,
                        page_size=args.page_size,
                        num_pages=args.num_pages or None,
                        mesh=mesh, sharding=SERVE_POLICY if mesh else None,
                        temperature=args.temperature, top_k=args.top_k,
                        top_p=args.top_p, sample_seed=args.sample_seed,
                        prefix_sharing=args.prefix_sharing, spec=spec)
    # built once, in the engine's layout: on a mesh each device only ever
    # holds its own shard
    params = engine.init_params(jax.random.PRNGKey(0))
    # the engine's jitted entries carry their own shardings; shard_ctx
    # around the stream simulator covers any ad-hoc constrain/device_put
    # in the serve path (identity when no mesh is installed)
    mesh_ctx = (shd.shard_ctx(mesh, SERVE_POLICY) if mesh
                else contextlib.nullcontext())
    chaos_ref = None
    with mesh_ctx:
        if resilient:
            import copy

            from repro.serve.faults import FaultInjector
            from repro.serve.resilient import serve_resilient
            # fault-free reference stream first (same engine: traces stay
            # warm; fresh request copies: lifecycle fields are mutated)
            chaos_ref = copy.deepcopy(requests)
            serve(engine, params, chaos_ref, realtime=args.rate > 0)
            injector = None
            if fault_spec is not None:
                site, at = fault_spec
                injector = FaultInjector(schedule={site: [at]},
                                         seed=args.seed)
            report = serve_resilient(
                engine, params, requests, realtime=args.rate > 0,
                snapshot_every=args.snapshot_every,
                max_restarts=args.max_restarts,
                watchdog_ms=args.watchdog_ms or None, injector=injector)
        else:
            report = serve(engine, params, requests,
                           realtime=args.rate > 0, overload=overload)

    lat = report.latency_percentiles()
    ttft = report.ttft_percentiles()
    itl = report.itl_percentiles()
    mesh_desc = (f" mesh={args.mesh} ({jax.device_count()} devices)"
                 if mesh else "")
    print(f"arch={cfg.name} capacity={args.capacity} "
          f"requests={args.requests} rate={args.rate or 'inf'}/s "
          f"gated={gated} paged={args.paged}"
          + (" prefix_sharing" if args.prefix_sharing else "")
          + mesh_desc
          + (f" temperature={args.temperature} top_k={args.top_k} "
             f"top_p={args.top_p}"
             if args.temperature > 0 else ""))
    print(f"  traces: decode={engine.decode_traces} "
          f"prefill_buckets={engine.prefill_traces} "
          f"(decode chunks run: {engine.decode_calls})")
    print(f"  throughput: {report.decode_tokens} tokens in "
          f"{report.wall_s:.2f}s = {report.tokens_per_s:.1f} tok/s")
    print(f"  latency: p50={lat['p50']*1e3:.0f}ms p99={lat['p99']*1e3:.0f}ms "
          f"mean={lat['mean']*1e3:.0f}ms")
    print(f"  ttft: p50={ttft['p50']*1e3:.0f}ms p99={ttft['p99']*1e3:.0f}ms"
          f"  itl: p50={itl['p50']*1e3:.1f}ms max={itl['max']*1e3:.1f}ms")
    if overload is not None:
        print(f"  overload[{overload.mode}]: "
              f"{int(report.stats['preemptions'])} preemptions "
              f"({int(report.stats['swap_resumes'])} swap / "
              f"{int(report.stats['recompute_resumes'])} recompute "
              f"resumes), {int(report.stats['chunked_admissions'])} chunked"
              f" admissions, shed {int(report.stats['shed_ttft'])} ttft + "
              f"{int(report.stats['shed_deadline'])} deadline, "
              f"completion {report.completion_rate:.0%}")
    print(f"  concurrency: peak {int(report.stats['max_concurrency'])} "
          f"slots" + (f", peak pages {int(report.stats['peak_pages'])}"
                      f"/{engine.num_pages - 1}" if args.paged else ""))
    if spec is not None:
        print(f"  spec[k={spec.k} draft={args.draft}]: acceptance "
              f"{report.stats['spec_acceptance']:.1%} "
              f"({int(report.stats['spec_accepted'])}/"
              f"{int(report.stats['spec_proposed'])} drafts accepted), "
              f"{int(report.stats['realized_tokens'])} realized tokens "
              f"over {engine.decode_calls} chunks")
    if args.prefix_sharing:
        print(f"  sharing: {int(report.stats['shared_admissions'])} shared "
              f"admissions, {int(report.stats['shared_tokens'])} prompt "
              f"tokens served from resident pages "
              f"(prefill pushed {int(report.stats['prefill_tokens'])} "
              f"bucketed tokens)")
    if report.rejected:
        print(f"  rejected: {len(report.rejected)} request(s) "
              f"(first: {report.rejected[0].reject_reason})")
    print(f"  exit stats: exit_rate={report.stats['exit_rate']:.2%} "
          f"gated_fraction={report.stats['gated_fraction']:.2%}")
    if chaos_ref is not None:
        ref_toks = {r.rid: r.tokens for r in chaos_ref}
        mismatched = [r.rid for r in requests if r.tokens != ref_toks[r.rid]]
        spec = (f"site={fault_spec[0]} chunk={fault_spec[1]}"
                if fault_spec else "watchdog-only")
        rec = report.stats.get("recovery_s_max", 0.0)
        print(f"  chaos[{spec}]: restarts={int(report.stats['restarts'])} "
              f"faults={int(report.stats['faults_injected'])} "
              f"recovery_max={rec * 1e3:.1f}ms "
              f"completion={report.completion_rate:.0%} "
              f"identical_tokens={len(requests) - len(mismatched)}"
              f"/{len(requests)}")
        if injector is not None:
            # a scheduled fault that never fires makes the smoke vacuous —
            # the stream must be long enough to reach the chunk index
            assert injector.fired >= 1, (
                f"--inject-fault {spec} never fired: the stream made only "
                f"{injector.calls[fault_spec[0]]} {fault_spec[0]} calls — "
                "raise --new-tokens/--requests or lower chunk")
        assert report.completion_rate == 1.0, \
            f"chaos run shed requests: {[r.reject_reason for r in report.rejected]}"
        assert not mismatched, \
            f"chaos run diverged from fault-free reference: rids {mismatched}"
        print("  chaos: 100% completion, tokens identical to fault-free run")


if __name__ == "__main__":
    main()
