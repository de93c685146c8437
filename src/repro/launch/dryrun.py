import os
# Run before ANY other import. The dry-run is a CPU-only analysis: pinning
# the platform (inherited by the --all children) keeps it off any
# accelerator the host has, whose single owner is the process serving on it.
os.environ["JAX_PLATFORMS"] = "cpu"
# jax locks the device count at first init and the production meshes need
# 512 host placeholders. APPEND-if-absent — a user-set XLA_FLAGS (e.g. the
# serving tests' forced 4-device host) must never be clobbered by merely
# importing this module.
if "--xla_force_host_platform_device_count" not in os.environ.get(
        "XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=512").strip()

"""Multi-pod dry-run: prove every (arch x shape x mesh) cell lowers,
compiles, fits, and derive its roofline terms — with zero real allocation.

The guard above runs before jax import. Smoke tests / benches never import
this module and keep seeing 1 device.

Per cell this driver produces:
  * full-module ``jit(step).lower(...).compile()`` — THE deliverable gate:
    sharding mismatches, unsupported collectives, or compile-time OOM fail
    here. memory_analysis() proves the per-chip footprint fits 16 GiB HBM.
  * component costing — XLA's cost_analysis counts while-loop (lax.scan)
    bodies ONCE (verified empirically), so per-layer costs are lowered as
    standalone components (superblock fwd+vjp, embed/head/loss, optimizer
    update) and scaled by their static trip counts; sequential mixer inner
    loops (blockwise attention, SSM chunk scans, sLSTM) get analytic
    corrections (launch/roofline.py). The full-module cost_analysis is also
    reported raw for reference.
  * collective bytes parsed from the post-SPMD optimized HLO of each
    component (scaled by trip count) and of the full module (raw).

Usage:
  python -m repro.launch.dryrun --arch yi-9b --shape train_4k --mesh single
  python -m repro.launch.dryrun --all [--mesh both] [--jobs N]
Results land in experiments/dryrun/<arch>__<shape>__<mesh>.json.
"""
import argparse
import functools
import json
import subprocess
import sys
import time
import traceback
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import (AccelConfig, ArchConfig, RunConfig,
                                SHAPES_BY_NAME, ShapeConfig, ShardingPolicy,
                                applicable_shapes, get_arch, list_archs)
from repro.dist import sharding as shd
from repro.launch import roofline as rl
from repro.launch.mesh import make_production_mesh
from repro.launch.specs import input_specs, token_struct
from repro.models import lm
from repro.optim.adamw import adamw_update, init_adamw

SDS = jax.ShapeDtypeStruct
OUT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                       "experiments", "dryrun")

# Dry-run op backends: pure-XLA blockwise attention + parallel assoc scan
# (Pallas kernels are validated separately in interpret mode; on real TPU
# hardware they swap in via the same AccelConfig).
DRYRUN_ACCEL = AccelConfig(
    backends={"attention": "blockwise", "ssm_scan": "assoc"})

# per-arch training microbatch counts (gradient accumulation) sized so the
# per-chip activation footprint fits; tuned from memory_analysis.
MICROBATCH = {
    "mistral-large-123b": 16,
    "chameleon-34b": 8,
    "jamba-v0.1-52b": 8,
    "qwen1.5-32b": 8,
    "yi-9b": 4,
    "musicgen-medium": 2,
    "chatglm3-6b": 4,
    "deepseek-v2-lite-16b": 4,
    "qwen3-moe-30b-a3b": 4,
    "xlstm-350m": 2,
}


def build_run(arch: ArchConfig, shape: ShapeConfig,
              policy: Optional[ShardingPolicy] = None,
              remat: str = "full", multi_pod: bool = False,
              loss_chunk: int = 0, weight_quant: bool = False) -> RunConfig:
    if policy is None:
        policy = ShardingPolicy(sequence_parallel=(shape.kind == "train"))
    nmb = MICROBATCH.get(arch.name, 2) if shape.kind == "train" else 1
    if multi_pod:
        # keep B/nmb divisible by the 32-way (pod, data) batch sharding
        nmb = min(nmb, max(shape.global_batch // 32, 1))
    return RunConfig(arch=arch, shape=shape, accel=DRYRUN_ACCEL,
                     sharding=policy, remat=remat, microbatch=nmb,
                     loss_chunk=loss_chunk, weight_quant=weight_quant)


# ---------------------------------------------------------------------------
# Shardings for step-function arguments
# ---------------------------------------------------------------------------


def _batch_shardings(ctx, arch, shape):
    mesh = ctx.mesh
    ba = ctx.data_axes if shape.global_batch >= ctx.size(ctx.data_axes) else None
    if arch.frontend_stub:
        tok = NamedSharding(mesh, P(ba, None, None))
    else:
        tok = NamedSharding(mesh, P(ba, None))
    lab = NamedSharding(mesh, P(ba, None))
    return tok, lab


# ---------------------------------------------------------------------------
# Cell lowering
# ---------------------------------------------------------------------------


def _analyze(compiled) -> Dict[str, Any]:
    cost = compiled.cost_analysis() or {}
    if isinstance(cost, (list, tuple)):     # older jax: one dict per device
        cost = cost[0] if cost else {}
    cost = dict(cost)
    mem = compiled.memory_analysis()
    coll = rl.collective_bytes(compiled.as_text())
    return {
        "flops": float(cost.get("flops", 0.0)),
        "bytes": float(cost.get("bytes accessed", 0.0)),
        "collectives": coll,
        "memory": None if mem is None else {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
        },
    }


def _lower_train(run: RunConfig, ctx) -> Dict[str, Any]:
    from repro.train.train_step import make_train_step
    cfg, shape = run.arch, run.shape
    init_fn, step_fn = make_train_step(run)
    state_struct = jax.eval_shape(
        functools.partial(init_fn, jax.random.PRNGKey(0)))
    state_sh = shd.param_shardings(state_struct)
    tok_sh, lab_sh = _batch_shardings(ctx, cfg, shape)
    batch_struct = input_specs(cfg, shape)
    batch_sh = {"inputs": tok_sh, "labels": lab_sh}
    lowered = jax.jit(step_fn, in_shardings=(state_sh, batch_sh),
                      out_shardings=(state_sh, None),
                      donate_argnums=(0,)).lower(state_struct, batch_struct)
    compiled = lowered.compile()
    return _analyze(compiled)


def _params_struct(run: RunConfig):
    cfg = run.arch

    def build():
        p = lm.init_lm(jax.random.PRNGKey(0), cfg)
        if run.weight_quant:
            from repro.serve.quantize import quantize_weights_int8
            p = quantize_weights_int8(p)
        return p

    return jax.eval_shape(build)


def _lower_serve(run: RunConfig, ctx, prefill: bool) -> Dict[str, Any]:
    from repro.serve.engine import make_prefill, make_serve_step
    cfg, shape = run.arch, run.shape
    specs = input_specs(cfg, shape)
    params_struct = _params_struct(run)
    params_sh = shd.param_shardings(params_struct)
    cache_sh = shd.cache_shardings(specs["cache"], shape.global_batch)
    ba = (ctx.data_axes
          if shape.global_batch >= ctx.size(ctx.data_axes) else None)
    tok_sh = NamedSharding(ctx.mesh, P(ba, None, None) if cfg.frontend_stub
                           else P(ba, None))
    fn = make_prefill(run) if prefill else make_serve_step(run)
    lowered = jax.jit(fn, in_shardings=(params_sh, cache_sh, tok_sh),
                      donate_argnums=(1,)).lower(
        params_struct, specs["cache"], specs["tokens"])
    compiled = lowered.compile()
    return _analyze(compiled)


# ---------------------------------------------------------------------------
# Component costing (accurate FLOPs/bytes/collectives; see module docstring)
# ---------------------------------------------------------------------------


def _slot_structs(cfg: ArchConfig):
    """Unstacked (single-superblock) slot param structs."""
    params_struct = jax.eval_shape(
        functools.partial(lm.init_lm, jax.random.PRNGKey(0), cfg))
    slots = params_struct["slots"]
    one = jax.tree_util.tree_map(
        lambda s: SDS(s.shape[1:], s.dtype), slots)
    return params_struct, one


def _x_struct(cfg, batch, seq):
    return SDS((batch, seq, cfg.d_model), jnp.dtype(cfg.dtype))


def _superblock_fwd(cfg, policy, mode="train"):
    def f(slot_params, x):
        for j, spec in enumerate(cfg.block_pattern):
            x, _, _ = lm._apply_layer(slot_params[j], x, spec, cfg, policy,
                                      mode="train")
        return x
    return f


def _component(fn, in_shardings, *structs, out_shardings=None,
               donate_argnums=()) -> Dict[str, Any]:
    kw = {"in_shardings": in_shardings}
    if out_shardings is not None:
        kw["out_shardings"] = out_shardings
    if donate_argnums:
        kw["donate_argnums"] = donate_argnums
    lowered = jax.jit(fn, **kw).lower(*structs)
    compiled = lowered.compile()
    return _analyze(compiled)


def component_costs(run: RunConfig, ctx) -> Dict[str, Any]:
    cfg, shape = run.arch, run.shape
    policy = run.accel
    kind = shape.kind
    n_sb = cfg.num_superblocks
    b, t = shape.global_batch, shape.seq_len
    comps: Dict[str, Dict[str, Any]] = {}
    mults: Dict[str, float] = {}

    if run.weight_quant:
        params_struct = _params_struct(run)
        slot_struct = jax.tree_util.tree_map(
            lambda s: SDS(s.shape[1:], s.dtype), params_struct["slots"])
    else:
        params_struct, slot_struct = _slot_structs(cfg)
    slot_sh = shd.param_shardings(slot_struct)

    if kind == "train":
        nmb = run.microbatch
        bmb = b // nmb
        x_s = _x_struct(cfg, bmb, t)
        x_sh = NamedSharding(ctx.mesh, shd.spec_for(
            x_s.shape, "batch", "sp" if run.sharding.sequence_parallel else None,
            None))
        fwd = _superblock_fwd(cfg, policy)

        def sb_vjp(slot_params, x, ct):
            y, pull = jax.vjp(fwd, slot_params, x)
            return pull(ct)

        comps["superblock_fwd"] = _component(fwd, (slot_sh, x_sh),
                                             slot_struct, x_s,
                                             out_shardings=x_sh)
        # grads must come out SHARDED like the params (reduce-scatter, not
        # all-reduce-to-replicated) — exactly as the real train step's
        # optimizer consumes them
        comps["superblock_vjp"] = _component(
            sb_vjp, (slot_sh, x_sh, x_sh), slot_struct, x_s, x_s,
            out_shardings=(slot_sh, x_sh))
        # remat recompute: one extra forward per layer for remat=full
        extra_fwd = {"full": 1.0, "dots": 0.5, "nothing": 0.0}[run.remat]
        mults["superblock_fwd"] = n_sb * nmb * extra_fwd
        mults["superblock_vjp"] = n_sb * nmb

        # embed + head + loss (+ exit heads)
        head_keys = ["embed", "final_norm", "unembed"] + (
            ["exits"] if cfg.early_exit is not None else [])
        hp_struct = {k: params_struct[k] for k in head_keys}
        hp_sh = shd.param_shardings(hp_struct)
        tok_s = token_struct(cfg, bmb, t)
        lab_s = SDS((bmb, t), jnp.int32)
        tok_sh, lab_sh = _batch_shardings(ctx, cfg, shape)

        def head_loss(hp, tokens, labels, ct_unused):
            from repro.core.early_exit import cross_entropy
            x = lm._embed(hp, tokens, cfg)

            def f(hp_, x_):
                logits = lm._head(hp_, x_, cfg, policy)
                loss = cross_entropy(logits, labels)
                if cfg.early_exit is not None:
                    for i in range(len(cfg.early_exit.exit_layers)):
                        el = lm._exit_logits(hp_, x_, i, cfg, policy)
                        loss = loss + cfg.early_exit.loss_weight * \
                            cross_entropy(el, labels)
                return loss
            loss, pull = jax.vjp(f, hp, x)
            return pull(jnp.ones_like(loss))

        comps["embed_head_loss"] = _component(
            head_loss, (hp_sh, tok_sh, lab_sh, None),
            hp_struct, tok_s, lab_s, SDS((), jnp.float32),
            out_shardings=(hp_sh, x_sh))
        mults["embed_head_loss"] = nmb

        # prefix layers (explicit, unscanned)
        if cfg.first_k_dense:
            pl_struct = params_struct["prefix"][0]
            pl_sh = shd.param_shardings(pl_struct)

            def pfx_vjp(p, x, ct):
                def f(p_, x_):
                    y, _, _ = lm._apply_layer(p_, x_, cfg.layer_spec(0), cfg,
                                              policy, mode="train")
                    return y
                y, pull = jax.vjp(f, p, x)
                return pull(ct)

            comps["prefix_vjp"] = _component(
                pfx_vjp, (pl_sh, x_sh, x_sh), pl_struct, x_s, x_s)
            mults["prefix_vjp"] = cfg.first_k_dense * nmb

        # optimizer update over the full tree
        opt_struct = jax.eval_shape(lambda p: init_adamw(p, True),
                                    params_struct)
        opt_sh = shd.param_shardings(opt_struct)
        p_sh = shd.param_shardings(params_struct)

        def opt_step(params, grads, opt):
            p, o, _ = adamw_update(params, grads, opt, lr=1e-4)
            return p, o

        grads_struct = jax.tree_util.tree_map(
            lambda s: SDS(s.shape, jnp.float32), params_struct)
        g_sh = shd.param_shardings(grads_struct)
        comps["optimizer"] = _component(
            opt_step, (p_sh, g_sh, opt_sh), params_struct, grads_struct,
            opt_struct)
        mults["optimizer"] = 1.0

    else:
        # serve: superblock decode/prefill step over cache slices
        specs = input_specs(cfg, shape)
        cache_struct = specs["cache"]
        slot_state_struct = jax.tree_util.tree_map(
            lambda s: SDS(s.shape[1:], s.dtype), cache_struct.slots)
        slot_state_sh = shd.cache_shardings(slot_state_struct, b)
        seq = 1 if kind == "decode" else t
        x_s = _x_struct(cfg, b, seq)
        x_sh = NamedSharding(ctx.mesh, shd.spec_for(
            x_s.shape, "batch" if b >= ctx.size(ctx.data_axes) else None,
            None, None))
        pos_s = SDS((b,), jnp.int32)
        pos_sh = NamedSharding(ctx.mesh, P(
            ctx.data_axes if b >= ctx.size(ctx.data_axes) else None))
        mode = "decode" if kind == "decode" else "prefill"

        def sb_step(slot_params, x, states, pos):
            new_states = []
            for j, spec in enumerate(cfg.block_pattern):
                x, _, ns = lm._apply_layer(slot_params[j], x, spec, cfg,
                                           policy, state=states[j], mode=mode,
                                           cache_pos=pos)
                new_states.append(ns)
            return x, tuple(new_states)

        # donate the cache states: the real serve step updates them in
        # place (donate_argnums in _lower_serve); without donation the
        # .at[].set would be measured as a full cache copy per layer
        comps["superblock_step"] = _component(
            sb_step, (slot_sh, x_sh, slot_state_sh, pos_sh),
            slot_struct, x_s, slot_state_struct, pos_s,
            donate_argnums=(2,))
        mults["superblock_step"] = n_sb

        head_keys = ["embed", "final_norm", "unembed"] + (
            ["exits"] if cfg.early_exit is not None else [])
        hp_struct = {k: params_struct[k] for k in head_keys}
        hp_sh = shd.param_shardings(hp_struct)

        def head_step(hp, x):
            logits = lm._head(hp, x, cfg, policy)[:, -1]
            if cfg.early_exit is not None and kind == "decode":
                from repro.core.early_exit import merge_exit_logits
                exit_lg = tuple(
                    lm._exit_logits(hp, x, i, cfg, policy)[:, -1]
                    for i in range(len(cfg.early_exit.exit_layers)))
                logits, _, _ = merge_exit_logits(logits, exit_lg,
                                                 cfg.early_exit)
            return jnp.argmax(logits, axis=-1)

        comps["head"] = _component(head_step, (hp_sh, x_sh), hp_struct, x_s)
        mults["head"] = 1.0

        if cfg.first_k_dense:
            pl_struct = params_struct["prefix"][0]
            pl_sh = shd.param_shardings(pl_struct)
            st_struct = jax.tree_util.tree_map(lambda s: s, cache_struct.prefix[0])
            st_sh = shd.cache_shardings(st_struct, b)

            def pfx_step(p, x, st, pos):
                y, _, ns = lm._apply_layer(p, x, cfg.layer_spec(0), cfg,
                                           policy, state=st, mode=mode,
                                           cache_pos=pos)
                return y, ns

            comps["prefix_step"] = _component(
                pfx_step, (pl_sh, x_sh, st_sh, pos_sh),
                pl_struct, x_s, st_struct, pos_s)
            mults["prefix_step"] = cfg.first_k_dense

    # aggregate
    total = {"flops": 0.0, "bytes": 0.0, "coll_bytes": 0.0}
    for name, c in comps.items():
        mult = mults[name]
        total["flops"] += c["flops"] * mult
        total["bytes"] += c["bytes"] * mult
        total["coll_bytes"] += c["collectives"].get("total", 0.0) * mult
    corr = rl.loop_corrections(cfg, shape, chips=int(ctx.mesh.devices.size))
    total["flops"] += corr["flops"]
    total["bytes"] += corr["bytes"]
    return {"components": {k: {"flops": v["flops"], "bytes": v["bytes"],
                               "coll": v["collectives"].get("total", 0.0),
                               "coll_mix": v["collectives"],
                               "mult": mults[k]} for k, v in comps.items()},
            "corrections": corr, "total": total}


# ---------------------------------------------------------------------------
# Cell driver
# ---------------------------------------------------------------------------


def run_cell(arch_name: str, shape_name: str, mesh_name: str,
             with_components: bool = True, remat: str = "full",
             policy: Optional[ShardingPolicy] = None,
             loss_chunk: int = 0, weight_quant: bool = False) -> Dict[str, Any]:
    arch = get_arch(arch_name)
    shape = SHAPES_BY_NAME[shape_name]
    multi = mesh_name == "multi"
    mesh = make_production_mesh(multi_pod=multi)
    run = build_run(arch, shape, remat=remat, policy=policy, multi_pod=multi,
                    loss_chunk=loss_chunk, weight_quant=weight_quant)
    t0 = time.time()
    result: Dict[str, Any] = {
        "arch": arch_name, "shape": shape_name, "mesh": mesh_name,
        "chips": int(mesh.devices.size), "remat": remat,
        "microbatch": run.microbatch,
    }
    with mesh, shd.shard_ctx(mesh, run.sharding) as ctx:
        if shape.kind == "train":
            full = _lower_train(run, ctx)
        else:
            full = _lower_serve(run, ctx, prefill=(shape.kind == "prefill"))
        result["full_module"] = full
        result["compile_s"] = time.time() - t0
        if with_components:
            comp = component_costs(run, ctx)
            result["component_costs"] = comp
            terms = rl.derive_terms(
                arch, shape, mesh_name, int(mesh.devices.size),
                {"flops": comp["total"]["flops"],
                 "bytes accessed": comp["total"]["bytes"]},
                {"total": comp["total"]["coll_bytes"]})
            result["roofline"] = terms.to_dict()
    return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--mesh", default="single", choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--jobs", type=int, default=2)
    ap.add_argument("--no-components", action="store_true")
    ap.add_argument("--remat", default="full")
    ap.add_argument("--out-dir", default=None)
    ap.add_argument("--policy", default=None,
                    help="comma-separated ShardingPolicy overrides for perf "
                         "iteration, e.g. dp_over_model=1,fsdp=0,"
                         "sequence_parallel=1")
    ap.add_argument("--loss-chunk", type=int, default=0,
                    help="chunked head+CE (beyond-paper memory opt)")
    ap.add_argument("--wq8", action="store_true",
                    help="serve-time int8 weight quantization "
                         "(beyond-paper memory opt for decode)")
    ap.add_argument("--tag", default=None,
                    help="suffix for the output filename (perf variants)")
    args = ap.parse_args()
    out_dir = args.out_dir or os.path.abspath(OUT_DIR)
    os.makedirs(out_dir, exist_ok=True)

    if args.all:
        cells = []
        for a in list_archs():
            for s in applicable_shapes(get_arch(a)):
                for m in (("single", "multi") if args.mesh == "both"
                          else (args.mesh,)):
                    cells.append((a, s.name, m))
        procs = []
        for (a, s, m) in cells:
            out_file = os.path.join(out_dir, f"{a}__{s}__{m}.json")
            if os.path.exists(out_file):
                print(f"skip (exists): {out_file}")
                continue
            cmd = [sys.executable, "-m", "repro.launch.dryrun",
                   "--arch", a, "--shape", s, "--mesh", m,
                   "--out-dir", out_dir]
            if args.no_components:
                cmd.append("--no-components")
            while len([p for p in procs if p[0].poll() is None]) >= args.jobs:
                time.sleep(2)
            print("launch:", a, s, m)
            procs.append((subprocess.Popen(cmd), (a, s, m)))
        failed = []
        for p, cell in procs:
            p.wait()
            print("done:", cell, "rc=", p.returncode)
            if p.returncode != 0:
                failed.append(cell)
        if failed:
            print(f"FAILED {len(failed)}/{len(procs)} cells:",
                  file=sys.stderr)
            for cell in failed:
                print(f"  {'__'.join(cell)} "
                      f"(see {os.path.join(out_dir, '__'.join(cell))}.json)",
                      file=sys.stderr)
            sys.exit(1)
        return

    assert args.arch and args.shape
    policy = None
    if args.policy:
        shape_kind = SHAPES_BY_NAME[args.shape].kind
        kw = {"sequence_parallel": shape_kind == "train"}
        for kv in args.policy.split(","):
            k, v = kv.split("=")
            kw[k] = bool(int(v))
        policy = ShardingPolicy(**kw)
    meshes = ("single", "multi") if args.mesh == "both" else (args.mesh,)
    for m in meshes:
        suffix = f"__{args.tag}" if args.tag else ""
        out_file = os.path.join(out_dir,
                                f"{args.arch}__{args.shape}__{m}{suffix}.json")
        try:
            res = run_cell(args.arch, args.shape, m,
                           with_components=not args.no_components,
                           remat=args.remat, policy=policy,
                           loss_chunk=args.loss_chunk,
                           weight_quant=args.wq8)
            res["status"] = "ok"
            res["policy"] = args.policy
            res["loss_chunk"] = args.loss_chunk
            res["wq8"] = args.wq8
            res["tag"] = args.tag
        except (RuntimeError, ValueError, TypeError, KeyError,
                AssertionError, NotImplementedError, MemoryError) as e:
            # the failure classes a cell actually produces (XlaRuntimeError
            # is a RuntimeError: compile/OOM; Value/Type/Assertion: config
            # and sharding gates) — anything else is a driver bug and must
            # crash loudly. The failing cell identity goes through the
            # FaultEvent path so post-mortems see it alongside serve-time
            # faults, and into the JSON report.
            from repro.dist.fault import FaultEvent
            ev = FaultEvent(kind="dryrun-cell", step=0,
                            info=f"{args.arch}__{args.shape}__{m}: {e!r}")
            res = {"arch": args.arch, "shape": args.shape, "mesh": m,
                   "status": "error", "error": repr(e),
                   "fault_events": [{"kind": ev.kind, "step": ev.step,
                                     "info": ev.info, "t": ev.t}],
                   "traceback": traceback.format_exc()}
        with open(out_file, "w") as f:
            json.dump(res, f, indent=2, default=float)
        print(json.dumps({k: res.get(k) for k in
                          ("arch", "shape", "mesh", "status", "compile_s")},
                         indent=None))
        if res["status"] == "ok" and "roofline" in res:
            print(json.dumps(res["roofline"], indent=2, default=float))
        if res["status"] != "ok":
            print(res["traceback"], file=sys.stderr)
            sys.exit(1)


if __name__ == "__main__":
    main()
