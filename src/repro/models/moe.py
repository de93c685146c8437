"""Token-choice top-k Mixture of Experts: capacity-bounded scatter dispatch
for prefill/train, DROPLESS per-token dispatch for serve decode.

Two dispatch paths share one router core (``_route``) and one parameter
tree:

* **Capacity path** (``apply_moe`` — prefill/train): per group (= one
  sequence) each token's position-in-expert comes from a cumsum-free
  sort-based ranking, tokens are *scattered* into a [G, E, C, d] buffer and
  *gathered* back weighted by the router gate. No [tokens, E, C] dispatch
  einsum — the classic GSPMD one-hot formulation costs more FLOPs than the
  experts themselves at these expert counts; scatter keeps MODEL_FLOPS /
  HLO_FLOPS honest (§Roofline). Tokens over capacity are dropped (standard
  dropping MoE; the router aux loss keeps load balanced).
* **Dropless path** (``apply_moe_decode`` — one-token decode): each token's
  top-k expert GEMMs dispatch through the ``moe_decode`` XAIF op
  (``kernels/moe_decode/``). There is NO capacity constant and NO drops, so
  a slot's output depends only on its own hidden state — never on which
  other requests are batched beside it. This is what lets the serve engine
  extend its token-identity-under-backfill guarantee to MoE archs
  (serve/engine.py; the capacity path shared one expert-capacity group
  across the decode batch, so co-batch composition leaked into numerics).

Both paths take a ``valid`` mask so the serve engine can exclude
dead/retired slots from routing: a freed slot's stale hidden state no
longer consumes expert capacity or inflates the aux-loss counts, and a
live slot's output is provably independent of dead-slot contents.

Experts compute as stacked SwiGLU GEMMs [E, d, h] — sharding E over the
"model" mesh axis (the ``ep`` logical axis) gives expert parallelism.
DeepSeek-style shared experts run densely on every token and are added in.
"""
from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig, MoEConfig
from repro.core import xaif
from repro.kernels._tiling import sorted_run_ranks
from repro.models.layers import dense_init, init_mlp, apply_mlp


def init_moe(key, cfg: ArchConfig, dtype) -> Dict:
    m = cfg.moe
    d = cfg.d_model
    ks = jax.random.split(key, 5)
    p = {
        "router": dense_init(ks[0], d, m.num_experts, jnp.float32),
        "w_gate_e": _expert_init(ks[1], m.num_experts, d, m.d_expert, dtype),
        "w_up_e": _expert_init(ks[2], m.num_experts, d, m.d_expert, dtype),
        "w_down_e": _expert_init(ks[3], m.num_experts, m.d_expert, d, dtype),
    }
    if m.num_shared_experts > 0:
        d_sh = m.d_shared_expert or m.num_shared_experts * m.d_expert
        p["shared"] = init_mlp(ks[4], d, d_sh, dtype)
    return p


def _expert_init(key, e, d_in, d_out, dtype):
    return (jax.random.normal(key, (e, d_in, d_out), jnp.float32)
            * (d_in ** -0.5)).astype(dtype)


# ---------------------------------------------------------------------------
# Shared router / ranking core
# ---------------------------------------------------------------------------


def _route(router: jax.Array, xg: jax.Array, m: MoEConfig,
           row_stable: bool = False):
    """Router core shared by every dispatch path. xg [G, S, d] ->
    (probs [G, S, E], gate_vals [G, S, K], expert_idx [G, S, K]).

    fp32 logits -> softmax -> top-k, gates renormalized over the selected k.

    ``row_stable`` (the decode path) computes the logits as an explicit
    multiply+reduce instead of a dot: XLA:CPU's dot emitter picks its loop
    tiling from the ROW COUNT, so a matmul's per-row bits can change with
    the co-batch size — a single ulp in a logit can flip top-k and send a
    token to different EXPERTS depending on who is batched beside it. The
    reduce formulation vectorizes identically per row at any batch size,
    which is what the serve engine's composition-independence rests on.
    Prefill/train keep the einsum (unchanged numerics)."""
    if row_stable:
        logits = jnp.sum(xg.astype(jnp.float32)[..., None]
                         * router.astype(jnp.float32)[None, None], axis=-2)
    else:
        logits = jnp.einsum("gsd,de->gse", xg.astype(jnp.float32),
                            router.astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)                       # [G, S, E]
    gate_vals, expert_idx = jax.lax.top_k(probs, m.top_k)          # [G, S, K]
    gate_vals = gate_vals / jnp.maximum(
        jnp.sum(gate_vals, axis=-1, keepdims=True), 1e-9)          # renorm
    return probs, gate_vals, expert_idx


def _ranked_positions(expert_idx: jax.Array, m: MoEConfig,
                      vg: Optional[jax.Array] = None) -> jax.Array:
    """Token-major position-in-expert of each (token, k) assignment.

    (§Perf iteration Q1: the textbook k x one-hot-cumsum materializes
    k x [G, S, E] int32 tensors — 67 GB/chip/layer at qwen3's E=128 —
    and dominated the memory roofline term. Sorting the flattened
    [G, S*K] assignment and ranking within equal-expert runs is
    O(S*K log) and bytes-free by comparison. Priority becomes
    token-major instead of slot-major — an equally valid deterministic
    dropping order.)

    ``vg`` [G, S] bool: INVALID tokens are pushed into a sentinel segment
    past every real expert before sorting, so they never consume a real
    expert's capacity and the valid tokens' ranks are independent of their
    (stale) contents. Returns pos [G, S, K].
    """
    g, s, k = expert_idx.shape
    sk = s * k
    flat_e = expert_idx.reshape(g, sk)
    flat_sort = flat_e
    if vg is not None:
        vflat = jnp.repeat(vg, k, axis=1)                  # [G, S*K]
        flat_sort = jnp.where(vflat, flat_e, m.num_experts)
    order = jnp.argsort(flat_sort, axis=1, stable=True)    # group by expert
    sorted_e = jnp.take_along_axis(flat_sort, order, axis=1)
    pos_sorted = sorted_run_ranks(sorted_e)                 # rank in expert
    gidx = jnp.arange(g)[:, None]
    pos_flat = jnp.zeros_like(flat_e).at[gidx, order].set(pos_sorted)
    return pos_flat.reshape(g, s, k)


def _group_capacity(s: int, m: MoEConfig) -> int:
    return max(1, math.ceil(s * m.top_k / m.num_experts * m.capacity_factor))


# ---------------------------------------------------------------------------
# Capacity-bounded scatter dispatch (prefill / train)
# ---------------------------------------------------------------------------


def apply_moe(params, x: jax.Array, cfg: ArchConfig, policy: xaif.PolicyLike,
              groups: Optional[int] = None,
              valid: Optional[jax.Array] = None) -> Tuple[jax.Array, jax.Array]:
    """x [B, T, d] -> (y [B, T, d], aux_loss scalar).

    ``groups``: number of independent capacity groups; defaults to B (one
    per sequence). The legacy grouped decode path passes 1 so the whole
    batch shares capacity (superseded at serve decode by
    :func:`apply_moe_decode` unless ``MoEConfig.dropless_decode`` is off).

    ``valid`` [B, T] bool: tokens marked False (dead/retired serve slots)
    are masked OUT of routing — they consume no expert capacity, contribute
    nothing to the aux-loss counts/density, and their routed output is
    zeroed — so a live token's output never depends on a dead slot's stale
    hidden state. ``None`` (the default) keeps the exact legacy graph.
    """
    m = cfg.moe
    b, t, d = x.shape
    g = b if groups is None else groups
    s = (b * t) // g
    xg = x.reshape(g, s, d)
    vg = None if valid is None else valid.reshape(g, s)

    probs, gate_vals, expert_idx = _route(params["router"], xg, m)
    capacity = _group_capacity(s, m)
    pos = _ranked_positions(expert_idx, m, vg)
    keeps = [pos[:, :, j] < capacity for j in range(m.top_k)]
    if vg is not None:
        keeps = [kj & vg for kj in keeps]
    positions = [jnp.minimum(pos[:, :, j], capacity - 1)
                 for j in range(m.top_k)]
    gidx = jnp.arange(g)[:, None]

    # ---- dispatch: scatter tokens into [G, E, C, d] ------------------------
    buf = jnp.zeros((g, m.num_experts, capacity, d), x.dtype)
    for j in range(m.top_k):
        upd = jnp.where(keeps[j][..., None], xg, 0).astype(x.dtype)
        buf = buf.at[gidx, expert_idx[:, :, j], positions[j]].add(upd)

    # ---- expert SwiGLU (stacked GEMMs; E shards over "model") -------------
    gact = jnp.einsum("gecd,edh->gech", buf, params["w_gate_e"])
    up = jnp.einsum("gecd,edh->gech", buf, params["w_up_e"])
    hidden = (jax.nn.silu(gact.astype(jnp.float32)) * up.astype(jnp.float32)
              ).astype(x.dtype)
    out_buf = jnp.einsum("gech,ehd->gecd", hidden, params["w_down_e"])

    # ---- combine: gather back with gate weighting --------------------------
    combine = [gate_vals[:, :, j] * keeps[j].astype(jnp.float32)
               for j in range(m.top_k)]
    if m.renorm_kept:
        # redistribute a dropped expert's share over the kept ones (the
        # default renorm above happens over the full top-k BEFORE dropping,
        # so without this a dropped expert's share is silently lost)
        tot = jnp.maximum(sum(combine), 1e-9)
        combine = [c / tot for c in combine]
    y = jnp.zeros_like(xg, dtype=jnp.float32)
    for j in range(m.top_k):
        tok = out_buf[gidx, expert_idx[:, :, j], positions[j]]     # [G, S, d]
        y = y + combine[j][..., None] * tok.astype(jnp.float32)

    # ---- shared experts (always-on) ----------------------------------------
    if "shared" in params:
        y = y + apply_mlp(params["shared"], xg, policy).astype(jnp.float32)

    # ---- load-balance aux loss (Switch) ------------------------------------
    # (§Perf Q1: scatter-add counts instead of a [G, S, K, E] fp32 one-hot;
    # masked tokens carry zero weight so stale slots can't skew the balance)
    flat_e = expert_idx.reshape(g, s * m.top_k)
    if vg is None:
        counts = jnp.zeros((m.num_experts,), jnp.float32).at[
            flat_e.reshape(-1)].add(1.0)
        density = counts / (g * s)                                 # [E]
        density_proxy = jnp.mean(probs, axis=(0, 1))               # [E]
    else:
        w = jnp.repeat(vg, m.top_k, axis=1).astype(jnp.float32)
        counts = jnp.zeros((m.num_experts,), jnp.float32).at[
            flat_e.reshape(-1)].add(w.reshape(-1))
        n = jnp.maximum(jnp.sum(vg.astype(jnp.float32)), 1.0)
        density = counts / n
        density_proxy = jnp.sum(
            probs * vg[..., None].astype(jnp.float32), axis=(0, 1)) / n
    aux = m.num_experts * jnp.sum(density / m.top_k * density_proxy)

    return y.reshape(b, t, d).astype(x.dtype), aux * m.router_aux_weight


# ---------------------------------------------------------------------------
# Dropless per-token dispatch (serve decode)
# ---------------------------------------------------------------------------


def apply_moe_decode(params, x: jax.Array, cfg: ArchConfig,
                     policy: xaif.PolicyLike,
                     valid: Optional[jax.Array] = None
                     ) -> Tuple[jax.Array, jax.Array]:
    """Dropless one-token decode. x [B, 1, d] -> (y [B, 1, d], aux).

    Routes each token independently and dispatches its top-k expert GEMMs
    through the ``moe_decode`` XAIF op: per-token weight gather in the ref
    backend (bitwise-deterministic per slot regardless of co-batch — the
    serve engine's composition-independence contract rests on it), sorted
    ragged dispatch in the pallas backend. No capacity constant, no drops.

    ``valid`` [B] bool masks dead/retired slots out of routing: their gates
    are zeroed (no expert compute is attributed to them) and they are
    excluded from the aux-loss counts — masking can never change a live
    slot's output, because no state is shared across tokens here.
    """
    m = cfg.moe
    b, t, d = x.shape
    assert t == 1, "apply_moe_decode is the one-token decode path"
    probs, gate_vals, expert_idx = _route(params["router"], x, m,
                                          row_stable=True)
    probs, gate_vals, expert_idx = probs[:, 0], gate_vals[:, 0], expert_idx[:, 0]
    if valid is not None:
        gate_vals = gate_vals * valid.astype(jnp.float32)[:, None]
    y = xaif.call("moe_decode", policy, x[:, 0], expert_idx, gate_vals,
                  params["w_gate_e"], params["w_up_e"], params["w_down_e"])
    y = y[:, None, :]                                              # [B, 1, d]

    if "shared" in params:
        # the shared expert rides the same op as one always-selected expert
        # with gate 1: as a plain dense MLP its dot's per-row bits changed
        # with the co-batch size on XLA:CPU (up to 5e-7), which broke the
        # composition-independence contract above
        sp = params["shared"]
        y = y + xaif.call(
            "moe_decode", policy, x[:, 0], jnp.zeros((b, 1), jnp.int32),
            jnp.ones((b, 1), jnp.float32), sp["w_gate"][None],
            sp["w_up"][None], sp["w_down"][None])[:, None, :]

    w = (jnp.ones((b,), jnp.float32) if valid is None
         else valid.astype(jnp.float32))
    counts = jnp.zeros((m.num_experts,), jnp.float32).at[
        expert_idx.reshape(-1)].add(jnp.repeat(w, m.top_k))
    n = jnp.maximum(jnp.sum(w), 1.0)
    density = counts / n
    density_proxy = jnp.sum(probs * w[:, None], axis=0) / n
    aux = m.num_experts * jnp.sum(density / m.top_k * density_proxy)
    return y.astype(x.dtype), aux * m.router_aux_weight


def capacity_drop_count(params, x: jax.Array, cfg: ArchConfig,
                        groups: Optional[int] = None,
                        valid: Optional[jax.Array] = None) -> jax.Array:
    """(token, expert) assignments the capacity path would DROP for ``x``.

    Pure routing math (no expert FLOPs) — the diagnostic behind the serving
    benchmark's drop accounting: the grouped decode path reports real drops
    under load, the dropless decode path is 0 by construction.
    """
    m = cfg.moe
    b, t, d = x.shape
    g = b if groups is None else groups
    s = (b * t) // g
    xg = x.reshape(g, s, d)
    vg = None if valid is None else valid.reshape(g, s)
    _, _, expert_idx = _route(params["router"], xg, m)
    pos = _ranked_positions(expert_idx, m, vg)
    dropped = pos >= _group_capacity(s, m)
    if vg is not None:
        dropped = dropped & vg[..., None]
    return jnp.sum(dropped.astype(jnp.int32))
