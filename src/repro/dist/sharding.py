"""Logical-axis sharding (DESIGN.md C1: the "bus topology" knob).

Model code never names mesh axes. It talks in LOGICAL axes:

  * ``batch`` — the data-parallel direction (``("pod", "data")`` on the
    multi-pod mesh, ``"data"`` on a single pod, everything when
    ``dp_over_model`` folds the model axis into DP);
  * ``tp``    — tensor parallelism over the model axis (heads / d_ff / vocab);
  * ``sp``    — Megatron-style sequence parallelism over the model axis;
  * ``fsdp``  — ZeRO weight/optimizer sharding over the data axis;
  * ``ep``    — expert parallelism over the model axis.

``shard_ctx(mesh, policy)`` installs the mapping; ``constrain`` and the
``*_shardings`` helpers read it. With NO context installed every helper is
an identity/no-op, so tests and single-device examples run the exact same
model code without a mesh. Axes that would not divide a dimension are
dropped silently (GSPMD would pad; we prefer the predictable layout).
"""
from __future__ import annotations

import contextlib
from typing import Any, Optional, Tuple, Union

import jax
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ShardingPolicy

Axis = Union[str, Tuple[str, ...], None]

# ---------------------------------------------------------------------------
# Context
# ---------------------------------------------------------------------------


class ShardCtx:
    """Resolved (mesh, policy) pair: logical axis -> mesh axis mapping."""

    def __init__(self, mesh: Mesh, policy: ShardingPolicy):
        self.mesh = mesh
        self.policy = policy
        names = tuple(mesh.axis_names)
        has_model = "model" in names
        data = tuple(n for n in names if n in ("pod", "data"))
        if policy.dp_over_model and has_model:
            data = data + ("model",)
        self.data_axes: Axis = data[0] if len(data) == 1 else data
        model_free = has_model and not policy.dp_over_model
        self._map = {
            "batch": self.data_axes,
            "tp": "model" if (model_free and policy.tensor_parallel) else None,
            "sp": "model" if (model_free and policy.sequence_parallel) else None,
            "ep": "model" if (model_free and policy.expert_parallel) else None,
            "fsdp": ("data" if (policy.fsdp and "data" in names) else None),
            None: None,
        }

    def axis(self, logical: Optional[str]) -> Axis:
        return self._map[logical]

    def size(self, axis: Axis) -> int:
        if axis is None:
            return 1
        axes = (axis,) if isinstance(axis, str) else axis
        n = 1
        for a in axes:
            n *= self.mesh.shape[a]
        return n


_CTX: list = []   # stack; [-1] is the active context


def make_mesh(shape: Tuple[int, ...], names: Tuple[str, ...]) -> Mesh:
    """``jax.make_mesh`` with every axis ``Auto``. Since JAX 0.9 its axes
    default to ``Explicit``, on which ``with_sharding_constraint`` (and so
    :func:`constrain`) refuses to run; this module's layouts are
    constraint-driven, so every mesh in the repo is built here."""
    return jax.make_mesh(shape, names,
                         axis_types=(AxisType.Auto,) * len(names))


def current_ctx() -> Optional[ShardCtx]:
    return _CTX[-1] if _CTX else None


@contextlib.contextmanager
def shard_ctx(mesh: Mesh, policy: ShardingPolicy):
    """Install (mesh, policy) as the ambient sharding context."""
    ctx = ShardCtx(mesh, policy)
    _CTX.append(ctx)
    try:
        yield ctx
    finally:
        _CTX.pop()


# ---------------------------------------------------------------------------
# Specs and constraints
# ---------------------------------------------------------------------------


def _resolved_spec(ctx: ShardCtx, shape: Tuple[int, ...],
                   logical: Tuple[Optional[str], ...]) -> P:
    assert len(logical) == len(shape), (shape, logical)
    out = []
    for dim, name in zip(shape, logical):
        axis = ctx.axis(name)
        if axis is not None and dim % ctx.size(axis) != 0:
            axis = None              # axis would not divide: keep replicated
        out.append(axis)
    return P(*out)


def spec_for(shape: Tuple[int, ...], *logical: Optional[str]) -> P:
    """PartitionSpec for `shape` under the active context (P() without one)."""
    ctx = current_ctx()
    if ctx is None:
        return P(*([None] * len(shape)))
    return _resolved_spec(ctx, tuple(shape), logical)


def constrain(x: jax.Array, *logical: Optional[str]) -> jax.Array:
    """with_sharding_constraint by logical axes; identity with no context."""
    ctx = current_ctx()
    if ctx is None:
        return x
    spec = _resolved_spec(ctx, x.shape, logical)
    return jax.lax.with_sharding_constraint(x, NamedSharding(ctx.mesh, spec))


# ---------------------------------------------------------------------------
# Parameter sharding rules (path-name based, right-aligned so the same rule
# covers both stacked [n_sb, ...] slot weights and unstacked per-layer ones)
# ---------------------------------------------------------------------------

# column-parallel: output (last) dim over tp, input dim fsdp-sharded
_COL_PARALLEL = frozenset({
    "wq", "wk", "wv", "w_gate", "w_up", "up_proj", "unembed", "embed",
    "in_proj", "x_proj", "w_uk", "w_uv", "w_kr", "w_dkv", "wx", "wr",
    "w_if", "router",
})
# row-parallel: input (second-to-last) dim over tp, output dim fsdp-sharded
_ROW_PARALLEL = frozenset({"wo", "w_down", "down_proj", "out_proj",
                           "dt_proj"})
# expert-stacked [..., E, d_in, d_out]: experts over ep
_EXPERT = frozenset({"w_gate_e", "w_up_e", "w_down_e"})


def _leaf_spec(name: str, shape: Tuple[int, ...], ctx: ShardCtx) -> P:
    nd = len(shape)
    logical: list = [None] * nd
    if nd >= 2 and name in _COL_PARALLEL:
        logical[-1] = "tp"
        logical[-2] = "fsdp"
    elif nd >= 2 and name in _ROW_PARALLEL:
        logical[-2] = "tp"
        logical[-1] = "fsdp"
    elif nd >= 3 and name in _EXPERT:
        logical[-3] = "ep"
        logical[-1] = "fsdp"
    return _resolved_spec(ctx, tuple(shape), tuple(logical))


def _scale_spec(name: str, shape: Tuple[int, ...], ctx: ShardCtx) -> P:
    """WeightQ per-output-channel scales: tp on the last dim only."""
    nd = len(shape)
    logical: list = [None] * nd
    if nd >= 1 and name in (_COL_PARALLEL | _EXPERT):
        logical[-1] = "tp"
    elif nd >= 1 and name in _ROW_PARALLEL:
        logical[-1] = "fsdp"
    return _resolved_spec(ctx, tuple(shape), tuple(logical))


def _walk_pspecs(node: Any, name: str, ctx: ShardCtx) -> Any:
    # WeightQ (serve/quantize) inherits the PARENT weight's rules
    if type(node).__name__ == "WeightQ":
        return type(node)(_leaf_spec(name, tuple(node.q.shape), ctx),
                          _scale_spec(name, tuple(node.scale.shape), ctx))
    if isinstance(node, dict):
        return {k: _walk_pspecs(v, k, ctx) for k, v in node.items()}
    if isinstance(node, tuple) and hasattr(node, "_fields"):   # NamedTuple
        return type(node)(*(_walk_pspecs(v, f, ctx)
                            for f, v in zip(node._fields, node)))
    if isinstance(node, (list, tuple)):
        seq = [_walk_pspecs(v, name, ctx) for v in node]
        return seq if isinstance(node, list) else tuple(seq)
    if node is None:
        return None
    return _leaf_spec(name, tuple(node.shape), ctx)


def param_pspecs(tree: Any) -> Any:
    """Matching pytree of PartitionSpec for a params/optimizer tree."""
    ctx = current_ctx()
    assert ctx is not None, "param_pspecs requires an active shard_ctx"
    return _walk_pspecs(tree, "", ctx)


def param_shardings(tree: Any) -> Any:
    """Matching pytree of NamedSharding (jit in_shardings / device_put)."""
    ctx = current_ctx()
    assert ctx is not None, "param_shardings requires an active shard_ctx"
    specs = _walk_pspecs(tree, "", ctx)
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(ctx.mesh, s), specs,
        is_leaf=lambda s: isinstance(s, P))


# ---------------------------------------------------------------------------
# Cache sharding: the decode KV/SSM caches shard over the BATCH (slot) dim
# ---------------------------------------------------------------------------


def cache_shardings(tree: Any, batch: int) -> Any:
    """Shard the batch (slot) dimension of a decode cache over the data axes.

    LMCache trees are handled STRUCTURALLY: prefix states carry batch at
    axis 0, stacked slot states at axis 1 (after the [n_sb] stack dim), and
    ``pos`` is [B] — so a stack/head/seq dimension that happens to equal the
    batch size can never be sharded by accident. Pre-sliced sub-trees
    (per-layer states, as the dry-run's component costing passes) carry
    batch at axis 0; a size match on a later axis is only a fallback.

    PagedLMCache trees have NO batch dimension in their attention storage —
    the page pools are shared across slots — so the pools shard their
    capacity-agnostic HEAD dim over ``tp`` (GQA ``[.., P, Hkv, ps, D]``
    pools; decode attention is head-parallel, so this needs no collective),
    the MLA latent pools stay replicated (their single shared head has no
    head axis and the lora width is a decode-score contraction dim — see
    below), and the ``[capacity, max_pages]`` page table is replicated (it
    is rewritten wholesale from the host mirror between chunks). Recurrent
    slot states and ``pos`` shard over the data axes exactly like the
    contiguous cache.
    """
    ctx = current_ctx()
    assert ctx is not None, "cache_shardings requires an active shard_ctx"
    ba = ctx.axis("batch") if ctx.policy.shard_kv_batch else None
    if ba is not None and batch % ctx.size(ba) != 0:
        ba = None

    def replicated(s):
        return NamedSharding(ctx.mesh, P(*([None] * len(s.shape))))

    def leaf_at(axis):
        def leaf(s):
            spec: list = [None] * len(s.shape)
            if ba is not None and len(s.shape) > axis and s.shape[axis] == batch:
                spec[axis] = ba
            return NamedSharding(ctx.mesh, P(*spec))
        return leaf

    if type(tree).__name__ == "LMCache":
        return type(tree)(
            prefix=jax.tree_util.tree_map(leaf_at(0), tree.prefix),
            slots=jax.tree_util.tree_map(leaf_at(1), tree.slots),
            pos=leaf_at(0)(tree.pos))

    if type(tree).__name__ == "PagedLMCache":
        ta = ctx.axis("tp")

        def pool_or_state(state, stacked: bool):
            name = type(state).__name__
            if name == "PagedKVCache":
                # [(n_sb,) P, Hkv, ps, D]: heads over tp when they divide
                def pool_leaf(s):
                    spec: list = [None] * len(s.shape)
                    if ta is not None and s.shape[-3] % ctx.size(ta) == 0:
                        spec[-3] = ta
                    return NamedSharding(ctx.mesh, P(*spec))
                return jax.tree_util.tree_map(pool_leaf, state)
            if name == "PagedMLACache":
                # one shared latent "head"; sharding the lora width would
                # turn the absorbed-decode score dot into a cross-device
                # partial sum (and break bitwise identity) — replicate
                return jax.tree_util.tree_map(replicated, state)
            return jax.tree_util.tree_map(leaf_at(1 if stacked else 0),
                                          state)

        return type(tree)(
            prefix=tuple(pool_or_state(s, False) for s in tree.prefix),
            slots=tuple(pool_or_state(s, True) for s in tree.slots),
            pos=leaf_at(0)(tree.pos),
            page_table=replicated(tree.page_table))

    def leaf(s):
        spec: list = [None] * len(s.shape)
        if ba is not None:
            if len(s.shape) and s.shape[0] == batch:
                spec[0] = ba
            else:
                for i, d in enumerate(s.shape):
                    if d == batch:
                        spec[i] = ba
                        break
        return NamedSharding(ctx.mesh, P(*spec))

    return jax.tree_util.tree_map(leaf, tree)


def serve_shardings(cache_struct: Any, state_struct: Any,
                    capacity: int) -> Tuple[Any, Any]:
    """jit in/out shardings for the slot engine's (cache, DecodeState) pair.

    The cache shards its slot axis over the data axes (page pools per tp —
    see :func:`cache_shardings`); the DecodeState is fully REPLICATED: its
    leaves are per-slot scalars the host fetches every chunk, and every
    decode step reduces over them (done/budget bookkeeping, the statistics
    sums), so replication costs nothing and keeps the per-chunk fetch a
    single local transfer.
    """
    ctx = current_ctx()
    assert ctx is not None, "serve_shardings requires an active shard_ctx"
    state_sh = jax.tree_util.tree_map(
        lambda s: NamedSharding(ctx.mesh, P(*([None] * len(s.shape)))),
        state_struct)
    return cache_shardings(cache_struct, capacity), state_sh
