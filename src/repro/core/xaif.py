"""XAIF v2 — the eXtendible Accelerator InterFace, adapted to JAX (DESIGN.md C2).

X-HEEP's XAIF bundles everything an accelerator needs to plug into the host
without RTL changes: OBI slave+master ports, DMA extension, interrupts and
power-control signals — and the paper's headline claim is that accelerators
with *varying requirements* can be selected per workload. The JAX analogue
is a **shape-aware op-level dispatch table**:

  * an **op** is a named computational contract ("gemm", "rmsnorm",
    "attention", "entropy_exit", "ssm_scan", "attn_decode",
    "attn_decode_paged", "moe_decode") with a fixed signature — the
    "port" of the interface;
  * a **backend** is an implementation of that contract — the pure-jnp
    reference (the host-CPU path of the paper), a Pallas TPU kernel (the
    integrated accelerator), or an XLA-structured variant (blockwise
    attention, associative scan). A backend declares
      - a ``cost_fn`` (the power-management side of XAIF) used by
        ``repro.core.energy`` and as the autotuner's *prior*,
      - a ``supports(shapes, dtype)`` predicate — which workload shapes the
        backend can legally run (an accelerator's "requirements"),
      - ``tunables`` — block-size knobs with candidate values the autotuner
        may sweep (e.g. ``bm``/``bn``/``bk`` for the GEMM kernel);
  * a **shape bucket** classifies a call site's argument shapes into a
    small workload class ("decode" vs "prefill" for attention; row-count
    classes for row ops) — computed at TRACE time from static shapes, so
    bucketing costs nothing at runtime;
  * a :class:`DispatchPolicy` is a resolved, hashable, JSON-serializable
    table mapping (op, bucket) -> (backend, tuning params). It supersedes
    the v1 ``AccelConfig`` string map (still accepted everywhere for
    compatibility): a backend that wins at decode (batch x 1) is no longer
    forced on prefill (batch x 32k).

Model code *never* imports a kernel directly — it calls
``xaif.call("gemm", policy, ...)`` where ``policy`` is either an
``AccelConfig`` (static per-op map) or a ``DispatchPolicy`` (per-op,
per-shape-bucket map), exactly like swapping an accelerator on the bus
without touching the host. ``repro.core.autotune`` *measures* every
registered backend per (op, bucket) cell and emits the winning
``DispatchPolicy``, persisted to JSON and loadable at serve startup.

Registering a new backend is one decorator — the "seamless integration"
claim of the paper, transplanted::

    @xaif.register("gemm", "mine", cost_fn=my_cost,
                   supports=lambda shapes, dtype: shapes[0][-1] % 128 == 0,
                   tunables={"bm": (128, 256)})
    def my_gemm(x, w, bias=None, activation="none", *, bm=128): ...

Both policy types are hashable (usable as ``jax.jit`` static arguments)
and usable as dict keys for trace caches.
"""
from __future__ import annotations

import json
from dataclasses import dataclass
from typing import Callable, Dict, Mapping, Optional, Tuple, Union

import jax

from repro.configs.base import AccelConfig

# ---------------------------------------------------------------------------
# Registry entries
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class BackendEntry:
    op: str
    name: str
    fn: Callable
    # optional cost model: (dims...) -> dict(flops=..., hbm_bytes=...);
    # doubles as the autotuner's prior (see core/autotune.py)
    cost_fn: Optional[Callable] = None
    description: str = ""
    takes_interpret: bool = False
    # optional predicate: (shapes, dtype) -> bool. None = supports anything.
    # ``shapes`` is the tuple of argument shapes as seen by xaif.call.
    supports: Optional[Callable] = None
    # declared tuning knobs: ((kwarg_name, (candidate, ...)), ...) — only
    # these kwargs may be injected by a DispatchRule's tuning params.
    tunables: Tuple[Tuple[str, Tuple[int, ...]], ...] = ()
    # True for backends that trade accuracy for speed (e.g. on-the-fly int8
    # quantization): the autotuner excludes them unless explicitly allowed,
    # so a latency win can never silently change model numerics.
    lossy: bool = False

    @property
    def tunable_names(self) -> Tuple[str, ...]:
        return tuple(k for k, _ in self.tunables)

    def accepts(self, shapes, dtype) -> bool:
        if self.supports is None:
            return True
        try:
            return bool(self.supports(shapes, dtype))
        except (IndexError, TypeError):
            return False


_REGISTRY: Dict[Tuple[str, str], BackendEntry] = {}


def register(op: str, name: str, *, cost_fn=None, description: str = "",
             supports=None, tunables: Optional[Mapping] = None,
             lossy: bool = False):
    """Decorator: register ``fn`` as backend ``name`` for ``op``."""

    def deco(fn):
        import inspect
        takes_interpret = "interpret" in inspect.signature(fn).parameters
        tun = ()
        if tunables:
            tun = tuple(sorted(
                (str(k), tuple(int(x) for x in v))
                for k, v in dict(tunables).items()))
        _REGISTRY[(op, name)] = BackendEntry(
            op, name, fn, cost_fn, description, takes_interpret,
            supports, tun, lossy)
        return fn

    return deco


# ---------------------------------------------------------------------------
# Shape buckets — trace-time workload classification
# ---------------------------------------------------------------------------
#
# Buckets are deliberately coarse: each bucket is one autotuner cell and one
# row of the dispatch table; fine-grained bucketing would multiply traces
# without changing which backend wins.


def _rows(shape) -> int:
    m = 1
    for d in shape[:-1]:
        m *= int(d)
    return m


def _rows_bucket(shapes, _dtype):
    m = _rows(shapes[0])
    if m <= 32:
        return "rows_s"          # decode-sized: a handful of rows
    if m <= 2048:
        return "rows_m"          # small-batch prefill / train microbatch
    return "rows_l"              # large prefill / train


def _attention_bucket(shapes, _dtype):
    # q is [B, Hq, T, D]; T==1 is the decode step, anything longer prefill
    return "decode" if int(shapes[0][-2]) == 1 else "prefill"


def _ssm_bucket(shapes, _dtype):
    # u is [B, T, Din]
    return "decode" if int(shapes[0][1]) == 1 else "scan"


def _paged_bucket(shapes, _dtype):
    # (q [B,Hq,D], k_pages [P,Hkv,ps,D], v_pages, page_table [B,NP], pos):
    # bucket by resident KV extent NP*ps — short contexts fit a gather,
    # long ones want the page-blocked kernel
    s = int(shapes[1][2]) * int(shapes[3][1])
    return "kv_s" if s <= 1024 else "kv_l"


def _decode_kv_bucket(shapes, _dtype):
    # (q [B,Hq,D], k [B,Hkv,S,D], v, cache_pos): bucket by the contiguous
    # KV extent S — same boundary as the paged op, so a policy tuned for
    # one engine transfers its bucket structure to the other
    return "kv_s" if int(shapes[1][2]) <= 1024 else "kv_l"


def _ssm_decode_bucket(shapes, _dtype):
    # (x, g, a, ...): the mamba decode step feeds rank-2 activations
    # [B, Din]; the mlstm cell feeds rank-3 per-head tensors [B, H, dh]
    return "mamba" if len(shapes[0]) == 2 else "mlstm"


def _moe_bucket(shapes, _dtype):
    # (x [B,d], expert_idx [B,K], gate [B,K], w_gate [E,d,h], ...): bucket
    # by routed-expert count E — the knob that decides whether a per-token
    # panel gather or a sorted ragged dispatch wins at decode
    return "e_s" if int(shapes[3][0]) <= 16 else "e_l"


_BUCKET_FNS: Dict[str, Callable] = {
    "gemm": _rows_bucket,
    "rmsnorm": _rows_bucket,
    "entropy_exit": _rows_bucket,
    "attention": _attention_bucket,
    "ssm_scan": _ssm_bucket,
    "ssm_decode": _ssm_decode_bucket,
    "attn_decode": _decode_kv_bucket,
    "attn_decode_paged": _paged_bucket,
    # verify ops: q gains a K1 query axis but k / page_table sit at the
    # same argument positions, so the decode bucket fns apply unchanged
    "verify_decode": _decode_kv_bucket,
    "verify_decode_paged": _paged_bucket,
    "moe_decode": _moe_bucket,
}

_OP_BUCKETS: Dict[str, Tuple[str, ...]] = {
    "gemm": ("rows_s", "rows_m", "rows_l"),
    "rmsnorm": ("rows_s", "rows_m", "rows_l"),
    "entropy_exit": ("rows_s", "rows_m", "rows_l"),
    "attention": ("decode", "prefill"),
    "ssm_scan": ("decode", "scan"),
    "ssm_decode": ("mamba", "mlstm"),
    "attn_decode": ("kv_s", "kv_l"),
    "attn_decode_paged": ("kv_s", "kv_l"),
    "verify_decode": ("kv_s", "kv_l"),
    "verify_decode_paged": ("kv_s", "kv_l"),
    "moe_decode": ("e_s", "e_l"),
}

WILDCARD = "*"


def shape_bucket(op: str, shapes, dtype=None) -> str:
    """Classify argument shapes into this op's workload bucket.

    Unknown ops fall back to row-count bucketing; malformed shapes fall
    back to the wildcard bucket (which every policy resolves).
    """
    fn = _BUCKET_FNS.get(op, _rows_bucket)
    try:
        return fn(tuple(tuple(s) for s in shapes), dtype)
    except (IndexError, TypeError, ValueError):
        return WILDCARD


def op_buckets(op: str) -> Tuple[str, ...]:
    """The bucket names the autotuner enumerates for ``op``."""
    return _OP_BUCKETS.get(op, ("rows_s", "rows_m", "rows_l"))


def _shapes_of(args) -> Tuple[Tuple[int, ...], ...]:
    shapes = []
    for a in args:
        if hasattr(a, "shape"):
            shapes.append(tuple(a.shape))
        elif hasattr(a, "q") and hasattr(a.q, "shape"):   # serve WeightQ
            shapes.append(tuple(a.q.shape))
    return tuple(shapes)


# ---------------------------------------------------------------------------
# DispatchPolicy — the resolved, hashable dispatch table
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class DispatchRule:
    """One cell of the table: which backend, with which tuning params."""

    backend: str
    tuning: Tuple[Tuple[str, int], ...] = ()

    def __post_init__(self):
        t = self.tuning
        if isinstance(t, Mapping):
            t = t.items()
        object.__setattr__(
            self, "tuning",
            tuple(sorted((str(k), int(v)) for k, v in t)))

    def tuning_kwargs(self) -> Dict[str, int]:
        return dict(self.tuning)


@dataclass(frozen=True)
class DispatchPolicy:
    """(op, shape-bucket) -> DispatchRule.

    Frozen, hashable (usable as a ``jax.jit`` static argument / trace-cache
    key) and losslessly JSON-serializable. Lookup falls back
    (op, bucket) -> (op, "*") -> ``default`` backend, so a policy tuned for
    the buckets it measured still dispatches everything else.
    """

    rules: Tuple[Tuple[str, str, DispatchRule], ...] = ()
    default: str = "ref"

    def __post_init__(self):
        norm = []
        for op, bucket, rule in self.rules:
            if isinstance(rule, str):
                rule = DispatchRule(rule)
            elif isinstance(rule, tuple) and not isinstance(rule, DispatchRule):
                rule = DispatchRule(*rule)
            norm.append((str(op), str(bucket), rule))
        norm.sort(key=lambda t: (t[0], t[1]))
        object.__setattr__(self, "rules", tuple(norm))
        object.__setattr__(
            self, "_table", {(o, b): r for o, b, r in self.rules})

    # -- construction -------------------------------------------------------

    @classmethod
    def make(cls, table: Mapping, *,
             default: str = "ref") -> "DispatchPolicy":
        """Build from {(op, bucket): backend | (backend, tuning) | rule}.
        A plain-string key ``op`` means the wildcard bucket."""
        rules = []
        for key, val in dict(table).items():
            op, bucket = key if isinstance(key, tuple) else (key, WILDCARD)
            rules.append((op, bucket, val))
        return cls(rules=tuple(rules), default=default)

    @classmethod
    def from_accel(cls, accel: AccelConfig) -> "DispatchPolicy":
        """Lift a v1 static AccelConfig into a wildcard-bucket policy."""
        return cls.make(dict(accel.backends))

    # -- lookup -------------------------------------------------------------

    def rule_for(self, op: str, bucket: str) -> DispatchRule:
        table = self._table
        rule = table.get((op, bucket))
        if rule is None:
            rule = table.get((op, WILDCARD))
        return rule if rule is not None else DispatchRule(self.default)

    def backend_for(self, op: str, bucket: str = WILDCARD) -> str:
        return self.rule_for(op, bucket).backend

    # -- serialization ------------------------------------------------------

    def to_json(self, **extra) -> str:
        doc = {
            "version": 2,
            "default": self.default,
            "rules": [
                {"op": o, "bucket": b, "backend": r.backend,
                 "tuning": dict(r.tuning)}
                for o, b, r in self.rules
            ],
        }
        doc.update(extra)
        return json.dumps(doc, indent=2, sort_keys=True)

    @classmethod
    def from_json(cls, s: str) -> "DispatchPolicy":
        doc = json.loads(s)
        rules = tuple(
            (r["op"], r["bucket"],
             DispatchRule(r["backend"], tuple(r.get("tuning", {}).items())))
            for r in doc.get("rules", ()))
        return cls(rules=rules, default=str(doc.get("default", "ref")))

    def save(self, path, **extra) -> None:
        with open(path, "w") as f:
            f.write(self.to_json(**extra))

    @classmethod
    def load(cls, path) -> "DispatchPolicy":
        with open(path) as f:
            return cls.from_json(f.read())


PolicyLike = Union[AccelConfig, DispatchPolicy]


# ---------------------------------------------------------------------------
# Resolution + dispatch
# ---------------------------------------------------------------------------


def pallas_interpret() -> bool:
    """Whether Pallas backends run through the interpreter: compiled on a
    TPU, interpreted on every other platform. The platform decides — no
    policy, config or flag carries this."""
    return jax.default_backend() != "tpu"


def get_entry(op: str, name: str) -> BackendEntry:
    _ensure_builtin_backends()
    key = (op, name)
    if key not in _REGISTRY:
        known = sorted(n for (o, n) in _REGISTRY if o == op)
        raise KeyError(f"no backend {name!r} for op {op!r}; known: {known}")
    return _REGISTRY[key]


def _accepting_fallback(op: str, policy: "DispatchPolicy", shapes,
                        dtype) -> BackendEntry:
    """Fallback chain when a rule's backend rejects the shapes: the
    policy's default, then "ref", then any accepting non-lossy backend,
    then (last resort, to keep serving alive) an accepting lossy one —
    never a backend that itself declared the shapes illegal."""
    seen = set()
    for name in (policy.default, "ref"):
        if name in seen:
            continue
        seen.add(name)
        try:
            entry = get_entry(op, name)
        except KeyError:
            continue
        if entry.accepts(shapes, dtype):
            return entry
    rest = [e for e in entries_for(op)
            if e.name not in seen and e.accepts(shapes, dtype)]
    for entry in sorted(rest, key=lambda e: e.lossy):
        return entry
    raise KeyError(f"no registered backend for op {op!r} accepts "
                   f"shapes {shapes}")


def resolve(op: str, policy: PolicyLike, shapes=None,
            dtype=None) -> BackendEntry:
    """Resolve the backend a call with ``shapes`` would dispatch to.

    With an AccelConfig the answer is shape-independent; with a
    DispatchPolicy, ``shapes`` selects the bucket (omitted -> wildcard).
    """
    _ensure_builtin_backends()
    if isinstance(policy, DispatchPolicy):
        bucket = shape_bucket(op, shapes, dtype) if shapes else WILDCARD
        entry = get_entry(op, policy.rule_for(op, bucket).backend)
        if shapes and not entry.accepts(shapes, dtype):
            entry = _accepting_fallback(op, policy, shapes, dtype)
        return entry
    return get_entry(op, policy.backend_for(op))


def call(op: str, policy: PolicyLike, *args, **kwargs):
    """Dispatch an op through the interface.

    The signature is unchanged from v1 — model code stays mechanical — but
    with a DispatchPolicy the backend AND its tuning params are selected
    per shape bucket (computed from static trace-time shapes, zero runtime
    cost). Explicit kwargs always win over policy tuning params; a backend
    whose ``supports`` predicate rejects the shapes falls back to the
    policy's default backend.

    When a :class:`CircuitBreaker` is installed, a backend that RAISES at
    call time is degraded around: the breaker pins this (op, bucket) cell
    to its fallback backend for the rest of the stream, records a
    FaultEvent, and the call is re-dispatched to the fallback. Dispatch
    happens at trace time, so the jit trace completes with the fallback
    baked in — no partial graphs.

    The backend that runs is traced inside ``jax.named_scope(
    "xaif.<op>.<backend>")``, so its ops carry that name in their HLO
    ``op_name`` metadata (no runtime cost, no numeric change).
    """
    _ensure_builtin_backends()
    if isinstance(policy, DispatchPolicy):
        shapes = _shapes_of(args)
        dtype = next((a.dtype for a in args if hasattr(a, "dtype")), None)
        bucket = shape_bucket(op, shapes, dtype)
        rule = policy.rule_for(op, bucket)
        entry = get_entry(op, rule.backend)
        if not entry.accepts(shapes, dtype):
            entry = _accepting_fallback(op, policy, shapes, dtype)
            rule = DispatchRule(entry.name)
        tuning = rule.tuning
    else:
        bucket = WILDCARD
        entry = get_entry(op, policy.backend_for(op))
        tuning = ()

    def _kwargs(e: BackendEntry) -> Dict:
        allowed = e.tunable_names
        m = {k: v for k, v in tuning if k in allowed}
        m.update(kwargs)
        if e.takes_interpret:
            m["interpret"] = pallas_interpret()
        return m

    def _run(e: BackendEntry):
        # HLO metadata only (op_name): a device trace finds each op's
        # kernels by this name, whatever their shapes or layouts
        with jax.named_scope(f"xaif.{op}.{e.name}"):
            return e.fn(*args, **_kwargs(e))

    breaker = _BREAKER
    if breaker is not None:
        pin = breaker.pinned.get((op, bucket))
        if pin is not None and pin != entry.name:
            entry = get_entry(op, pin)           # cell already degraded
        if entry.name != breaker.fallback:
            try:
                return _run(entry)
            except Exception as exc:             # noqa: BLE001 — degrade
                breaker.trip(op, bucket, entry.name, exc)
                entry = get_entry(op, breaker.fallback)
            return _run(entry)
    return _run(entry)


# ---------------------------------------------------------------------------
# Circuit breaker — graceful degradation for backends that raise at call
# (trace) time. The serving supervisor installs one so a broken tuned
# kernel downgrades the cell instead of killing the stream.
# ---------------------------------------------------------------------------


class CircuitBreaker:
    """Pins (op, bucket) cells whose backend raised to ``fallback``.

    A trip is permanent for the breaker's lifetime — the failed backend is
    never retried mid-stream (a raising kernel would otherwise re-raise on
    every re-trace). Each trip is logged as a
    :class:`repro.dist.fault.FaultEvent` (kind ``"circuit-breaker"``) into
    ``events`` — shareable with a :class:`~repro.serve.faults.FaultInjector`
    so one timeline covers injected faults and the degradations they caused.
    """

    def __init__(self, fallback: str = "ref", events=None):
        self.fallback = fallback
        self.pinned: Dict[Tuple[str, str], str] = {}
        self.events = events if events is not None else []
        self.trips = 0

    def trip(self, op: str, bucket: str, backend: str, exc: Exception):
        from repro.dist.fault import FaultEvent
        self.pinned[(op, bucket)] = self.fallback
        self.trips += 1
        self.events.append(FaultEvent(
            "circuit-breaker", self.trips,
            f"op={op} bucket={bucket} backend={backend} -> "
            f"{self.fallback}: {type(exc).__name__}: {exc}"))


_BREAKER: Optional["CircuitBreaker"] = None


def install_breaker(breaker: Optional["CircuitBreaker"]
                    ) -> Optional["CircuitBreaker"]:
    """Install ``breaker`` as the process-wide circuit breaker (None to
    remove). Returns the previous one, so callers can restore it."""
    global _BREAKER
    prev, _BREAKER = _BREAKER, breaker
    return prev


def active_breaker() -> Optional["CircuitBreaker"]:
    return _BREAKER


def backends_for(op: str) -> Tuple[str, ...]:
    _ensure_builtin_backends()
    return tuple(sorted(n for (o, n) in _REGISTRY if o == op))


def entries_for(op: str) -> Tuple[BackendEntry, ...]:
    _ensure_builtin_backends()
    return tuple(_REGISTRY[(o, n)]
                 for (o, n) in sorted(_REGISTRY) if o == op)


def ops() -> Tuple[str, ...]:
    _ensure_builtin_backends()
    return tuple(sorted({o for (o, _) in _REGISTRY}))


# ---------------------------------------------------------------------------
# Built-in backends are registered lazily so importing xaif stays cheap and
# cycle-free; kernels' ops.py modules call register() at import time.
# ---------------------------------------------------------------------------

_BUILTIN_LOADED = False


def _ensure_builtin_backends():
    global _BUILTIN_LOADED
    if _BUILTIN_LOADED:
        return
    _BUILTIN_LOADED = True
    from repro.kernels.gemm import ops as _gemm_ops              # noqa: F401
    from repro.kernels.rmsnorm import ops as _rmsnorm_ops        # noqa: F401
    from repro.kernels.entropy_exit import ops as _entropy_ops   # noqa: F401
    from repro.kernels.flash_attention import ops as _fa_ops     # noqa: F401
    from repro.kernels.ssm_scan import ops as _ssm_ops           # noqa: F401
    from repro.kernels.ssm_decode import ops as _ssm_dec_ops     # noqa: F401
    from repro.kernels.attn_decode import ops as _decode_ops     # noqa: F401
    from repro.kernels.paged_attention import ops as _paged_ops  # noqa: F401
    from repro.kernels.verify_decode import ops as _verify_ops   # noqa: F401
    from repro.kernels.moe_decode import ops as _moe_ops         # noqa: F401
