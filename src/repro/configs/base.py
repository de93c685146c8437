"""Declarative architecture / run configuration.

This is the X-HEEP "generator" analogue (DESIGN.md C1): a single declarative
config fully determines the model (blocks, mixers, FFN kind, early exits),
the accelerator backends (XAIF, C2), the sharding layout, and the runtime
policies (remat, microbatching). Everything downstream is generated from it.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Mapping, Optional, Tuple

# ---------------------------------------------------------------------------
# Sub-configs
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class MoEConfig:
    """Token-choice top-k mixture of experts (capacity-based dispatch)."""

    num_experts: int
    top_k: int
    d_expert: int                      # hidden size of each routed expert
    num_shared_experts: int = 0        # DeepSeek-style always-on experts
    d_shared_expert: int = 0           # hidden size of the shared expert(s)
    capacity_factor: float = 1.25
    router_aux_weight: float = 0.01    # load-balance auxiliary loss weight
    router_dtype: str = "float32"
    # renormalize gates over the KEPT experts after capacity dropping, so a
    # dropped expert's share is redistributed instead of silently lost
    # (prefill/train only — the dropless decode path never drops); the
    # default pins the legacy numerics
    renorm_kept: bool = False
    # serve decode (T==1) dispatches each token's top-k expert GEMMs through
    # the per-token ``moe_decode`` XAIF op — no capacity constant, no drops,
    # so a slot's tokens never depend on its co-batch; False restores the
    # batch-grouped capacity path (benchmarks/serving_bench.py compares them)
    dropless_decode: bool = True


@dataclass(frozen=True)
class MLAConfig:
    """DeepSeek-V2 Multi-head Latent Attention."""

    kv_lora_rank: int = 512
    q_lora_rank: int = 0               # 0 => full-rank query projection
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128


@dataclass(frozen=True)
class MambaConfig:
    """Mamba-1 selective SSM mixer."""

    d_state: int = 16
    d_conv: int = 4
    expand: int = 2                    # d_inner = expand * d_model
    dt_rank: int = 0                   # 0 => ceil(d_model / 16)


@dataclass(frozen=True)
class XLSTMConfig:
    """xLSTM cell parameters (mLSTM + sLSTM blocks)."""

    mlstm_proj_factor: float = 2.0     # up-projection in mLSTM blocks
    slstm_proj_factor: float = 4.0 / 3.0
    conv_kernel: int = 4
    chunk_size: int = 64               # chunkwise-parallel mLSTM chunk length


@dataclass(frozen=True)
class EarlyExitConfig:
    """The paper's technique (C5): entropy-thresholded early exit.

    ``exit_layers`` are indices of the block AFTER which an exit head is
    attached (must align with scan super-block boundaries; see lm.py).
    The paper uses a single exit after the first major stage; its final
    operating points are (weight=0.1, threshold=0.45) for the transformer
    and (weight=0.01, threshold=0.35) for the CNN.
    """

    exit_layers: Tuple[int, ...]
    loss_weight: float = 0.1
    entropy_threshold: float = 0.45
    share_unembed: bool = True         # CALM-style shared unembedding


@dataclass(frozen=True)
class AccelConfig:
    """XAIF v1 static policy (C2): per-op backend selection.

    ``backends`` maps op name -> backend name registered in core/xaif.py;
    a dict passed at construction is normalized to a sorted tuple of pairs
    so the config is hashable (usable as a ``jax.jit`` static argument and
    as a trace-cache key). Unlisted ops fall back to "ref" (pure jnp — the
    "CPU-only" path of the paper).

    Superseded by the shape-aware ``xaif.DispatchPolicy`` (which a measured
    autotune produces — see core/autotune.py); both are accepted wherever a
    dispatch policy is expected.
    """

    # accepts a Mapping at construction; STORED as tuple(sorted(pairs)) so
    # the frozen config hashes — read through backend_for(), not by indexing
    backends: "Mapping[str, str] | Tuple[Tuple[str, str], ...]" = field(
        default_factory=dict)

    def __post_init__(self):
        object.__setattr__(
            self, "backends",
            tuple(sorted((str(k), str(v))
                         for k, v in dict(self.backends).items())))

    def backend_for(self, op: str) -> str:
        return dict(self.backends).get(op, "ref")


# ---------------------------------------------------------------------------
# Architecture config
# ---------------------------------------------------------------------------

MIXERS = ("attn", "mamba", "mlstm", "slstm")
FFNS = ("mlp", "moe", "none")


@dataclass(frozen=True)
class BlockSpec:
    """One layer = (sequence mixer, channel mixer)."""

    mixer: str  # one of MIXERS
    ffn: str    # one of FFNS

    def __post_init__(self):
        assert self.mixer in MIXERS, self.mixer
        assert self.ffn in FFNS, self.ffn


@dataclass(frozen=True)
class ArchConfig:
    name: str
    family: str                        # dense | moe | hybrid | ssm | audio | vlm
    num_layers: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    d_ff: int
    vocab_size: int

    head_dim: int = 0                  # 0 => d_model // num_heads
    # --- layer pattern ----------------------------------------------------
    # The model is `first_k_dense` explicit layers followed by
    # (num_layers - first_k_dense) / len(block_pattern) scanned repetitions
    # of `block_pattern` (stacked weights, lax.scan over super-blocks).
    block_pattern: Tuple[BlockSpec, ...] = (BlockSpec("attn", "mlp"),)
    first_k_dense: int = 0             # DeepSeek first_k_dense_replace
    # --- attention flavor --------------------------------------------------
    rope: str = "full"                 # full | partial | none
    rope_theta: float = 10_000.0
    rope_partial_pct: float = 0.5      # used when rope == "partial"
    qkv_bias: bool = False
    qk_norm: bool = False              # Chameleon-style
    # --- sub-configs --------------------------------------------------------
    moe: Optional[MoEConfig] = None
    mla: Optional[MLAConfig] = None
    mamba: Optional[MambaConfig] = None
    xlstm: Optional[XLSTMConfig] = None
    early_exit: Optional[EarlyExitConfig] = None
    # --- numerics -----------------------------------------------------------
    dtype: str = "bfloat16"
    norm_eps: float = 1e-5
    tie_embeddings: bool = False
    # --- modality stub (audio / vlm): frontend provides embeddings ----------
    frontend_stub: bool = False

    # -- derived -------------------------------------------------------------
    def __post_init__(self):
        hd = self.head_dim or self.d_model // self.num_heads
        object.__setattr__(self, "head_dim", hd)
        assert self.num_heads % max(self.num_kv_heads, 1) == 0
        n_scanned = self.num_layers - self.first_k_dense
        assert n_scanned % len(self.block_pattern) == 0, (
            f"{self.name}: {n_scanned} scanned layers not divisible by "
            f"pattern period {len(self.block_pattern)}"
        )

    @property
    def period(self) -> int:
        return len(self.block_pattern)

    @property
    def num_superblocks(self) -> int:
        return (self.num_layers - self.first_k_dense) // self.period

    @property
    def uses_attention(self) -> bool:
        return any(b.mixer == "attn" for b in self.block_pattern) or self.first_k_dense > 0

    @property
    def subquadratic(self) -> bool:
        """True if attention is absent or a minority (hybrid/SSM) — these
        archs run the long_500k shape; pure full-attention archs skip it."""
        mixers = [b.mixer for b in self.block_pattern]
        return mixers.count("attn") * 2 < len(mixers)

    def layer_spec(self, i: int) -> BlockSpec:
        """BlockSpec of absolute layer index i."""
        if i < self.first_k_dense:
            base = self.block_pattern[i % self.period]
            return BlockSpec(base.mixer, "mlp")
        return self.block_pattern[(i - self.first_k_dense) % self.period]

    def param_count(self) -> int:
        """Total parameters (used by the static-characterization bench and
        the MODEL_FLOPS roofline term)."""
        from repro.models.lm import count_params  # lazy: avoid cycle

        return count_params(self)

    def active_param_count(self) -> int:
        from repro.models.lm import count_params

        return count_params(self, active_only=True)

    def reduced(self, **overrides) -> "ArchConfig":
        """A tiny same-family config for CPU smoke tests."""
        changes: dict = dict(
            num_layers=max(self.period * 2 + self.first_k_dense, self.first_k_dense + self.period),
            d_model=64,
            num_heads=4,
            num_kv_heads=max(1, min(self.num_kv_heads, 2)),
            d_ff=128,
            vocab_size=256,
            head_dim=16,
        )
        if self.moe is not None:
            changes["moe"] = dataclasses.replace(
                self.moe,
                num_experts=4,
                top_k=2,
                d_expert=32,
                d_shared_expert=32 if self.moe.num_shared_experts else 0,
            )
        if self.mla is not None:
            changes["mla"] = MLAConfig(
                kv_lora_rank=32, q_lora_rank=0,
                qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
            )
        if self.mamba is not None:
            changes["mamba"] = dataclasses.replace(self.mamba, d_state=8)
        if self.xlstm is not None:
            changes["xlstm"] = dataclasses.replace(self.xlstm, chunk_size=16)
        if self.early_exit is not None:
            # keep a single exit aligned to the reduced depth
            nl = changes["num_layers"]
            changes["early_exit"] = dataclasses.replace(
                self.early_exit, exit_layers=(self.first_k_dense + self.period,) if nl > self.period else (self.period,)
            )
        changes.update(overrides)
        return dataclasses.replace(self, **changes)


# ---------------------------------------------------------------------------
# Run shapes (assigned input-shape set)
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class ShapeConfig:
    name: str
    seq_len: int
    global_batch: int
    kind: str  # "train" | "prefill" | "decode"


TRAIN_4K = ShapeConfig("train_4k", 4_096, 256, "train")
PREFILL_32K = ShapeConfig("prefill_32k", 32_768, 32, "prefill")
DECODE_32K = ShapeConfig("decode_32k", 32_768, 128, "decode")
LONG_500K = ShapeConfig("long_500k", 524_288, 1, "decode")

SHAPES: Tuple[ShapeConfig, ...] = (TRAIN_4K, PREFILL_32K, DECODE_32K, LONG_500K)
SHAPES_BY_NAME = {s.name: s for s in SHAPES}


def applicable_shapes(arch: ArchConfig) -> Tuple[ShapeConfig, ...]:
    """All archs are decoder-only; long_500k only for sub-quadratic archs."""
    out = [TRAIN_4K, PREFILL_32K, DECODE_32K]
    if arch.subquadratic:
        out.append(LONG_500K)
    return tuple(out)


# ---------------------------------------------------------------------------
# Run config: arch + shape + distribution + runtime policies
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class ShardingPolicy:
    """Logical-axis placement — the "bus topology" knob (DESIGN.md C1)."""

    fsdp: bool = True                  # shard weights over the data axis too
    tensor_parallel: bool = True       # shard heads / d_ff / vocab over model
    expert_parallel: bool = True       # shard MoE experts over model
    sequence_parallel: bool = False    # shard activations' seq dim over model
    shard_kv_batch: bool = True        # decode: KV batch over data axis
    dp_over_model: bool = False        # fold the model axis into extra DP
    #   (small-model mode: batch shards over (pod, data, model); TP/SP/EP off
    #    — kills per-layer activation collectives at the cost of per-chip
    #    weight residency; a §Perf hillclimb lever)


@dataclass(frozen=True)
class RunConfig:
    arch: ArchConfig
    shape: ShapeConfig
    # static AccelConfig or a shape-aware xaif.DispatchPolicy — both are
    # hashable and flow through model code unchanged
    accel: AccelConfig = AccelConfig()
    sharding: ShardingPolicy = ShardingPolicy()
    remat: str = "dots"                # nothing | dots | full
    microbatch: int = 1                # gradient-accumulation steps
    loss_chunk: int = 0                # 0 = off; else chunked head+CE (seq)
    weight_quant: bool = False         # serve-time int8 weights (WeightQ)
    learning_rate: float = 3e-4
    weight_decay: float = 0.1
    grad_clip: float = 1.0
    seed: int = 0


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------

_ARCH_REGISTRY: dict = {}


def register_arch(fn):
    """Decorator: register a zero-arg builder returning an ArchConfig."""
    cfg = fn()
    _ARCH_REGISTRY[cfg.name] = cfg
    return fn


def get_arch(name: str) -> ArchConfig:
    _ensure_loaded()
    try:
        return _ARCH_REGISTRY[name]
    except KeyError:
        raise KeyError(f"unknown arch {name!r}; known: {sorted(_ARCH_REGISTRY)}")


def list_archs() -> Tuple[str, ...]:
    _ensure_loaded()
    return tuple(sorted(_ARCH_REGISTRY))


_LOADED = False


def _ensure_loaded():
    global _LOADED
    if _LOADED:
        return
    _LOADED = True
    # import every config module so @register_arch runs
    from repro.configs import (  # noqa: F401
        jamba_v0_1_52b,
        yi_9b,
        chatglm3_6b,
        mistral_large_123b,
        qwen1_5_32b,
        musicgen_medium,
        chameleon_34b,
        deepseek_v2_lite_16b,
        qwen3_moe_30b_a3b,
        xlstm_350m,
        paper_seizure_transformer,
        paper_seizure_cnn,
    )
