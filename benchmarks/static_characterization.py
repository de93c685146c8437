"""Paper Fig. 2 analogue: static characterization of the "host platform".

X-HEEP reports area (0.15 mm^2) and leakage (29 uW) per component, memory
dominating both (44 % area, 84 % leakage), and a 3 uW power-gated floor.
The software-platform equivalent of area/leakage is BYTES RESIDENT PER
DEVICE per component — params, optimizer state, KV cache — plus the
"power-gated floor": what remains after releasing every gateable component
(optimizer freed between jobs, KV freed between requests, exit-head
analogue of the paper's peripheral gating).

Reported per architecture for the single-pod mesh (256 chips): component
breakdown in bytes/chip and percentage — the same shape as the paper's
pie charts.
"""
from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp

from repro.configs.base import SHAPES_BY_NAME, get_arch, list_archs
from repro.models import lm

CHIPS = 256
OPT_BYTES_PER_PARAM = 12.0          # fp32 m + v + master
PARAM_BYTES = 2.0                   # bf16


def _cache_bytes(cfg, shape) -> float:
    tree = jax.eval_shape(lambda: lm.init_cache(cfg, shape.global_batch,
                                                shape.seq_len))
    return sum(math.prod(l.shape) * l.dtype.itemsize
               for l in jax.tree_util.tree_leaves(tree))


def _component_bytes(cfg) -> Dict[str, float]:
    shapes = jax.eval_shape(lambda: lm.init_lm(jax.random.PRNGKey(0), cfg))
    comp = {"embeddings": 0.0, "attention": 0.0, "ffn_dense": 0.0,
            "ffn_experts": 0.0, "mixer_ssm": 0.0, "exit_heads": 0.0,
            "other": 0.0}
    for path, leaf in jax.tree_util.tree_flatten_with_path(shapes)[0]:
        key = jax.tree_util.keystr(path)
        n = math.prod(leaf.shape) * leaf.dtype.itemsize
        if "embed" in key or "unembed" in key:
            comp["embeddings"] += n
        elif "exits" in key:
            comp["exit_heads"] += n
        elif any(s in key for s in ("_e'", "_e]", "router", "shared")):
            comp["ffn_experts"] += n
        elif "ffn" in key:
            comp["ffn_dense"] += n
        elif any(s in key for s in ("wq", "wk", "wv", "wo", "w_dkv", "w_uk",
                                    "w_uv", "w_kr", "q_norm", "k_norm")):
            comp["attention"] += n
        elif any(s in key for s in ("in_proj", "x_proj", "dt_", "a_log",
                                    "conv", "d_skip", "up_proj", "down_proj",
                                    "wx", "wr", "w_if", "w_ff")):
            comp["mixer_ssm"] += n
        else:
            comp["other"] += n
    return comp


def characterize(arch_name: str) -> Dict:
    cfg = get_arch(arch_name)
    comp = _component_bytes(cfg)
    params_total = sum(comp.values())
    decode = SHAPES_BY_NAME["decode_32k"]
    kv = _cache_bytes(cfg, decode)
    rows = {}
    for k, v in comp.items():
        if v:
            rows[f"params/{k}"] = v / CHIPS
    rows["optimizer_state"] = params_total / PARAM_BYTES * OPT_BYTES_PER_PARAM / CHIPS
    rows["kv_cache(decode_32k)"] = kv / CHIPS
    total = sum(rows.values())
    gated_floor = sum(v for k, v in rows.items() if k.startswith("params/"))
    return {
        "arch": arch_name,
        "bytes_per_chip": rows,
        "percent": {k: 100.0 * v / total for k, v in rows.items()},
        "total_bytes_per_chip": total,
        "power_gated_floor_bytes": gated_floor,   # opt freed, KV freed
        "floor_fraction": gated_floor / total,
    }


def table() -> Dict[str, Dict]:
    return {a: characterize(a) for a in list_archs()}


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    import json
    print(json.dumps(table(), indent=2))
