"""The system under test, as a configuration file states it: weights drawn
by the benchmark from the seed, the program's ``ArchConfig``, its
``SlotEngine`` and its dispatch policy.

The weights belong to the benchmark, in its own layout (``layers`` holds
every per-layer tensor stacked over depth). ``to_program`` re-nests the
same arrays into the program's parameter tree without a copy, so the plain
reference reads weights that the program did not make.
"""
from __future__ import annotations

import functools
from typing import Dict, Optional

import jax
import jax.numpy as jnp

# weights a layer holds, in the order they are drawn
_PROJ = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")
_BIAS = ("bq", "bk", "bv")
# the benchmark's tiny stand-in widths for a CPU rehearsal
REHEARSAL_MODEL = dict(num_layers=2, d_model=64, num_heads=8, head_dim=16,
                       d_ff=128, vocab_size=256)


def rehearsal(config: dict) -> dict:
    """``config`` at the rehearsal widths, with an engine to match: only
    for running the harness's control flow on a CPU."""
    model = dict(config["model"], **REHEARSAL_MODEL,
                 num_kv_heads=min(config["model"]["num_kv_heads"], 4))
    model["rope_dims"] = (model["head_dim"] * config["model"]["rope_dims"]
                          // config["model"]["head_dim"])
    eng = dict(config["engine"], capacity=4, max_len=256, num_pages=80,
               prompt_bucket=32)
    scale = eng["max_len"] / config["engine"]["max_len"]
    correct = dict(config["correct"], min_compared=max(
        int(config["correct"]["min_compared"] * scale), 1))
    return dict(config, model=model, engine=eng, correct=correct,
                mesh=None if config.get("mesh") is None
                else dict(config["mesh"]))


def shapes(model: dict) -> Dict[str, tuple]:
    """Shape of each per-layer weight (without the depth axis)."""
    d, dh, ff = model["d_model"], model["head_dim"], model["d_ff"]
    hq, hkv = model["num_heads"], model["num_kv_heads"]
    s = {"ln1": (d,), "wq": (d, hq * dh), "wk": (d, hkv * dh),
         "wv": (d, hkv * dh), "wo": (hq * dh, d), "ln2": (d,),
         "w_gate": (d, ff), "w_up": (d, ff), "w_down": (ff, d)}
    if model.get("qkv_bias"):
        s.update(bq=(hq * dh,), bk=(hkv * dh,), bv=(hkv * dh,))
    return s


def _draw_layer(key, model: dict, dtype):
    """One layer: projections N(0, 1/fan_in), biases N(0, 0.1^2), norm
    scales 1 + N(0, 0.1^2) in float32."""
    out = {}
    for i, (name, shp) in enumerate(sorted(shapes(model).items())):
        k = jax.random.fold_in(key, i)
        if name.startswith("ln"):
            out[name] = 1.0 + 0.1 * jax.random.normal(k, shp, jnp.float32)
        elif name in _BIAS:
            out[name] = (0.1 * jax.random.normal(k, shp, jnp.float32)
                         ).astype(dtype)
        else:
            out[name] = (jax.random.normal(k, shp, jnp.float32)
                         * shp[0] ** -0.5).astype(dtype)
    return out


def _draw(key, model: dict):
    dtype = jnp.dtype(model["dtype"])
    d, v = model["d_model"], model["vocab_size"]
    k_emb, k_un, k_norm, k_layers = jax.random.split(key, 4)
    # one layer at a time: no float32 temporary the size of a whole stack
    layers = jax.lax.map(functools.partial(_draw_layer, model=model,
                                           dtype=dtype),
                         jax.random.split(k_layers, model["num_layers"]))
    return {
        "embed": (0.02 * jax.random.normal(k_emb, (v, d), jnp.float32)
                  ).astype(dtype),
        "unembed": (jax.random.normal(k_un, (d, v), jnp.float32)
                    * d ** -0.5).astype(dtype),
        "final_norm": 1.0 + 0.1 * jax.random.normal(k_norm, (d,),
                                                    jnp.float32),
        "layers": layers,
    }


def seed_key(seed: int):
    """A PRNG key for any whole-number seed (64 bits kept)."""
    s = int(seed) % (1 << 64)
    return jax.random.fold_in(jax.random.PRNGKey(s & 0xFFFFFFFF), s >> 32)


def draw_weights(model: dict, seed: int, shardings=None):
    """Every weight from ``seed``, on the device, in one jitted call, in
    the type the model is served in. ``shardings``: the benchmark-layout
    tree of output shardings (a mesh), or None (one device)."""
    kw = {} if shardings is None else dict(out_shardings=shardings)
    return jax.jit(functools.partial(_draw, model=model), **kw)(
        seed_key(seed))


def to_program(w: dict) -> dict:
    """The program's parameter tree (``repro.models.lm.init_lm`` layout:
    one scanned slot of (attn, mlp) layers) over the same arrays."""
    L = w["layers"]
    mixer = {k: L[k] for k in ("wq", "wk", "wv", "wo", *_BIAS) if k in L}
    slot = {"ln1": {"scale": L["ln1"]}, "mixer": mixer,
            "ln2": {"scale": L["ln2"]},
            "ffn": {k: L[k] for k in ("w_gate", "w_up", "w_down")}}
    return {"embed": w["embed"], "final_norm": {"scale": w["final_norm"]},
            "unembed": w["unembed"], "slots": (slot,)}


def from_program(p: dict) -> dict:
    """Inverse of ``to_program`` (used to lay out shardings)."""
    slot = p["slots"][0]
    layers = dict(slot["mixer"], ln1=slot["ln1"]["scale"],
                  ln2=slot["ln2"]["scale"], **slot["ffn"])
    return {"embed": p["embed"], "unembed": p["unembed"],
            "final_norm": p["final_norm"]["scale"], "layers": layers}


def arch_config(model: dict, name: str):
    """The program's ``ArchConfig`` for the configuration's ``model``
    sizes: the published architecture, with no early-exit head."""
    from repro.configs.base import ArchConfig
    rd, dh = model["rope_dims"], model["head_dim"]
    return ArchConfig(
        name=name, family="dense", num_layers=model["num_layers"],
        d_model=model["d_model"], num_heads=model["num_heads"],
        num_kv_heads=model["num_kv_heads"], d_ff=model["d_ff"],
        vocab_size=model["vocab_size"], head_dim=dh,
        rope="full" if rd == dh else "partial", rope_partial_pct=rd / dh,
        rope_theta=float(model["rope_theta"]),
        qkv_bias=bool(model.get("qkv_bias", False)),
        norm_eps=float(model["norm_eps"]), dtype=model["dtype"],
        early_exit=None)


def build_engine(config: dict, cfg, devices):
    """The configuration's ``SlotEngine`` (and its mesh, or None)."""
    from repro.configs.base import AccelConfig, RunConfig, SHAPES_BY_NAME
    from repro.core import xaif
    from repro.serve.engine import SlotEngine
    policy = (xaif.DispatchPolicy.make(config["policy"])
              if config.get("policy") else AccelConfig())
    run = RunConfig(arch=cfg, shape=SHAPES_BY_NAME["decode_32k"],
                    accel=policy)
    mesh, kw = None, {}
    if config.get("mesh"):
        import numpy as np
        from jax.sharding import AxisType, Mesh
        from repro.dist.sharding import ShardingPolicy
        m = config["mesh"]
        n = int(np.prod(m["shape"]))
        mesh = Mesh(np.asarray(devices[:n]).reshape(m["shape"]),
                    tuple(m["axes"]),
                    axis_types=(AxisType.Auto,) * len(m["axes"]))
        kw = dict(mesh=mesh, sharding=ShardingPolicy(**m["policy"]))
    return SlotEngine(run, **config["engine"], **kw), mesh


def program_shardings(engine) -> Optional[dict]:
    """The engine's parameter shardings in the benchmark's layout (None
    without a mesh)."""
    sh = engine._shardings
    return None if sh is None else from_program(sh[0])


def check_layout(w: dict, cfg) -> None:
    """The re-nested weights must have exactly the program's tree and
    shapes; a mismatch is a harness fault, found before any request."""
    from repro.models import lm
    want = jax.eval_shape(functools.partial(lm.init_lm, cfg=cfg),
                          jax.random.PRNGKey(0))
    got = to_program(w)
    s = lambda t: jax.tree_util.tree_map(lambda a: (a.shape, str(a.dtype)),
                                         t)
    if s(got) != s(want):
        raise ValueError(f"weights do not match the program's layout:\n"
                         f"{s(got)}\n!=\n{s(want)}")
