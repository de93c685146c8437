"""Paper §V reproduction: train the seizure transformer + CNN with early
exit, sweep loss weights (0.001–0.1) and entropy thresholds (0.1–0.5), and
report exit rate + F1 at the paper's final operating points.

Paper claims to validate against:
  transformer: w=0.1,  th=0.45 -> 73 % exit rate, F1 0.6223 -> 0.53
  CNN:         w=0.01, th=0.35 -> 82 % exit rate, F1 0.57  -> 0.49
(absolute F1s depend on their private clinical dataset; on our synthetic
unbalanced bio-signal task we reproduce the STRUCTURE of the claim: high
exit rates at small F1 cost, and the sweep shape.)
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import AccelConfig
from repro.core.early_exit import (cross_entropy, multi_exit_loss,
                                   normalized_entropy)
from repro.data.pipeline import bio_signal_batches
from repro.models import cnn as paper_models

ACCEL = AccelConfig()


def f1_score(pred: np.ndarray, labels: np.ndarray) -> float:
    tp = float(np.sum((pred == 1) & (labels == 1)))
    fp = float(np.sum((pred == 1) & (labels == 0)))
    fn = float(np.sum((pred == 0) & (labels == 1)))
    denom = tp + 0.5 * (fp + fn)
    return tp / denom if denom else 0.0


def _make_train(model_cfg, forward, init, loss_weight: float,
                lr: float = 3e-3):
    cfg_ee = dataclasses.replace(model_cfg.early_exit,
                                 loss_weight=loss_weight)

    def loss_fn(params, x, y):
        logits, exits = forward(params, x, model_cfg, ACCEL)
        # class-weighted CE for the unbalanced data (paper's domain issue)
        w = jnp.where(y == 1, 4.0, 1.0)
        lf = _weighted_ce(logits, y, w)
        le = _weighted_ce(exits[0], y, w)
        return lf + loss_weight * le

    @jax.jit
    def step(params, opt, x, y):
        loss, g = jax.value_and_grad(loss_fn)(params, x, y)
        new_p, new_o = {}, {}
        m, v, t = opt
        t = t + 1
        upd_m = jax.tree_util.tree_map(lambda mm, gg: 0.9 * mm + 0.1 * gg, m, g)
        upd_v = jax.tree_util.tree_map(lambda vv, gg: 0.999 * vv + 0.001 * gg * gg,
                                       v, g)
        params = jax.tree_util.tree_map(
            lambda p, mm, vv: p - lr * (mm / (1 - 0.9 ** t))
            / (jnp.sqrt(vv / (1 - 0.999 ** t)) + 1e-8),
            params, upd_m, upd_v)
        return params, (upd_m, upd_v, t), loss

    return step


def _weighted_ce(logits, labels, w):
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), -1)
    ll = jnp.take_along_axis(logp, labels[:, None], axis=-1)[:, 0]
    return -jnp.sum(ll * w) / jnp.sum(w)


def train_model(kind: str, loss_weight: float, steps: int = 300,
                batch: int = 64, seed: int = 0):
    if kind == "cnn":
        cfg = paper_models.SeizureCNNConfig()
        params = paper_models.init_cnn(jax.random.PRNGKey(seed), cfg)
        forward = paper_models.forward_cnn
    else:
        cfg = paper_models.SeizureTransformerConfig()
        params = paper_models.init_transformer(jax.random.PRNGKey(seed), cfg)
        forward = paper_models.forward_transformer
    step = _make_train(cfg, forward, None, loss_weight)
    zeros = jax.tree_util.tree_map(jnp.zeros_like, params)
    opt = (zeros, jax.tree_util.tree_map(jnp.zeros_like, params), 0)
    data = bio_signal_batches(batch, cfg.window, cfg.in_channels, seed=seed)
    for i, b in zip(range(steps), data):
        params, opt, loss = step(params, opt, jnp.asarray(b["inputs"]),
                                 jnp.asarray(b["labels"]))
    return cfg, params, forward


def evaluate(cfg, params, forward, threshold: float, n_eval: int = 2048,
             seed: int = 1) -> Dict[str, float]:
    data = bio_signal_batches(256, cfg.window, cfg.in_channels, seed=seed)
    preds, exit_preds, merged, labels, exited = [], [], [], [], []
    fwd = jax.jit(lambda p, x: forward(p, x, cfg, ACCEL))
    seen = 0
    for b in data:
        if seen >= n_eval:
            break
        logits, exits = fwd(params, jnp.asarray(b["inputs"]))
        ent = normalized_entropy(exits[0])
        mask = np.asarray(ent < threshold)
        pf = np.argmax(np.asarray(logits), -1)
        pe = np.argmax(np.asarray(exits[0]), -1)
        preds.append(pf)
        exit_preds.append(pe)
        merged.append(np.where(mask, pe, pf))
        exited.append(mask)
        labels.append(b["labels"])
        seen += 256
    preds, merged = np.concatenate(preds), np.concatenate(merged)
    labels, exited = np.concatenate(labels), np.concatenate(exited)
    return {
        "exit_rate": float(np.mean(exited)),
        "f1_full": f1_score(preds, labels),
        "f1_early_exit": f1_score(merged, labels),
        "accuracy_full": float(np.mean(preds == labels)),
        "accuracy_early_exit": float(np.mean(merged == labels)),
    }


def sweep(kind: str, weights=(0.001, 0.01, 0.1),
          thresholds=(0.1, 0.2, 0.35, 0.45, 0.5), steps=300):
    rows = []
    for w in weights:
        cfg, params, forward = train_model(kind, w, steps=steps)
        for th in thresholds:
            r = evaluate(cfg, params, forward, th)
            rows.append({"model": kind, "weight": w, "threshold": th, **r})
    return rows


def paper_operating_points(steps=300):
    """The two final configurations of §V."""
    out = {}
    for kind, w, th in (("transformer", 0.1, 0.45), ("cnn", 0.01, 0.35)):
        cfg, params, forward = train_model(kind, w, steps=steps)
        out[kind] = {"weight": w, "threshold": th,
                     **evaluate(cfg, params, forward, th)}
    return out


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    import json
    print(json.dumps(paper_operating_points(), indent=2))
