"""What decides ``correct``: the served tokens against the plain reference.

Once the window has closed and the program's state is freed, a sample of
the finished requests, drawn from the seed and holding the one with the
longest sequence, is run through the configuration's reference in float32
(``references/<reference>.py``): once over each prompt with its served
tokens. For every served token the gap is the reference's best logit at
that position minus the reference's logit of the served token (0 where the
program chose the reference's argmax). The number compared is the widest
gap over the sample; greedy decoding makes it valid.

The control reads the same positions with the reference computed in the
precision below the configuration's (``quant``): at each position it takes
the token the lower precision puts first, and reads that token's gap.
"""
from __future__ import annotations

import importlib
from typing import List, Optional

import numpy as np


def reference_module(config: dict):
    return importlib.import_module(f"bench.references.{config['reference']}")


def sample(requests: List, seed: int, min_tokens: int,
           max_requests: int) -> List:
    """Finished requests for the comparison: the longest (prompt + served
    tokens), then others in an order drawn from the seed, until
    ``min_tokens`` served tokens or ``max_requests`` requests."""
    done = [r for r in requests
            if r.reject_reason is None and len(r.tokens) == r.max_new_tokens]
    if not done:
        return []
    done.sort(key=lambda r: (-(len(r.prompt) + len(r.tokens)), r.rid))
    rng = np.random.default_rng((int(seed) % (1 << 64)) ^ 0x5EED)
    rest = [done[i] for i in 1 + rng.permutation(len(done) - 1)]
    out, n = [done[0]], len(done[0].tokens)
    for r in rest:
        if n >= min_tokens or len(out) >= max_requests:
            break
        out.append(r)
        n += len(r.tokens)
    return out


def gaps(weights: dict, config: dict, reqs: List, width: int,
         quant: Optional[str] = None) -> np.ndarray:
    """The gap of every served token of ``reqs`` (``quant=None``), or of
    the token the ``quant`` reference puts first at the same positions."""
    ref = reference_module(config)
    model = config["model"]
    out = []
    for r in reqs:
        seq = np.concatenate([np.asarray(r.prompt, np.int32),
                              np.asarray(r.tokens[:-1], np.int32)])
        toks = np.zeros((width,), np.int32)
        toks[:len(seq)] = seq                     # causal: padding is inert
        rows = np.arange(len(r.prompt) - 1, len(seq))
        full = np.asarray(ref.logits(weights, model, toks, rows),
                          np.float64)
        best = full.max(axis=-1)
        if quant is None:
            chosen = np.asarray(r.tokens)
        else:
            low = np.asarray(ref.logits(weights, model, toks, rows,
                                        quant=quant))
            chosen = low.argmax(axis=-1)
        out.append(best - full[np.arange(len(rows)), chosen])
    return np.concatenate(out) if out else np.zeros((0,))
