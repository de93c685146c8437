"""The one traffic generator: reads a mix's parameters (``traffic/*.json``)
and draws an open-loop request schedule from the seed.

One kind of mix so far, ``poisson``: independent requests, exponential
gaps at ``rate_hz``, lognormal prompt and output lengths. A mix file may
name a ``base`` mix and override some of its parameters (``spec.py``).

Every seed gets the same work: each family of draws (gaps, prompt
lengths, output lengths) is the set of its distribution's quantiles at
``(i + 0.5) / n``, and the seed permutes it and draws the token ids. A mix
that states ``order_seed`` takes the permutations from that number
instead, so every run serves the same arrangement and the run's seed draws
only the token ids: where a window holds a few dozen requests, the
arrangement moves its tails more than the system does.

The schedule spans ``ramp_s + seconds``: the ramp fills the system to its
steady state before the measured window ``[ramp_s, ramp_s + seconds)``.
"""
from __future__ import annotations

from dataclasses import dataclass
from statistics import NormalDist
from typing import List

import numpy as np


@dataclass
class Req:
    prompt: np.ndarray          # [t] int32
    max_new: int
    arrival: float              # due time, seconds from stream start


def rng_for(seed: int) -> np.random.Generator:
    """A generator for any whole-number seed, negative or past 64 bits."""
    return np.random.default_rng(int(seed) % (1 << 64))


def _probs(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lognormal_set(n: int, spec: dict) -> np.ndarray:
    """``n`` lognormal lengths (``median``, ``sigma``) at stratified
    quantiles, clipped to [``min``, ``max``]."""
    nd = NormalDist()
    z = np.array([nd.inv_cdf(p) for p in _probs(n)])
    v = np.rint(spec["median"] * np.exp(spec["sigma"] * z))
    return np.clip(v, spec["min"], spec["max"]).astype(np.int64)


def exponential_set(n: int, mean: float) -> np.ndarray:
    return -mean * np.log1p(-_probs(n))


def _arrivals(rng, n: int, rate_hz: float) -> np.ndarray:
    """``n`` arrivals from 0 with the n - 1 stratified exponential gaps of
    ``rate_hz`` in a seeded order: every seed spans the same time."""
    gaps = rng.permutation(exponential_set(n - 1, 1.0 / rate_hz))
    return np.concatenate([[0.0], np.cumsum(gaps)])


def _tokens(rng, n: int, vocab: int) -> np.ndarray:
    return rng.integers(0, vocab, (int(n),), dtype=np.int32)


def poisson_mix(mix: dict, seed: int, seconds: float, vocab: int,
                max_len: int) -> List[Req]:
    rng = rng_for(seed)
    order = rng_for(mix["order_seed"]) if "order_seed" in mix else rng
    span = mix["ramp_s"] + seconds
    n = max(int(round(mix["rate_hz"] * span)), 1)
    arrivals = _arrivals(order, n, mix["rate_hz"])
    plen = order.permutation(lognormal_set(n, mix["prompt_len"]))
    olen = order.permutation(lognormal_set(n, mix["output_len"]))
    out = []
    for i in range(n):
        t = int(min(plen[i], max_len - olen[i]))
        out.append(Req(_tokens(rng, t, vocab), int(olen[i]),
                       float(arrivals[i])))
    return out


KINDS = {"poisson": poisson_mix}


def generate(mix: dict, seed: int, seconds: float, vocab: int,
             max_len: int) -> List[Req]:
    """The request schedule of ``mix`` for one run."""
    return KINDS[mix["kind"]](mix, seed, seconds, vocab, max_len)


def window(mix: dict, seconds: float) -> tuple:
    """(start, end) of the measured window on the stream's clock."""
    return float(mix["ramp_s"]), float(mix["ramp_s"] + seconds)


REHEARSAL_RATE_HZ = 4.0


def rehearsal_mix(mix: dict, r: float) -> dict:
    """``mix`` for a rehearsal: every token length multiplied by ``r`` (at
    least 1), since the tiny engine cannot hold the real lengths, and the
    rate raised to ``REHEARSAL_RATE_HZ`` where it is lower, so that a
    window of a few seconds holds requests."""
    def walk(v):
        if isinstance(v, dict):
            return {k: (max(int(x * r), 1) if k in ("median", "min", "max",
                                                     "len") else walk(x))
                    for k, x in v.items()}
        return v
    out = walk(mix)
    out["rate_hz"] = max(out["rate_hz"], REHEARSAL_RATE_HZ)
    return out
