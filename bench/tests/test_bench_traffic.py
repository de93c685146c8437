"""The traffic generator: seeded, the same work for every seed, and the
mixes' stated shapes."""
import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench import spec, traffic  # noqa: E402

TRAFFIC = spec.BENCH_DIR / "traffic"
BIG_SEED = 2**31 + 12345


def _mix(name):
    return spec.load_json(TRAFFIC / f"{name}.json")


def test_same_seed_same_schedule():
    a = traffic.generate(_mix("chat"), BIG_SEED, 30, 65024, 2560)
    b = traffic.generate(_mix("chat"), BIG_SEED, 30, 65024, 2560)
    assert len(a) == len(b)
    assert all(np.array_equal(x.prompt, y.prompt) and x.max_new == y.max_new
               and x.arrival == y.arrival for x, y in zip(a, b))


def test_every_seed_gets_the_same_work_in_another_order():
    mix = {k: v for k, v in _mix("chat").items() if k != "order_seed"}
    a = traffic.generate(mix, 1, 30, 65024, 2560)
    b = traffic.generate(mix, BIG_SEED, 30, 65024, 2560)
    assert sorted(len(r.prompt) for r in a) == sorted(len(r.prompt) for r in b)
    assert sorted(r.max_new for r in a) == sorted(r.max_new for r in b)
    ga, gb = np.diff([r.arrival for r in a]), np.diff([r.arrival for r in b])
    assert abs(ga.sum() - gb.sum()) < 1e-6
    assert np.allclose(sorted(ga), sorted(gb))
    assert [len(r.prompt) for r in a] != [len(r.prompt) for r in b]


@pytest.mark.parametrize("seeds", [(1, BIG_SEED), (7, 2**33 + 5)])
def test_order_seed_fixes_the_arrangement(seeds):
    """A mix with ``order_seed`` serves one arrangement on every seed; the
    run's seed draws only the token ids."""
    mix = _mix("chat")
    assert "order_seed" in mix
    a, b = (traffic.generate(mix, s, 51, 65024, 2560) for s in seeds)
    assert [(len(r.prompt), r.max_new, r.arrival) for r in a] == \
        [(len(r.prompt), r.max_new, r.arrival) for r in b]
    assert any(not np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    other = traffic.generate(dict(mix, order_seed=mix["order_seed"] + 1),
                             seeds[0], 51, 65024, 2560)
    assert [len(r.prompt) for r in other] != [len(r.prompt) for r in a]


def test_chat_lengths_follow_the_mix():
    mix = _mix("chat")
    reqs = traffic.generate(mix, 7, 30, 65024, 2560)
    span = mix["ramp_s"] + 30
    assert len(reqs) == round(mix["rate_hz"] * span)
    plen = np.array([len(r.prompt) for r in reqs])
    olen = np.array([r.max_new for r in reqs])
    assert plen.min() >= 64 and plen.max() <= 2048
    assert abs(np.median(plen) - 512) < 40
    assert olen.min() >= 16 and olen.max() <= 512
    assert abs(np.median(olen) - 128) < 12
    assert all(0 <= r.arrival < span for r in reqs)
    assert all(len(r.prompt) + r.max_new <= 2560 for r in reqs)


def test_stratified_sets():
    e = traffic.exponential_set(1000, 2.0)
    assert abs(e.mean() - 2.0) < 0.05
