"""End-to-end metric arithmetic on synthetic requests."""
import math
import sys
from dataclasses import dataclass, field
from pathlib import Path
from typing import List, Optional

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench import stats  # noqa: E402


@dataclass
class R:
    arrival: float
    t_first_token: Optional[float] = None
    t_admitted: Optional[float] = None
    reject_reason: Optional[str] = None
    itl: List[float] = field(default_factory=list)


def test_nearest_rank_percentile():
    v = list(range(1, 101))                       # 1..100
    assert stats.percentile(v, 95) == 95
    assert stats.percentile(v, 50) == 50
    assert stats.percentile([3.0], 95) == 3.0
    assert stats.percentile([1, 2, math.inf], 95) == math.inf
    with pytest.raises(ValueError):
        stats.percentile([], 95)


def test_ttft_p95_counts_failures_as_infinitely_late():
    served = [R(arrival=i, t_first_token=i + 0.01 * (i + 1))
              for i in range(19)]
    rejected = R(arrival=0.0, reject_reason="too-long: x")
    # 19 served (ttft 10..190 ms) + 1 refused: p95 is the 19th value
    assert stats.ttft_p95_ms(served + [rejected]) == pytest.approx(190.0)
    # with 2 refused of 20 the p95 is infinite
    assert stats.ttft_p95_ms(served[:18] + [rejected, rejected]) == math.inf


def test_itl_p95_pools_every_token():
    reqs = [R(0.0, 0.1, itl=[0.010] * 90), R(0.0, 0.1, itl=[0.050] * 10)]
    assert stats.itl_p95_ms(reqs) == pytest.approx(50.0)
    reqs = [R(0.0, 0.1, itl=[0.010] * 96), R(0.0, 0.1, itl=[0.050] * 4)]
    assert stats.itl_p95_ms(reqs) == pytest.approx(10.0)


def test_delivered_counts_only_the_window():
    firsts = [0.5, 1.5, 2.5]
    chunks = [(0.9, 8), (1.0, 8), (1.99, 4), (2.0, 8)]
    assert stats.delivered(firsts, chunks, 1.0, 2.0) == 1 + 8 + 4


def test_slo_met_with_failures_missed():
    ok = R(0.0, 0.2, itl=[0.02] * 20)
    slow_first = R(0.0, 1.5, itl=[0.02] * 20)
    slow_gaps = R(0.0, 0.2, itl=[0.02] * 18 + [0.5] * 2)   # own p95 = 0.5
    one_token = R(0.0, 0.3)                                 # no gaps
    refused = R(0.0, reject_reason="shed: x")
    never = R(0.0)                                          # no first token
    reqs = [ok, slow_first, slow_gaps, one_token, refused, never]
    assert stats.slo_met_pct(reqs, ttft_ms=1000, itl_ms=100) == \
        pytest.approx(100 * 2 / 6)


def test_due_in_window():
    reqs = [R(arrival=a) for a in (0.0, 4.99, 5.0, 34.99, 35.0)]
    assert [r.arrival for r in stats.due_in(reqs, 5.0, 35.0)] == [5.0, 34.99]
