"""The program's own tracing: ``serve.*`` host spans that pair with
``ServeReport.chunks`` by id, the queue-wait counters on a fake clock, and
the XAIF scopes and stable name of the lowered decode chunk."""
import re

import jax
import numpy as np
import pytest

from repro.configs.base import AccelConfig, RunConfig, SHAPES_BY_NAME, get_arch
from repro.core import xaif
from repro.models import lm
from repro.serve import scheduler as sch
from repro.serve.engine import SlotEngine
from repro.serve.overload import OverloadConfig
from repro.serve.scheduler import Request, serve

CHILDREN = {"serve.grow_pages": "serve.chunk", "serve.decode": "serve.chunk",
            "serve.fetch": "serve.chunk", "serve.retire": "serve.chunk",
            "serve.prefill": "serve.admit",
            "serve.first_token": "serve.admit"}


def _engine(policy=None, capacity=2, max_len=48, chunk=4):
    cfg = get_arch("chatglm3-6b").reduced()
    run = RunConfig(arch=cfg, shape=SHAPES_BY_NAME["decode_32k"],
                    accel=policy or AccelConfig())
    params = lm.init_lm(jax.random.PRNGKey(0), cfg)
    eng = SlotEngine(run, capacity=capacity, max_len=max_len, chunk=chunk,
                     paged=True, page_size=8)
    return cfg, eng, params


def _requests(cfg, lens, budgets, arrivals=None):
    rng = np.random.default_rng(7)
    arrivals = arrivals or [0.0] * len(lens)
    return [Request(rid=i, prompt=rng.integers(0, cfg.vocab_size, (t,),
                                               dtype=np.int32),
                    max_new_tokens=n, arrival=a)
            for i, (t, n, a) in enumerate(zip(lens, budgets, arrivals))]


def _serve_spans(trace_dir):
    """[(name, start_ns, end_ns, stats)] of the serve.* host spans."""
    import glob
    from jax.profiler import ProfileData
    path, = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out.extend((e.name, e.start_ns, e.start_ns + e.duration_ns,
                            dict(e.stats)) for e in line.events
                           if e.name.startswith("serve."))
    return out


@pytest.mark.parametrize("overload", [None, OverloadConfig()],
                         ids=["slot", "overload"])
def test_stream_spans_pair_with_chunk_records(tmp_path, overload):
    cfg, eng, params = _engine()
    reqs = _requests(cfg, [5, 11, 7, 3], [6, 9, 5, 7])
    with jax.profiler.trace(str(tmp_path)):
        report = serve(eng, params, reqs, overload=overload)
    spans = _serve_spans(tmp_path)
    recs = report.chunks
    assert [r.index for r in recs] == list(range(len(recs))) and recs
    assert sum(r.produced for r in recs) + len(reqs) == report.decode_tokens
    for r in recs:
        assert r.t_start <= r.t_dispatched <= r.t_fetched <= r.t_end
    # exactly one serve.chunk per record, with the record's id
    chunk_ids = sorted(s["chunk"] for n, _, _, s in spans
                       if n == "serve.chunk")
    assert chunk_ids == [r.index for r in recs]
    # every child inside a parent of the same chunk / request
    parents = {}
    for n, a, b, s in spans:
        if n in ("serve.chunk", "serve.admit"):
            key = (n, s["chunk"] if n == "serve.chunk" else s["rid"])
            parents.setdefault(key, []).append((a, b))
    assert {k[1] for k in parents if k[0] == "serve.admit"} == \
        {r.rid for r in reqs}
    children = {}
    for n, a, b, s in spans:
        if n in CHILDREN:
            key = s["chunk"] if CHILDREN[n] == "serve.chunk" else s["rid"]
            assert any(pa <= a and b <= pb
                       for pa, pb in parents[(CHILDREN[n], key)]), (n, s)
            children.setdefault(key if CHILDREN[n] == "serve.chunk"
                                else ("rid", key), set()).add(n)
    for r in recs:
        assert children[r.index] == {"serve.grow_pages", "serve.decode",
                                     "serve.fetch", "serve.retire"}
    for r in reqs:
        assert children[("rid", r.rid)] == {"serve.prefill",
                                            "serve.first_token"}


class _FakeClock:
    """``time`` as ``scheduler.py`` reaches it: ``perf_counter`` and
    ``sleep`` on a clock that moves only when told."""

    def __init__(self):
        self.t = 100.0

    def perf_counter(self):
        return self.t

    def sleep(self, s):
        self.t += s


def test_queue_waits_split_by_what_held_the_scheduler(monkeypatch):
    """A prefill takes 0.5 s and a decode chunk 1.0 s on the fake clock.
    r0 is admitted at 0 and chunk 0 runs [0.5, 1.5); r1, due at 1.3, is
    admitted at 1.5 ([1.5, 2.0)); r2, due at 1.7 inside r1's admission,
    waits through it and then chunk 1 ([2.0, 3.0))."""
    cfg, eng, params = _engine(capacity=4)
    clock = _FakeClock()
    monkeypatch.setattr(sch, "time", clock)
    decode, prefill = eng.decode, eng.prefill_into

    def slow_decode(*a, **k):
        clock.t += 1.0
        return decode(*a, **k)

    def slow_prefill(*a, **k):
        clock.t += 0.5
        return prefill(*a, **k)

    eng.decode, eng.prefill_into = slow_decode, slow_prefill
    reqs = _requests(cfg, [5, 6, 7], [16, 8, 8], [0.0, 1.3, 1.7])
    report = serve(eng, params, reqs, realtime=True)
    r0, r1, r2 = reqs
    assert [r.t_admitted for r in reqs] == pytest.approx([0.0, 1.5, 3.0])
    assert report.chunks[0][1:5] == pytest.approx((0.5, 1.5, 1.5, 1.5))
    assert (r0.wait_decode_s, r0.wait_prefill_s) == (0.0, 0.0)
    # what was left of chunk 0 when r1 fell due
    assert r1.wait_decode_s == pytest.approx(0.2)
    assert r1.wait_prefill_s == 0.0
    # what was left of r1's admission, then the whole of chunk 1
    assert r2.wait_prefill_s == pytest.approx(0.3)
    assert r2.wait_decode_s == pytest.approx(1.0)
    for r in reqs:
        assert r.wait_decode_s + r.wait_prefill_s <= \
            r.t_admitted - r.arrival + 1e-9


@pytest.mark.parametrize("backend", ["ref", "pallas"])
def test_decode_program_names_its_xaif_ops(backend):
    """The decode chunk's HLO names its program ``jit_decode_chunk`` and
    carries the scope of each XAIF backend that ran in its ``op_name``s."""
    policy = xaif.DispatchPolicy.make({"gemm": backend,
                                       "attn_decode_paged": backend})
    _, eng, params = _engine(policy)
    cache, st = eng.init_state()
    # the optimized HLO, as a traced benchmark run keeps it
    hlo = eng._decode.lower(params, cache, st).compile().as_text()
    assert re.search(r"HloModule jit_decode_chunk\b", hlo)
    names = set(re.findall(r'op_name="([^"]*)"', hlo))
    for op in ("attn_decode_paged", "gemm"):
        scope = f"xaif.{op}.{backend}"
        assert any(scope in n and "/decode_chunk/" in n for n in names), op


@pytest.mark.parametrize("overload", [None, OverloadConfig()],
                         ids=["slot", "overload"])
def test_live_pages_pct_is_the_mean_share_of_table_entries(monkeypatch,
                                                           overload):
    """``stats["attn_live_pages_pct"]``: the mean over decode chunks of the
    share of page-table entries that hold a page, as counted entry by entry
    from the allocator's table when each chunk is dispatched."""
    counted = []
    decode = SlotEngine.decode

    def counting_decode(self, params, cache, state):
        table = np.asarray(cache.page_table)
        held = sum(1 for row in table for pid in row if pid >= 0)
        counted.append(100.0 * held / table.size)
        return decode(self, params, cache, state)

    monkeypatch.setattr(SlotEngine, "decode", counting_decode)
    cfg, eng, params = _engine(capacity=3)
    report = serve(eng, params, _requests(cfg, [5, 17, 9, 30],
                                          [6, 3, 9, 4]),
                   overload=overload)
    pct = report.stats["attn_live_pages_pct"]
    assert len(counted) == len(report.chunks) > 0
    assert 0.0 < pct <= 100.0
    assert pct == pytest.approx(float(np.mean(counted)))
