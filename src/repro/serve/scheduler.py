"""Continuous-batching scheduler: fixed-capacity slots over the SlotEngine.

Host-side counterpart of ``serve.engine``: requests queue, get admitted into
free slots (one bucketed prefill each), decode advances ALL occupied slots
in jitted chunks, and finished slots are retired and backfilled without
re-tracing — the decode graph is compiled once per capacity.

With a PAGED engine, admission is by free PAGES rather than free slots
alone (a short request no longer strands a worst-case ``max_len`` KV row),
pages are grown on demand between decode chunks (covered by the admission
reservation, so growth never fails) and retirement returns a request's
pages to the free list. All of it is host bookkeeping over
``serve.paging.PageAllocator``; the device page table is pushed once per
chunk when dirty.

Prompts that cannot fit (``len(prompt) + max_new_tokens > max_len``) are
REJECTED — ``Request.reject_reason`` is set and the request is returned to
the caller unserved, never silently truncated.

The host's only per-chunk work is one fetch of (tokens, slot state) and the
free-list bookkeeping; token validity is reconstructed from the per-slot
generated counts, so no device round-trip happens inside the token loop.

TRACING: ``serve()`` opens ``jax.profiler.TraceAnnotation`` spans, which
record into a running profiler's trace on the device planes' clock and
cost nothing without one. ``serve.admit`` (``rid``, ``bucket``) holds
``serve.prefill`` and ``serve.first_token``; ``serve.chunk`` (``chunk``,
``live``) holds ``serve.grow_pages``, ``serve.decode``, ``serve.fetch``
and ``serve.retire``; ``serve.wait`` is the realtime sleep. Spans of one
request share ``rid=``, spans of one chunk ``chunk=`` (from 0 in each
stream). Always on, on the serve clock: ``ServeReport.chunks`` (one
:class:`ChunkRecord` a chunk, ``index`` = the span's ``chunk``) and each
request's ``wait_decode_s`` / ``wait_prefill_s``.

OVERLOAD CONTROL: pass ``serve(..., overload=OverloadConfig(...))`` to run
the stream through :class:`repro.serve.overload.OverloadScheduler` instead —
priority-aged admission, optimistic paging with preemption (host swap or
re-prefill resume), SLO shedding and chunked prefill. This base scheduler
keeps the PR 3 worst-case-reservation behavior and is the reject-only
baseline the overload benchmarks compare against.
"""
from __future__ import annotations

import bisect
import itertools
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Callable, Dict, List, NamedTuple, Optional, Tuple

import jax
import numpy as np

from repro.serve.engine import SlotEngine
from repro.serve.paging import PageAllocator

# host span in a running profiler's trace; a no-op without one
_span = jax.profiler.TraceAnnotation


@dataclass
class Request:
    rid: int
    prompt: np.ndarray                 # [t] int32
    max_new_tokens: int
    arrival: float = 0.0               # seconds from stream start
    # optional per-request sample seed: identical seeded requests replay
    # the same sample stream regardless of slot placement (greedy ignores)
    seed: Optional[int] = None
    # optional early stop: retire at the FIRST emission of this token id
    # (kept inclusive), so ``max_new_tokens`` is a reservation CAP, not the
    # realized length — the worst-case-vs-actual gap paged admission exploits
    stop_token: Optional[int] = None
    # -- overload-control knobs (all optional) -----------------------------
    priority: int = 0                  # higher = more important
    deadline_ms: Optional[float] = None   # complete within this, or shed
    slo_ttft_ms: Optional[float] = None   # first token within this, or shed

    # lifecycle (filled by the scheduler)
    t_admitted: Optional[float] = None
    t_first_token: Optional[float] = None
    t_finished: Optional[float] = None
    reject_reason: Optional[str] = None
    preemptions: int = 0
    tokens: List[int] = field(default_factory=list)
    itl: List[float] = field(default_factory=list)  # inter-token gaps (s)
    # parts of [arrival, t_admitted] in which a decode chunk, or another
    # request's admission, held the scheduler (s); the rest of the queue
    # time waited for pool space or on the host
    wait_decode_s: float = 0.0
    wait_prefill_s: float = 0.0

    @property
    def latency(self) -> float:
        return self.t_finished - self.arrival

    @property
    def ttft(self) -> Optional[float]:
        if self.t_first_token is None:
            return None
        return self.t_first_token - self.arrival


def _pctiles(vals) -> Dict[str, float]:
    a = np.asarray(vals, np.float64)
    if a.size == 0:
        nan = float("nan")
        return {"p50": nan, "p99": nan, "mean": nan, "max": nan}
    return {"p50": float(np.percentile(a, 50)),
            "p99": float(np.percentile(a, 99)),
            "mean": float(np.mean(a)), "max": float(np.max(a))}


class ChunkRecord(NamedTuple):
    """One decode chunk of a ``serve()`` stream, on the serve clock (s).
    ``t_fetched - t_dispatched`` is the host's wait for the device; a chunk
    that dispatched nothing has both at ``t_end``."""
    index: int              # ``chunk=`` of its serve.* spans
    t_start: float
    t_dispatched: float     # the decode call returned
    t_fetched: float        # its tokens and slot state reached the host
    t_end: float            # retirement done
    live: int               # occupied slots when the chunk began
    produced: int           # valid tokens it produced


@dataclass
class ServeReport:
    requests: List[Request]
    wall_s: float
    decode_tokens: int
    stats: Dict[str, float]
    chunks: List[ChunkRecord] = field(default_factory=list)

    @property
    def served(self) -> List[Request]:
        return [r for r in self.requests if r.reject_reason is None]

    @property
    def rejected(self) -> List[Request]:
        return [r for r in self.requests if r.reject_reason is not None]

    @property
    def tokens_per_s(self) -> float:
        return self.decode_tokens / max(self.wall_s, 1e-9)

    @property
    def completion_rate(self) -> float:
        return len(self.served) / max(len(self.requests), 1)

    def latency_percentiles(self) -> Dict[str, float]:
        return _pctiles([r.latency for r in self.served])

    def ttft_percentiles(self, min_priority: Optional[int] = None
                         ) -> Dict[str, float]:
        """Time-to-first-token percentiles over served requests (optionally
        only those with ``priority >= min_priority`` — the SLO class the
        overload benchmarks assert on)."""
        return _pctiles([r.ttft for r in self.served
                         if r.ttft is not None
                         and (min_priority is None
                              or r.priority >= min_priority)])

    def itl_percentiles(self) -> Dict[str, float]:
        """Inter-token-latency percentiles pooled over every served
        request's decode gaps (chunk-granular: each chunk's wall time is
        spread over the tokens it produced)."""
        gaps: List[float] = []
        for r in self.served:
            gaps.extend(r.itl)
        return _pctiles(gaps)

    def breakdown(self) -> Dict[str, float]:
        """Mean per-request time split: queue (arrival -> admission),
        prefill (admission -> first token), decode (first token -> done)."""
        done = [r for r in self.served if r.t_finished is not None
                and r.t_first_token is not None and r.t_admitted is not None]
        if not done:
            nan = float("nan")
            return {"queue_s": nan, "prefill_s": nan, "decode_s": nan}
        return {
            "queue_s": float(np.mean(
                [max(r.t_admitted - r.arrival, 0.0) for r in done])),
            "prefill_s": float(np.mean(
                [max(r.t_first_token - r.t_admitted, 0.0) for r in done])),
            "decode_s": float(np.mean(
                [max(r.t_finished - r.t_first_token, 0.0) for r in done])),
        }


# admit() outcomes
ADMITTED = "admitted"
FULL = "full"          # retry when a slot / pages free up
REJECTED = "rejected"  # can never be served by this engine

# -- reject-reason codes ----------------------------------------------------
# Every ``Request.reject_reason`` the stack sets is "<code>: <detail>" with
# <code> one of REJECT_REASONS. Callers branch on the code prefix (or just
# ``reason is None`` for served); the detail stays free-form for humans.
REASON_SHED = "shed"              # load shedding / unservable by this pool
REASON_DEADLINE = "deadline"      # completion deadline infeasible
REASON_TTFT = "ttft-slo"          # first-token SLO already missed
REASON_TOO_LONG = "too-long"      # prompt + budget exceeds engine max_len
REASON_NAN = "nan-quarantined"    # non-finite logits: slot quarantined
REJECT_REASONS = (REASON_SHED, REASON_DEADLINE, REASON_TTFT,
                  REASON_TOO_LONG, REASON_NAN)


def reject_reason(code: str, detail: str) -> str:
    """Format a ``Request.reject_reason`` as ``"<code>: <detail>"``."""
    assert code in REJECT_REASONS, code
    return f"{code}: {detail}"


class SlotScheduler:
    """Admission / retirement / backfill over a SlotEngine's slot batch."""

    # overload subclass flips this; the allocator then admits on current
    # free pages and raises PoolExhausted instead of asserting
    _optimistic = False

    def __init__(self, engine: SlotEngine, params):
        self.engine = engine
        # one device_put per stream: on a mesh this commits the params to
        # their sharding so every chunk hits the jit fast path (identity on
        # a single device)
        self.params = engine.place_params(params)
        resident = None
        if engine.persistent_prefix_index and engine.resident is not None:
            # resume the previous serve() call's pool: radix index, page
            # refcounts and the device cache stay warm, so recurring
            # prefixes hit on the SECOND stream. Popped before reuse — the
            # engine never holds a handle to a donated cache.
            resident = engine.resident
            engine.resident = None
        if resident is not None:
            self.cache, self.state, self.alloc = resident
            self.alloc.optimistic = self._optimistic
        else:
            self.cache, self.state = engine.init_state()
            self.alloc: Optional[PageAllocator] = None
            if engine.paged:
                self.alloc = PageAllocator(
                    engine.num_pages, engine.capacity, engine.max_pages,
                    engine.page_size, sharing=engine.prefix_sharing,
                    optimistic=self._optimistic)
        if self.alloc is not None:
            # chaos: the engine's injector also covers host page allocation
            self.alloc.injector = engine.injector
        self.free: deque = deque(range(engine.capacity))
        self.occupant: Dict[int, Request] = {}       # slot -> request
        self._gen_seen: Dict[int, int] = {}          # slot -> tokens recorded
        self._true_len: Dict[int, int] = {}          # slot -> prompt length
        self._budget: Dict[int, int] = {}            # slot -> admission budget
        self._t_last: Dict[int, float] = {}          # slot -> last token time
        self.clock: Optional[Callable[[], float]] = None   # set by serve()
        self.max_concurrency = 0                     # peak occupied slots
        self.shared_tokens = 0                       # prompt tokens NOT prefilled
        self.shared_admissions = 0                   # fork-point admissions
        # serve-clock (start, end) of each admission that ran a prefill
        self.admissions: List[Tuple[float, float]] = []
        self.chunk_id = 0                            # set by serve()
        # (t_dispatched, t_fetched) of the last step_chunk's decode
        self.chunk_marks: Optional[Tuple[float, float]] = None
        # per paged chunk: % of the page table's entries that hold a page,
        # i.e. the share of the table paged decode attention reads
        self.live_pages_pct: List[float] = []

    def _now(self, fallback: float) -> float:
        return self.clock() if self.clock is not None else fallback

    # -- admission ---------------------------------------------------------

    def admit(self, req: Request, now: float,
              prompt: Optional[np.ndarray] = None,
              budget: Optional[int] = None) -> str:
        """Prefill ``req`` into a free slot. Returns ADMITTED, FULL (at
        capacity — retry later) or REJECTED (impossible request — the
        caller gets it back with ``reject_reason`` set, NOT truncated).

        ``prompt``/``budget`` override the request's own (the overload
        scheduler resumes a preempted request by re-admitting its
        prompt ++ generated tokens with the REMAINING budget)."""
        prompt = req.prompt if prompt is None else prompt
        budget = req.max_new_tokens if budget is None else budget
        t = int(prompt.shape[0])
        if t + budget > self.engine.max_len:
            req.reject_reason = reject_reason(
                REASON_TOO_LONG,
                f"prompt ({t}) + max_new_tokens ({budget}) "
                f"exceeds engine max_len ({self.engine.max_len})")
            return REJECTED
        if not self.free:
            return FULL
        bucket = self.engine._bucket(t)
        t0 = self._now(now)
        with _span("serve.admit", rid=req.rid, bucket=bucket):
            res = self._admit_free(req, now, prompt, budget, t, bucket)
        if res == ADMITTED:
            self.admissions.append((t0, self._now(now)))
        return res

    def _admit_free(self, req: Request, now: float, prompt: np.ndarray,
                    budget: int, t: int, bucket: int) -> str:
        """``admit`` once a slot is free: fork-point admission against the
        prefix index, else a bucketed prefill. ADMITTED or FULL."""
        if self.alloc is not None and self.alloc.index is not None:
            res = self._admit_shared(req, now, prompt, budget, t)
            if res is not None:
                return res                           # ADMITTED
        page_ids = None
        if self.alloc is not None:
            if not self.alloc.can_admit(bucket, t, budget):
                return FULL                          # admission by free pages
            slot = self.free.popleft()
            page_ids = self.alloc.admit(slot, bucket, t, budget)
        else:
            slot = self.free.popleft()
        with _span("serve.prefill", rid=req.rid):
            self.cache, self.state, tok0 = self.engine.prefill_into(
                self.params, self.cache, self.state, prompt, slot,
                budget, page_ids=page_ids, seed=req.seed)
        # (the jitted fill wrote this slot's device table row; any OTHER
        # pending mirror changes — e.g. rows cleared by release() — keep
        # alloc.dirty set and are pushed before the next decode chunk.
        # That push must land before a freed page is re-read: a retired
        # slot's stale device row would otherwise route its dead-slot
        # appends into a page that now belongs to someone else.)
        if self.alloc is not None and self.alloc.index is not None:
            # index the prompt's full pages (their KV lands before any
            # matching reader's gather — device program order)
            self.alloc.register(np.asarray(prompt), slot)
        return self._finish_admit(req, slot, tok0, now, t, budget)

    def _admit_shared(self, req: Request, now: float, prompt: np.ndarray,
                      budget: int, t: int):
        """Fork-point admission against the prefix index. Returns ADMITTED
        or None — either no indexed prefix, or the COW/suffix region cannot
        be reserved right now. Bucket rounding can make the shared
        reservation LARGER than the standard one (rem + bucket(t - start)
        may exceed bucket(t)), so a failed check falls through to the
        standard prefill path rather than reporting FULL."""
        prompt = np.asarray(prompt)
        pages, boundary, rem = self.alloc.match(prompt)
        if not pages:
            return None                              # min share: 1 full page
        if boundary is None:
            rem = 0
        ps = self.engine.page_size
        start = len(pages) * ps + rem
        suffix_bucket = self.engine._bucket(t - start)
        if not self.alloc.can_admit_shared(pages, boundary, rem,
                                           suffix_bucket, t, budget):
            return None
        slot = self.free.popleft()
        prefix_ids, region_ids = self.alloc.admit_shared(
            slot, pages, boundary, rem, suffix_bucket, t, budget)
        if rem > 0:
            # copy-on-write: the boundary page is duplicated BEFORE the
            # suffix prefill appends into it — the donor's page is never
            # touched by this slot
            self.cache = self.engine.copy_page(self.cache, int(boundary),
                                               int(region_ids[0]))
        with _span("serve.prefill", rid=req.rid):
            self.cache, self.state, tok0 = self.engine.prefill_into_shared(
                self.params, self.cache, self.state, prompt, start, slot,
                budget, prefix_ids, region_ids,
                self.alloc.table[slot], seed=req.seed)
        self.alloc.register(prompt, slot)
        self.shared_tokens += start
        self.shared_admissions += 1
        return self._finish_admit(req, slot, tok0, now, t, budget)

    def _finish_admit(self, req: Request, slot: int, tok0, now: float,
                      t: int, budget: int) -> str:
        with _span("serve.first_token", rid=req.rid):
            tok_i = int(tok0)                        # device sync: prefill done
        t_tok = max(self._now(now), req.arrival)
        if req.t_admitted is None:
            req.t_admitted = now
        if req.t_first_token is None:
            req.t_first_token = t_tok
        req.tokens.append(tok_i)                     # per-REQUEST fetch
        self.occupant[slot] = req
        self._gen_seen[slot] = 1
        self._true_len[slot] = t
        self._budget[slot] = budget
        self._t_last[slot] = t_tok
        self.max_concurrency = max(self.max_concurrency, len(self.occupant))
        return ADMITTED

    def admission_round(self, waiting: deque, now: float,
                        realtime: bool) -> bool:
        """Admit everything currently admissible, FIFO in arrival order.
        Returns True if any request left the queue."""
        progressed = False
        while waiting and self.free:
            if realtime and waiting[0].arrival > now:
                break
            req = waiting[0]
            res = self.admit(req, max(now, req.arrival))
            if res == FULL:
                break
            progressed = True
            waiting.popleft()                        # ADMITTED or REJECTED
        return progressed

    # -- decode + retire ---------------------------------------------------

    def _grow_pages(self) -> None:
        """On-demand page allocation before a chunk: every live slot gets
        coverage for the positions this chunk can ACCEPT (reservation-backed,
        so the pops cannot fail). ``tokens_per_chunk`` is chunk×(k+1) under
        speculation — a chunk may realize that many tokens per slot; verify
        rows past the covered positions route to scratch and are never part
        of an accepted prefix this chunk."""
        chunk = self.engine.tokens_per_chunk
        for slot in self.occupant:
            gen = self._gen_seen[slot]
            live_steps = min(chunk, self._budget[slot] - gen)
            if live_steps <= 0:
                continue                              # done: appends pinned
            pos_now = self._true_len[slot] + gen - 1
            self.alloc.ensure(slot, pos_now + live_steps - 1)
        self._push_table()

    def _push_table(self) -> None:
        if self.alloc is not None and self.alloc.dirty:
            self.cache = self.engine.set_page_table(self.cache,
                                                    self.alloc.table)
            self.alloc.dirty = False

    def _retire(self, slot: int, req: Request, now: float,
                register: bool = True) -> None:
        """Return a finished slot to the pool (host bookkeeping only).
        ``register=False`` skips prefix indexing — quarantined slots hold
        poisoned KV that must never be shared."""
        del self.occupant[slot]
        del self._gen_seen[slot]
        del self._true_len[slot]
        del self._budget[slot]
        self._t_last.pop(slot, None)
        if self.alloc is not None:
            if register and self.alloc.index is not None:
                # index the retired chain so FUTURE requests can share it.
                # KV is resident through position t + len(tokens) - 2 only
                # (the final token was never fed back), hence tokens[:-1].
                # The invariant survives preemption: a resumed request's
                # chain is its ORIGINAL prompt ++ every generated token.
                chain = np.concatenate([
                    np.asarray(req.prompt, np.int64),
                    np.asarray(req.tokens[:-1], np.int64)])
                self.alloc.register(chain, slot)
            self.alloc.release(slot)                 # pages -> free list
        self.free.append(slot)                       # backfill: host-only

    def step_chunk(self, now: float) -> int:
        """One jitted decode chunk + ONE host fetch; retire finished slots.
        Returns the number of valid tokens produced this chunk and leaves
        (t_dispatched, t_fetched) in ``chunk_marks``."""
        c = self.chunk_id
        if self.alloc is not None:
            with _span("serve.grow_pages", chunk=c):
                self._grow_pages()
            table = self.alloc.table
            self.live_pages_pct.append(
                100.0 * np.count_nonzero(table >= 0) / table.size)
        with _span("serve.decode", chunk=c):
            self.cache, self.state, toks = self.engine.decode(
                self.params, self.cache, self.state)
        t_dispatched = self._now(now)
        # the single per-chunk host transfer: the wait for the device
        with _span("serve.fetch", chunk=c):
            toks_np = np.asarray(toks)
            gen_np = np.asarray(self.state.generated)
            done_np = np.asarray(self.state.done)
            quar_np = np.asarray(self.state.quarantined)
        t_tok = self._now(now)
        self.chunk_marks = (t_dispatched, t_tok)
        with _span("serve.retire", chunk=c):
            return self._harvest(now, t_tok, toks_np, gen_np, done_np,
                                 quar_np)

    def _harvest(self, now: float, t_tok: float, toks_np, gen_np, done_np,
                 quar_np) -> int:
        """Record each live slot's fresh tokens from the chunk's host fetch
        and retire the finished slots. Returns the valid tokens."""
        produced = 0
        for slot, req in list(self.occupant.items()):
            fresh = int(gen_np[slot]) - self._gen_seen[slot]
            req.tokens.extend(int(t) for t in toks_np[slot, :fresh])
            self._gen_seen[slot] += fresh
            produced += fresh
            if fresh > 0:
                gap = max(t_tok - self._t_last.get(slot, t_tok), 0.0) / fresh
                req.itl.extend([gap] * fresh)
                self._t_last[slot] = t_tok
            if quar_np[slot]:
                # non-finite logits: the decode scan pinned this slot (no
                # token was accepted past the poison) — shed ONLY this
                # request; co-batched slots never read its KV, so their
                # tokens are untouched. The poisoned pages/row are scrubbed
                # before recycling (NaN survives read-time masking) and
                # must not be indexed for sharing.
                scrub = None
                if self.alloc is not None:
                    # exclusively-owned pages only: refcnt > 1 pages hold a
                    # donor's prefix KV, which other slots still read
                    scrub = [p for p in self.alloc.owned.get(slot, ())
                             if self.alloc.refcnt.get(p) == 1]
                self.cache = self.engine.scrub_slot_kv(self.cache, slot,
                                                       scrub)
                req.reject_reason = reject_reason(
                    REASON_NAN, "non-finite logits: slot quarantined, "
                    f"{len(req.tokens)} tokens salvaged")
                req.t_finished = max(now, req.arrival)
                self._retire(slot, req, now, register=False)
                continue
            if req.stop_token is not None and req.stop_token in req.tokens:
                # host-side early stop: truncate past the first stop token
                # (inclusive) and retire — the decode scan may have run a
                # few rows further inside this chunk; they are discarded
                # AND deducted from the realized count (under speculation a
                # chunk can overshoot by up to tokens_per_chunk - 1, which
                # would visibly inflate throughput if left in)
                k = req.tokens.index(req.stop_token)
                discarded = len(req.tokens) - (k + 1)
                produced -= min(discarded, fresh)
                del req.tokens[k + 1:]
                del req.itl[max(k, 0):]
                req.t_finished = max(now, req.arrival)
                self._retire(slot, req, now)
                continue
            if done_np[slot]:
                # clamp: closed-loop runs (realtime=False) may finish a
                # request before its nominal arrival time
                req.t_finished = max(now, req.arrival)
                self._retire(slot, req, now)
        return produced

    @property
    def busy(self) -> bool:
        return bool(self.occupant)

    def extra_stats(self) -> Dict[str, float]:
        return {}


def _step(sched: SlotScheduler, chunks: List[ChunkRecord],
          now: Callable[[], float]) -> int:
    """One ``step_chunk`` inside its ``serve.chunk`` span, recorded in
    ``chunks``. Returns the tokens it produced."""
    i, live = len(chunks), len(sched.occupant)
    sched.chunk_id, sched.chunk_marks = i, None
    t_start = now()
    with _span("serve.chunk", chunk=i, live=live):
        produced = sched.step_chunk(t_start)
    t_end = now()
    t_dispatched, t_fetched = sched.chunk_marks or (t_end, t_end)
    chunks.append(ChunkRecord(i, t_start, t_dispatched, t_fetched, t_end,
                              live, produced))
    return produced


def _held(intervals: List[Tuple[float, float]]):
    """``held(lo, hi)``: seconds of the ordered, disjoint ``intervals``
    inside ``[lo, hi]``."""
    starts = [a for a, _ in intervals]
    ends = [b for _, b in intervals]
    cum = list(itertools.accumulate(b - a for a, b in intervals))

    def before(t: float) -> float:
        i = bisect.bisect_right(starts, t)
        return cum[i - 1] - max(ends[i - 1] - t, 0.0) if i else 0.0

    return lambda lo, hi: max(before(hi) - before(lo), 0.0)


def _attribute_waits(requests: List[Request], chunks: List[ChunkRecord],
                     admissions: List[Tuple[float, float]]) -> None:
    """Fill each admitted request's ``wait_decode_s`` and
    ``wait_prefill_s`` from its queue time ``[arrival, t_admitted]``. Its
    own admission starts at or after ``t_admitted``, so every admission
    inside that interval is another request's."""
    decode = _held([(c.t_start, c.t_end) for c in chunks])
    prefill = _held(admissions)
    for r in requests:
        if r.t_admitted is not None:
            r.wait_decode_s = decode(r.arrival, r.t_admitted)
            r.wait_prefill_s = prefill(r.arrival, r.t_admitted)


def serve(engine: SlotEngine, params, requests: List[Request],
          realtime: bool = False, overload=None) -> ServeReport:
    """Drive a request stream to completion.

    ``realtime=False`` (benchmarks) admits requests as soon as a slot frees
    up, ignoring arrival times for *admission* but still charging queueing
    delay against them via the serve clock. ``realtime=True`` waits for
    wall-clock arrivals (the Poisson simulator). Requests the engine can
    never serve come back with ``reject_reason`` set.

    ``overload``: an :class:`repro.serve.overload.OverloadConfig` — route
    the stream through the priority-aware preemptive scheduler instead of
    this FIFO reject-only one.
    """
    waiting = deque(sorted(requests, key=lambda r: r.arrival))
    t0 = time.perf_counter()
    if overload is not None:
        from repro.serve.overload import OverloadScheduler
        sched = OverloadScheduler(engine, params, overload)
    else:
        sched = SlotScheduler(engine, params)
    decode_tokens = 0

    def now() -> float:
        return time.perf_counter() - t0

    sched.clock = now
    chunks: List[ChunkRecord] = []
    while waiting or sched.busy:
        progressed = sched.admission_round(waiting, now(), realtime)
        if not sched.busy:
            if realtime and waiting:
                with _span("serve.wait"):
                    time.sleep(max(waiting[0].arrival - now(), 0.0))
                continue
            if not progressed:
                break        # nothing running, nothing admissible: done
            continue
        decode_tokens += _step(sched, chunks, now)
    for req in waiting:
        # admission stalled with an idle batch: these can never be served
        if req.reject_reason is None:
            req.reject_reason = reject_reason(
                REASON_SHED, "unservable: needs more pages than an "
                "idle pool can provide")
    wall = now()
    _attribute_waits(requests, chunks, sched.admissions)
    # prefill-produced first tokens count toward throughput too
    total = decode_tokens + sum(1 for r in requests if r.tokens)
    stats = SlotEngine.stats(sched.state)
    stats["max_concurrency"] = float(sched.max_concurrency)
    stats["prefill_tokens"] = float(engine.prefill_tokens)   # cumulative
    if sched.alloc is not None:
        stats["peak_pages"] = float(sched.alloc.peak_pages)
        if sched.live_pages_pct:
            stats["attn_live_pages_pct"] = float(
                np.mean(sched.live_pages_pct))
        if sched.alloc.index is not None:
            stats["shared_tokens"] = float(sched.shared_tokens)
            stats["shared_admissions"] = float(sched.shared_admissions)
            stats["index_pages"] = float(len(sched.alloc.index))
    stats.update(sched.extra_stats())
    if engine.persistent_prefix_index:
        # park the warm pool for the NEXT serve() call (popped before reuse)
        engine.resident = (sched.cache, sched.state, sched.alloc)
    return ServeReport(requests=requests, wall_s=wall, decode_tokens=total,
                       stats=stats, chunks=chunks)


def poisson_requests(num: int, rate_hz: float, prompt_lens,
                     max_new_tokens, vocab_size: int,
                     seed: int = 0, priorities=None,
                     slo_ttft_ms: Optional[float] = None) -> List[Request]:
    """Synthetic open-loop workload: exponential inter-arrival gaps at
    ``rate_hz``, prompt lengths / token budgets drawn from the given
    (min, max) ranges. ``priorities``: optional (values, probabilities)
    pair sampled per request; ``slo_ttft_ms`` stamps every request with
    the same first-token SLO."""
    rng = np.random.default_rng(seed)
    lo, hi = prompt_lens
    nlo, nhi = ((max_new_tokens, max_new_tokens)
                if np.isscalar(max_new_tokens) else max_new_tokens)
    gaps = (rng.exponential(1.0 / rate_hz, num) if np.isfinite(rate_hz)
            else np.zeros(num))
    arrivals = np.cumsum(gaps)
    out = []
    for i in range(num):
        t = int(rng.integers(lo, hi + 1))
        prio = 0
        if priorities is not None:
            vals, probs = priorities
            prio = int(rng.choice(vals, p=probs))
        out.append(Request(
            rid=i,
            prompt=rng.integers(0, vocab_size, (t,), dtype=np.int32),
            max_new_tokens=int(rng.integers(nlo, nhi + 1)),
            arrival=float(arrivals[i]),
            priority=prio, slo_ttft_ms=slo_ttft_ms))
    return out
