"""Host spans and counters, taken from the benchmark's side of the calls
into the program's layers.

``Recorder.instrument()`` wraps, for the duration of one ``serve()``
stream, the scheduler's admission (``SlotScheduler.admit``: one prefill,
ending in the first token's host fetch), its decode chunk
(``SlotScheduler.step_chunk``: page growth, the jitted chunk and its one
host fetch, then retirement), the page-table push
(``SlotEngine.set_page_table``) and the scheduler's wait for the next
arrival (its ``time.sleep``). Each becomes a ``TraceAnnotation``
(``bench.admit``, ``bench.chunk``, ``bench.page_table``, ``bench.wait``),
so the trace reduction can name the device's idle gaps by what the host
was doing; admissions and chunks are also recorded on ``serve()``'s own
clock for the host-clock metrics. Nothing under ``src/`` changes; the wrappers are
removed when the stream ends.

``CompileClock`` counts JAX's compilations from its own monitoring events.
"""
from __future__ import annotations

import contextlib
import time
from dataclasses import dataclass, field
from typing import Callable, List, Optional

import jax


class CompileClock:
    """Seconds JAX spends tracing, lowering and compiling (or reading the
    persistent cache), and how many programs it compiled, from its own
    monitoring events. Listeners stay registered for the process."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax.monitoring as mon
        self.seconds = 0.0
        self.compiles = 0           # backend compiles or cache reads
        self.cache_hits = 0
        mon.register_event_duration_secs_listener(self._duration)
        mon.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event in self.EVENTS:
            self.seconds += secs
        if event == self.EVENTS[2]:
            self.compiles += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


@dataclass
class Recorder:
    """What one stream did, on ``serve()``'s clock (seconds)."""

    admits: List[tuple] = field(default_factory=list)   # t0, t1, result, t
    chunks: List[tuple] = field(default_factory=list)   # t0, t1, produced, live
    # profiler window on the stream clock: started at the first chunk at or
    # after trace_at, stopped at the first chunk at least trace_s later
    # that has trace_chunks chunks before it in the window
    trace_at: Optional[float] = None
    trace_s: float = 0.0
    trace_chunks: int = 3
    _first: int = 0                                     # chunks before it
    trace_dir: Optional[str] = None
    traced: Optional[tuple] = None                      # (t_start, t_stop)
    clock: Optional[Callable[[], float]] = None

    def now(self) -> float:
        return self.clock() if self.clock is not None else 0.0

    def _maybe_trace(self) -> None:
        if self.trace_at is None:
            return
        t = self.now()
        if self.traced is None and t >= self.trace_at:
            jax.profiler.start_trace(self.trace_dir)
            self.traced = (self.now(), None)
            self._first = len(self.chunks)
        elif (self.traced is not None and self.traced[1] is None
              and t >= self.traced[0] + self.trace_s
              and len(self.chunks) - self._first >= self.trace_chunks):
            self.traced = (self.traced[0], self.now())
            jax.profiler.stop_trace()

    def close(self) -> None:
        """Stop the profiler if the stream ended inside the traced slice."""
        if self.traced is not None and self.traced[1] is None:
            self.traced = (self.traced[0], self.now())
            jax.profiler.stop_trace()

    @contextlib.contextmanager
    def instrument(self, engine):
        from repro.serve import scheduler as sch
        rec = self
        orig_admit = sch.SlotScheduler.admit
        orig_chunk = sch.SlotScheduler.step_chunk
        orig_push = engine.set_page_table
        orig_time = sch.time

        def admit(sched, req, now, *a, **k):
            rec.clock = sched.clock or rec.clock
            t0 = rec.now()
            with jax.profiler.TraceAnnotation("bench.admit"):
                res = orig_admit(sched, req, now, *a, **k)
            rec.admits.append((t0, rec.now(), res, len(req.prompt)))
            return res

        def step_chunk(sched, now):
            rec.clock = sched.clock or rec.clock
            rec._maybe_trace()
            # live slots before the chunk: (cached positions, steps left)
            live = [(sched._true_len[s] + sched._gen_seen[s] - 1,
                     sched._budget[s] - sched._gen_seen[s])
                    for s in sched.occupant]
            t0 = rec.now()
            with jax.profiler.TraceAnnotation("bench.chunk"):
                produced = orig_chunk(sched, now)
            rec.chunks.append((t0, rec.now(), produced, live))
            return produced

        def set_page_table(cache, table):
            with jax.profiler.TraceAnnotation("bench.page_table"):
                return orig_push(cache, table)

        class _Time:
            perf_counter = staticmethod(time.perf_counter)

            @staticmethod
            def sleep(s):
                with jax.profiler.TraceAnnotation("bench.wait"):
                    time.sleep(s)

        sch.SlotScheduler.admit = admit
        sch.SlotScheduler.step_chunk = step_chunk
        engine.set_page_table = set_page_table
        sch.time = _Time
        try:
            yield self
        finally:
            sch.SlotScheduler.admit = orig_admit
            sch.SlotScheduler.step_chunk = orig_chunk
            del engine.set_page_table          # back to the class method
            sch.time = orig_time
            self.close()
