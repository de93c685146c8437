"""The program's own spans, counters and op names in a traced run, for the
per-layer metric readers.

* ``serve()`` opens ``serve.*`` host spans (``repro.serve.scheduler``).
  Each decode chunk's ``serve.chunk`` carries ``chunk=<index>``, which is
  also the ``index`` of its record in ``ServeReport.chunks``: a chunk's
  span and its record are paired by that id, never by order.
* Every op an XAIF backend traced carries ``xaif.<op>.<backend>`` in its
  HLO ``op_name`` metadata. The traced run keeps the decode program's
  optimized HLO (``ctx["hlo"]``), whose instruction names are the names
  of the device trace's op events.

Both are read once a run and kept in ``ctx``. A program that has neither
(no ``serve.*`` span, no ``ServeReport.chunks``, no ``xaif.`` scope) gives
the readers nothing here; they then read the benchmark's own ``bench.*``
spans, recorder and operand-shape classes (``devmetrics``), so a traced
run of such a program still reads every metric it declares.
"""
from __future__ import annotations

import re
from typing import Dict, List, Optional, Tuple

from bench import devmetrics, stats, trace_reduce

# ``%name = <shape> <op>(...), ..., metadata={op_name="..." ...}``
_INSTR = re.compile(r'^\s*(?:ROOT\s+)?%?([\w.\-]+) = .*?, metadata=\{'
                    r'op_name="([^"]*)"', re.M)


def is_decode(module_name: str) -> bool:
    """A decode program: the program's ``jit_decode_chunk`` (and the
    older ``jit_counted_decode``)."""
    return "decode" in module_name


def spans(ctx) -> List[trace_reduce.Ev]:
    """The program's ``serve.*`` host spans of the traced slice, by
    start; none where the run was not traced or the program opens none."""
    if "_serve_spans" not in ctx:
        rec = ctx["recorder"]
        out: List[trace_reduce.Ev] = []
        if ctx.get("trace") is not None and rec.trace_dir:
            path = trace_reduce.find_xplane(rec.trace_dir)
            out = trace_reduce.load(path, span_prefix="serve.").spans
        ctx["_serve_spans"] = out
    return ctx["_serve_spans"]


def records(ctx) -> Optional[list]:
    """``ServeReport.chunks`` of the stream; None where the program keeps
    no such record."""
    return getattr(ctx["report"], "chunks", None) or None


def chunk_pairs(ctx) -> List[tuple]:
    """[(record, serve.chunk span)] of the traced chunks that ran a
    decode, paired by the span's ``chunk`` id, in chunk order. A span
    without a record, or a record whose span the trace lacks (the profiler
    started or stopped inside it), is left out."""
    recs = {r.index: r for r in (records(ctx) or ())}
    out = []
    for sp in spans(ctx):
        if sp.name != "serve.chunk" or "chunk" not in sp.stats:
            continue
        r = recs.get(int(sp.stats["chunk"]))
        if r is not None and r.t_fetched > r.t_dispatched:
            out.append((r, sp))
    return sorted(out, key=lambda p: p[0].index)


def decode_spans(ctx) -> List[trace_reduce.Ev]:
    """The host spans of the traced decode chunks: the program's
    ``serve.chunk`` spans paired with their records, else the benchmark's
    ``bench.chunk`` spans."""
    if ctx.get("trace") is None:
        return []
    paired = [sp for _, sp in chunk_pairs(ctx)]
    if paired:
        return paired
    return [s for s in ctx["trace"].spans if s.name == "bench.chunk"]


def scopes(ctx) -> Dict[str, str]:
    """{HLO instruction name: its ``op_name``} of the decode program."""
    if "_scopes" not in ctx:
        ctx["_scopes"] = dict(_INSTR.findall(ctx.get("hlo") or ""))
    return ctx["_scopes"]


def xaif_op(op_name: str) -> Optional[str]:
    """The XAIF op whose backend traced an HLO op: the innermost
    ``xaif.<op>.<backend>`` scope of its ``op_name``."""
    for part in reversed(op_name.split("/")):
        if part.startswith("xaif."):
            return part.split(".")[1]
    return None


def _classifier(ctx):
    """``kind(op event) -> XAIF op or None``: by the program's scopes where
    its HLO has them, else by the Pallas calls' operand shapes."""
    sc = scopes(ctx)
    if any(xaif_op(v) for v in sc.values()):
        def kind(e):
            n = sc.get(e.name) or sc.get(str(e.stats.get("hlo_op", "")))
            return xaif_op(n) if n else None
        return kind
    table = devmetrics.kernels(ctx.get("hlo"))

    def by_shape(e):
        k = table.get(e.name) or table.get(str(e.stats.get("hlo_op", "")))
        return k[0] if k else None
    return by_shape


def _mostly_in(span, ev) -> bool:
    """Most of ``ev`` lies inside ``span``: a program belongs to the chunk
    whose span holds most of it, though the device's clock may put its
    edges a little outside."""
    inside = min(ev.end, span.end) - max(ev.start, span.start)
    return 2 * inside > ev.end - ev.start


def per_chunk_s(ctx, op: Optional[str] = None) -> List[float]:
    """Device seconds of each traced decode chunk, averaged over the
    devices: the decode programs' time inside its span, or (``op``) the
    time of the leaf ops inside them that ``op``'s backend traced."""
    tr = ctx.get("trace")
    if tr is None or not tr.devices:
        return []
    kind = _classifier(ctx) if op is not None else None
    out = []
    for sp in decode_spans(ctx):
        mods = [[m for m in dev.modules
                 if is_decode(m.name) and _mostly_in(sp, m)]
                for dev in tr.devices]
        if not any(mods):
            continue                  # a chunk that ran no decode program
        t = 0.0
        for dev, ms in zip(tr.devices, mods):
            if op is None:
                t += sum(m.end - m.start for m in ms) * 1e-9
                continue
            ops = trace_reduce.leaves(trace_reduce.ops_in(tr, ms, dev))
            t += sum(e.end - e.start for e in ops if kind(e) == op) * 1e-9
        out.append(t / len(tr.devices))
    return out


def step_ms(ctx, op: Optional[str] = None) -> Optional[float]:
    """Median over the traced decode chunks of ``per_chunk_s`` per decode
    step, in ms; None where no decode chunk (or no such op) was traced."""
    per = per_chunk_s(ctx, op)
    if not per or max(per) <= 0:
        return None
    return 1e3 * stats.median(per) / ctx["engine"].chunk


def host_self_s(ctx) -> List[float]:
    """Each chunk's host time outside its wait for the device: from the
    program's records, the window's chunks' ``t_end - t_start - (t_fetched
    - t_dispatched)``; else, from the trace, each ``bench.chunk`` span
    less the device's busy time inside it (averaged over devices)."""
    recs = records(ctx)
    if recs is not None:
        lo, hi = ctx["window"]
        return [r.t_end - r.t_start - (r.t_fetched - r.t_dispatched)
                for r in recs if lo <= r.t_start < hi]
    tr = ctx.get("trace")
    if tr is None or not tr.devices:
        return []
    out = []
    for sp in (s for s in tr.spans if s.name == "bench.chunk"):
        busy = sum(sum(b - a for a, b in trace_reduce.busy_intervals(
            d, sp.start, sp.end)) for d in tr.devices) / len(tr.devices)
        out.append((sp.end - sp.start - busy) * 1e-9)
    return out


def _held(intervals: List[Tuple[float, float]], lo: float,
          hi: float) -> float:
    return sum(max(min(b, hi) - max(a, lo), 0.0) for a, b in intervals)


def wait_decode_s(ctx) -> List[float]:
    """Each window request's ``wait_decode_s`` (infinite if never
    admitted); where the program keeps no such counter, the overlap of its
    queue time with the benchmark recorder's chunks."""
    reqs = ctx["requests"]
    chunks = [(c[0], c[1]) for c in ctx["recorder"].chunks]
    out = []
    for r in reqs:
        if r.t_admitted is None:
            out.append(stats.INF)
        elif hasattr(r, "wait_decode_s"):
            out.append(r.wait_decode_s)
        else:
            out.append(_held(chunks, r.arrival, r.t_admitted))
    return out
