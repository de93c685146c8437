"""Device numbers of the decode chunks in a traced slice, for the
per-layer metric readers.

The chunks the host recorded while the profiler ran are matched, in
order, with the ``bench.chunk`` spans in the trace. A chunk's device time
is the time of the programs that ran inside its span (the decode chunk
is the only program a chunk runs). Kernels are told apart by the shapes of
their operands in the decode program's optimized HLO, which the traced
run keeps (``ctx["hlo"]``): trace events carry HLO instruction names.
"""
from __future__ import annotations

import re
from typing import Dict, List, Optional, Tuple

from bench import flops, trace_reduce

_CALL = re.compile(r'%(\S+) = [^=]*?custom-call\(.*?custom_call_target='
                   r'"tpu_custom_call".*?operand_layout_constraints='
                   r'\{((?:[^{}]|\{[^{}]*\})*)\}')
_SHAPE = re.compile(r'(\w+)\[([\d,]*)\]')


def kernels(hlo: Optional[str]) -> Dict[str, Tuple[str, list]]:
    """{instruction name: (kind, operand shapes)} of the Pallas calls in
    ``hlo``; kind is ``gemm`` ([M,K] x [K,N] + [1,N]), ``attn_decode_paged``
    (a 4-D page pool beside a 2-D int32 page table) or ``other``."""
    out = {}
    for m in _CALL.finditer(hlo or ""):
        shapes = [(dt, [int(x) for x in dims.split(",") if x])
                  for dt, dims in _SHAPE.findall(m.group(2))]
        dims = [s for _, s in shapes]
        kind = "other"
        if (len(dims) == 3 and all(len(d) == 2 for d in dims)
                and dims[0][1] == dims[1][0] and dims[2] == [1, dims[1][1]]):
            kind = "gemm"
        elif (any(len(d) == 4 for d in dims)
              and any(dt == "s32" and len(d) == 2 for dt, d in shapes)):
            kind = "attn_decode_paged"
        out[m.group(1)] = (kind, dims)
    return out


def live_steps(live, steps: int) -> List[List[int]]:
    """Context length of each live slot at each step of a chunk, from the
    chunk's host record: (cached positions, steps left) per live slot."""
    out = [[] for _ in range(steps)]
    for pos, left in live:
        for j in range(min(steps, max(left, 0))):
            out[j].append(pos + 1 + j)
    return out


def traced_chunks(ctx) -> List[tuple]:
    """[(host record, trace span)] of the decode chunks in the slice."""
    tr, rec = ctx.get("trace"), ctx["recorder"]
    if tr is None or rec.traced is None:
        return []
    spans = [s for s in tr.spans if s.name == "bench.chunk"]
    t0, t1 = rec.traced
    recs = [c for c in rec.chunks if t0 <= c[0] and c[1] <= t1]
    return list(zip(recs, spans))


def chunk_modules(ctx, span) -> List[List[trace_reduce.Ev]]:
    """Per device, the program runs inside ``span``."""
    return [[m for m in d.modules if span.start <= m.start and
             m.end <= span.end] for d in ctx["trace"].devices]


def decode_device_s(ctx) -> Optional[float]:
    """Decode program seconds in the slice, averaged over the devices."""
    spans = [sp for _, sp in traced_chunks(ctx)]
    if not spans or not ctx["trace"].devices:
        return None
    t, _ = trace_reduce.module_time(ctx["trace"], lambda name: True, spans)
    return t if t > 0 else None


def decode_cost(ctx) -> Dict[str, float]:
    """FLOPs and must-read bytes of every decode step of the traced
    chunks (all devices together)."""
    tot = {"flops": 0.0, "bytes": 0.0}
    steps = ctx["engine"].chunk
    for rec, _ in traced_chunks(ctx):
        for lens in live_steps(rec[3], steps):
            if lens:
                c = flops.decode_step(ctx["model"], lens)
                tot["flops"] += c["flops"]
                tot["bytes"] += c["bytes"]
    return tot


def kernel_events(ctx, kind: str) -> List[Tuple[trace_reduce.Ev, list, tuple]]:
    """(event, operand shapes, host chunk record) of every ``kind`` kernel
    run inside a traced decode chunk, on every device."""
    table = kernels(ctx.get("hlo"))
    out = []
    for rec, sp in traced_chunks(ctx):
        for dev, mods in zip(ctx["trace"].devices, chunk_modules(ctx, sp)):
            for e in trace_reduce.ops_in(ctx["trace"], mods, dev):
                k = table.get(e.name) or table.get(e.stats.get("hlo_op", ""))
                if k and k[0] == kind:
                    out.append((e, k[1], rec))
    return out
