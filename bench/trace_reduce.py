"""Reduce a profiler trace (``*.xplane.pb``) of a traced slice to the
device numbers the per-layer metrics read.

Planes named ``/device:<kind>:<n>`` are devices; their ``XLA Ops`` line
holds one event per operation run (named by its HLO line, of which the
instruction name is kept; device start and duration in ns), their ``XLA Modules`` line one event per program run. The
host's ``bench.*`` ``TraceAnnotation`` spans (see ``spans.py``) are on the
host plane, on the same clock.

* traced slice: from the first ``bench.*`` span's start to the last one's
  end;
* busy: the union of a device's op intervals inside the slice, averaged
  over the devices; idle is the rest;
* idle gaps: the intervals in which a device ran nothing, cut at the
  host spans' edges, each piece named by the innermost ``bench.*`` span
  it lies in (``host.other`` where none is);
* per module and per op: device seconds, to find a program's or a
  kernel's time; ops are the leaves (a loop's body ops, not the loop);
* collectives: ops whose HLO name is a collective.
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

COLLECTIVE = re.compile(r"all-reduce|all-gather|reduce-scatter|"
                        r"collective-permute|all-to-all|send|recv")


@dataclass
class Ev:
    name: str
    start: int                  # ns
    end: int
    stats: Dict[str, object] = field(default_factory=dict)


@dataclass
class Device:
    name: str
    ops: List[Ev]
    modules: List[Ev]


@dataclass
class Trace:
    devices: List[Device]
    spans: List[Ev]             # bench.* host spans, by start

    @property
    def window(self) -> Tuple[int, int]:
        if not self.spans:
            raise ValueError("no bench.* host span in the trace")
        return (min(s.start for s in self.spans),
                max(s.end for s in self.spans))


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def op_name(name: str) -> str:
    """The HLO instruction name of an op event: a TPU trace names each op
    by its whole HLO line (``%fusion.3 = bf16[...] fusion(...)``)."""
    return name.split(" = ", 1)[0].lstrip("%") if name.startswith("%") \
        else name


def _events(line, rename=lambda n: n) -> List[Ev]:
    out = []
    for e in line.events:
        start = int(e.start_ns)
        out.append(Ev(rename(e.name), start, start + int(e.duration_ns),
                      dict(e.stats)))
    return out


def load(path: str, span_prefix: str = "bench.") -> Trace:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices, spans = [], []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            lines = {ln.name: ln for ln in plane.lines}
            ops = (_events(lines["XLA Ops"], op_name)
                   if "XLA Ops" in lines else [])
            mods = (_events(lines["XLA Modules"])
                    if "XLA Modules" in lines else [])
            if ops or mods:
                devices.append(Device(plane.name, ops, mods))
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                spans.extend(e for e in _events(ln)
                             if e.name.startswith(span_prefix))
    spans.sort(key=lambda s: s.start)
    return Trace(devices, spans)


def _union(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[List[int]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _clip(ev: Ev, lo: int, hi: int) -> Optional[Tuple[int, int]]:
    a, b = max(ev.start, lo), min(ev.end, hi)
    return (a, b) if b > a else None


def busy_intervals(dev: Device, lo: int, hi: int) -> List[Tuple[int, int]]:
    return _union([c for c in (_clip(e, lo, hi) for e in dev.ops) if c])


def span_at(spans: List[Ev], t: int) -> str:
    """The innermost span around time ``t`` (``host.other`` if none)."""
    inner = None
    for s in spans:
        if s.start <= t < s.end and (inner is None or
                                     s.end - s.start < inner.end - inner.start):
            inner = s
    return inner.name if inner is not None else "host.other"


def _named_pieces(spans: List[Ev], a: int, b: int
                  ) -> List[Tuple[str, float]]:
    """[a, b) cut at every span edge inside it, each piece named by the
    innermost span it lies in; adjacent pieces of one name merge."""
    if b <= a:
        return []
    cuts = sorted({a, b} | {t for s in spans for t in (s.start, s.end)
                            if a < t < b})
    out: List[Tuple[str, float]] = []
    for x, y in zip(cuts, cuts[1:]):
        name = span_at(spans, (x + y) // 2)
        if out and out[-1][0] == name:
            out[-1] = (name, out[-1][1] + (y - x) * 1e-9)
        else:
            out.append((name, (y - x) * 1e-9))
    return out


def leaves(ops: List[Ev]) -> List[Ev]:
    """The ops that hold no other op: a TPU trace shows a loop (``while``)
    and the ops of its body, nested in time, on one line."""
    out, stack = [], []
    for e in sorted(ops, key=lambda e: (e.start, -e.end)):
        while stack and stack[-1][0].end <= e.start:
            top, holds = stack.pop()
            if not holds:
                out.append(top)
        if stack:
            stack[-1][1] = True
        stack.append([e, False])
    out.extend(e for e, holds in stack if not holds)
    return out


def reduce(tr: Trace) -> dict:
    """Device numbers of the traced slice (seconds)."""
    lo, hi = tr.window
    n = max(len(tr.devices), 1)
    busy = 0.0
    gaps: List[Tuple[str, float]] = []
    ops: Dict[str, float] = defaultdict(float)
    collective = 0.0
    for dev in tr.devices:
        iv = busy_intervals(dev, lo, hi)
        busy += sum(b - a for a, b in iv) * 1e-9
        edges = [lo] + [x for ab in iv for x in ab] + [hi]
        for a, b in zip(edges[0::2], edges[1::2]):
            gaps.extend(_named_pieces(tr.spans, a, b))
        for e in leaves(dev.ops):
            c = _clip(e, lo, hi)
            if c:
                ops[e.name] += (c[1] - c[0]) * 1e-9
                if COLLECTIVE.search(e.name):
                    collective += (c[1] - c[0]) * 1e-9
    gap_total: Dict[str, float] = defaultdict(float)
    for name, s in gaps:
        gap_total[name] += s / n
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": busy / n,
        "collective_s": collective / n,
        "ops": dict(ops),
        "gaps": sorted(gaps, key=lambda g: -g[1]),
        "gap_total": dict(gap_total),
    }


def module_time(tr: Trace, match, spans: Optional[List[Ev]] = None
                ) -> Tuple[float, int]:
    """(device seconds averaged over devices, runs on the first device) of
    the program runs whose module name satisfies ``match``, restricted to
    runs that lie inside one of ``spans`` where given."""
    total, runs = 0.0, 0
    for i, dev in enumerate(tr.devices):
        for m in dev.modules:
            if not match(m.name):
                continue
            if spans is not None and not any(s.start <= m.start and
                                             m.end <= s.end for s in spans):
                continue
            total += (m.end - m.start) * 1e-9
            runs += i == 0
    return total / max(len(tr.devices), 1), runs


def ops_in(tr: Trace, modules: List[Ev], dev: Device) -> List[Ev]:
    """The ops of ``dev`` that ran inside one of ``modules``."""
    out, j = [], 0
    ms = sorted(modules, key=lambda m: m.start)
    for e in sorted(dev.ops, key=lambda e: e.start):
        while j < len(ms) and ms[j].end < e.start:
            j += 1
        if j < len(ms) and ms[j].start <= e.start and e.end <= ms[j].end:
            out.append(e)
    return out
