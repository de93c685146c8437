"""Find a cell's pieces by the names ``BENCHMARK.json`` gives them.

Nothing here knows a particular cell: a configuration is
``configs/<name>.json``, a traffic mix ``traffic/<name>.json`` and a
per-layer metric's reader ``metrics/<name>.py``, so a later cell adds files
and a ``workloads`` entry and edits nothing.
"""
from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass
from pathlib import Path
from typing import Callable, Dict, List

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent


@dataclass(frozen=True)
class Metric:
    name: str
    unit: str


@dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict                # configs/<config>.json, plus its "name"
    traffic: dict               # traffic/<traffic>.json, plus its "name"
    end_to_end: List[Metric]
    per_layer: List[Metric]


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_mix(name: str, root: Path = ROOT) -> dict:
    """``traffic/<name>.json``; a mix that names a ``base`` mix is that
    mix with its own parameters put over it."""
    mix = load_json(root / "bench" / "traffic" / f"{name}.json")
    base = mix.pop("base", None)
    return dict(load_mix(base, root), **mix) if base else mix


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: Path = ROOT) -> Cell:
    """The workload ``name`` of ``<root>/BENCHMARK.json`` with its
    configuration, traffic mix and the metrics it reports."""
    bench = load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                       f"known: {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = dict(load_json(root / configs[w["config"]]["file"]),
                  name=w["config"])
    traffic = dict(load_mix(w["traffic"], root), name=w["traffic"])
    e2e = [Metric(m["name"], m["unit"])
           for m in bench["end_to_end"] if _applies(m, name)]
    layer = [Metric(m["name"], m["unit"])
             for m in bench["per_layer"] if _applies(m, name)]
    return Cell(name, int(w["chips"]), config, traffic, e2e, layer)


def metric_reader(name: str, root: Path = ROOT) -> Callable[[dict], object]:
    """``read(ctx)`` of ``bench/metrics/<name>.py``: the metric's value, or
    None where the run gave it nothing to read."""
    path = root / "bench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def peaks(device_kind: str, root: Path = ROOT) -> Dict[str, float]:
    """Published peaks of one chip of ``device_kind``; a kind missing from
    ``peaks.json`` is an error, never a default."""
    table = load_json(root / "bench" / "peaks.json")["devices"]
    if device_kind not in table:
        raise KeyError(f"device kind {device_kind!r} is not in "
                       f"bench/peaks.json (known: {sorted(table)})")
    return table[device_kind]
