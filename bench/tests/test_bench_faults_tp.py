"""The tensor-parallel cell's check catches a missing exchange between
chips: a rehearsal on four CPU devices with the MLP's all-reduce left out
reads ``correct`` false; without the fault, true.

``yi-9b-tp4.chat`` is not in ``BENCHMARK.json`` (no chip runs measured it
yet); its configuration and mix are kept under ``bench/``, and the test
names the cell in a copy of the benchmark, as a later cell is added."""
import json
import shutil

import pytest

from test_bench_faults import ROOT, rehearse

CELL = "yi-9b-tp4.chat"


@pytest.fixture(scope="module")
def cache_dir(tmp_path_factory):
    return str(tmp_path_factory.mktemp("jax_cache_tp"))


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """A checkout whose BENCHMARK.json holds the four-chip cell."""
    root = tmp_path_factory.mktemp("bench_tp")
    shutil.copytree(ROOT / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench["configs"].append({
        "name": "yi-9b-tp4", "source": "https://huggingface.co/01-ai/Yi-9B",
        "file": "bench/configs/yi-9b-tp4.json", "reduced": [],
        "why": "dense GQA over a dp1 x tp4 mesh"})
    bench["workloads"].append({
        "name": CELL, "config": "yi-9b-tp4", "traffic": "chat-tp4",
        "chips": 4, "why": "tensor-parallel all-reduces"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


@pytest.mark.parametrize("fault", ["none", "no_exchange", "token"])
def test_four_chip_cell(fault, cache_dir, root):
    out = rehearse(CELL, fault, cache_dir, devices=4, root=root)
    assert out["correct"] is (fault == "none"), out["check"]
