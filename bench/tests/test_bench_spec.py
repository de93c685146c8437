"""BENCHMARK.json holds to the benchmark's contract, and every piece it
names exists under ``bench/``."""
import json
import re
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import spec  # noqa: E402

B = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
WIDTH = re.compile(r"(hidden|intermediate|latent|state|proj|ffn|_dim$|"
                   r"_rank$|head|channels|expansion|per_tok)")


def test_top_level_keys():
    assert set(B) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert B["command"] == ["python3", "bench/run.py"]
    assert B["paths"] == ["bench"]
    assert (ROOT / "BENCHMARK.json").stat().st_size <= 64 * 1024


def test_run_seconds_fits_the_full_check():
    rs = B["run_seconds"]
    assert isinstance(rs, int) and 1 <= rs <= 51
    runs = 2 + 14 * 24
    assert runs * (rs + 60) + 24 * 2 * 90 + 1200 <= 43200


def test_names_and_units():
    metrics = B["end_to_end"] + B["per_layer"]
    names = [m["name"] for m in metrics] + \
        [w["name"] for w in B["workloads"]] + [c["name"] for c in B["configs"]]
    assert all(NAME.match(n) for n in names), names
    assert len({m["name"] for m in metrics}) == len(metrics)
    assert len({w["name"] for w in B["workloads"]}) == len(B["workloads"])
    assert all(UNIT.match(m["unit"]) for m in metrics)
    assert all(m["better"] in ("lower", "higher") for m in metrics)
    for m in metrics:
        if m["name"].endswith("_roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%"


def test_end_to_end_bounds_and_sources():
    names = {m["name"] for m in B["end_to_end"]}
    assert "setup_s" in names
    for m in B["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")


def test_per_layer_metrics_have_readers_and_move_an_end_to_end_metric():
    e2e = {m["name"] for m in B["end_to_end"]}
    cells = {w["name"] for w in B["workloads"]}
    for m in B["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["moves"] in e2e and m["moves"] != "setup_s"
        assert set(m.get("workloads", cells)) <= cells
        assert (ROOT / "bench" / "metrics" / f"{m['name']}.py").exists()
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    for m in B["end_to_end"]:
        assert (ROOT / "bench" / "metrics" / f"{m['name']}.py").exists()


@pytest.mark.parametrize("cell", [w["name"] for w in B["workloads"]])
def test_each_cell_is_found_by_name_and_reports_enough(cell):
    c = spec.load_cell(cell)
    assert c.chips in (1, 4)
    e2e = {m.name for m in c.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert c.per_layer
    assert c.traffic["why"] and c.config["source"].startswith("https://")
    w = next(w for w in B["workloads"] if w["name"] == cell)
    assert len(w["why"]) <= 200 and "\n" not in w["why"]
    assert c.config["chips"] == c.chips


def test_at_most_half_the_cells_take_four_chips():
    four = sum(w["chips"] == 4 for w in B["workloads"])
    assert four <= max(len(B["workloads"]) // 2, 1)


def test_each_config_is_used_and_cuts_no_width():
    used = {w["config"] for w in B["workloads"]}
    files = set()
    for c in B["configs"]:
        assert c["name"] in used
        assert c["file"].startswith("bench/") and c["file"] not in files
        files.add(c["file"])
        assert len(c["reduced"]) <= 16
        assert not any(WIDTH.search(k) for k in c["reduced"]), c["reduced"]
        assert set(c["reduced"]) <= set(json.loads(
            (ROOT / c["file"]).read_text()))


# published config.json key -> the benchmark's ``model`` key, per family
HF = {
    "chatglm": {"num_layers": "num_layers", "hidden_size": "d_model",
                "num_attention_heads": "num_heads",
                "multi_query_group_num": "num_kv_heads",
                "kv_channels": "head_dim", "ffn_hidden_size": "d_ff",
                "padded_vocab_size": "vocab_size",
                "add_qkv_bias": "qkv_bias", "layernorm_epsilon": "norm_eps"},
    "llama": {"num_hidden_layers": "num_layers", "hidden_size": "d_model",
              "num_attention_heads": "num_heads",
              "num_key_value_heads": "num_kv_heads",
              "intermediate_size": "d_ff", "vocab_size": "vocab_size",
              "attention_bias": "qkv_bias", "rms_norm_eps": "norm_eps",
              "rope_theta": "rope_theta"},
}


@pytest.mark.parametrize("path", sorted((ROOT / "bench" / "configs")
                                        .glob("*.json")),
                         ids=lambda p: p.stem)
def test_model_sizes_are_the_published_ones(path):
    """Every configuration file, in a cell or kept for one."""
    f = json.loads(path.read_text())
    m = f["model"]
    for hf_key, key in HF[f["model_type"]].items():
        assert f[hf_key] == m[key], (hf_key, key)
    if f["model_type"] == "llama":
        assert m["head_dim"] * m["num_heads"] == f["hidden_size"]
        assert m["rope_dims"] == m["head_dim"]
    else:                       # GLM's rotary covers half of each head
        assert m["rope_dims"] * 2 == m["head_dim"]
    # served as published, or in a type the file states as assumed
    assert m["dtype"] == f["torch_dtype"] or "dtype" in f["assumed"]
