"""The harness end to end on the CPU: without a chip it prints no result;
a rehearsal at tiny widths runs every step and still prints none; and a
cell, a traffic mix and a per-layer metric added as new files, with a
``workloads`` entry, are found by name."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
RUN = ROOT / "bench" / "run.py"
REHEARSE = ROOT / "bench" / "tests" / "rehearse.py"


@pytest.fixture(scope="module")
def env(tmp_path_factory):
    return dict(os.environ, JAX_PLATFORMS="cpu",
                JAX_COMPILATION_CACHE_DIR=str(
                    tmp_path_factory.mktemp("jax_cache")))


def _run(args, env, cwd=ROOT):
    return subprocess.run([sys.executable] + [str(a) for a in args],
                          capture_output=True, text=True, env=env,
                          timeout=600, cwd=str(cwd))


def test_no_chip_no_result(env):
    p = _run([RUN, "--workload", "chatglm3-6b.chat", "--seed", 2**31 + 3,
              "--seconds", 2, "--trace", 0], env)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no accelerator" in p.stderr


def test_rehearsal_runs_every_step_and_prints_no_result(env):
    p = _run([RUN, "--workload", "chatglm3-6b.chat", "--seed", 5,
              "--seconds", 2, "--trace", 0, "--rehearse"], env)
    assert p.returncode != 0, p.stderr[-2000:]
    assert p.stdout.strip() == ""                 # no device metric
    assert "[window]" in p.stderr and "[check]" in p.stderr
    assert "compiles inside the stream: 0" in p.stderr
    tail = p.stderr.strip().splitlines()[-3:]
    assert [t.split()[1] for t in tail] == ["max_gap", "compared_tokens",
                                             "lost_requests"]


def test_unknown_workload_is_an_error(env):
    p = _run([RUN, "--workload", "no-such.cell", "--seed", 1, "--seconds",
              2, "--trace", 0, "--rehearse"], env)
    assert p.returncode != 0 and p.stdout.strip() == ""


def test_new_cell_mix_and_metric_are_found_by_name(tmp_path, env):
    """Only new files and a new ``workloads`` entry: nothing edited."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    b = tmp_path / "bench"
    cfg = json.loads((b / "configs" / "chatglm3-6b.json").read_text())
    cfg["engine"]["capacity"] = 32
    (b / "configs" / "glm-small-batch.json").write_text(json.dumps(cfg))
    mix = json.loads((b / "traffic" / "chat.json").read_text())
    mix["rate_hz"] = 3.0
    (b / "traffic" / "chat-slow.json").write_text(json.dumps(mix))
    (b / "metrics" / "requests_due.py").write_text(
        '"""requests_due: requests due in the window."""\n\n\n'
        'def read(ctx):\n    return len(ctx["requests"])\n')
    bench = json.loads((tmp_path / "BENCHMARK.json").read_text())
    bench["configs"].append(dict(bench["configs"][0], name="glm-small-batch",
                                 file="bench/configs/glm-small-batch.json"))
    bench["workloads"].append({"name": "glm-small-batch.chat-slow",
                               "config": "glm-small-batch",
                               "traffic": "chat-slow", "chips": 1,
                               "why": "a cell added by files alone"})
    bench["per_layer"].append({"name": "requests_due", "unit": "requests",
                               "better": "higher", "source": "host_clock",
                               "layer": "scheduler", "moves": "out_tok_s",
                               "workloads": ["glm-small-batch.chat-slow"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    p = _run([REHEARSE, "glm-small-batch.chat-slow", "--root", tmp_path,
              "--trace", "--seed", 77], env)
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    sys.path.insert(0, str(ROOT))
    from bench import traffic
    sched = traffic.generate(traffic.rehearsal_mix(mix, 0.1), 77, 2.0, 256,
                             256)
    lo, hi = traffic.window(mix, 2.0)
    due = sum(lo <= r.arrival < hi for r in sched)
    assert due > 0
    assert out["metrics"]["requests_due"]["value"] == due
    assert out["correct"] is True, out["check"]
    # device metrics find nothing to read off the chip and are left out
    assert "decode_hbm_pct" not in out["metrics"]
    assert "queue_p95_ms" in out["metrics"]


def test_benchmark_files_alone_are_not_enough(tmp_path, env):
    """A directory that holds only BENCHMARK.json and bench/ has no system
    under test: the run fails and prints no result."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bare = {k: v for k, v in env.items() if k != "PYTHONPATH"}
    p = _run([tmp_path / "bench" / "run.py", "--workload",
              "chatglm3-6b.chat", "--seed", 1, "--seconds", 2, "--trace", 0,
              "--rehearse"], bare, cwd=tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "repro" in p.stderr
