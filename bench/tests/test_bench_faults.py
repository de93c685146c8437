"""The check against the reference catches each fault the serving cells
can have: a rehearsal (tiny widths, on the CPU) with the fault planted
under the timed path reads ``correct`` false; without it, true."""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
SCRIPT = ROOT / "bench" / "tests" / "rehearse.py"


@pytest.fixture(scope="module")
def cache_dir(tmp_path_factory):
    return str(tmp_path_factory.mktemp("jax_cache"))


def rehearse(workload, fault, cache_dir, devices=1, root=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=cache_dir,
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}")
    extra = ["--root", str(root)] if root is not None else []
    p = subprocess.run([sys.executable, str(SCRIPT), workload,
                        "--fault", fault, *extra], capture_output=True,
                       text=True, env=env, timeout=600, cwd=str(ROOT))
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("fault", ["none", "stale_kv", "half_batch",
                                   "token"])
def test_one_chip_cell(fault, cache_dir):
    out = rehearse("chatglm3-6b.chat", fault, cache_dir)
    assert out["correct"] is (fault == "none"), out["check"]
    if fault != "none":
        assert out["check"]["max_gap"]["value"] > \
            out["check"]["max_gap"]["limit"]
