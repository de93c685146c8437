"""The control comes out not correct: the reference computed in float8
(the precision below the configuration's bfloat16) and put in the
program's place is judged by the cell's own limits, as a run's served
tokens are, and fails them, while the program's own runs pass. Here at the
rehearsal's tiny widths on the CPU; the chip readings at the cells' own
sizes are in PERF.md."""
import json
import os
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def test_fp8_control_fails_the_cells_limits(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    p = subprocess.run(
        [sys.executable, str(ROOT / "bench" / "readings.py"), "--workload",
         "chatglm3-6b.chat", "--seeds", "1,2", "--control-seeds", "1,2",
         "--quant", "fp8", "--seconds", "2", "--rehearse"],
        capture_output=True, text=True, env=env, timeout=600, cwd=str(ROOT))
    assert p.returncode == 0, p.stderr[-3000:]
    rows = [json.loads(x) for x in p.stdout.strip().splitlines()]
    summary = rows[-1]
    assert all(r["lost"] == 0 and r["window_compiles"] == 0
               for r in rows[:-1])
    assert all(r["correct"] for r in rows[:-1]), rows
    assert not any(r["control_correct_fp8"] for r in rows[:-1]), rows
    assert summary["upper_fp8"] >= 3 * summary["lower"], summary
