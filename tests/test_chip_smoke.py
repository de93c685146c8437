"""``chip_smoke.py`` never reports success off the TPU.

The smoke is the repository's proof that the serving path runs on the
chip; a fallback to the CPU must not pass for one. Both runs here are
child processes pinned to the CPU platform.
"""
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _smoke(*args, timeout):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "chip_smoke.py", *args], cwd=ROOT,
                          env=env, capture_output=True, text=True,
                          timeout=timeout)


def test_chip_smoke_fails_without_tpu():
    out = _smoke(timeout=300)
    assert out.returncode != 0
    assert '"ok": true' not in out.stdout
    assert "needs a TPU" in out.stderr


def test_chip_smoke_reduced_rehearsal_runs_every_phase_then_fails():
    """The CPU rehearsal drives every phase at reduced widths (Pallas
    interpreted) and still exits non-zero without printing a result."""
    out = _smoke("--reduced", timeout=900)
    assert out.returncode != 0, out.stdout
    assert '"ok": true' not in out.stdout
    for phase in ("ref", "pallas", "gated"):
        assert f"[{phase}] ok" in out.stdout, out.stdout + out.stderr
    assert "rehearsal passed; no TPU, so no result" in out.stderr
