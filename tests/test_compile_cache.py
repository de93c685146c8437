"""The persistent compilation cache lands where the entry points say:
``$JAX_COMPILATION_CACHE_DIR`` when set, else ``.jax_cache/`` at the
checkout root. Child processes, so this worker's JAX config is untouched."""
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

CHILD = """
import jax, jax.numpy as jnp
from repro.launch.compile_cache import enable_compile_cache
print(enable_compile_cache())
jax.jit(lambda x: jnp.sin(x) * 3)(jnp.ones(7)).block_until_ready()
"""


@pytest.mark.parametrize("from_env", [True, False])
def test_compile_cache_location(tmp_path, from_env):
    from repro.launch.compile_cache import CHECKOUT_CACHE
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(ROOT, "src"),
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if from_env:
        env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path)
    want = str(tmp_path) if from_env else str(CHECKOUT_CACHE)
    assert CHECKOUT_CACHE == Path(ROOT) / ".jax_cache"
    out = subprocess.run([sys.executable, "-c", CHILD], env=env, cwd=tmp_path,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr
    assert out.stdout.split()[0] == want
    assert os.path.isdir(want) and os.listdir(want)
