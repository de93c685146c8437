import os
import sys

# Tests run on the single real CPU device — the 512-device override belongs
# to launch/dryrun.py ONLY (see the dry-run spec).
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax  # noqa: E402
import pytest  # noqa: E402

# Mesh tests need a forced multi-device host; they skip on the default
# single-device run and execute in the CI mesh-smoke job. Shared here so
# the device requirement lives in exactly one place.
needs_mesh = pytest.mark.skipif(
    jax.device_count() < 4,
    reason="mesh tests need a forced multi-device host "
           "(XLA_FLAGS=--xla_force_host_platform_device_count=4)")
