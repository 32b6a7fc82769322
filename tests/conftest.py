"""Test harness configuration.

Tests run on a virtual 8-device CPU mesh so multi-chip sharding logic
(pjit/shard_map over a Mesh) is exercised without TPU hardware. The
platform is pinned through jax.config before the first backend
initialization, so the suite stays on the CPU even on a machine that has
a chip (``tests_tpu/`` is the tier that runs there).
"""

import os
import sys

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

# Make the repo root importable regardless of pytest invocation directory.
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
