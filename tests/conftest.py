"""Test harness config: force an 8-device virtual CPU mesh.

Tests and rehearsals run on the CPU: sharding correctness is tested on
eight virtual CPU devices, and the chip is only ever driven through
``python chip_smoke.py`` (one process, on the machine that holds it).
Must run before any jax import.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

# jax may already be imported (a plugin, an earlier conftest) with another
# platform chosen; backends initialise lazily, so a config update here still
# lands before any device is created.
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
