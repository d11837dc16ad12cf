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

import weakref  # noqa: E402

import pytest  # noqa: E402


@pytest.fixture(scope="module", autouse=True)
def _close_the_apps_a_module_leaves_open():
    """An app nobody closed keeps its threads (the canary prober fires
    every thirty seconds), and they record launches and stages in
    whatever recorder a LATER module of the same worker has planted.
    Every ``BeaconApp`` a module made and left open is closed when the
    module ends (``close`` is safe to call twice)."""
    from sbeacon_tpu.api.app import BeaconApp

    made = []
    init = BeaconApp.__init__

    def tracked(self, *args, **kwargs):
        made.append(weakref.ref(self))
        init(self, *args, **kwargs)

    BeaconApp.__init__ = tracked
    try:
        yield
    finally:
        BeaconApp.__init__ = init
        for ref in made:
            app = ref()
            if app is not None and hasattr(app, "canary"):
                app.close()
