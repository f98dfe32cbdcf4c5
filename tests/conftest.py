"""Test configuration.

Sets env so tests (and the subprocess workloads they launch) use the JAX CPU
backend with 8 virtual host devices, exercising real multi-device code paths
without TPU hardware (SURVEY.md §4 "Rebuild translation").

jax itself is NOT imported here — control-plane tests stay jax-free. Test
modules that use jax in-process must ``import tests.jaxenv`` first, which
runs the same ``setup_backend`` a replica runs. The tests are CPU tests:
``JAX_PLATFORMS=cpu`` is set here, before any jax import, so a bare
``pytest`` never reaches for the chip (which only the chip tool holds).
"""

import os

# Read at CPU client creation — must be set before any backend is built.
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ["JAX_PLATFORMS"] = "cpu"

import pytest  # noqa: E402


@pytest.fixture
def tmp_state_dir(tmp_path):
    """A fresh supervisor state directory."""
    d = tmp_path / "tpujob-state"
    d.mkdir()
    return d
