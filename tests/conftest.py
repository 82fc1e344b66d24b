"""Test configuration: force an 8-device virtual CPU mesh so SPMD code paths
get genuine multi-device coverage without hardware (the reference's tests run
single-device PlacementMeshImpl on CPU — see SURVEY.md §4; this is strictly
stronger)."""
import os

# Force CPU even where the environment selects the TPU (the chip machine
# sets JAX_PLATFORMS=tpu,cpu): the suite needs the 8-device virtual mesh.
os.environ["JAX_PLATFORMS"] = "cpu"
xla_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in xla_flags:
    os.environ["XLA_FLAGS"] = (
        xla_flags + " --xla_force_host_platform_device_count=8").strip()

import pytest  # noqa: E402
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")


@pytest.fixture(scope="session")
def eight_devices():
    devs = jax.devices()
    assert len(devs) == 8, f"expected 8 virtual CPU devices, got {devs}"
    return devs
