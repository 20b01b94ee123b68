"""Test configuration: multi-device SPMD on a virtual CPU mesh.

The reference has no automated tests (SURVEY.md §4); its rig is `mpiexec`
oversubscription.  The JAX-native substitute: force 8 virtual CPU devices so
every sharding/collective path runs as real SPMD without TPU hardware.
Must be set before jax initializes.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()

import jax

# Pin the CPU in-process as well: an installed TPU plugin must never be
# initialized by a test run, whatever the environment says.
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", True)

assert len(jax.devices()) == 8, f"expected 8 virtual CPU devices, got {jax.devices()}"
