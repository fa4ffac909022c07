"""Test env: force JAX onto a virtual 8-device CPU mesh.

Must run before any jax import — tests exercise the device weaver and
the multi-chip sharding path on 8 virtual CPU devices
(xla_force_host_platform_device_count), so the suite never needs real
TPU hardware; the driver separately dry-runs the multi-chip path.
"""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

# jax may already be imported with another platform default, so the env
# vars above can arrive too late for it. The backend itself is lazily
# initialized, so flipping the config here (before any jax.devices()/jit
# call) still lands us on the 8-device CPU mesh.
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
