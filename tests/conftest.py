"""Test configuration: force an 8-device virtual CPU platform.

The reference had no fake/loopback backend and therefore no tests
(SURVEY.md §4).  Here every distributed code path runs on
``xla_force_host_platform_device_count=8`` CPU devices, so the full mesh /
ppermute machinery is exercised without TPU hardware.
"""

import os

# force CPU: the suite never needs an accelerator
os.environ["JAX_PLATFORMS"] = "cpu"
xla_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in xla_flags:
    os.environ["XLA_FLAGS"] = (
        xla_flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

jax.config.update("jax_enable_x64", False)
