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
import pytest  # noqa: E402

jax.config.update("jax_enable_x64", False)


@pytest.fixture(autouse=True)
def _step_store_off_after_each_test():
    """An entry point run in-process (``gossip_lm.main``, ``gossip_sgd.main``)
    arms the step store with its compile cache; the tests after it in the
    same worker build their steps with plain ``jit`` again."""
    yield
    from stochastic_gradient_push_tpu.utils import step_store

    step_store.disarm()
