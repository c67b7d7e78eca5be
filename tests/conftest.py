"""Test env: force an 8-device virtual CPU mesh before jax backend init.

SURVEY.md §4d: mesh/collective/topo-partition tests run on CPU in CI via
``xla_force_host_platform_device_count`` — no TPU hardware required.

The suite is CPU by statement, never by finding no chip: the platform
is set in the environment AND through ``jax.config`` (which wins over
whatever the caller exported, as long as it runs before first backend
use), so the tests behave the same on a machine that has an accelerator.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
