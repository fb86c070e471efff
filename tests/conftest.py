"""Test harness: force an 8-device CPU platform so multi-chip sharding tests
run without TPU hardware (replaces the reference's torchrun subprocess
harness, SURVEY §4). Must run before jax is imported anywhere."""

import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
