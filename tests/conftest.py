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


import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _no_trace_context_from_the_test_before():
    """``SessionTracer.start_session`` and ``set_task_context`` set context
    variables that outlive a test on a worker's main thread, and every span
    carries them in its args: start each test without."""
    from areal_tpu.utils import perf_tracer

    perf_tracer.clear_task_context()
