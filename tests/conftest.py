"""Test configuration.

Mirrors the reference's multi-GPU-without-a-cluster strategy (SURVEY.md §4:
raft-dask's LocalCUDACluster fixture): tests run on a virtual 8-device CPU
backend so sharded/mesh code paths execute exactly as they would across a TPU
slice, without hardware.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
xla_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in xla_flags:
    os.environ["XLA_FLAGS"] = (
        xla_flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402

# the env var above is read when jax is first imported; if something
# imported jax earlier, config.update still pins the CPU backend.
# XLA_FLAGS is read at first backend init, so the 8 virtual devices hold.
jax.config.update("jax_platforms", "cpu")
# persistent compilation cache (<repo>/.jax_cache unless
# JAX_COMPILATION_CACHE_DIR says otherwise): jit compiles dominate suite
# runtime on the CPU box
from raft_tpu.core.platform import setup_compile_cache  # noqa: E402

setup_compile_cache()


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: long-running; the fast gate tier runs with -m 'not slow'")


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_makereport(item, call):
    """On test failure, dump the flight recorder if RAFT_TPU_FLIGHT_DUMP
    is set (CI exports it so the Chrome-trace forensics ride the failure
    artifact).  No-op — not even an env read — on passing tests."""
    outcome = yield
    report = outcome.get_result()
    if report.when == "call" and report.failed:
        from raft_tpu.observability import flight
        path = flight.maybe_auto_dump(f"test_failure:{item.nodeid}")
        if path:
            tr = item.config.pluginmanager.get_plugin("terminalreporter")
            if tr is not None:
                tr.write_line(f"flight dump: {path}")


@pytest.fixture
def res():
    from raft_tpu import DeviceResources
    return DeviceResources(seed=42)


@pytest.fixture
def mesh8():
    devs = jax.devices()
    if len(devs) < 8:
        pytest.skip("needs 8 devices (set "
                    "XLA_FLAGS=--xla_force_host_platform_device_count=8)")
    return jax.sharding.Mesh(np.asarray(devs[:8]), ("data",))
