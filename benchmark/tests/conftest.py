"""The benchmark's own tests run on the CPU at small sizes; the measuring
command refuses the CPU."""

import os

os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")


import pytest  # noqa: E402

from benchmark import run  # noqa: E402


@pytest.fixture
def on_cpu(monkeypatch):
    """Stand down the checks that the timed path reached its Pallas
    kernels and never fell back off them: the CPU runs the XLA twins."""
    monkeypatch.setattr(run, "check_kernels", lambda spies, n: {})
    monkeypatch.setattr(run, "fallback_events", lambda system: 0)
