"""``stage(name)`` — the timing primitive, composed with tracing.range.

A stage is one phase of an algorithm (``"cagra.build.scan"``,
``"ivf_pq.search.coarse"``).  Entering a stage **always** opens the host
annotation ``raft_tpu:<name>`` (:func:`raft_tpu.core.tracing.annotation`),
so a profiler trace shows the library's phases on the same clock as the
device's work whether or not collection is on; with no profiler session
recording it costs about a microsecond.  While collection is enabled the
stage also

  * opens the named scope of :func:`raft_tpu.core.tracing.range` under the
    **same label**, so the TPU profiler timeline and the metrics registry
    agree on stage names, and
  * starts a wall clock whose reading is recorded into
    ``registry().timer(name)`` on exit.

JAX dispatch is async, so a wall clock alone would measure enqueue time; the
yielded handle exposes :meth:`_StageHandle.fence` for the caller to block on
the stage's outputs before the clock stops.  **When collection is disabled
(the default) the context manager yields a no-op singleton: no named scope,
no clock, no registry write and — critically — ``fence`` does nothing, so
instrumented hot paths keep their async dispatch.**  That contract is
load-bearing for search QPS and is pinned by tests/test_observability.py.

Also here: the ``jax.monitoring`` listener that surfaces XLA compile events
(``/jax/core/compile/*``) as registry metrics, making recompile storms
visible as the ``xla.compiles`` counter.
"""

from __future__ import annotations

import time
from typing import Any, Iterator, Optional

import contextlib

import jax

from raft_tpu.core import tracing as _tracing
from raft_tpu.observability import trace as _request_trace
from raft_tpu.observability.registry import (
    MetricsRegistry,
    enabled as _enabled,
    registry as _registry,
)

# Indirection so tests can observe (or forbid) fencing: the disabled-path
# test monkeypatches this and asserts it is never called.
_block_until_ready = jax.block_until_ready


def fence(*values: Any) -> None:
    """Block until every non-tracer jax array in ``values`` is ready.

    Safe to call from inside ``jit`` tracing: tracers are skipped (a traced
    stage then times tracing, not execution — which is what a trace-time
    caller gets anyway)."""
    for leaf in jax.tree_util.tree_leaves(values):
        if isinstance(leaf, jax.core.Tracer):
            continue
        if isinstance(leaf, jax.Array):
            _block_until_ready(leaf)


class _StageHandle:
    """Yielded by an *enabled* stage; carries the fence."""

    __slots__ = ("name",)

    def __init__(self, name: str) -> None:
        self.name = name

    def fence(self, *values: Any) -> None:
        fence(*values)


class _NoopHandle:
    """Yielded when collection is disabled — every method is free."""

    __slots__ = ()
    name = ""

    def fence(self, *values: Any) -> None:  # noqa: ARG002 - deliberate no-op
        return None


_NOOP = _NoopHandle()


@contextlib.contextmanager
def stage(name: str,
          registry: Optional[MetricsRegistry] = None) -> Iterator[Any]:
    """Time one algorithm phase under ``name`` (see module docstring).

    Usage::

        with stage("cagra.build.scan") as s:
            knn = run_the_scan(...)
            s.fence(knn)          # no-op when collection is off

    The final fence is the caller's responsibility — without it the timer
    records dispatch time only (still useful for host-loop stages)."""
    if not _enabled():
        with _tracing.annotation(name):
            yield _NOOP
        return
    reg = registry if registry is not None else _registry()
    with _tracing.range(name):
        t0 = time.perf_counter()
        try:
            yield _StageHandle(name)
        finally:
            dt = time.perf_counter() - t0
            reg.timer(name).record(dt)
            # mirror onto the ambient request trace (one flag check when
            # per-request tracing is off) so stage timers nest inside
            # request spans under the same labels
            _request_trace.stage_hook(name, dt)


# ---------------------------------------------------------------------------
# XLA compile-event tracking (jax.monitoring)

_COMPILE_PREFIX = "/jax/core/compile/"
# the event marking one actual backend (XLA) compilation; fires once per
# cache-missing jit specialization — its count is the recompile-storm signal
_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"

_listener_installed = False


def _on_event_duration(name: str, secs: float, **kwargs: Any) -> None:
    # listener stays registered for the life of the process (jax.monitoring
    # has no public unregister), so gate on the collection flag instead
    if not _enabled() or not name.startswith(_COMPILE_PREFIX):
        return
    reg = _registry()
    reg.timer("xla." + name[len(_COMPILE_PREFIX):]).record(secs)
    if name == _BACKEND_COMPILE:
        reg.counter("xla.compiles").inc()


def _install_compile_listener() -> None:
    """Idempotently register the compile-event listener (called by enable())."""
    global _listener_installed
    if _listener_installed:
        return
    jax.monitoring.register_event_duration_secs_listener(_on_event_duration)
    _listener_installed = True
