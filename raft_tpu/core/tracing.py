"""Tracing / profiling annotations.

Counterpart of the reference's NVTX ranges (cpp/include/raft/core/nvtx.hpp:48-76):
RAII ``common::nvtx::range<domain>``, used at every algorithm entry point.
On TPU the profiler is ``jax.profiler`` and the annotation primitives are
``jax.named_scope`` (names ops in traced programs) and
``jax.profiler.TraceAnnotation`` (a host span on the profiler's clock).

:func:`annotation` is the host span alone: a ``<domain>:<name>`` event on
the calling thread's line of the profiler's host plane, the same clock the
device planes are read on.  It costs about a microsecond when no profiler
session is recording, so it may stay on in hot paths (the stage timers and
the serving dispatcher open one per phase).  :func:`range` adds the named
scope on top.
"""

from __future__ import annotations

import contextlib
from typing import Any, Iterator

import jax


class domain:
    """Annotation domains (reference: core/nvtx.hpp ``domain::app`` / ``domain::raft``)."""

    app = "app"
    raft = "raft_tpu"


def annotation(name: str, domain: str = domain.raft
               ) -> jax.profiler.TraceAnnotation:
    """The host span ``<domain>:<name>`` on the profiler's clock, with no
    named scope: a context manager, entered with ``with``."""
    return jax.profiler.TraceAnnotation(f"{domain}:{name}")


@contextlib.contextmanager
def range(name: str, *fmt_args: Any, domain: str = domain.raft) -> Iterator[None]:
    """RAII-style trace range (reference: ``common::nvtx::range``, core/nvtx.hpp:76).

    Inside a traced/jitted computation this adds a named scope to the HLO (so
    the op shows up grouped in the TPU profiler); outside it also emits a
    ``jax.profiler`` trace annotation visible in host traces.
    """
    if fmt_args:
        name = name % fmt_args
    with jax.named_scope(f"{domain}:{name}"), annotation(name, domain):
        yield
