"""Adapters from a configuration's index kind to the library's public API.

``systems/<kind>.py`` (``kind`` is a configuration's ``index.kind``)
defines:

- ``KERNELS``: ``(module, attribute)`` Pallas entry points the timed path
  must reach; the harness counts their traces during warm-up.
- ``FALLBACK_EVENT``: the flight-recorder event the library records when a
  call falls back off its kernels, or None.
- ``build(res, cfg, db)``: the index.
- ``batch_fn(res, cfg, index, db)``: ``f(queries) -> (distances, ids)``
  on the device, for closed loops.
- ``executor(res, cfg, index, mix)``: a ``serving.Executor``, for open
  loops.
- ``layout(index, cfg)``: host copies of what the work counts need, or
  None.
"""

import importlib


def load(kind: str):
    return importlib.import_module(f"benchmark.systems.{kind}")
