"""JAX spellings the library standardizes on (JAX >= 0.9).

``shard_map`` defaults ``check_vma`` to False: the library's SPMD bodies
mix replicated and per-shard values through explicit collectives, which
the varying-manual-axes check would reject.
"""

from __future__ import annotations

import functools

import jax

shard_map = functools.partial(jax.shard_map, check_vma=False)
enable_x64 = jax.enable_x64
axis_size = jax.lax.axis_size
