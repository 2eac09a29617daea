"""Boundary validation — input hardening at every public entry point.

The TPU analogue of the input checking the reference does in its C++ API
layer (``RAFT_EXPECTS`` guards on every public header): *validate at the
boundary*, so garbage inputs (NaN/Inf rows, malformed shapes) are
reported where they enter instead of flowing through jitted kernels and
coming out as wrong-but-plausible neighbors.

Behavior is governed by :func:`raft_tpu.config.get_validation_policy`:

``raise``
    One fused ``isfinite`` reduction over the input plus a host sync; a
    non-finite row raises :class:`~raft_tpu.integrity.errors.ValidationError`
    naming the first bad row.  The default (serving-safe).
``mask``
    Jit-compatible, sync-free: non-finite rows are replaced by zeros
    in-graph and the per-row validity vector is returned so callers flag
    the corresponding *outputs* (search marks masked rows with id -1 /
    worst distance) — one bad row cannot poison the batch.
``off``
    Every function here returns immediately — zero added work, the
    jitted path is identical to an unvalidated call.

Counters: ``integrity.boundary.checks`` / ``.raised`` / ``.masked_rows``
(the masked-row count syncs only when observability collection is on).

The ``raise`` policy's check, device or host, runs inside the always-on
profiler annotation ``raft_tpu:integrity.sync``: on the device path it is
where the host waits for everything queued before the check.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from raft_tpu import config
from raft_tpu import observability as obs
from raft_tpu.core import tracing as _tracing
from raft_tpu.integrity.errors import ValidationError


def _is_floating(x) -> bool:
    return jnp.issubdtype(jnp.asarray(x).dtype, jnp.floating)


def guard_nonfinite(x, *, site: str, policy: Optional[str] = None,
                    host: bool = False
                    ) -> Tuple[jax.Array, Optional[jax.Array]]:
    """Policy-driven non-finite guard over the rows of ``x``.

    Returns ``(x, ok_rows)`` where ``ok_rows`` is a per-row bool vector
    under policy ``mask`` (callers use it to flag outputs) and ``None``
    otherwise.  Non-floating inputs pass through untouched.

    ``host=True`` runs the identical policy in numpy and returns host
    arrays — for callers validating request-shaped data whose sizes are
    unbounded (the serving submit path), where a per-shape device
    compile would break the zero-recompile contract.
    """
    p = policy if policy is not None else config.get_validation_policy()
    if p == "off":
        return x, None
    if host:
        return _guard_nonfinite_host(x, site=site, policy=p)
    x = jnp.asarray(x)
    if not _is_floating(x):
        return x, None
    if p == "raise" and isinstance(x, jax.core.Tracer):
        # inside an outer jit/vmap there is no host to sync to; 'raise'
        # degrades to a no-op there ('mask' stays fully in-graph)
        return x, None
    if obs.enabled():
        obs.registry().counter("integrity.boundary.checks").inc()
    reduce_axes = tuple(range(1, x.ndim))
    ok = jnp.all(jnp.isfinite(x.astype(jnp.float32)), axis=reduce_axes)
    if p == "raise":
        with _tracing.annotation("integrity.sync"):
            # the policy's one host sync (a second only on a bad row)
            bad = None if bool(jnp.all(ok)) else int(jnp.argmin(ok))
        if bad is not None:
            if obs.enabled():
                obs.registry().counter("integrity.boundary.raised").inc()
            raise ValidationError(
                f"{site}: non-finite values in input row {bad} "
                f"(policy 'raise'; use config.validation_policy('mask') "
                f"to flag rows instead, or 'off' for trusted inputs)",
                invariant="boundary.nonfinite", coord=(bad,))
        return x, None
    # mask: in-graph replacement, no host sync on the result path
    shape_ok = ok.reshape(ok.shape + (1,) * (x.ndim - 1))
    clean = jnp.where(shape_ok, x, jnp.zeros((), x.dtype))
    if obs.enabled():
        obs.registry().counter("integrity.boundary.masked_rows").inc(
            int(jnp.sum(~ok)))
    return clean, ok


def _guard_nonfinite_host(x, *, site: str, policy: str
                          ) -> Tuple[np.ndarray, Optional[np.ndarray]]:
    """Numpy twin of the device guard — same policy semantics, same
    counters, zero device work (and therefore zero compiles)."""
    x = np.asarray(x)
    if not jnp.issubdtype(x.dtype, jnp.floating):  # dtype-level, no transfer
        return x, None
    if obs.enabled():
        obs.registry().counter("integrity.boundary.checks").inc()
    reduce_axes = tuple(range(1, x.ndim))
    ok = np.all(np.isfinite(x.astype(np.float32)), axis=reduce_axes)
    if policy == "raise":
        with _tracing.annotation("integrity.sync"):
            bad = None if bool(np.all(ok)) else int(np.argmin(ok))
        if bad is not None:
            if obs.enabled():
                obs.registry().counter("integrity.boundary.raised").inc()
            raise ValidationError(
                f"{site}: non-finite values in input row {bad} "
                f"(policy 'raise'; use config.validation_policy('mask') "
                f"to flag rows instead, or 'off' for trusted inputs)",
                invariant="boundary.nonfinite", coord=(bad,))
        return x, None
    shape_ok = ok.reshape(ok.shape + (1,) * (x.ndim - 1))
    clean = np.where(shape_ok, x, np.zeros((), x.dtype))
    if obs.enabled():
        obs.registry().counter("integrity.boundary.masked_rows").inc(
            int(np.sum(~ok)))
    return clean, ok


def check_matrix(x, name: str, *, site: str, dim: Optional[int] = None,
                 allow_empty: bool = True, policy: Optional[str] = None,
                 host: bool = False
                 ) -> Tuple[jax.Array, Optional[jax.Array]]:
    """Structural + non-finite validation for a 2-D input.

    Host-side O(1) shape checks (always under ``raise``/``mask``; skipped
    entirely under ``off``), then :func:`guard_nonfinite`.  Returns
    ``(x, ok_rows)`` as :func:`guard_nonfinite` does.
    """
    p = policy if policy is not None else config.get_validation_policy()
    if p == "off":
        return x, None
    xs = np.shape(x) if not hasattr(x, "shape") else x.shape
    if len(xs) != 2:
        raise ValidationError(
            f"{site}: {name} must be 2-D, got shape {tuple(xs)}",
            invariant="boundary.rank")
    if dim is not None and xs[1] != dim:
        raise ValidationError(
            f"{site}: {name} has {xs[1]} columns, expected {dim}",
            invariant="boundary.dim")
    if not allow_empty and xs[0] == 0:
        raise ValidationError(
            f"{site}: {name} has no rows",
            invariant="boundary.empty")
    return guard_nonfinite(x, site=site, policy=p, host=host)


def mask_search_outputs(distances: jax.Array, indices: jax.Array,
                        ok_rows: Optional[jax.Array], *,
                        select_min: bool = True
                        ) -> Tuple[jax.Array, jax.Array]:
    """Flag masked query rows in search outputs: id -1 and the worst
    distance for the metric (sync-free; composes with the in-graph
    masking of :func:`guard_nonfinite`)."""
    if ok_rows is None:
        return distances, indices
    worst = jnp.inf if select_min else -jnp.inf
    bad = ~ok_rows[:, None]
    return (jnp.where(bad, jnp.asarray(worst, distances.dtype), distances),
            jnp.where(bad, jnp.asarray(-1, indices.dtype), indices))
