"""The cell's vectors, made on the device in one program.

The distribution is that of the repository's SIFT-shaped generator
(``bench._make_dataset``): rows ``z @ a + noise`` with ``z`` standard
normal in a ``latent_dim``-dimensional space, ``a`` normal scaled by
``1/sqrt(latent_dim)`` and Gaussian noise of scale ``noise``.  The last
``n_queries`` rows are held out as the query pool.

Every seed gets the same vectors, made from a fixed key, in an order
drawn from ``--seed``: the database's row order (and so the index's ids,
its k-means samples and its build order) and the pool's order.  So the
seed changes which run sees which order, not how much work there is.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

VECTORS_KEY = 0


def key_for(seed: int) -> jax.Array:
    """A PRNG key for any non-negative seed, including seeds wider than
    32 bits: the low word seeds the key and the high word is folded in."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be non-negative, got {seed}")
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


@functools.partial(jax.jit, static_argnames=("n_db", "n_queries", "dim",
                                             "latent_dim", "noise"))
def _make(vectors_key, order_key, n_db, n_queries, dim, latent_dim, noise):
    kz, ka, kn = jax.random.split(vectors_key, 3)
    n = n_db + n_queries
    z = jax.random.normal(kz, (n, latent_dim), jnp.float32)
    a = (jax.random.normal(ka, (latent_dim, dim), jnp.float32)
         / jnp.sqrt(jnp.float32(latent_dim)))
    x = jnp.dot(z, a, precision=jax.lax.Precision.HIGHEST)
    x = x + noise * jax.random.normal(kn, (n, dim), jnp.float32)
    k_db, k_pool = jax.random.split(order_key)
    db = x[:n_db][jax.random.permutation(k_db, n_db)]
    pool = x[n_db:][jax.random.permutation(k_pool, n_queries)]
    return db, pool


def make(seed: int, dataset: dict):
    """(database (n_db, dim), query pool (n_queries, dim)), f32 on the
    default device."""
    return _make(key_for(VECTORS_KEY), key_for(seed),
                 n_db=int(dataset["n_db"]),
                 n_queries=int(dataset["n_queries"]),
                 dim=int(dataset["dim"]),
                 latent_dim=int(dataset["latent_dim"]),
                 noise=float(dataset["noise"]))
