"""Deterministic synthetic vector streams: a clustered corpus, queries
drawn from its mixture, and insert waves that may drift away from it.

Each stream is a pure function of its PRNG key, so a run that keeps its
keys replays the same corpus, queries and inserts.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def make_clustered(key: jax.Array, n: int, dim: int, *, n_clusters: int = 32,
                   scale: float = 3.0, noise: float = 1.0):
    """Clustered-Gaussian corpus (the synthetic stand-in for FineWeb/
    MSMARCO/DEEP embeddings).  Returns (vectors [n, dim], assignments)."""
    kc, kv, ka = jax.random.split(key, 3)
    cents = jax.random.normal(kc, (n_clusters, dim), jnp.float32) * scale
    assign = jax.random.randint(ka, (n,), 0, n_clusters)
    vecs = cents[assign] + noise * jax.random.normal(kv, (n, dim), jnp.float32)
    return vecs, assign, cents


def query_stream(key: jax.Array, cents: jax.Array, n: int, *,
                 noise: float = 1.0) -> jax.Array:
    """Queries drawn from the same cluster mixture as the corpus."""
    ka, kv = jax.random.split(key)
    assign = jax.random.randint(ka, (n,), 0, cents.shape[0])
    return cents[assign] + noise * jax.random.normal(kv, (n, cents.shape[1]), jnp.float32)


def insert_stream(key: jax.Array, cents: jax.Array, n: int, *,
                  noise: float = 1.0, drift: float = 0.0) -> jax.Array:
    """Fresh vectors to insert.  ``drift`` shifts the cluster mixture —
    the paper's 'newly inserted regions' that a static entrance graph
    drifts away from (§3.2)."""
    ka, kv, kd = jax.random.split(key, 3)
    assign = jax.random.randint(ka, (n,), 0, cents.shape[0])
    shift = drift * jax.random.normal(kd, cents.shape, jnp.float32)
    return (cents + shift)[assign] + noise * jax.random.normal(
        kv, (n, cents.shape[1]), jnp.float32)
