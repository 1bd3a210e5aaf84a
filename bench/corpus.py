"""A configuration's base corpus, made on the device from ``data_seed``.

``make_clustered`` is a copy of ``repro.data.pipeline``'s generator, kept
here so that a change to the program cannot move the corpus the
benchmark measures.  The saved index depends on this file
(``index_cache.digest``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def make_clustered(key: jax.Array, n: int, dim: int, *, n_clusters: int,
                   scale: float, noise: float):
    """Clustered-Gaussian corpus: (vectors [n, dim], centres)."""
    kc, kv, ka = jax.random.split(key, 3)
    cents = jax.random.normal(kc, (n_clusters, dim), jnp.float32) * scale
    assign = jax.random.randint(ka, (n,), 0, n_clusters)
    vecs = cents[assign] + noise * jax.random.normal(kv, (n, dim),
                                                     jnp.float32)
    return vecs, cents


def corpus(cfg: dict):
    """The configuration's base vectors and centres, from ``data_seed``."""
    return _corpus(cfg["data_seed"], cfg["n_base"], cfg["dim"],
                   cfg["n_clusters"], cfg["scale"], cfg["noise"])


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4, 5))
def _corpus(data_seed, n, dim, n_clusters, scale, noise):
    return make_clustered(jax.random.PRNGKey(data_seed), n, dim,
                          n_clusters=n_clusters, scale=scale, noise=noise)
