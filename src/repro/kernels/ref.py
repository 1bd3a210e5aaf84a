"""Pure-jnp oracles for every Pallas kernel (the allclose targets).

These are also the ops the engine's hot loops run, on every backend, so
they are *dtype-preserving*: they compute in the input dtype exactly like
the engine's previous inline jnp (``pq.adc_distance`` / ``pq.exact_l2`` /
stable ``lax.top_k`` merge) — under x64 the engine's distance math stays
float64.  The Pallas kernels themselves emit float32 (TPU VPU/MXU
accumulation dtype); parity checks compare at float32 tolerance.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

INF = jnp.float32(3.4e38)


def adc_distance_ref(lut: jax.Array, codes: jax.Array) -> jax.Array:
    """lut: [M, 256]; codes: [B, M] uint8 -> [B]."""
    idx = codes.astype(jnp.int32)
    vals = jnp.take_along_axis(lut, idx.T, axis=1)
    return vals.sum(0)


def rerank_l2_ref(q: jax.Array, xs: jax.Array) -> jax.Array:
    """q: [D]; xs: [P, D] -> [P] squared L2."""
    diff = xs - q[None]
    return jnp.sum(diff * diff, axis=-1)


def pool_merge_ref(pool_d, pool_ids, new_d, new_ids):
    """Keep the P smallest of the concatenation (stable on ties)."""
    p = pool_d.shape[0]
    d = jnp.concatenate([pool_d, new_d])
    ids = jnp.concatenate([pool_ids, new_ids])
    order = jnp.argsort(d, stable=True)[:p]
    return d[order], ids[order]
