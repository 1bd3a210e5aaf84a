"""Pure-jnp oracles for every Pallas kernel (the allclose targets).

These are also the ops the engine's hot loops run, so they are
*dtype-preserving*: they compute in the input dtype exactly like the
engine's previous inline jnp (``pq.adc_distance`` / ``pq.exact_l2`` /
stable ``lax.top_k`` merge) — under x64 the engine's distance math stays
float64.  The ADC lookup has two forms, picked by :func:`adc_distance`
when the program is lowered: a one-hot select over the LUT on TPU, where
a per-element gather runs serially, and the gather everywhere else.  The
Pallas kernels themselves emit float32 (TPU VPU/MXU accumulation dtype);
parity checks compare at float32 tolerance.
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


def adc_distance_onehot(lut: jax.Array, codes: jax.Array) -> jax.Array:
    """:func:`adc_distance_ref` without a gather: each code selects its
    LUT entry by a compare against an iota of 256, and the row of
    selections is summed.  Exactly one term per subspace is nonzero, so
    the per-subspace values equal the gather's bit for bit; only the sum
    over subspaces may round in another order."""
    idx = codes.astype(jnp.int32).T                           # [M, B]
    hit = idx[:, None, :] == jnp.arange(256)[None, :, None]   # [M, 256, B]
    vals = jnp.where(hit, lut[:, :, None], 0).sum(1)          # [M, B]
    return vals.sum(0)


@jax.named_scope("navis.adc")
def adc_distance(lut: jax.Array, codes: jax.Array) -> jax.Array:
    """The engine's ADC, its form picked when the program is lowered:
    the one-hot select for TPU, whose gather fetches one element at a
    time; the gather on every other platform, where the one-hot's
    256-fold work costs more than the lookup.  Its ops carry the scope
    ``navis.adc`` under their caller's stage."""
    return jax.lax.platform_dependent(lut, codes, tpu=adc_distance_onehot,
                                      default=adc_distance_ref)


def rerank_l2_ref(q: jax.Array, xs: jax.Array) -> jax.Array:
    """q: [D]; xs: [P, D] -> [P] squared L2."""
    diff = xs - q[None]
    return jnp.sum(diff * diff, axis=-1)


def pool_merge_ref(pool_d, pool_ids, new_d, new_ids, *extra):
    """Keep the P smallest of the concatenation (stable on ties: pool
    entries ahead of new ones, each in its own order).

    ``extra`` holds ``(pool_x, new_x)`` pairs of further per-slot operands,
    each moved with its slot.  One multi-operand sort keyed on the
    distance carries every operand, so no gather follows it.  Returns
    ``(d, ids, *xs)``, each cut to P."""
    p = pool_d.shape[0]
    ops = [jnp.concatenate(pair)
           for pair in ((pool_d, new_d), (pool_ids, new_ids), *extra)]
    return tuple(o[:p] for o in jax.lax.sort(ops, num_keys=1,
                                             is_stable=True))
