"""Pallas TPU kernel: candidate-pool merge (partial top-k without sort).

Every traversal hop merges the explored pool [P] with the beam's freshly
scored neighbors [Q] and keeps the P closest (§2.2 ②).  A comparison sort
is a poor fit for the VPU; instead we compute each element's *rank* with
one dense pairwise comparison reduction —

    rank_i = Σ_j [ d_j < d_i  or  (d_j = d_i and j < i) ]

— an [L, L] boolean matrix reduced along rows (L = P + Q ≤ a few hundred,
so the O(L²) mask is a handful of VPU tiles), then scatter each element
whose rank < P to output slot ``rank``.  One pass, no data-dependent
control flow, stable under ties: exactly the semantics of the jnp argsort
oracle in ref.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _merge_kernel(d_ref, ids_ref, out_d_ref, out_ids_ref, *, p: int):
    d = d_ref[...]                                     # [L]
    ids = ids_ref[...]                                 # [L]
    L = d.shape[0]
    di = d[:, None]                                    # [L, 1]
    dj = d[None, :]                                    # [1, L]
    ii = jax.lax.broadcasted_iota(jnp.int32, (L, L), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (L, L), 1)
    before = (dj < di) | ((dj == di) & (jj < ii))      # [L, L]
    rank = jnp.sum(before.astype(jnp.int32), axis=1)   # [L]

    keep = rank < p
    slot = jnp.where(keep, rank, p)                    # p = drop bin
    out_d = jnp.full((p + 1,), jnp.float32(3.4e38))
    out_i = jnp.full((p + 1,), jnp.int32(-1))
    out_d = out_d.at[slot].set(jnp.where(keep, d, out_d[slot]))
    out_i = out_i.at[slot].set(jnp.where(keep, ids, out_i[slot]))
    out_d_ref[...] = out_d[:p]
    out_ids_ref[...] = out_i[:p]


def pool_merge_pallas(pool_d: jax.Array, pool_ids: jax.Array,
                      new_d: jax.Array, new_ids: jax.Array, *,
                      interpret: bool):
    """Merge (pool_d [P], new_d [Q]) keeping the P smallest.

    Returns (d [P], ids [P]) ascending, -1-padded like the pool inputs.
    """
    p = pool_d.shape[0]
    d = jnp.concatenate([pool_d, new_d]).astype(jnp.float32)
    ids = jnp.concatenate([pool_ids, new_ids]).astype(jnp.int32)
    L = d.shape[0]

    out_d, out_ids = pl.pallas_call(
        functools.partial(_merge_kernel, p=p),
        in_specs=[pl.BlockSpec((L,), lambda: (0,)),
                  pl.BlockSpec((L,), lambda: (0,))],
        out_specs=(pl.BlockSpec((p,), lambda: (0,)),
                   pl.BlockSpec((p,), lambda: (0,))),
        out_shape=(jax.ShapeDtypeStruct((p,), jnp.float32),
                   jax.ShapeDtypeStruct((p,), jnp.int32)),
        interpret=interpret,
    )(d, ids)
    return out_d, out_ids
