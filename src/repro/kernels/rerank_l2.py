"""Pallas TPU kernel: grouped exact-L2 rerank (the CASR compute stage).

Computes d[p] = ‖q − x_p‖² over a PQ-ordered candidate matrix, in groups
of ``s`` rows — the TPU materialisation of Algorithm 1's speculative
pipeline.  The paper overlaps group t+1's io_uring submission with group
t's exact-distance compute; here the grid dimension *is* the group index,
and Pallas's automatic pipelining issues block t+1's HBM→VMEM DMA while
block t runs on the VPU/MXU — the same submission/compute overlap,
expressed as BlockSpec streaming (DESIGN.md §2, io_uring row).

The group dimension stays a *grid* axis (not folded into one big block) so
the engine can bound the number of groups it launches: CASR's early stop
truncates the candidate matrix before calling, and the kernel never
touches vectors past the convergence point.

d is computed as ‖q‖² − 2·q·x + ‖x‖² with the q·x term on the MXU
(a [s, D] × [D, 1] matmul per group) — at D ≥ 512 this is ~2× fewer VPU
flops than the subtract-square-reduce form.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _rerank_kernel(q_ref, x_ref, out_ref):
    q = q_ref[...]                                    # [1, D]
    x = x_ref[...]                                    # [s, D]
    qx = jnp.dot(x, q.T, preferred_element_type=jnp.float32)  # [s, 1] (MXU)
    xx = jnp.sum(x * x, axis=1, keepdims=True)        # [s, 1]
    qq = jnp.sum(q * q, axis=1, keepdims=True)        # [1, 1]
    out_ref[...] = (xx - 2.0 * qx + qq)[:, 0]


def rerank_l2_pallas(q: jax.Array, xs: jax.Array, *, group: int = 8,
                     interpret: bool) -> jax.Array:
    """q: [D]; xs: [P, D] candidate vectors (PQ order) -> [P] distances.

    ``group`` is CASR's s: one grid step per group, giving the
    double-buffered load/compute overlap on real TPU hardware.
    """
    p, d = xs.shape
    ng = -(-p // group)
    pad = ng * group - p
    if pad:
        xs = jnp.pad(xs, ((0, pad), (0, 0)))

    out = pl.pallas_call(
        _rerank_kernel,
        grid=(ng,),
        in_specs=[
            pl.BlockSpec((1, d), lambda i: (0, 0)),        # query pinned
            pl.BlockSpec((group, d), lambda i: (i, 0)),    # groups stream
        ],
        out_specs=pl.BlockSpec((group,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((ng * group,), jnp.float32),
        interpret=interpret,
    )(q[None].astype(jnp.float32), xs.astype(jnp.float32))
    return out[:p]
