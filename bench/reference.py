"""The plain reference: exact top-k under squared L2 over the live corpus.

Written for the benchmark and independent of the program.  ``topk`` ranks
by ``|x|^2 - 2 q.x`` with the matmul at ``Precision.HIGHEST`` (f32; the
TPU's default precision runs bf16 passes and misorders near neighbours)
and returns exact distances ``sum((q - x)^2)`` of the ids it picks.
``topk_bf16`` is the control: the same search computed in bfloat16, the
precision one step below the deployments' float32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

BIG = jnp.float32(3.4e38)
ROWS = 16_384          # corpus rows scored per block


def exact_dist(q: jax.Array, x: jax.Array) -> jax.Array:
    """Squared L2 of each query to its rows: q [Q, D], x [Q, k, D]."""
    diff = x - q[:, None, :]
    return jnp.sum(diff * diff, axis=-1)


def _scan_topk(queries, corpus, live, k, score):
    """Top-k (smallest score) over ``corpus[:live]`` block by block."""
    n, dim = corpus.shape
    rows = min(ROWS, n)
    blocks = -(-n // rows)
    pad = blocks * rows - n
    xs = jnp.pad(corpus, ((0, pad), (0, 0))).reshape(blocks, rows, dim)
    q_n = queries.shape[0]

    def step(carry, b):
        best_s, best_i = carry
        ids = b * rows + jnp.arange(rows)
        s = score(queries, xs[b])                           # [Q, rows]
        s = jnp.where((ids < live)[None, :], s, BIG)
        s = jnp.concatenate([best_s, s], axis=1)
        i = jnp.concatenate(
            [best_i, jnp.broadcast_to(ids, (q_n, rows))], axis=1)
        neg, sel = lax.top_k(-s, k)
        return (-neg, jnp.take_along_axis(i, sel, axis=1)), None

    init = (jnp.full((q_n, k), BIG), jnp.full((q_n, k), -1, jnp.int32))
    (best_s, best_i), _ = lax.scan(step, init, jnp.arange(blocks))
    return best_s, jnp.where(best_s < BIG, best_i, -1)


def _f32_score(q, x):
    dot = jnp.dot(q, x.T, precision=lax.Precision.HIGHEST)
    return jnp.sum(x * x, axis=1)[None, :] - 2.0 * dot


def _bf16_score(q, x):
    qb, xb = q.astype(jnp.bfloat16), x.astype(jnp.bfloat16)
    dot = jnp.dot(qb, xb.T, preferred_element_type=jnp.bfloat16)
    norm = jnp.sum(xb * xb, axis=1, dtype=jnp.bfloat16)
    return (norm[None, :] - 2 * dot).astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("k",))
def topk(queries: jax.Array, corpus: jax.Array, live, *, k: int):
    """(ids [Q, k], exact squared distances [Q, k]) of the ``k`` nearest
    rows among ``corpus[:live]``."""
    _, ids = _scan_topk(queries, corpus, live, k, _f32_score)
    d = exact_dist(queries, corpus[jnp.maximum(ids, 0)])
    return ids, jnp.where(ids >= 0, d, BIG)


@functools.partial(jax.jit, static_argnames=("k",))
def topk_bf16(queries: jax.Array, corpus: jax.Array, live, *, k: int):
    """The control: the same search with every step in bfloat16; its
    distances are the bfloat16 ones it ranked by."""
    _, ids = _scan_topk(queries, corpus, live, k, _bf16_score)
    xb = corpus[jnp.maximum(ids, 0)].astype(jnp.bfloat16)
    diff = xb - queries.astype(jnp.bfloat16)[:, None, :]
    d = jnp.sum(diff * diff, axis=-1, dtype=jnp.bfloat16)
    return ids, jnp.where(ids >= 0, d.astype(jnp.float32), BIG)


@jax.jit
def dist_of(queries: jax.Array, corpus: jax.Array, ids: jax.Array):
    """Exact squared distances of ``ids`` [Q, k] (``BIG`` where < 0)."""
    d = exact_dist(queries, corpus[jnp.maximum(ids, 0)])
    return jnp.where(ids >= 0, d, BIG)
