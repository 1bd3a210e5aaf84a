"""Convergence-Aware Speculative Reranking (CASR, Algorithm 1).

Replaces the full-pool exact rerank at the end of position seeking (and,
with a smaller pool, of search).  Vectors are fetched from the slow tier in
groups of ``s`` in PQ-distance order; each group's I/O submission overlaps
the previous group's exact-distance compute; the loop stops when the running
exact top-K stabilises.

The speculative pipeline means that when convergence is detected after
processing group *t*, group *t+1*'s I/O has already been issued — that
overrun is charged to the counters, exactly as the paper's io_uring
implementation pays it.  On TPU the same structure is a double-buffered
HBM→VMEM DMA (kernels/rerank_l2.py); this module is the engine-level
reference with full I/O accounting.
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.core.iomodel import IOCounters, PAGE_BYTES
from repro.core.layout import GraphStore, LayoutSpec
from repro.kernels import ref as kernel_ref

INF = jnp.float32(3.4e38)


class CASRResult(NamedTuple):
    ids: jax.Array          # [pool] candidate ids (the input order)
    exact_d: jax.Array      # [pool] exact distances (INF where not loaded)
    loaded: jax.Array       # [pool] bool — vector fetched
    topk_ids: jax.Array     # [k] converged exact top-K (-1 padded)
    topk_d: jax.Array       # [k]
    n_loaded: jax.Array     # int32 — vectors fetched (incl. speculative)
    n_groups: jax.Array     # int32 — pipeline rounds executed
    rerank_rounds: jax.Array  # int32 — serial I/O rounds on the latency path
    counters: IOCounters


def _topk_ids(ids: jax.Array, d: jax.Array, k: int) -> tuple[jax.Array,
                                                             jax.Array]:
    """Smallest-k by d; ties broken by position (stable).  Runs through the
    pool merge (the candidate array is the "pool" prefix merged with its
    own tail)."""
    out_d, out_i = kernel_ref.pool_merge_ref(d[:k], ids[:k], d[k:], ids[k:])
    return jnp.where(out_d < INF, out_i, -1), out_d


def _charge_vec_reads(counters: IOCounters, spec: LayoutSpec,
                      n: jax.Array, useful: bool = True) -> IOCounters:
    """n full-vector reads from the decoupled vector file."""
    pages = spec.vector_pages_per_read
    bytes_ = (n * pages * PAGE_BYTES).astype(jnp.int64)
    vec_payload = (n * spec.vector_bytes).astype(jnp.int64)
    pad = bytes_ - vec_payload
    field = "useful_vec_bytes_read" if useful else "wasted_vec_bytes_read"
    return dataclasses.replace(
        counters,
        read_requests=counters.read_requests + n.astype(jnp.int64),
        vectors_read=counters.vectors_read + n.astype(jnp.int64),
        pad_bytes_read=counters.pad_bytes_read + pad,
        **{field: getattr(counters, field) + vec_payload})


@jax.named_scope("navis.rerank")
def casr_rerank(store: GraphStore, spec: LayoutSpec, q: jax.Array,
                pool_ids: jax.Array, counters: IOCounters, *, k: int,
                s: int) -> CASRResult:
    """Algorithm 1 over a PQ-sorted candidate pool.

    ``pool_ids``: [P] main-graph ids sorted ascending by PQ distance
    (-1 padded at the tail).  Returns exact distances for the loaded prefix
    and the converged top-``k``.
    """
    P = pool_ids.shape[0]
    s = max(min(s, P), 1)
    max_groups = -(-P // s)
    valid = pool_ids >= 0
    safe = jnp.maximum(pool_ids, 0)

    def load_group(exact_d, loaded, counters, g):
        """Fetch vectors for group g (positions [g*s, g*s+s))."""
        start = g * s
        in_group = (jnp.arange(P) >= start) & (jnp.arange(P) < start + s)
        take = in_group & valid & ~loaded
        n = take.sum()
        counters = _charge_vec_reads(counters, spec, n)
        d = jnp.where(take,
                      kernel_ref.rerank_l2_ref(q, store.vectors[safe]),
                      exact_d)
        return d, loaded | take, counters, n

    # pipeline start: group 0 is loaded before the loop (Alg 1 line 3)
    exact_d = jnp.full((P,), INF)
    loaded = jnp.zeros((P,), bool)
    exact_d, loaded, counters, n0 = load_group(exact_d, loaded, counters,
                                               jnp.int32(0))

    # carry: (exact_d, loaded, topk_prev, next_group, done, rounds, counters)
    # Each iteration mirrors Alg 1's while body: speculatively issue group
    # ``next_group``'s I/O, then compute exact distances of the *previous*
    # group (already folded into exact_d by load_group — the compute is the
    # L2 inside load_group; the separation only matters for I/O accounting,
    # which is what we model), then run the convergence test.
    topk0 = jnp.full((k,), -1, jnp.int32)

    def cond(c):
        _, _, _, g, done, _, _, _ = c
        return ~done & (g <= max_groups)

    def body(c):
        exact_d, loaded, topk_prev, g, done, rounds, n_loaded, counters = c
        # speculative next-group I/O (charged even if we converge this round).
        # Unconditional: past the last group the group mask is empty and the
        # load is a no-op.  A ``lax.cond`` here would, under ``vmap``, become
        # a select whose operands — the closed-over corpus included — are
        # broadcast to every lane of the wave.
        exact_d, loaded, counters, n = load_group(exact_d, loaded,
                                                  counters, g)
        n_loaded = n_loaded + n
        # convergence test over distances known so far (groups < g)
        known_d = jnp.where(loaded & (jnp.arange(P) < g * s), exact_d, INF)
        topk_new, _ = _topk_ids(pool_ids, known_d, k)
        stable = (topk_new == topk_prev).all() & (topk_prev >= 0).any()
        exhausted = g >= max_groups
        return (exact_d, loaded, topk_new, g + 1, stable | exhausted,
                rounds + 1, n_loaded, counters)

    carry = (exact_d, loaded, topk0, jnp.int32(1), jnp.bool_(False),
             jnp.int32(1), n0, counters)
    exact_d, loaded, topk_prev, g, _, rounds, n_loaded, counters = \
        lax.while_loop(cond, body, carry)

    known_d = jnp.where(loaded, exact_d, INF)
    topk_ids, topk_d = _topk_ids(pool_ids, known_d, k)
    # latency model: the speculative pipeline keeps the I/O stream
    # continuous (group t+1 is in flight while group t computes), so the
    # rerank adds ~2 dependent round-trips (fill + drain) regardless of
    # how many groups ran — that is the entire point of Algorithm 1.
    return CASRResult(ids=pool_ids, exact_d=exact_d, loaded=loaded,
                      topk_ids=topk_ids, topk_d=topk_d, n_loaded=n_loaded,
                      n_groups=g - 1,
                      rerank_rounds=jnp.minimum(rounds, 2),
                      counters=counters)


def casr_rerank_many(store: GraphStore, spec: LayoutSpec, qs: jax.Array,
                     pools: jax.Array, counters: IOCounters, *, k: int,
                     s: int) -> CASRResult:
    """Batched Algorithm 1: one CASR rerank per query, vectorised.

    The convergence ``while_loop`` carries per-query state only, so the
    whole batch runs under ``vmap`` (lanes that converge early idle until
    the slowest lane finishes — the SIMD analogue of the paper's
    per-thread early exit).  ``counters`` is the per-query starting tally
    (usually zeros); every CASRResult field gains a leading [Q] axis, so
    total I/O is ``iomodel.sum_counters(result.counters)``.  This is the
    rerank stage the engine's ``search_many`` fan-out executes.
    """
    return jax.vmap(
        lambda q, p: casr_rerank(store, spec, q, p, counters, k=k, s=s)
    )(qs, pools)


# ---------------------------------------------------------------------------
# Classifier + calibration
# ---------------------------------------------------------------------------

def casr_stop_point(q: jax.Array, vectors: jax.Array, pool_ids: jax.Array,
                    *, k: int, s: int = 1) -> jax.Array:
    """Number of vectors CASR (group size s) would load for this pool.

    Runs the convergence recurrence on *free* exact distances — used as the
    paper's "PQ-distance-based classifier" to split useful vs wasted vector
    I/O inside the packed-layout baselines (Fig. 4a), and by the warm-up
    calibration below.  Returns an int32 count (includes the speculative
    overrun group).
    """
    P = pool_ids.shape[0]
    valid = pool_ids >= 0
    d_all = jnp.where(valid, kernel_ref.rerank_l2_ref(
        q, vectors[jnp.maximum(pool_ids, 0)]), INF)
    max_groups = -(-P // s)

    def topk_at(g):
        known = jnp.where(jnp.arange(P) < g * s, d_all, INF)
        return _topk_ids(pool_ids, known, k)[0]

    def cond(c):
        g, done = c
        return ~done & (g < max_groups)

    def body(c):
        g, _ = c
        stable = (topk_at(g) == topk_at(g + 1)).all() & \
            (topk_at(g) >= 0).any()
        return g + 1, stable

    g, _ = lax.while_loop(cond, body, (jnp.int32(1), jnp.bool_(False)))
    # loads = converged group count + one speculative group
    return jnp.minimum((g + 1) * s, valid.sum())


def calibrate_group_size(key: jax.Array, vectors: jax.Array,
                         pools: jax.Array, queries: jax.Array, *, k: int,
                         percentile: float = 25.0) -> int:
    """Warm-up calibration of s (paper §5.2): run the s=1 recurrence over
    ~100 queries' pools and take the P25 of the vectors-to-converge
    distribution."""
    stops = jax.vmap(
        lambda q, p: casr_stop_point(q, vectors, p, k=k, s=1))(queries,
                                                               pools)
    s = jnp.percentile(stops.astype(jnp.float32), percentile)
    return int(max(int(s), 1))
