"""GVS search: entry-point selection → on-disk beam traversal (→ rerank).

The traversal is the paper's ② stage: greedy beam search over the on-disk
graph using in-memory PQ distances, loading only edgelist pages under the
decoupled layout (packed layout drags vectors along — counted).  A fixed
size explored pool (|E_search| for queries, |E_pos| for position seeking) is
maintained until convergence.

Everything is jittable: the pool, visited sets, cache state and I/O
counters thread through a ``lax.while_loop``.

Traversal state is O(1) in the corpus: the pool carries each slot's
"expanded" flag, and the ``vec_loaded`` / ``page_seen`` sets are
fixed-capacity hash sets bounded by the search frontier
(``max_hops × beam_width`` marks — see :mod:`repro.core.visited`), not
``[n_max]`` bitmaps, so a B-lane fan-out wave costs
``O(B·max_hops·beam_width)`` memory instead of ``O(B·n_max)``.  The
``visited="bitmap"`` mode keeps the dense reference implementation
(equivalence tests / ablation).  Per-hop examination compute (ADC
distances, exact L2, pool merge) runs the jnp ops of
:mod:`repro.kernels.ref`; the ADC is a one-hot select on TPU and a
gather elsewhere (:func:`repro.kernels.ref.adc_distance`).
"""
from __future__ import annotations

import dataclasses
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.core import cache as cache_mod
from repro.core import visited as visited_mod
from repro.core.entrance import EntranceGraph, empty_entrance  # noqa: F401
from repro.core.iomodel import IOCounters, PAGE_BYTES
from repro.core.layout import GraphStore, LayoutSpec
from repro.kernels import ref as kernel_ref

INF = jnp.float32(3.4e38)


def entrance_search(ent: EntranceGraph, lut: jax.Array, codes: jax.Array,
                    *, n_entry: int, pool_size: int = 32,
                    max_hops: int = 64, visited: str = "hash"):
    """In-memory beam search over the entrance graph (no storage I/O).

    Returns (entry ids [n_entry] into the MAIN graph, explored-set main ids
    E_ent [pool_size] with their PQ distances) — the explored set feeds
    NAVIS-update (Algorithm 2).

    The ``expanded`` set is a hash set of ≤ ``min(max_hops, c_max)`` slots
    (one expansion per hop), so per-query state does not scale with the
    entrance graph; ``visited="bitmap"`` keeps the dense reference.
    """
    c = ent.c_max
    # seed: the first *live* entry slot.  Build keeps a medoid-ish vertex at
    # slot 0, but deletes scrub entrance members — after the medoid dies the
    # seed must fall back to the next live slot, not a dead one.
    live = ent.ids >= 0
    seed = jnp.argmax(live).astype(jnp.int32)[None]
    seed_main = ent.ids[seed]
    seed_d = jnp.where(seed_main >= 0,
                       kernel_ref.adc_distance(lut, codes[jnp.maximum(
                           seed_main, 0)]), INF)

    pool_idx = jnp.full((pool_size,), -1, jnp.int32).at[0].set(seed[0])
    pool_d = jnp.full((pool_size,), INF).at[0].set(seed_d[0])
    if visited == "bitmap":
        expanded = visited_mod.make_dense(c)
    else:
        # one expansion per hop, at most c distinct slots: never overflows
        expanded = visited_mod.make_hash(min(max_hops, c))
    unexp0 = pool_idx >= 0

    def cond(carry):
        unexp, hops = carry[3], carry[4]
        return (hops < max_hops) & unexp.any()

    def body(carry):
        pool_idx, pool_d, expanded, unexp, hops = carry
        cand_d = jnp.where(unexp, pool_d, INF)
        best = jnp.argmin(cand_d)
        v = pool_idx[best]
        expanded = visited_mod.add(expanded, v[None], jnp.ones((1,), bool))
        nbrs = ent.edges[v]                                   # [R_ent]
        in_pool = (nbrs[:, None] == pool_idx[None, :]).any(axis=1)
        valid = (nbrs >= 0) & ~visited_mod.contains(expanded, nbrs) & \
            ~in_pool
        main_ids = ent.ids[jnp.maximum(nbrs, 0)]
        d = jnp.where(valid & (main_ids >= 0),
                      kernel_ref.adc_distance(lut, codes[jnp.maximum(
                          main_ids, 0)]), INF)
        pool_d, pool_idx = kernel_ref.pool_merge_ref(
            pool_d, pool_idx, d, jnp.where(valid, nbrs, -1))
        unexp = (pool_idx >= 0) & ~visited_mod.contains(expanded, pool_idx)
        return (pool_idx, pool_d, expanded, unexp, hops + 1)

    pool_idx, pool_d, expanded, _, hops = lax.while_loop(
        cond, body, (pool_idx, pool_d, expanded, unexp0,
                     jnp.zeros((), jnp.int32)))
    main = jnp.where(pool_idx >= 0, ent.ids[jnp.maximum(pool_idx, 0)], -1)
    return main[:n_entry], main, pool_d


# ---------------------------------------------------------------------------
# On-disk traversal
# ---------------------------------------------------------------------------

class TraverseResult(NamedTuple):
    pool_ids: jax.Array       # [pool] main-graph ids sorted by PQ distance
    pool_dists: jax.Array     # [pool] PQ distances
    vec_loaded: visited_mod.VisitedSet   # vectors dragged in (packed)
    hops: jax.Array
    cache: cache_mod.CacheState
    counters: IOCounters
    # pages this traversal read: a VisitedSet, or a raw [P_max] bool array
    # when the caller seeded one (bulk-merge sharing) / bitmap mode
    page_seen: jax.Array | visited_mod.VisitedSet
    # frozen-cache mode only (else None): charged page accesses, in order
    trace: jax.Array | None = None       # [max_hops * W] int32, -1 padded
    trace_n: jax.Array | None = None     # int32 — valid trace entries


def _charge_page_read(counters: IOCounters, spec: LayoutSpec, *,
                      is_edge_page: jax.Array, n=1) -> IOCounters:
    """Account ``n`` 4 KiB page reads from the slow tier (n may be traced:
    the frozen fan-out path charges a whole beam's misses at once)."""
    if spec.kind == "packed":
        per = spec.packed_per_page
        payload = per * spec.packed_record_bytes
        vec = per * spec.vector_bytes
        edge = per * spec.edgelist_bytes
        # vectors counted provisionally as wasted; reranking reclassifies
        return dataclasses.replace(
            counters,
            read_requests=counters.read_requests + n,
            edge_bytes_read=counters.edge_bytes_read + n * edge,
            wasted_vec_bytes_read=counters.wasted_vec_bytes_read + n * vec,
            pad_bytes_read=counters.pad_bytes_read +
            n * (PAGE_BYTES - payload))
    per = spec.edgelists_per_page
    payload = per * spec.edgelist_bytes
    return dataclasses.replace(
        counters,
        read_requests=counters.read_requests + n,
        edge_bytes_read=counters.edge_bytes_read + n * payload,
        pad_bytes_read=counters.pad_bytes_read +
        n * (PAGE_BYTES - payload))


def _charge_access(counters: IOCounters, spec: LayoutSpec,
                   hit: jax.Array) -> IOCounters:
    """Account one cache probe: tally hit/miss, charge a page read on miss."""
    counters = dataclasses.replace(
        counters,
        cache_hits=counters.cache_hits + hit,
        cache_misses=counters.cache_misses + (~hit))
    return lax.cond(
        hit, lambda c: c,
        lambda c: _charge_page_read(c, spec, is_edge_page=True),
        counters)


def fetch_edgelists(store: GraphStore, spec: LayoutSpec,
                    cache: cache_mod.CacheState, counters: IOCounters,
                    page_seen: visited_mod.VisitedSet, ids: jax.Array,
                    valid: jax.Array,
                    trace: jax.Array | None = None,
                    trace_n: jax.Array | None = None):
    """Read the edge pages backing ``ids`` (beam of W vertices) through the
    per-query buffer (``page_seen``, a visited set) and the host cache.
    Pages already read by *this* traversal are free (the query holds them in
    its scratch buffer, as DiskANN-lineage systems do) — this is where the
    decoupled layout's page-level locality pays off, since
    ~``edgelists_per_page`` co-traversed vertices ride on one read.  Packed
    layout: the page also carries the vertices' vectors (marked loaded by
    the caller).

    With ``trace``/``trace_n`` supplied the cache is treated as a *frozen
    snapshot*: hits come from :func:`cache_mod.lookup` (pure), the cache is
    returned untouched, and every charged access is appended to ``trace``
    for later :func:`cache_mod.apply_trace` replay.  This is the read path
    concurrent (vmapped) searches share.

    Returns (edges [W,R], cache, counters, page_seen, trace, trace_n).
    """
    frozen = trace is not None
    w = ids.shape[0]
    safe = jnp.maximum(ids, 0)
    pages = store.edge_page[safe]

    if frozen:
        # No mutation ordering constraint against a snapshot, so the whole
        # beam is processed vectorised (the sequential path must scan: each
        # access's eviction depends on the previous one).  The trace keeps
        # slot order, so replay still matches the sequential access order.
        safe_p = jnp.maximum(pages, 0)
        # charged if: valid, not already read by this traversal, and not a
        # duplicate of an earlier valid slot in this beam
        eq_earlier = (pages[:, None] == pages[None, :]) & valid[None, :] & \
            (jnp.arange(w)[None, :] < jnp.arange(w)[:, None])
        charged = valid & ~visited_mod.contains(page_seen, pages) & \
            ~eq_earlier.any(axis=1)
        hit = cache_mod.lookup(cache, safe_p) & charged
        n_hit = hit.sum()
        n_miss = charged.sum() - n_hit
        counters = dataclasses.replace(
            counters,
            cache_hits=counters.cache_hits + n_hit,
            cache_misses=counters.cache_misses + n_miss)
        counters = _charge_page_read(counters, spec, is_edge_page=True,
                                     n=n_miss)
        # scatter charged pages at trace_n.. in slot order (OOB = dropped)
        pos = jnp.where(charged, trace_n + jnp.cumsum(charged) - 1,
                        trace.shape[0])
        trace = trace.at[pos].set(pages)
        trace_n = trace_n + charged.sum().astype(jnp.int32)
        page_seen = visited_mod.add(page_seen, pages, valid)
    else:
        def step(carry, i):
            cache_c, counters, page_seen = carry
            page = pages[i]
            # free if: invalid, duplicate within this beam, or already read
            # by this traversal (per-query buffer)
            earlier = jnp.arange(w) < i
            dup = jnp.any((pages == page) & valid & earlier)
            dup = dup | ~valid[i] | visited_mod.contains(page_seen, page)

            def charged(args):
                cache_c, counters = args
                hit, cache_c = cache_mod.access(cache_c, page)
                return cache_c, _charge_access(counters, spec, hit)

            cache_c, counters = lax.cond(dup, lambda a: a, charged,
                                         (cache_c, counters))
            page_seen = visited_mod.add(page_seen, page[None],
                                        valid[i][None])
            return (cache_c, counters, page_seen), None

        (cache, counters, page_seen), _ = lax.scan(
            step, (cache, counters, page_seen), jnp.arange(w))
    edges = jnp.where(valid[:, None], store.edges[safe], -1)
    return edges, cache, counters, page_seen, trace, trace_n


def make_traversal_state(*, visited: str, pool_size: int, beam_width: int,
                         max_hops: int, n_max: int, p_max: int,
                         visited_capacity: int | None = None,
                         frozen: bool = False):
    """The per-query traversal state ``disk_traverse`` carries besides its
    pool — the ONE place the capacity recipe lives
    (``traversal_state_bytes`` and the footprint benchmark account the
    same structures).

    Two sets: ``vec_loaded``, the vectors a packed page dragged in, and
    ``page_seen``, the pages this traversal read.  A hop reads the pages of
    ≤ ``beam_width`` vertices for ≤ ``max_hops`` hops, so
    ``max_hops × beam_width`` bounds ``page_seen`` exactly; ``vec_loaded``
    additionally absorbs ``full_rerank`` marking the surviving pool.  Which
    vertices were expanded is not a set: the pool carries it as a per-slot
    flag.  Returns (vec_loaded, page_seen, trace) — ``trace`` is None
    unless ``frozen``.
    """
    cap = (visited_capacity if visited_capacity is not None
           else max_hops * beam_width)
    if visited == "bitmap":
        sets = (visited_mod.make_dense(n_max),
                visited_mod.make_dense(p_max))
    else:
        sets = (visited_mod.make_hash(cap + pool_size),
                visited_mod.make_hash(cap))
    trace = (jnp.full((max_hops * beam_width,), -1, jnp.int32)
             if frozen else None)
    return sets + (trace,)


def _wrap_page_seen(page_seen, default: visited_mod.VisitedSet,
                    visited: str):
    """Normalise the caller's page buffer into a visited set.

    Returns (set, raw) — ``raw=True`` when the result must be handed back
    as a raw dense bool array (caller seeded one for bulk-merge sharing,
    or legacy bitmap mode)."""
    if page_seen is None:
        return default, visited == "bitmap"
    if isinstance(page_seen, (visited_mod.DenseVisited,
                              visited_mod.HashVisited)):
        return page_seen, False
    return visited_mod.DenseVisited(page_seen), True


def empty_page_seen(store: GraphStore, *, visited: str = "hash",
                    max_hops: int, beam_width: int,
                    visited_capacity: int | None = None):
    """An empty per-query page buffer matching what ``disk_traverse`` would
    create for these parameters (callers that need a structurally matching
    placeholder, e.g. masked branches of an insert)."""
    _, ps, _ = make_traversal_state(
        visited=visited, pool_size=1, beam_width=beam_width,
        max_hops=max_hops, n_max=store.n_max,
        p_max=store.page_live.shape[0], visited_capacity=visited_capacity)
    return ps.bits if visited == "bitmap" else ps


@jax.named_scope("navis.traverse")
def disk_traverse(store: GraphStore, spec: LayoutSpec, lut: jax.Array,
                  codes: jax.Array, cache: cache_mod.CacheState,
                  counters: IOCounters, entry_ids: jax.Array, *,
                  pool_size: int, beam_width: int = 4,
                  max_hops: int = 512,
                  page_seen=None,
                  frozen_cache: bool = False,
                  visited: str = "hash",
                  visited_capacity: int | None = None) -> TraverseResult:
    """Greedy beam search over the on-disk graph with PQ distances.

    ``entry_ids``: [n_entry] main-graph ids (-1 padded) from ① entry-point
    selection.  Pool converges when no unexpanded candidate remains among
    the top ``pool_size``.  ``page_seen`` optionally seeds the per-query
    page buffer (bulk merges share one buffer across many seeks so repeated
    page reads amortise — FreshDiskANN's batched-I/O advantage); it may be
    a raw dense bool array or a :mod:`repro.core.visited` set.

    ``frozen_cache=True`` runs the traversal as a pure *reader* of the
    cache snapshot: no cache mutation threads through the loop (so a batch
    of traversals vectorises under ``vmap``), and the charged page-access
    sequence comes back in ``result.trace`` / ``result.trace_n`` for
    ordered replay into the shared cache afterwards.  Both fan-outs ride
    on this: ``search_many`` (|E_search| pools) and ``insert_many``'s
    position-seek phase (|E_pos| pools via :func:`insert.position_seek`).

    Which pool entries were expanded is a flag on each pool slot, moved
    with the slot by the merge (see the comment in the loop), so the
    traversal keeps no set of expanded ids.

    ``visited="hash"`` (default) bounds the page and vector sets by the
    frontier: a hop reads the pages of at most ``beam_width`` vertices, so
    ``max_hops × beam_width`` is an exact capacity bound and the hash sets
    behave bit-identically to the ``visited="bitmap"`` reference.
    ``visited_capacity`` overrides the bound (smaller values saturate: a
    page may be charged again — counted in ``counters.visited_overflow``
    — but the pool and hops are those of the exact run).
    """
    n_max = store.n_max
    n_entry = entry_ids.shape[0]

    safe_e = jnp.maximum(entry_ids, 0)
    e_valid = entry_ids >= 0
    e_d = jnp.where(e_valid, kernel_ref.adc_distance(lut, codes[safe_e]),
                    INF)
    order = jnp.argsort(e_d)
    pool_ids = jnp.full((pool_size,), -1, jnp.int32)
    pool_d = jnp.full((pool_size,), INF)
    k = min(n_entry, pool_size)
    pool_ids = pool_ids.at[:k].set(
        jnp.where(e_valid[order][:k], entry_ids[order][:k], -1))
    pool_d = pool_d.at[:k].set(e_d[order][:k])
    vec_loaded, default_ps, trace0 = make_traversal_state(
        visited=visited, pool_size=pool_size, beam_width=beam_width,
        max_hops=max_hops, n_max=n_max, p_max=store.page_live.shape[0],
        visited_capacity=visited_capacity, frozen=frozen_cache)
    ps, raw_pages = _wrap_page_seen(page_seen, default_ps, visited)
    ovf0 = visited_mod.overflow(ps)
    # each hop charges ≤ beam_width accesses, so the trace never overflows
    trace_n0 = jnp.zeros((), jnp.int32) if frozen_cache else None
    pool_exp0 = jnp.zeros((pool_size,), bool)

    def cond(carry):
        pool_ids, pool_exp, hops = carry[0], carry[2], carry[-1]
        return (hops < max_hops) & ((pool_ids >= 0) & ~pool_exp).any()

    def body(carry):
        if frozen_cache:
            (pool_ids, pool_d, pool_exp, vec_loaded, ps,
             trace, trace_n, counters, hops) = carry
            cache_in = cache                  # closed-over snapshot
        else:
            (pool_ids, pool_d, pool_exp, vec_loaded, ps,
             cache_in, counters, hops) = carry
            trace, trace_n = None, None
        # one hop: navis.merge picks the beam and folds the scored
        # neighbours back into the pool, navis.fetch reads the beam's
        # edgelists, navis.score measures the new neighbours
        with jax.named_scope("navis.merge"):
            unexp = (pool_ids >= 0) & ~pool_exp
            cand_d = jnp.where(unexp, pool_d, INF)
            # top_k (stable, like argsort) is O(n) selection, not a full sort
            neg_sel, sel = lax.top_k(-cand_d, beam_width)
            beam = jnp.where(-neg_sel < INF, pool_ids[sel], -1)
            beam_valid = beam >= 0
            # mark by id, not by slot: entry points may repeat an id
            pool_exp = pool_exp | ((pool_ids >= 0) & (
                pool_ids[:, None] == beam[None, :]).any(axis=1))

        with jax.named_scope("navis.fetch"):
            edges, cache_out, counters, ps, trace, trace_n = \
                fetch_edgelists(store, spec, cache_in, counters, ps,
                                beam, beam_valid, trace, trace_n)
            if spec.kind == "packed":
                vec_loaded = visited_mod.add(vec_loaded, beam, beam_valid)

        # Vamana semantics: the explored pool is a *set* and expansion is
        # permanent.  "Expanded" is a flag on the pool slot, moved with it
        # by the merge: an expanded vertex still in the pool is dropped
        # from the new neighbours by ``in_pool``, and one evicted from the
        # pool cannot re-enter it, since the pool's worst distance never
        # rises, its distance is fixed, and the stable merge places pool
        # entries ahead of new ones.  So no set of expanded ids is kept.
        with jax.named_scope("navis.score"):
            nbrs = edges.reshape(-1)                          # [W*R]
            safe_n = jnp.maximum(nbrs, 0)
            in_pool = (nbrs[:, None] == pool_ids[None, :]).any(axis=1)
            nvalid = (nbrs >= 0) & ~in_pool
            # dedupe within the flat neighbor list (first occurrence wins):
            # sort the W*R keys instead of scattering through an O(n_max)
            # position table — the stable sort keeps the lowest flat index
            # first among equal keys, so the same occurrence survives
            key_ = jnp.where(nvalid, nbrs, jnp.iinfo(jnp.int32).max)
            sort_idx = jnp.argsort(key_)
            sorted_key = key_[sort_idx]
            first = jnp.concatenate([
                jnp.ones((1,), bool), sorted_key[1:] != sorted_key[:-1]])
            keep = jnp.zeros_like(nvalid).at[sort_idx].set(first)
            nvalid = nvalid & keep
            nd = jnp.where(nvalid,
                           kernel_ref.adc_distance(lut, codes[safe_n]),
                           INF)

        with jax.named_scope("navis.merge"):
            pool_d, pool_ids, pool_exp = kernel_ref.pool_merge_ref(
                pool_d, pool_ids, nd, jnp.where(nvalid, nbrs, -1),
                (pool_exp, jnp.zeros_like(nvalid)))
        counters = dataclasses.replace(counters, hops=counters.hops + 1)
        if frozen_cache:
            return (pool_ids, pool_d, pool_exp, vec_loaded, ps,
                    trace, trace_n, counters, hops + 1)
        return (pool_ids, pool_d, pool_exp, vec_loaded, ps,
                cache_out, counters, hops + 1)

    if frozen_cache:
        carry = (pool_ids, pool_d, pool_exp0, vec_loaded, ps,
                 trace0, trace_n0, counters, jnp.zeros((), jnp.int32))
        (pool_ids, pool_d, _, vec_loaded, ps, trace,
         trace_n, counters, hops) = lax.while_loop(cond, body, carry)
        cache_out = cache
    else:
        carry = (pool_ids, pool_d, pool_exp0, vec_loaded, ps,
                 cache, counters, jnp.zeros((), jnp.int32))
        (pool_ids, pool_d, _, vec_loaded, ps, cache_out,
         counters, hops) = lax.while_loop(cond, body, carry)
        trace, trace_n = None, None
    ovf = (visited_mod.overflow(vec_loaded) + visited_mod.overflow(ps)
           - ovf0).astype(jnp.int64)
    counters = dataclasses.replace(
        counters, visited_overflow=counters.visited_overflow + ovf)
    return TraverseResult(pool_ids, pool_d, vec_loaded, hops, cache_out,
                          counters, ps.bits if raw_pages else ps,
                          trace, trace_n)


# ---------------------------------------------------------------------------
# Per-query traversal state accounting (footprint benchmark / tests)
# ---------------------------------------------------------------------------

def traversal_state_bytes(*, n_max: int, p_max: int, pool_size: int,
                          beam_width: int, max_hops: int,
                          visited: str = "hash",
                          frozen: bool = False) -> int:
    """Bytes of per-query traversal state ``disk_traverse`` carries
    (vec_loaded + page_seen, + the trace in frozen fan-out mode; the
    pool and its per-slot flags are not counted) — accounted over the
    very structures :func:`make_traversal_state` hands the traversal, so
    this cannot drift from the implementation.
    Pure shape math via ``eval_shape`` — nothing is allocated, so
    million-vector hypotheticals are free."""
    def build():
        return make_traversal_state(
            visited=visited, pool_size=pool_size, beam_width=beam_width,
            max_hops=max_hops, n_max=n_max, p_max=p_max, frozen=frozen)

    shapes = jax.tree.leaves(jax.eval_shape(build))
    return int(sum(math.prod(s.shape) * s.dtype.itemsize for s in shapes))


# ---------------------------------------------------------------------------
# Full-rerank baseline (packed layout: vectors already piggybacked)
# ---------------------------------------------------------------------------

@jax.named_scope("navis.rerank")
def full_rerank(store: GraphStore, spec: LayoutSpec, q: jax.Array,
                res: TraverseResult, counters: IOCounters, *, k: int):
    """Exact-rerank every pool candidate (the non-CASR baseline).

    Under the packed layout the vectors rode along with the edge pages
    (zero extra I/O); under the decoupled layout this costs one vector read
    per candidate — the naïve-unpacking strawman of §3.1.
    """
    ids = res.pool_ids
    valid = ids >= 0
    safe = jnp.maximum(ids, 0)
    if spec.kind == "decoupled":
        n_loads = valid.sum()
        pages = spec.vector_pages_per_read
        counters = dataclasses.replace(
            counters,
            read_requests=counters.read_requests + n_loads,
            vectors_read=counters.vectors_read + n_loads,
            wasted_vec_bytes_read=counters.wasted_vec_bytes_read +
            n_loads * pages * PAGE_BYTES)
        vec_loaded = visited_mod.add(res.vec_loaded, ids, valid)
        ovf = (visited_mod.overflow(vec_loaded) -
               visited_mod.overflow(res.vec_loaded)).astype(jnp.int64)
        counters = dataclasses.replace(
            counters, visited_overflow=counters.visited_overflow + ovf)
    else:
        vec_loaded = res.vec_loaded
    d = jnp.where(valid, kernel_ref.rerank_l2_ref(q, store.vectors[safe]),
                  INF)
    order = jnp.argsort(d)
    return ids[order][:k], d[order][:k], vec_loaded, counters
