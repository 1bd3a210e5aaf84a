"""Proximity-graph construction (Vamana-lineage) + quality helpers.

The base index is built *with the paper's own insertion machinery*: after a
small fully-connected bootstrap, vertices are added in random order in
blocks — each block position-seeks on a frozen snapshot (embarrassingly
parallel, like DiskANN's locked parallel build), is exact-reranked against
the in-memory build vectors, RobustPrune(α)-ed for diversity (close
neighbors + long-range shortcuts), and then wired sequentially through
:func:`insert.structural_update`.  One code path for build and runtime
updates means the invariants tested for inserts hold for the base index
too.

I/O during build is charged to a throwaway counter set (index construction
is offline; the paper measures it separately).
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax import lax

from repro.core import cache as cache_mod
from repro.core import insert as insert_mod
from repro.core import pq as pq_mod
from repro.core import search as search_mod
from repro.core.iomodel import IOCounters
from repro.core.layout import (GraphStore, LayoutSpec, assign_initial_pages,
                               defrag_edgelists, empty_store)

INF = jnp.float32(3.4e38)


# ---------------------------------------------------------------------------
# Ground truth + recall
# ---------------------------------------------------------------------------

def brute_force_topk(queries: jax.Array, vectors: jax.Array,
                     n_live, k: int) -> jax.Array:
    """Exact top-k ids per query.  queries: [Q, D].

    ``n_live`` is either a count (considers the prefix ``[0, n_live)`` —
    fresh builds, where live vertices are contiguous) or a [N] bool mask
    (churned corpora: deletions punch holes in the prefix and reclaimed
    slots hold stale vectors, so the caller passes the exact live set).
    """
    vnorm = jnp.sum(vectors * vectors, axis=1)                 # [N]
    if getattr(n_live, "dtype", None) == jnp.bool_ and \
            getattr(n_live, "ndim", 0) == 1:
        live = n_live
    else:
        live = jnp.arange(vectors.shape[0]) < n_live

    def per_q(q):
        # f32 at HIGHEST: TPU's DEFAULT runs bf16 passes, which misorder
        # neighbours whose distances differ by less than the rounding
        d = vnorm - 2.0 * jnp.dot(vectors, q,
                                  precision=lax.Precision.HIGHEST)  # [N]
        d = jnp.where(live, d, INF)
        _, idx = lax.top_k(-d, k)
        return idx.astype(jnp.int32)

    return jax.lax.map(per_q, queries, batch_size=64)


def recall_at_k(pred: jax.Array, truth: jax.Array) -> jax.Array:
    """Mean |pred ∩ truth| / k over queries.  pred, truth: [Q, k]."""
    hits = (pred[:, :, None] == truth[:, None, :]) & (truth[:, None, :] >= 0)
    return hits.any(axis=1).mean()


def medoid(vectors: jax.Array, n_live: int) -> jax.Array:
    """Vertex closest to the centroid of the live prefix."""
    live = vectors[:n_live]
    c = live.mean(axis=0)
    return jnp.argmin(jnp.sum((live - c) ** 2, axis=1)).astype(jnp.int32)


# ---------------------------------------------------------------------------
# RobustPrune (Vamana)
# ---------------------------------------------------------------------------

def robust_prune(q: jax.Array, cand_ids: jax.Array, cand_d: jax.Array,
                 vectors: jax.Array, *, alpha: float, r: int) -> jax.Array:
    """Diversity-pruned neighbor selection.

    Iteratively keeps the closest unpruned candidate p, then prunes every c
    with α·d(p,c) ≤ d(q,c) — c is better reached *through* p.  Returns [r]
    ids (-1 padded).  ``cand_d`` must be exact distances to q.
    """
    C = cand_ids.shape[0]
    safe = jnp.maximum(cand_ids, 0)
    cvecs = vectors[safe]                                       # [C, D]
    pruned = cand_ids < 0

    def step(carry, _):
        pruned = carry
        d_masked = jnp.where(pruned, INF, cand_d)
        best = jnp.argmin(d_masked)
        ok = d_masked[best] < INF
        kept_id = jnp.where(ok, cand_ids[best], -1)
        pvec = cvecs[best]
        d_pc = jnp.sum((cvecs - pvec[None]) ** 2, axis=1)       # [C]
        newly = ok & (alpha * d_pc <= cand_d)
        return pruned | newly, kept_id

    _, kept = lax.scan(step, pruned, None, length=r)
    return kept


# ---------------------------------------------------------------------------
# Build
# ---------------------------------------------------------------------------

def bootstrap_store(vectors: jax.Array, spec: LayoutSpec, n_max: int,
                    n_boot: int) -> GraphStore:
    """Fully-connected clique over the first ``n_boot`` (≤ R+1) vectors."""
    store = empty_store(n_max, spec.dim, spec.r)
    idx = jnp.arange(n_boot, dtype=jnp.int32)
    # edges[i] = all j != i, padded to R
    all_ids = jnp.broadcast_to(idx, (n_boot, n_boot))
    mask = ~jnp.eye(n_boot, dtype=bool)
    # compact each row's neighbors to the front
    order = jnp.argsort(~mask, axis=1, stable=True)             # True first
    row = jnp.take_along_axis(jnp.where(mask, all_ids, -1), order, axis=1)
    edges = store.edges.at[:n_boot, :min(n_boot - 1, spec.r)].set(
        row[:, :min(n_boot - 1, spec.r)])
    store = dataclasses.replace(
        store,
        vectors=store.vectors.at[:n_boot].set(vectors[:n_boot]),
        edges=edges,
        degree=store.degree.at[:n_boot].set(
            min(n_boot - 1, spec.r)),
        count=jnp.asarray(n_boot, jnp.int32))
    return assign_initial_pages(store, spec)


@functools.partial(jax.jit, static_argnames=("spec", "e_pos", "alpha",
                                             "beam_width", "max_hops"))
def _build_block(store: GraphStore, spec: LayoutSpec, block_vecs: jax.Array,
                 codes: jax.Array, sym_tables: jax.Array,
                 codebooks: jax.Array, entry_ids: jax.Array, *,
                 e_pos: int, alpha: float, beam_width: int,
                 max_hops: int) -> GraphStore:
    """Insert one block: parallel position seeking on the snapshot, then
    sequential structural updates."""
    codec = pq_mod.PQCodec(codebooks)
    dummy_cache = cache_mod.init_cache(store.page_live.shape[0], 2, "none",
                                       jax.random.PRNGKey(0))

    def seek(q):
        lut = pq_mod.adc_lut(codec, q)
        res = search_mod.disk_traverse(
            store, spec, lut, codes, dummy_cache, IOCounters.zeros(),
            entry_ids, pool_size=e_pos, beam_width=beam_width,
            max_hops=max_hops)
        # exact rerank against the build vectors (offline: vectors on hand)
        valid = res.pool_ids >= 0
        d = jnp.where(valid, pq_mod.exact_l2(
            q, store.vectors[jnp.maximum(res.pool_ids, 0)]), INF)
        return robust_prune(q, res.pool_ids, d, store.vectors,
                            alpha=alpha, r=store.r)

    nbrs_all = jax.vmap(seek)(block_vecs)                      # [B, R]

    def wire(store, i):
        sres = insert_mod.structural_update(
            store, spec, dummy_cache, IOCounters.zeros(), block_vecs[i],
            nbrs_all[i], codes, sym_tables)
        return sres.store, None

    store, _ = lax.scan(wire, store, jnp.arange(block_vecs.shape[0]))
    return store


@functools.partial(jax.jit, static_argnames=("spec", "e_pos", "alpha",
                                             "beam_width", "max_hops"))
def _refine_block(store: GraphStore, spec: LayoutSpec, ids_block: jax.Array,
                  codes: jax.Array, codebooks: jax.Array,
                  entry_ids: jax.Array, *, e_pos: int, alpha: float,
                  beam_width: int, max_hops: int) -> GraphStore:
    """Second Vamana pass: re-seek each vertex on the finished graph,
    RobustPrune(pool ∪ current edges), replace its edgelist, and re-add
    reciprocal edges (replace-worst by exact distance — vectors are in
    memory at build time)."""
    codec = pq_mod.PQCodec(codebooks)
    dummy_cache = cache_mod.init_cache(store.page_live.shape[0], 2, "none",
                                       jax.random.PRNGKey(0))
    r = store.r
    n_max = store.n_max

    def reseek(i):
        q = store.vectors[i]
        lut = pq_mod.adc_lut(codec, q)
        res = search_mod.disk_traverse(
            store, spec, lut, codes, dummy_cache, IOCounters.zeros(),
            entry_ids, pool_size=e_pos, beam_width=beam_width,
            max_hops=max_hops)
        cand = jnp.concatenate([res.pool_ids, store.edges[i]])
        # dedupe (first occurrence) + exclude self
        arange = jnp.arange(cand.shape[0], dtype=jnp.int32)
        safe = jnp.maximum(cand, 0)
        first = jnp.full((n_max,), jnp.iinfo(jnp.int32).max, jnp.int32)
        first = first.at[safe].min(
            jnp.where(cand >= 0, arange, jnp.iinfo(jnp.int32).max))
        keep = (cand >= 0) & (cand != i) & (first[safe] == arange)
        cand = jnp.where(keep, cand, -1)
        d = jnp.where(keep, pq_mod.exact_l2(
            q, store.vectors[jnp.maximum(cand, 0)]), INF)
        return robust_prune(q, cand, d, store.vectors, alpha=alpha, r=r)

    new_edges = jax.vmap(reseek)(ids_block)                  # [B, r]

    # apply the replacement edgelists
    edges = store.edges.at[ids_block].set(new_edges)
    degree = store.degree.at[ids_block].set((new_edges >= 0).sum(axis=1))
    store = dataclasses.replace(store, edges=edges, degree=degree)

    # reciprocal wiring (flattened (vertex, nbr) pairs, exact distances)
    pairs_v = jnp.repeat(ids_block, r)
    pairs_j = new_edges.reshape(-1)

    def wire(carry, t):
        edges, degree = carry
        v, j = pairs_v[t], pairs_j[t]

        def do(args):
            edges, degree = args
            row = edges[j]
            present = (row == v).any()
            occupied = row >= 0
            free = jnp.argmin(occupied)
            has_free = ~occupied.all()
            jvec = store.vectors[j]
            d_row = jnp.where(occupied, pq_mod.exact_l2(
                jvec, store.vectors[jnp.maximum(row, 0)]), -INF)
            worst = jnp.argmax(d_row)
            d_v = jnp.sum((jvec - store.vectors[v]) ** 2)
            tgt = jnp.where(has_free, free, worst)
            write = (has_free | (d_v < d_row[worst])) & ~present
            new_row = jnp.where(write, row.at[tgt].set(v), row)
            new_deg = jnp.where(write & has_free, degree[j] + 1, degree[j])
            return edges.at[j].set(new_row), degree.at[j].set(new_deg)

        edges, degree = lax.cond((j >= 0) & (j != v), do, lambda a: a,
                                 (edges, degree))
        return (edges, degree), None

    (edges, degree), _ = lax.scan(wire, (edges, degree),
                                  jnp.arange(pairs_v.shape[0]))
    return dataclasses.replace(store, edges=edges, degree=degree)


def build_graph(key: jax.Array, vectors: jax.Array, n: int,
                spec: LayoutSpec, codec: pq_mod.PQCodec, codes: jax.Array,
                *, n_max: int | None = None, e_pos: int = 64,
                alpha: float = 1.2, block: int = 64, beam_width: int = 4,
                max_hops: int = 128, n_entry: int = 4,
                refine: bool = True) -> GraphStore:
    """Build the base index over ``vectors[:n]``.

    Two passes, as Vamana prescribes: an incremental insertion pass at
    α=1.0 (cheap, but early vertices see a poor partial graph), then a
    refinement pass at α (default 1.2) that re-seeks every vertex on the
    finished graph and rebuilds its edgelist — this is what makes the graph
    navigable.  ``codes`` must already hold the PQ encodings of ``vectors``
    (the engine trains/encodes once and shares them with the runtime).
    """
    n_max = n_max or vectors.shape[0]
    sym_tables = pq_mod.sym_tables(codec)
    n_boot = min(spec.r + 1, n)
    store = bootstrap_store(vectors, spec, n_max, n_boot)
    entry_ids = jnp.arange(n_entry, dtype=jnp.int32) % n_boot

    # the insertion pass takes ``pages_per_insert`` fresh page ids per
    # vertex from a bump allocator over ``p_max`` ids (2 n_max): at r 48
    # (3 a vertex) a corpus that fills n_max would run past them, so the
    # pass re-packs the edgelists first whenever a block would
    p_max = store.page_live.shape[0]
    next_page = int(store.next_page)
    # each block returns a whole new store; the host waits for the block
    # before the one it just queued, so at most two are in flight
    # (unbounded, the queued blocks' stores set the build's peak)
    behind = None
    pos = n_boot
    while pos < n:
        if next_page + block * spec.pages_per_insert > p_max:
            store, _, n_pages = defrag_edgelists(
                store, jnp.arange(n_max) < pos, spec)
            next_page = int(n_pages)
        next_page += block * spec.pages_per_insert
        b = min(block, n - pos)
        block_vecs = vectors[pos:pos + b]
        if b < block:   # pad to the jitted block shape; wire only b of them
            block_vecs = jnp.pad(block_vecs, ((0, block - b), (0, 0)))
        store_full = _build_block(
            store, spec, block_vecs, codes, sym_tables, codec.codebooks,
            entry_ids, e_pos=e_pos, alpha=1.0, beam_width=beam_width,
            max_hops=max_hops)
        if b < block:
            # roll back the padded tail inserts (zero vectors)
            store = _truncate(store_full, pos + b)
        else:
            store = store_full
        pos += b
        if behind is not None:
            behind.block_until_ready()
        behind = store.count

    if refine and n > n_boot:
        order = jax.random.permutation(key, n).astype(jnp.int32)
        for start in range(0, n, block):
            ids_block = order[start:start + block]
            if ids_block.shape[0] < block:
                ids_block = jnp.pad(ids_block, (0, block - ids_block.shape[0]),
                                    constant_values=ids_block[0])
            store = _refine_block(
                store, spec, ids_block, codes, codec.codebooks, entry_ids,
                e_pos=e_pos, alpha=alpha, beam_width=beam_width,
                max_hops=max_hops)
            if behind is not None:
                behind.block_until_ready()
            behind = store.count
    return store


def _truncate(store: GraphStore, n_keep: int) -> GraphStore:
    """Drop vertices ≥ n_keep added by a padded block (host-side, rare)."""
    import numpy as np
    edges = np.asarray(store.edges).copy()
    degree = np.asarray(store.degree).copy()
    edge_page = np.asarray(store.edge_page).copy()
    page_live = np.asarray(store.page_live).copy()
    mask = edges >= n_keep
    degree = degree - mask.sum(axis=1)
    edges = np.where(mask, -1, edges)
    edges[n_keep:] = -1
    degree[n_keep:] = 0
    # give the dropped rows' page slots back: a phantom live count would
    # suppress the dead-page eviction hints downstream (§8.2, repair)
    dropped_pages = edge_page[n_keep:]
    np.subtract.at(page_live, dropped_pages[dropped_pages >= 0], 1)
    edge_page[n_keep:] = -1
    return dataclasses.replace(
        store, edges=jnp.asarray(edges), degree=jnp.asarray(degree),
        edge_page=jnp.asarray(edge_page),
        page_live=jnp.asarray(page_live),
        count=jnp.asarray(n_keep, jnp.int32))


# ---------------------------------------------------------------------------
# Graph invariants (tested; also used as a runtime sanity hook)
# ---------------------------------------------------------------------------

def check_invariants(store: GraphStore,
                     tombstone: jax.Array | None = None) -> dict:
    """Pure-jnp invariant summary: all must hold for a well-formed graph.

    With ``tombstone`` supplied, additionally checks the post-consolidation
    contract: no live vertex's edgelist references a tombstoned vertex
    (the maintenance repair pass spliced every dead pointer away).
    """
    n = store.count
    live = jnp.arange(store.n_max) < n
    edges = store.edges
    valid_edges = edges >= 0
    deg = valid_edges.sum(axis=1)
    in_range = jnp.where(valid_edges, edges < n, True).all()
    no_self = jnp.where(
        valid_edges, edges != jnp.arange(store.n_max)[:, None], True).all()
    deg_ok = (jnp.where(live, deg <= store.r, True)).all()
    deg_matches = (jnp.where(live, deg == store.degree, True)).all()
    dead_clean = (~live[:, None] | valid_edges | (edges == -1)).all()
    out = {"edges_in_range": in_range, "no_self_loops": no_self,
           "degree_le_r": deg_ok, "degree_field_consistent": deg_matches,
           "padding_clean": dead_clean}
    if tombstone is not None:
        row_live = live & ~tombstone
        out["no_dead_refs"] = jnp.where(
            row_live[:, None] & valid_edges,
            ~tombstone[jnp.maximum(edges, 0)], True).all()
    return out
