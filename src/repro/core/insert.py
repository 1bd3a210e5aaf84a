"""In-place insertion: ① position seeking → ② structural update.

Position seeking is a full graph traversal with a large explored pool
(|E_pos| ≫ |E_search|) whose only job is to surface ~R adequate neighbors
for the new vertex — the paper's diagnosis is that this step dominates
update cost.  The traversal itself reuses :func:`search.disk_traverse`;
the rerank is either the packed-layout full rerank or CASR.

The structural update wires the new vertex to its selected neighbors,
adds reciprocal edges (pruning the farthest edge by symmetric-PQ distance
when a neighbor is already at max degree R), and charges the layout's
write costs:

* packed:   (1 + #modified neighbors) full pages — every neighbor's vector
            is rewritten although the update never touched it (Fig. 4b).
* decoupled: the modified edgelists are gathered out-of-place onto fresh
            edge pages (⌈M/edgelists_per_page⌉ page writes) plus exactly
            one vector write for the new vertex.

RMW reads are free here: the wired neighbors come from the converged
explored pool, so their edge pages were read during this very insert's
traversal and still sit in the insert's RMW staging buffer (§8.2) — the
paper charges the same way.  The one exception is a *wave* commit
(``Engine.insert_many``): its staging buffer holds the pre-wave snapshot,
so pages dirtied by earlier commits in the same wave are stale and the
re-read is charged (:func:`charge_rmw_rereads`).

The module is split so the engine can overlap the read-heavy phase across
an update wave: :func:`position_seek` (pure, vmap-safe, frozen-cache
capable) produces the neighbor pool; :func:`commit_insert` /
:func:`structural_update` applies it; :func:`insert_vertex` is the
sequential composition of the two.
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.core import cache as cache_mod
from repro.core import casr as casr_mod
from repro.core import pq as pq_mod
from repro.core import search as search_mod
from repro.core.iomodel import IOCounters, PAGE_BYTES
from repro.core.layout import GraphStore, LayoutSpec, relocate_edgelists

INF = jnp.float32(3.4e38)


# ---------------------------------------------------------------------------
# Neighbor selection (paper §5.2-5.3)
# ---------------------------------------------------------------------------

@jax.named_scope("navis.select")
def select_neighbors(pool_ids: jax.Array, casr_res, r: int) -> jax.Array:
    """Order the pool for wiring: the CASR-loaded close portion ranked by
    exact distance first, then the unloaded remainder in PQ order (shortcut
    slots need diversity, not exactness).  Returns [r] ids (-1 padded)."""
    P = pool_ids.shape[0]
    valid = pool_ids >= 0
    arange = jnp.arange(P, dtype=jnp.float32)
    # loaded → exact distance;  unloaded-valid → big + PQ rank (stable);
    # invalid → +inf.  exact distances are always ≪ 1e30.
    key = jnp.where(casr_res.loaded & valid, casr_res.exact_d,
                    jnp.where(valid, 1e30 + arange, INF))
    order = jnp.argsort(key)
    return jnp.where(valid[order], pool_ids[order], -1)[:r]


@jax.named_scope("navis.select")
def full_pool_neighbors(pool_ids: jax.Array, r: int) -> jax.Array:
    """Baseline neighbor selection: pool already exact-reranked — take R."""
    return pool_ids[:r]


# ---------------------------------------------------------------------------
# Structural update
# ---------------------------------------------------------------------------

class StructuralResult(NamedTuple):
    store: GraphStore
    cache: cache_mod.CacheState
    counters: IOCounters
    n_wired: jax.Array      # reciprocal edges actually added
    modified: jax.Array     # [r] bool — which nbr edgelists were rewritten


def _wire_reciprocal(store: GraphStore, nbrs: jax.Array, new_id: jax.Array,
                     codes: jax.Array, sym_tables: jax.Array):
    """Add new_id into each neighbor's edgelist (prune farthest if full).

    Returns (edges, degree, modified[r] bool).
    """
    r_slots = nbrs.shape[0]

    def wire(carry, i):
        edges, degree = carry
        p = nbrs[i]

        def do(args):
            edges, degree = args
            row = edges[p]
            occupied = row >= 0
            free = jnp.argmin(occupied)                  # first empty slot
            has_free = ~occupied.all()
            p_code = codes[p]
            row_codes = codes[jnp.maximum(row, 0)]
            d_row = jnp.where(
                occupied,
                pq_mod.sym_distance(sym_tables, p_code, row_codes), -INF)
            worst = jnp.argmax(d_row)
            d_new = pq_mod.sym_distance(sym_tables, p_code,
                                        codes[new_id][None])[0]
            tgt = jnp.where(has_free, free, worst)
            write = has_free | (d_new < d_row[worst])
            new_row = jnp.where(write, row.at[tgt].set(new_id), row)
            new_deg = jnp.where(write & has_free, degree[p] + 1, degree[p])
            return (edges.at[p].set(new_row),
                    degree.at[p].set(new_deg)), write

        def skip(args):
            return args, jnp.bool_(False)

        dup = jnp.any((nbrs == p) & (jnp.arange(r_slots) < i))
        (edges, degree), modified = lax.cond(
            (p >= 0) & (p != new_id) & ~dup, do, skip, (edges, degree))
        return (edges, degree), modified

    (edges, degree), modified = lax.scan(
        wire, (store.edges, store.degree), jnp.arange(r_slots))
    return edges, degree, modified


def _charge_writes(counters: IOCounters, spec: LayoutSpec,
                   n_modified_nbrs: jax.Array,
                   edge_pages_written: jax.Array) -> IOCounters:
    """Write-side accounting for one insertion under either layout."""
    el = spec.edgelist_bytes
    vb = spec.vector_bytes
    if spec.kind == "packed":
        ppv = spec.packed_pages_per_vertex
        n_pages = (1 + n_modified_nbrs) * ppv
        edge_b = (1 + n_modified_nbrs) * el
        vec_b = jnp.int64(vb)                        # the new vertex (useful)
        wasted_b = (n_modified_nbrs * vb).astype(jnp.int64)  # co-written
        pad = (n_pages * PAGE_BYTES - edge_b - vec_b - wasted_b)
        return dataclasses.replace(
            counters,
            write_requests=counters.write_requests + n_pages.astype(jnp.int64),
            edge_bytes_written=counters.edge_bytes_written +
            edge_b.astype(jnp.int64),
            vec_bytes_written=counters.vec_bytes_written + vec_b,
            wasted_vec_bytes_written=counters.wasted_vec_bytes_written +
            wasted_b,
            pad_bytes_written=counters.pad_bytes_written +
            pad.astype(jnp.int64))
    # decoupled: out-of-place edge pages + exactly one vector write
    vec_pages = spec.vector_pages_per_read
    edge_b = ((1 + n_modified_nbrs) * el).astype(jnp.int64)
    edge_pad = edge_pages_written.astype(jnp.int64) * PAGE_BYTES - edge_b
    return dataclasses.replace(
        counters,
        write_requests=counters.write_requests +
        edge_pages_written.astype(jnp.int64) + vec_pages,
        edge_bytes_written=counters.edge_bytes_written + edge_b,
        vec_bytes_written=counters.vec_bytes_written + jnp.int64(vb),
        pad_bytes_written=counters.pad_bytes_written + edge_pad +
        jnp.int64(vec_pages * PAGE_BYTES - vb))


def structural_update(store: GraphStore, spec: LayoutSpec,
                      cache: cache_mod.CacheState, counters: IOCounters,
                      new_vec: jax.Array, nbrs: jax.Array,
                      codes: jax.Array, sym_tables: jax.Array,
                      new_id: jax.Array | None = None) -> StructuralResult:
    """② Commit a new vertex with neighbor list ``nbrs`` [R].

    ``new_id`` picks the slot: ``None`` (the default, and the only mode
    before the maintenance subsystem existed) appends at ``store.count``;
    an explicit id < count re-occupies a slot the maintenance pass
    reclaimed from a tombstoned vertex — ``count`` only advances when the
    slot extends the prefix, so reuse never inflates the live range.
    """
    new_id = (store.count if new_id is None else new_id).astype(jnp.int32)
    r = store.r

    # the new vertex's own record
    vectors = store.vectors.at[new_id].set(new_vec.astype(
        store.vectors.dtype))
    nbrs = jnp.where(nbrs == new_id, -1, nbrs)               # no self loops
    edges = store.edges.at[new_id].set(nbrs)
    degree = store.degree.at[new_id].set(
        (nbrs >= 0).sum().astype(store.degree.dtype))
    store = dataclasses.replace(store, vectors=vectors, edges=edges,
                                degree=degree)

    # reciprocal wiring + prune
    edges, degree, modified = _wire_reciprocal(store, nbrs, new_id, codes,
                                               sym_tables)
    store = dataclasses.replace(store, edges=edges, degree=degree,
                                count=jnp.maximum(store.count, new_id + 1))

    n_modified = modified.sum()
    if spec.kind == "packed":
        # in-place page rewrites; the new vertex gets a fresh page group
        edge_page = store.edge_page.at[new_id].set(store.next_page)
        page_live = store.page_live.at[store.next_page].add(1)
        store = dataclasses.replace(store, edge_page=edge_page,
                                    page_live=page_live,
                                    next_page=store.next_page + 1)
        counters = _charge_writes(counters, spec, n_modified,
                                  jnp.int32(0))
        return StructuralResult(store, cache, counters, n_modified, modified)

    # decoupled: gather new + modified edgelists onto fresh pages
    moved_ids = jnp.concatenate([jnp.array([new_id], jnp.int32),
                                 jnp.where(modified, nbrs, -1)])
    moved_valid = moved_ids >= 0
    old_pages = jnp.where(moved_valid,
                          store.edge_page[jnp.maximum(moved_ids, 0)], -1)
    store, pages_written = relocate_edgelists(store, moved_ids, moved_valid,
                                              spec)
    counters = _charge_writes(counters, spec, n_modified, pages_written)

    # §8.2 eviction hints: any old edge page left with zero live slots
    def hint(cache, i):
        pg = old_pages[i]
        dead = (pg >= 0) & (store.page_live[jnp.maximum(pg, 0)] <= 0)
        return lax.cond(dead,
                        lambda c: cache_mod.invalidate_page(c, pg),
                        lambda c: c, cache), None

    cache, _ = lax.scan(hint, cache, jnp.arange(moved_ids.shape[0]))
    return StructuralResult(store, cache, counters, n_modified, modified)


# ---------------------------------------------------------------------------
# Conflict-aware wave commits (batch-parallel insert fan-out)
# ---------------------------------------------------------------------------
#
# ``insert_many`` runs position seeking for a whole insert wave against one
# frozen snapshot of the engine state (phase ①, vmapped), then commits the
# structural updates serially (phase ②, lax.scan).  A commit late in the
# wave sees a graph already mutated by the earlier commits, so its
# snapshot-derived neighbor picks must be re-validated, and any neighbor
# edge page dirtied by a prior commit must be re-read before the RMW —
# the snapshot copy its own traversal read is stale.  These two helpers
# are that conflict handling; both are pure and scan-friendly.

def revalidate_neighbors(nbrs: jax.Array, new_id: jax.Array,
                         new_code: jax.Array, codes: jax.Array,
                         sym_tables: jax.Array,
                         tombstone: jax.Array) -> jax.Array:
    """Re-check a snapshot-selected neighbor list [r] at commit time.

    Drops self-references, duplicates and now-tombstoned picks, then
    re-prunes the survivors by symmetric-PQ distance to the new vertex
    — measured against ``new_code``, which the wave commit holds in hand
    (codes live in host memory — re-validation costs no storage I/O).
    Returns [r] ids, -1 padded at the tail.
    """
    r = nbrs.shape[0]
    safe = jnp.maximum(nbrs, 0)
    arange = jnp.arange(r)
    dup = ((nbrs[:, None] == nbrs[None, :]) & (nbrs[None, :] >= 0) &
           (arange[None, :] < arange[:, None])).any(axis=1)
    valid = (nbrs >= 0) & (nbrs != new_id) & ~tombstone[safe] & ~dup
    d = pq_mod.sym_distance(sym_tables, new_code, codes[safe])
    order = jnp.argsort(jnp.where(valid, d, INF))
    return jnp.where(valid[order], nbrs[order], -1)


def charge_rmw_rereads(counters: IOCounters, spec: LayoutSpec,
                       store: GraphStore, nbrs: jax.Array,
                       dirty_pages: jax.Array
                       ) -> tuple[IOCounters, jax.Array]:
    """Charge the RMW re-reads a wave commit owes for conflicting pages.

    The sequential insert path gets RMW reads for free: the wired
    neighbors come from the converged explored pool, so their edge pages
    sit in the insert's own staging buffer.  In a wave, that buffer holds
    the *snapshot* version — if a prior commit in the same wave dirtied a
    neighbor's current edge page, the commit must re-read it, one page
    read per distinct dirty page.  Returns (counters, n_reread).
    """
    r = nbrs.shape[0]
    valid = nbrs >= 0
    pages = jnp.where(valid, store.edge_page[jnp.maximum(nbrs, 0)], -1)
    arange = jnp.arange(r)
    dup = ((pages[:, None] == pages[None, :]) & (pages[None, :] >= 0) &
           (arange[None, :] < arange[:, None])).any(axis=1)
    hit = valid & (pages >= 0) & dirty_pages[jnp.maximum(pages, 0)] & ~dup
    n = hit.sum()
    counters = search_mod._charge_page_read(counters, spec,
                                            is_edge_page=True, n=n)
    return counters, n


def mark_dirty_pages(dirty_pages: jax.Array, store: GraphStore,
                     new_id: jax.Array, nbrs: jax.Array,
                     modified: jax.Array) -> jax.Array:
    """Record the pages a commit wrote (post-commit ``store``): the new
    vertex's page and every rewritten/relocated neighbor edgelist's
    current page.  Later commits in the wave consult this map to charge
    their RMW re-reads."""
    touched = jnp.concatenate([new_id[None].astype(jnp.int32),
                               jnp.where(modified, nbrs, -1)])
    pages = store.edge_page[jnp.maximum(touched, 0)]
    idx = jnp.where((touched >= 0) & (pages >= 0), pages,
                    dirty_pages.shape[0])                 # OOB = dropped
    return dirty_pages.at[idx].set(True)


# ---------------------------------------------------------------------------
# Full insertion (position seek + rerank + wire)
# ---------------------------------------------------------------------------

class SeekResult(NamedTuple):
    """Phase-① output: everything a structural commit needs, plus the
    traversal's I/O evidence (trace / page_seen) for cache replay."""
    nbrs: jax.Array           # [R] selected neighbors (-1 padded)
    pool_ids: jax.Array       # E_pos (PQ-sorted, tombstone-masked)
    hops: jax.Array
    rerank_rounds: jax.Array
    cache: cache_mod.CacheState   # threaded (sequential) / snapshot (frozen)
    counters: IOCounters
    page_seen: jax.Array      # pages this seek's traversal touched
    trace: jax.Array | None = None    # frozen mode: charged page accesses
    trace_n: jax.Array | None = None


def position_seek(store: GraphStore, spec: LayoutSpec, codec: pq_mod.PQCodec,
                  codes: jax.Array, cache: cache_mod.CacheState,
                  counters: IOCounters, new_vec: jax.Array,
                  entry_ids: jax.Array, *, e_pos: int, k: int, s: int,
                  rerank: str = "casr", beam_width: int = 4,
                  max_hops: int = 512, tombstone: jax.Array | None = None,
                  page_seen: jax.Array | None = None,
                  frozen_cache: bool = False,
                  cache_policy: str = "navis",
                  visited: str = "hash") -> SeekResult:
    """① Position seeking: traverse + rerank + neighbor selection, no
    structural mutation.  Pure in the engine state, so a whole insert wave
    runs concurrently under ``vmap`` with ``frozen_cache=True`` (each seek
    probes the cache snapshot and records its page-access trace, exactly
    like the search fan-out).  ``visited`` picks the traversal's visited
    sets — "hash" keeps per-seek state independent of the corpus, so an
    insert wave's memory is bounded by the frontier, not ``n_max``.
    ``cache_policy``: the cache's policy, static, for the threaded
    (unfrozen) traversal's accesses."""
    lut = pq_mod.adc_lut(codec, new_vec)
    res = search_mod.disk_traverse(
        store, spec, lut, codes, cache, counters, entry_ids,
        pool_size=e_pos, beam_width=beam_width, max_hops=max_hops,
        page_seen=page_seen, frozen_cache=frozen_cache,
        cache_policy=cache_policy, visited=visited)
    counters = res.counters
    cache = res.cache
    pool_ids = res.pool_ids
    if tombstone is not None:
        dead = (pool_ids >= 0) & tombstone[jnp.maximum(pool_ids, 0)]
        counters = dataclasses.replace(
            counters, tombstone_skips=counters.tombstone_skips +
            dead.sum().astype(jnp.int64))
        pool_ids = jnp.where(dead, -1, pool_ids)

    if rerank == "casr":
        cres = casr_mod.casr_rerank(store, spec, new_vec, pool_ids,
                                    counters, k=k, s=s)
        counters = cres.counters
        nbrs = select_neighbors(pool_ids, cres, store.r)
        rounds = cres.rerank_rounds
    else:
        ids, _, _, counters = search_mod.full_rerank(
            store, spec, new_vec, res._replace(pool_ids=pool_ids),
            counters, k=pool_ids.shape[0])
        nbrs = full_pool_neighbors(ids, store.r)
        rounds = jnp.int32(1)

    return SeekResult(nbrs=nbrs, pool_ids=pool_ids, hops=res.hops,
                      rerank_rounds=rounds, cache=cache, counters=counters,
                      page_seen=res.page_seen, trace=res.trace,
                      trace_n=res.trace_n)


# ② The structural commit for a precomputed neighbor pool is
# :func:`structural_update`; wave commits re-validate first.
commit_insert = structural_update


class InsertResult(NamedTuple):
    store: GraphStore
    cache: cache_mod.CacheState
    counters: IOCounters
    new_id: jax.Array
    pool_ids: jax.Array       # E_pos (PQ-sorted) — reused by NAVIS-update
    hops: jax.Array
    rerank_rounds: jax.Array
    page_seen: jax.Array      # pages this insert's traversal touched


def insert_vertex(store: GraphStore, spec: LayoutSpec, codec: pq_mod.PQCodec,
                  codes: jax.Array, sym_tables: jax.Array,
                  cache: cache_mod.CacheState, counters: IOCounters,
                  new_vec: jax.Array, entry_ids: jax.Array, *,
                  e_pos: int, k: int, s: int, rerank: str = "casr",
                  beam_width: int = 4, max_hops: int = 512,
                  tombstone: jax.Array | None = None,
                  page_seen: jax.Array | None = None,
                  visited: str = "hash",
                  new_id: jax.Array | None = None,
                  cache_policy: str = "navis") -> InsertResult:
    """One in-place insertion.  ``rerank``: "casr" | "full" (static).

    The caller encodes the new vector into the target slot of ``codes``
    *before* calling (PQ codes live in host memory and are updated
    synchronously).  ``tombstone`` masks deleted vertices out of neighbor
    selection; ``page_seen`` seeds the traversal's page buffer (bulk
    merges); ``new_id`` commits into a reclaimed slot instead of
    appending at ``store.count`` (free-list reuse); ``cache_policy`` is
    the cache's policy (static).
    """
    seek = position_seek(
        store, spec, codec, codes, cache, counters, new_vec, entry_ids,
        e_pos=e_pos, k=k, s=s, rerank=rerank, beam_width=beam_width,
        max_hops=max_hops, tombstone=tombstone, page_seen=page_seen,
        cache_policy=cache_policy, visited=visited)
    nid = (store.count if new_id is None else new_id).astype(jnp.int32)
    sres = commit_insert(store, spec, seek.cache, seek.counters, new_vec,
                         seek.nbrs, codes, sym_tables, new_id=nid)
    return InsertResult(store=sres.store, cache=sres.cache,
                        counters=sres.counters,
                        new_id=nid,
                        pool_ids=seek.pool_ids, hops=seek.hops,
                        rerank_rounds=seek.rerank_rounds,
                        page_seen=seek.page_seen)
