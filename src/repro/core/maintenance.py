"""Incremental maintenance: tombstone reclamation, edgelist repair + defrag.

Deletes only set a tombstone (paper §11, OdinANN's "deletion is benign"
argument): the slot is never reused, dead edges keep absorbing traversal
work, and out-of-place updates scatter edgelists across ever-fresher pages
— so a corpus under sustained delete+insert churn degrades on three axes
at once (capacity, recall, locality).  This module is the consolidation
path that undoes all three, FreshDiskANN-style but incremental:

① *repair* (``repair_block``): every live→dead edge is spliced away —
   the vacated slot is refilled with the dead vertex's symmetric-PQ-
   nearest live neighbor (a positional proxy for the removed edge), and
   the row's surviving edges are kept bit-identically, so connectivity
   routes *around* the hole and search results are preserved.  Runs in
   bounded blocks (``EngineSpec.maint_block``) so a step can interleave
   with foreground traffic.

①b *refine* (``refine_block``, engine-gated by
   ``EngineSpec.maint_refine``): vertices inserted since the last pass
   are re-seeked and RobustPrune(α)-rewired to build quality — the
   quality-restoring half of FreshDiskANN's StreamingConsolidate, which
   keeps a corpus whose membership turns over from drifting to
   unrefined-graph recall.

② *reclaim* (``reclaim_and_defrag``): after a full repair sweep no live
   edgelist references a dead vertex, so every tombstoned slot joins the
   free list that ``Engine._insert_inplace`` / ``insert_many`` draw from
   before falling back to fresh slots — inserts stop dropping once
   ``count`` reaches ``n_max``.  The tombstone bit stays set until the
   slot is actually reused (searches keep masking the stale record).

③ *defrag*: live edgelists are re-packed id-contiguously from page 0
   (:func:`repro.core.layout.defrag_edgelists`), restoring the
   decoupled layout's build-time page locality and resetting the bump
   page allocator; every page whose contents changed is invalidated in
   the host cache (``cache.invalidate_page``).

④ *entrance refresh* (``refresh_entrance``): surviving entrance members
   keep their wiring (static entrances top dead members' head-count back
   up; NAVIS's dynamic entrance re-grows through Algorithm 2 as inserts
   flow), holes are compacted near ``c_max``, and each member's edgelist
   page is priority-admitted into the frozen cache region
   (entrance-aware cache hint, §7).

All I/O is charged to ``IOCounters`` (``EngineState.ctr_maint``) so the
SSD model prices a pass exactly like foreground work: the repair sweep
reads each examined edge page once, repairs write through the layout's
normal update path (out-of-place relocation / in-place page rewrite),
and the defrag charges a stream read+write of every surviving page —
FreshDiskANN's documented consolidation overhead.  Maintenance reads
deliberately bypass the host cache (a full-file sweep would thrash the
frozen region the foreground searches depend on).
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
from jax import lax

from repro.core import cache as cache_mod
from repro.core import graph as graph_mod
from repro.core import pq as pq_mod
from repro.core import search as search_mod
from repro.core.iomodel import (IOCounters, PAGE_BYTES, merge_counters,
                                sum_counters)
from repro.core.layout import (GraphStore, LayoutSpec, defrag_edgelists,
                               relocate_edgelists)

INF = jnp.float32(3.4e38)
REFINE_ALPHA = 1.2      # RobustPrune diversity, as the Vamana build pass


def _charge_list_writes(counters: IOCounters, spec: LayoutSpec,
                        n_lists, n_pages) -> IOCounters:
    """Charge writing ``n_lists`` edgelists over ``n_pages`` pages.  The
    packed layout drags each record's vector along (the Fig 4b co-write
    tax); decoupled pages carry edgelists only."""
    edge_b = (n_lists * spec.edgelist_bytes).astype(jnp.int64)
    vec_b = ((n_lists * spec.vector_bytes).astype(jnp.int64)
             if spec.kind == "packed" else jnp.int64(0))
    n_pages = n_pages.astype(jnp.int64)
    pad = n_pages * PAGE_BYTES - edge_b - vec_b
    return dataclasses.replace(
        counters,
        write_requests=counters.write_requests + n_pages,
        edge_bytes_written=counters.edge_bytes_written + edge_b,
        wasted_vec_bytes_written=counters.wasted_vec_bytes_written + vec_b,
        pad_bytes_written=counters.pad_bytes_written + pad)


# ---------------------------------------------------------------------------
# ① Repair (one bounded block of the sweep)
# ---------------------------------------------------------------------------

def repair_block(store: GraphStore, codes: jax.Array, sym_tables: jax.Array,
                 tombstone: jax.Array, cache: cache_mod.CacheState,
                 counters: IOCounters, start: jax.Array, *,
                 spec: LayoutSpec, block: int):
    """Repair rows ``[start, start+block)``: every live row's surviving
    edges are kept bit-identically (they carry the RobustPrune(α)
    diversity — long-range shortcuts included — that makes the graph
    navigable; re-pruning them by plain nearest-distance measurably
    collapses recall), and each slot a tombstoned vertex vacated is
    spliced: refilled with the dead vertex's own symmetric-PQ-nearest
    live neighbor not already in the row.  The fill is ranked around the
    *dead* vertex, not the row owner, so the replacement edge is a
    positional proxy for the one removed — a route that used to pass
    v → dead → x survives as v → x′ with x′ ≈ dead, preserving the
    traversals the edge served (including long-range ones).  Rows
    without dead edges are untouched, so the sweep is idempotent and
    order-independent — dead rows are never rewritten during the sweep,
    which is what lets blocks run in any order and interleave with
    foreground ops.

    Charges one edge-page read per distinct page backing an examined row
    or a spliced dead neighbor, and the layout's normal write cost for
    each repaired edgelist.  Returns (store, cache, counters, n_repaired).
    """
    n_max = store.n_max
    r = store.r
    p_max = store.page_live.shape[0]
    rows = start.astype(jnp.int32) + jnp.arange(block, dtype=jnp.int32)
    safe_rows = jnp.minimum(rows, n_max - 1)
    in_range = rows < store.count
    row_live = in_range & ~tombstone[safe_rows]
    row_edges = store.edges[safe_rows]                        # [B, R]
    dead = (row_edges >= 0) & tombstone[jnp.maximum(row_edges, 0)] & \
        row_live[:, None]
    need = row_live & dead.any(axis=1)

    def fix(vid, row, dead_row):
        def fill_slot(cur, j):
            d_vertex = row[j]                 # the slot's dead occupant

            def do(cur):
                cand = store.edges[jnp.maximum(d_vertex, 0)]   # [R]
                ok = (cand >= 0) & ~tombstone[jnp.maximum(cand, 0)] & \
                    (cand != vid) & \
                    ~(cand[:, None] == cur[None, :]).any(axis=1)
                dd = jnp.where(ok, pq_mod.sym_distance(
                    sym_tables, codes[jnp.maximum(d_vertex, 0)],
                    codes[jnp.maximum(cand, 0)]), INF)
                best = jnp.argmin(dd)
                return cur.at[j].set(
                    jnp.where(dd[best] < INF, cand[best], -1))

            # a select, not a cond: under the block vmap a cond's operands
            # (the whole edge table among them) are broadcast to every row
            return jnp.where(dead_row[j], do(cur), cur), None

        start_row = jnp.where(dead_row, -1, row)
        out, _ = lax.scan(fill_slot, start_row, jnp.arange(r))
        return out

    fixed = jax.vmap(fix)(safe_rows, row_edges, dead)          # [B, R]
    scatter = jnp.where(need, rows, n_max)                     # OOB dropped
    edges = store.edges.at[scatter].set(fixed)
    degree = store.degree.at[scatter].set(
        (fixed >= 0).sum(axis=1).astype(store.degree.dtype))
    store = dataclasses.replace(store, edges=edges, degree=degree)

    # -- read charging: distinct pages behind examined rows + splice srcs
    touched = jnp.zeros((p_max,), bool)
    row_pages = store.edge_page[safe_rows]
    touched = touched.at[jnp.where(row_live & (row_pages >= 0), row_pages,
                                   p_max)].set(True)
    dead_flat = jnp.where(dead, row_edges, -1).reshape(-1)
    dpages = store.edge_page[jnp.maximum(dead_flat, 0)]
    touched = touched.at[jnp.where((dead_flat >= 0) & (dpages >= 0),
                                   dpages, p_max)].set(True)
    counters = search_mod._charge_page_read(
        counters, spec, is_edge_page=True,
        n=touched.sum().astype(jnp.int64))

    # -- write charging: repaired rows through the layout's update path
    n_mod = need.sum()
    if spec.kind == "decoupled":
        moved_ids = jnp.where(need, rows, -1)
        old_pages = jnp.where(need, row_pages, -1)
        store, pages_written = relocate_edgelists(store, moved_ids, need,
                                                  spec)
        counters = _charge_list_writes(counters, spec, n_mod, pages_written)

        # §8.2 eviction hints for fully-invalidated old pages
        def hint(cache, i):
            pg = old_pages[i]
            dead_pg = (pg >= 0) & (store.page_live[jnp.maximum(pg, 0)] <= 0)
            return lax.cond(dead_pg,
                            lambda c: cache_mod.invalidate_page(c, pg),
                            lambda c: c, cache), None

        cache, _ = lax.scan(hint, cache, jnp.arange(block))
    else:
        pages = (n_mod * spec.packed_pages_per_vertex).astype(jnp.int64)
        counters = _charge_list_writes(counters, spec, n_mod, pages)
    return store, cache, counters, n_mod


# ---------------------------------------------------------------------------
# ①b Refine (quality restoration for churn-inserted vertices)
# ---------------------------------------------------------------------------

def refine_block(store: GraphStore, codes: jax.Array, codebooks: jax.Array,
                 sym_tables: jax.Array, tombstone: jax.Array,
                 cache: cache_mod.CacheState, counters: IOCounters,
                 vids: jax.Array, valid: jax.Array, entries: jax.Array, *,
                 spec: LayoutSpec, e_pos: int, beam_width: int,
                 max_hops: int, visited: str):
    """Re-wire a block of churn-inserted ("young") vertices to build
    quality: re-seek each on the current graph, RobustPrune(α) its pool ∪
    current edges by exact distance, replace the edgelist, and re-add
    reciprocal links (replace-worst-by-exact if closer).

    The runtime insert path wires by PQ-ranked nearest neighbors — good
    enough to be searchable, but without the α-diversity pass the Vamana
    build runs, so a corpus whose membership turns over under churn
    drifts toward unrefined-graph recall.  Re-refining what changed since
    the last pass anchors steady-state quality at build grade — this is
    the quality-restoring half of FreshDiskANN's StreamingConsolidate,
    and it is priced accordingly: each refine charges its full traversal,
    one exact-vector read per surviving candidate, and the layout's write
    cost for every rewritten edgelist.

    Returns (store, counters, n_refined).
    """
    codec = pq_mod.PQCodec(codebooks)
    n_max = store.n_max
    r = store.r
    safe_v = jnp.maximum(vids, 0)

    def seek(vid, ok):
        v = store.vectors[jnp.maximum(vid, 0)]
        lut = pq_mod.adc_lut(codec, v)
        res = search_mod.disk_traverse(
            store, spec, lut, codes, cache, IOCounters.zeros(), entries,
            pool_size=e_pos, beam_width=beam_width, max_hops=max_hops,
            frozen_cache=True, visited=visited)
        cand = jnp.concatenate([res.pool_ids, store.edges[
            jnp.maximum(vid, 0)]])
        safe = jnp.maximum(cand, 0)
        keep = (cand >= 0) & (cand != vid) & ~tombstone[safe]
        # sort-based dedupe (first occurrence wins)
        imax = jnp.iinfo(jnp.int32).max
        key = jnp.where(keep, cand, imax)
        si = jnp.argsort(key)
        sk = key[si]
        first = jnp.concatenate([jnp.ones((1,), bool),
                                 sk[1:] != sk[:-1]])
        keep &= jnp.zeros_like(keep).at[si].set(first)
        d = jnp.where(keep, pq_mod.exact_l2(v, store.vectors[safe]), INF)
        newr = graph_mod.robust_prune(v, jnp.where(keep, cand, -1), d,
                                      store.vectors, alpha=REFINE_ALPHA,
                                      r=r)
        # exact distances read the candidates' vectors from the slow tier
        ctr = res.counters
        n_cand = keep.sum()
        vp = spec.vector_pages_per_read
        if spec.kind == "decoupled":
            ctr = dataclasses.replace(
                ctr,
                read_requests=ctr.read_requests + n_cand * vp,
                useful_vec_bytes_read=ctr.useful_vec_bytes_read +
                n_cand * spec.vector_bytes,
                pad_bytes_read=ctr.pad_bytes_read +
                n_cand * (vp * PAGE_BYTES - spec.vector_bytes))
        # (packed: the traversal's edge pages already dragged vectors in)
        ctr = jax.tree.map(lambda x: jnp.where(ok, x, jnp.zeros_like(x)),
                           ctr)
        return jnp.where(ok, newr, store.edges[jnp.maximum(vid, 0)]), ctr

    new_rows, ctrs = jax.vmap(seek)(vids, valid)
    counters = merge_counters(counters, sum_counters(ctrs))

    # serial application: replace each edgelist, wire reciprocals by
    # exact distance (skip if already present), relocate modified rows
    b = vids.shape[0]

    def apply(carry, i):
        store, counters = carry
        vid, ok = vids[i], valid[i]

        def do(args):
            store, counters = args
            newr = new_rows[i]
            edges = store.edges.at[vid].set(newr)
            degree = store.degree.at[vid].set(
                (newr >= 0).sum().astype(store.degree.dtype))

            def wire(carry, j):
                edges, degree, modified = carry
                p = newr[j]

                def wire_one(args):
                    edges, degree, modified = args
                    row = edges[p]
                    present = (row == vid).any()
                    occupied = row >= 0
                    free = jnp.argmin(occupied)
                    has_free = ~occupied.all()
                    pvec = store.vectors[p]
                    d_row = jnp.where(occupied, pq_mod.exact_l2(
                        pvec, store.vectors[jnp.maximum(row, 0)]), -INF)
                    worst = jnp.argmax(d_row)
                    d_v = jnp.sum((pvec - store.vectors[vid]) ** 2)
                    tgt = jnp.where(has_free, free, worst)
                    write = (has_free | (d_v < d_row[worst])) & ~present
                    new_row = jnp.where(write, row.at[tgt].set(vid), row)
                    new_deg = jnp.where(write & has_free, degree[p] + 1,
                                        degree[p])
                    return (edges.at[p].set(new_row),
                            degree.at[p].set(new_deg),
                            modified.at[j].set(write))

                return lax.cond((p >= 0) & (p != vid), wire_one,
                                lambda a: a, (edges, degree, modified)), \
                    None

            modified0 = jnp.zeros((r,), bool)
            (edges, degree, modified), _ = lax.scan(
                wire, (edges, degree, modified0), jnp.arange(r))
            store = dataclasses.replace(store, edges=edges, degree=degree)

            n_mod = modified.sum() + 1                 # + vid's own row
            if spec.kind == "decoupled":
                moved = jnp.concatenate([vid[None].astype(jnp.int32),
                                         jnp.where(modified, newr, -1)])
                mvalid = moved >= 0
                store, pages = relocate_edgelists(store, moved, mvalid,
                                                  spec)
                counters = _charge_list_writes(counters, spec, n_mod,
                                               pages)
            else:
                pages = (n_mod * spec.packed_pages_per_vertex).astype(
                    jnp.int64)
                counters = _charge_list_writes(counters, spec, n_mod,
                                               pages)
            return store, counters

        carry = lax.cond(ok & (vid >= 0), do, lambda a: a,
                         (store, counters))
        return carry, None

    (store, counters), _ = lax.scan(apply, (store, counters),
                                    jnp.arange(b))
    return store, counters, valid.sum()


# ---------------------------------------------------------------------------
# ② + ③ Reclaim + defrag (cycle finalization)
# ---------------------------------------------------------------------------

def reclaim_and_defrag(store: GraphStore, tombstone: jax.Array,
                       free_list: jax.Array, free_count: jax.Array,
                       free_mask: jax.Array, cache: cache_mod.CacheState,
                       counters: IOCounters, *, spec: LayoutSpec):
    """Finalize a maintenance cycle after the repair sweep.

    Reclaims every tombstoned slot that no live edgelist references into
    the free list (post-sweep that is all of them; the reference check is
    a safety net for slots deleted *during* an interleaved sweep), clears
    the reclaimed rows, re-packs the survivors' edgelists contiguously
    from page 0, and invalidates every cache-resident page whose contents
    moved.  Charges the defrag's stream read+write.  Returns
    (store, free_list, free_count, free_mask, cache, counters,
    n_reclaimed).
    """
    n_max = store.n_max
    p_max = store.page_live.shape[0]
    idx = jnp.arange(n_max, dtype=jnp.int32)
    in_prefix = idx < store.count
    row_live = in_prefix & ~tombstone

    tgt = jnp.where(row_live[:, None] & (store.edges >= 0), store.edges,
                    n_max)
    referenced = jnp.zeros((n_max,), bool).at[tgt.reshape(-1)].set(True)
    new_free = in_prefix & tombstone & ~free_mask & ~referenced

    pos = jnp.where(new_free,
                    free_count + jnp.cumsum(new_free.astype(jnp.int32)) - 1,
                    n_max)                                    # OOB dropped
    free_list = free_list.at[pos].set(idx)
    free_count = free_count + new_free.sum().astype(jnp.int32)
    free_mask = free_mask | new_free

    # reclaimed rows hold no graph state until an insert reuses the slot
    edges = jnp.where(free_mask[:, None], -1, store.edges)
    degree = jnp.where(free_mask, 0, store.degree)
    store = dataclasses.replace(store, edges=edges, degree=degree)

    # defrag: everything not reclaimed keeps a (fresh, contiguous) page
    holders = in_prefix & ~free_mask
    n_hold = holders.sum()
    pre_pages = jnp.zeros((p_max,), bool).at[
        jnp.where(holders & (store.edge_page >= 0), store.edge_page,
                  p_max)].set(True)
    store, changed, n_pages = defrag_edgelists(store, holders, spec)
    counters = search_mod._charge_page_read(
        counters, spec, is_edge_page=True,
        n=pre_pages.sum().astype(jnp.int64))                 # stream read
    counters = _charge_list_writes(counters, spec, n_hold, n_pages)

    # drop every cache-resident page whose contents moved, plus any page
    # the rebuilt map left without a single live edgelist (repair may
    # have drained a page without tripping its own fully-dead hint)
    drop = changed | (store.page_live <= 0)

    def inv(cache, p):
        return lax.cond(drop[p],
                        lambda c: cache_mod.invalidate_page(c, p),
                        lambda c: c, cache), None

    cache, _ = lax.scan(inv, cache, jnp.arange(p_max, dtype=jnp.int32))
    return (store, free_list, free_count, free_mask, cache, counters,
            new_free.sum())


# ---------------------------------------------------------------------------
# ④ Entrance-refresh helpers (engine orchestrates the rebuild itself)
# ---------------------------------------------------------------------------

def refresh_entrance(key: jax.Array, codes: jax.Array,
                     sym_tables: jax.Array, old_ent, tombstone,
                     live_ids, *, sample_frac: float, r_ent: int,
                     n_max: int, top_up: bool = True):
    """Refresh the entrance graph over the post-compaction live set,
    *incrementally*: surviving members and their wiring are untouched
    (their placement has been serving traversals; a from-scratch resample
    at the ~1% sample size has brutal seed-coverage variance, and keeping
    the structure is what preserves search results across a pass).

    ``top_up=True`` (static entrances — consolidation is their only
    refresh): the head-count dead members vacated is topped back up with
    fresh live samples via :func:`repro.core.entrance.add_member`.

    ``top_up=False`` (NAVIS's dynamic entrance): the paper's own
    Algorithm 2 re-grows coverage as inserts flow — its trigger compares
    *live* membership against the target fraction, so scrubbed members
    re-open promotion headroom — and consolidation leaves a
    still-serving structure bit-identical.

    Either way, when the slot high-water mark ``count`` nears ``c_max``
    (delete slots are never recycled in place, so sustained churn leaks
    them), the holes are compacted with a full survivor re-link
    (:func:`repro.core.entrance.link_members`).

    Host-orchestrated (member selection needs concrete counts); returns
    an :class:`EntranceGraph`.
    """
    import numpy as np
    from repro.core import entrance as ent_mod
    c_max = old_ent.c_max
    n_live = int(live_ids.shape[0])
    target = max(min(int(n_live * sample_frac), c_max), min(n_live, 2))

    old = np.asarray(old_ent.ids)
    old = old[old >= 0]
    survivors = old[~np.asarray(tombstone)[old]][:target]
    need = (target - len(survivors)) if top_up else 0
    if need > 0:
        pool = np.setdiff1d(np.asarray(live_ids), survivors)
        pick = jax.random.choice(key, pool.shape[0],
                                 (min(need, pool.shape[0]),),
                                 replace=False)
        fresh = pool[np.asarray(pick)]
    else:
        fresh = np.zeros((0,), np.int32)
    members = np.concatenate([survivors, fresh]).astype(np.int32)
    if int(old_ent.count) + len(fresh) + r_ent > c_max and \
            len(members) >= 2:                            # compact holes
        return ent_mod.link_members(
            jnp.asarray(members, jnp.int32), codes, sym_tables,
            c_max=c_max, r_ent=r_ent, n_max=n_max)
    ent = old_ent
    for vid in fresh:
        ent = ent_mod.add_member(ent, jnp.asarray(vid, jnp.int32), codes,
                                 sym_tables)
    return ent


def admit_entrance_pages(cache: cache_mod.CacheState, store: GraphStore,
                         ent) -> cache_mod.CacheState:
    """Priority-admit every live entrance member's edgelist page into the
    frozen cache region — after a refresh the new members seed every
    traversal, so their pages are the hottest in the system (§7's
    entrance-aware cache, lightweight version).  No-op for non-NAVIS
    cache policies (``priority_admit`` gates itself)."""
    def step(cache, i):
        vid = ent.ids[i]
        page = store.edge_page[jnp.maximum(vid, 0)]
        return lax.cond((vid >= 0) & (page >= 0),
                        lambda c: cache_mod.priority_admit(c, page),
                        lambda c: c, cache), None

    cache, _ = lax.scan(step, cache, jnp.arange(ent.c_max))
    return cache


def refresh_default_entries(key: jax.Array, vectors: jax.Array,
                            live_ids: jax.Array, n_entry: int) -> jax.Array:
    """Fallback entry points over the post-compaction live set: the live
    medoid first (mirroring the build), then random live picks.  The old
    defaults may be tombstoned — a traversal seeded there burns hops in
    a repaired-away region."""
    live_vecs = vectors[live_ids]
    c = live_vecs.mean(axis=0)
    med = live_ids[jnp.argmin(jnp.sum((live_vecs - c) ** 2, axis=1))]
    rest = live_ids[jax.random.randint(key, (n_entry - 1,), 0,
                                       live_ids.shape[0])]
    return jnp.concatenate([med[None], rest]).astype(jnp.int32)
