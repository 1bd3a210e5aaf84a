"""On-"disk" storage layouts: packed (baseline) vs locality-driven decoupling.

Packed layout (DiskANN/OdinANN/Starling lineage): one 4 KiB page per vertex
holding ``[vector][degree][edgelist]`` — every edge fetch drags the vector in,
and a structural update rewrites the whole page.

Locality-driven decoupling (NAVIS §5.1): an *edgelist file* packing multiple
edgelists per page, a *vector file*, and a host-memory *indirection table*
mapping vertex → (edge page, slot).  Edge updates are out-of-place: modified
edgelists are gathered onto a fresh page and the indirection pointers are
flipped; fully-invalidated pages are recycled.  Because co-updated vertices
are graph-adjacent, the fresh page preserves page-level locality.

Everything is a fixed-capacity JAX pytree so search/insert jit cleanly; the
"file" is the arrays, the "I/O" is the counters (iomodel.py).
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.core.iomodel import PAGE_BYTES


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class GraphStore:
    """The proximity graph + vectors + layout bookkeeping.

    edges[v]      : int32 [N_max, R], -1-padded neighbor ids
    degree[v]     : int32 [N_max]
    vectors[v]    : float32 [N_max, D] full-precision vectors
    count         : number of live vertices
    edge_page[v]  : indirection: which edge page holds v's edgelist
    page_live[p]  : live edgelists on page p (0 ⇒ recyclable)
    next_page     : bump allocator for fresh edge pages
    """

    edges: jax.Array
    degree: jax.Array
    vectors: jax.Array
    count: jax.Array
    edge_page: jax.Array
    page_live: jax.Array
    next_page: jax.Array

    @property
    def n_max(self) -> int:
        return self.edges.shape[0]

    @property
    def r(self) -> int:
        return self.edges.shape[1]

    @property
    def dim(self) -> int:
        return self.vectors.shape[1]


@dataclasses.dataclass(frozen=True)
class LayoutSpec:
    """Static layout geometry (bytes per record, records per page)."""

    kind: str                  # "packed" | "decoupled"
    dim: int
    r: int
    vec_dtype_bytes: int = 4

    @property
    def vector_bytes(self) -> int:
        return self.dim * self.vec_dtype_bytes

    @property
    def edgelist_bytes(self) -> int:
        return 8 + 4 * self.r          # id + degree + edge ids

    @property
    def packed_record_bytes(self) -> int:
        return self.vector_bytes + self.edgelist_bytes

    @property
    def packed_pages_per_vertex(self) -> int:
        return -(-self.packed_record_bytes // PAGE_BYTES)

    @property
    def packed_per_page(self) -> int:
        """Records per page in the packed layout (low-dim co-residency)."""
        return max(PAGE_BYTES // self.packed_record_bytes, 1)

    @property
    def edgelists_per_page(self) -> int:
        """Decoupled: edgelists co-resident on one 4 KiB edge page."""
        return max(PAGE_BYTES // self.edgelist_bytes, 1)

    @property
    def pages_per_insert(self) -> int:
        """Fresh edge pages one insert takes from the bump allocator: a
        page group for the new vertex (packed), or the new vertex's and its
        ``r`` neighbours' edgelists moved onto ``ceil((r + 1) /
        edgelists_per_page)`` pages (decoupled, :func:`relocate_edgelists`:
        2 at r 32, 3 at r 48)."""
        if self.kind == "packed":
            return 1
        return -(-(self.r + 1) // self.edgelists_per_page)

    @property
    def vector_pages_per_read(self) -> int:
        return -(-self.vector_bytes // PAGE_BYTES)

    def read_pad_bytes(self, kind_pages: int, payload: int) -> int:
        return kind_pages * PAGE_BYTES - payload


def empty_store(n_max: int, dim: int, r: int) -> GraphStore:
    # page capacity: worst case one fresh page per insert + initial pages
    p_max = 2 * n_max
    return GraphStore(
        edges=jnp.full((n_max, r), -1, jnp.int32),
        degree=jnp.zeros((n_max,), jnp.int32),
        vectors=jnp.zeros((n_max, dim), jnp.float32),
        count=jnp.zeros((), jnp.int32),
        edge_page=jnp.full((n_max,), -1, jnp.int32),
        page_live=jnp.zeros((p_max,), jnp.int32),
        next_page=jnp.zeros((), jnp.int32),
    )


def assign_initial_pages(store: GraphStore, spec: LayoutSpec) -> GraphStore:
    """Greedy page placement for the base index (Starling-style: consecutive
    ids — which the builder lays out in graph-adjacency order — share pages).

    packed: vertex v lives on its own page group (high-dim) or co-residency
    group (low-dim).  decoupled: ``edgelists_per_page`` neighbors per page.
    """
    n = store.n_max
    if spec.kind == "packed":
        per = spec.packed_per_page
    else:
        per = spec.edgelists_per_page
    pages = jnp.arange(n, dtype=jnp.int32) // per
    n_pages = -(-n // per)
    live = jnp.zeros_like(store.page_live).at[:n_pages].set(
        jnp.minimum(per, n - jnp.arange(n_pages) * per).astype(jnp.int32))
    return dataclasses.replace(
        store, edge_page=pages, page_live=live,
        next_page=jnp.asarray(n_pages, jnp.int32))


# ---------------------------------------------------------------------------
# Out-of-place edge update (decoupled layout, NAVIS §5.1)
# ---------------------------------------------------------------------------

def relocate_edgelists(store: GraphStore, vertex_ids: jax.Array,
                       valid: jax.Array, spec: LayoutSpec):
    """Move the modified vertices' edgelists onto a fresh page.

    vertex_ids: int32 [M] (with ``valid`` mask) — the co-updated vertices of
    one insertion (new vertex + its wired neighbors).  They are gathered onto
    ⌈M/edgelists_per_page⌉ fresh pages; old slots are invalidated and fully
    dead pages recycled implicitly via ``page_live``.

    Returns (store, pages_written:int32).
    """
    per = spec.edgelists_per_page
    m = vertex_ids.shape[0]
    n_new_pages = -(-m // per)

    safe_ids = jnp.where(valid, vertex_ids, 0)
    old_pages = store.edge_page[safe_ids]
    # decrement live counts of old pages (once per valid vertex)
    dec = jnp.zeros_like(store.page_live).at[old_pages].add(
        jnp.where(valid & (old_pages >= 0), 1, 0))
    page_live = store.page_live - dec

    base = store.next_page
    slot_page = base + (jnp.arange(m, dtype=jnp.int32) // per)
    edge_page = store.edge_page.at[safe_ids].set(
        jnp.where(valid, slot_page, store.edge_page[safe_ids]))
    inc = jnp.zeros_like(page_live).at[slot_page].add(
        jnp.where(valid, 1, 0))
    page_live = page_live + inc

    n_valid = valid.sum()
    pages_written = jnp.where(n_valid > 0, -(-n_valid // per), 0)
    store = dataclasses.replace(
        store, edge_page=edge_page, page_live=page_live,
        next_page=base + jnp.asarray(n_new_pages, jnp.int32))
    return store, pages_written.astype(jnp.int64)


# ---------------------------------------------------------------------------
# Defragmentation (maintenance pass, ISSUE 4)
# ---------------------------------------------------------------------------

def defrag_edgelists(store: GraphStore, holders: jax.Array,
                     spec: LayoutSpec):
    """Re-pack every page-holding vertex's edgelist contiguously from page 0.

    Sustained out-of-place updates scatter co-traversed edgelists across
    fresh pages (page-level locality drifts) and monotonically burn the
    bump allocator; a defrag pass restores the build-time id-contiguous
    placement — consecutive ids (graph-adjacency order, as the builder
    lays them out) share pages again — and *resets* ``next_page``, so the
    page-id space is bounded by churn-per-maintenance-cycle instead of
    lifetime insert count.

    ``holders``: [n_max] bool — vertices that must keep an edge page
    (live vertices, plus any tombstoned vertex not yet reclaimed).
    Everything else gets ``edge_page = -1``.

    Returns (store, changed_pages [p_max] bool, n_pages int32) —
    ``changed_pages`` marks every page id whose *contents* differ after
    the move (old homes of moved vertices + their new homes); the caller
    must invalidate those in the host cache.
    """
    per = (spec.packed_per_page if spec.kind == "packed"
           else spec.edgelists_per_page)
    p_max = store.page_live.shape[0]
    rank = jnp.cumsum(holders.astype(jnp.int32)) - 1
    new_page = jnp.where(holders, rank // per, -1).astype(jnp.int32)
    n_hold = holders.sum().astype(jnp.int32)
    n_pages = jnp.where(n_hold > 0, -(-n_hold // per), 0).astype(jnp.int32)

    page_live = jnp.zeros_like(store.page_live).at[
        jnp.where(holders, new_page, p_max)].add(1)        # OOB = dropped
    moved = store.edge_page != new_page
    changed = jnp.zeros((p_max,), bool)
    changed = changed.at[jnp.where(moved & (store.edge_page >= 0),
                                   store.edge_page, p_max)].set(True)
    changed = changed.at[jnp.where(moved & holders, new_page,
                                   p_max)].set(True)
    store = dataclasses.replace(store, edge_page=new_page,
                                page_live=page_live, next_page=n_pages)
    return store, changed, n_pages
