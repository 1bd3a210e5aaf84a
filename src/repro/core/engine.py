"""The NAVIS engine: composition of layout × rerank × entrance × cache ×
update-path.  Every paper baseline is a configuration, not a fork:

=================  =========  ======  ========  ==============  ===========
system             layout     rerank  entrance  cache           update path
=================  =========  ======  ========  ==============  ===========
freshdiskann       packed     full    static    none            buffered
odinann            packed     full    static    none            inplace
odinann_cache      packed     full    static    navis (packed)  inplace
layout_only        decoupled  full    static    none            inplace
sel_vec            decoupled  casr    static    none            inplace
navis              decoupled  casr    dynamic   navis           inplace
=================  =========  ======  ========  ==============  ===========

All per-op functions are jitted pure functions over :class:`EngineState`;
batches run under ``lax.scan`` so the cache/entrance/counter state threads
exactly as a concurrent run would interleave it.  The batch-parallel
fan-outs (``search_many``, ``insert_many``) instead run their whole wave
against one frozen snapshot — searches end to end, inserts for the
position-seek phase — and fold the wave's page-access traces back into
the shared cache; ``insert_many`` then serialises only the conflict-aware
structural commits.

Each stage of the two fan-outs runs under a ``jax.named_scope`` named
``navis.<stage>`` (entrance, traverse, rerank, cache_replay, seek, select,
encode, commit, ...).  The names reach every op's metadata, so a device
trace attributes time to stages; they change nothing that is compiled.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax

from repro.core import cache as cache_mod
from repro.core import casr as casr_mod
from repro.core import entrance as ent_mod
from repro.core import graph as graph_mod
from repro.core import insert as insert_mod
from repro.core import maintenance as maint_mod
from repro.core import pq as pq_mod
from repro.core import search as search_mod
from repro.core.iomodel import (IOCounters, PAGE_BYTES, merge_counters,
                                sum_counters)
from repro.core.layout import GraphStore, LayoutSpec
from repro.kernels import ref as kernel_ref

INF = jnp.float32(3.4e38)


# ---------------------------------------------------------------------------
# Specification
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class EngineSpec:
    """Static engine configuration (hashable — one jit per spec)."""

    dim: int
    r: int = 96
    n_max: int = 0                      # capacity incl. future inserts
    pq_m: int = 32                      # PQ subquantizers
    layout: str = "decoupled"           # packed | decoupled
    rerank: str = "casr"                # full | casr
    entrance: str = "dynamic"           # none | static | dynamic
    cache_policy: str = "navis"         # none | navis | lru | clock | lfu
    update_path: str = "inplace"        # inplace | buffered
    e_search: int = 40
    e_pos: int = 100
    k: int = 10
    beam_width: int = 4
    max_hops: int = 256
    visited_impl: str = "hash"          # hash (O(1) state) | bitmap (ref)
    s_search: int = 4                   # CASR group size (search path)
    s_pos: int = 8                      # CASR group size (position seeking)
    cache_capacity_pages: int = 1024
    ent_frac: float = 0.01
    r_ent: int = 32
    n_entry: int = 10
    ent_pool: int = 32
    buffer_frac: float = 0.06           # FreshDiskANN merge threshold
    buffer_max: int = 4096
    consolidate_frac: float = 0.2       # tombstone fraction triggering maint.
    maint_block: int = 256              # rows repaired per maintenance step
    maint_refine: bool = True           # re-RobustPrune young rows per pass

    @property
    def lspec(self) -> LayoutSpec:
        return LayoutSpec(kind=self.layout, dim=self.dim, r=self.r)

    def with_(self, **kw) -> "EngineSpec":
        return dataclasses.replace(self, **kw)


PRESETS = {
    "freshdiskann": dict(layout="packed", rerank="full", entrance="static",
                         cache_policy="none", update_path="buffered"),
    "odinann": dict(layout="packed", rerank="full", entrance="static",
                    cache_policy="none", update_path="inplace"),
    "odinann_cache": dict(layout="packed", rerank="full", entrance="static",
                          cache_policy="navis", update_path="inplace"),
    "layout_only": dict(layout="decoupled", rerank="full", entrance="static",
                        cache_policy="none", update_path="inplace"),
    "sel_vec": dict(layout="decoupled", rerank="casr", entrance="static",
                    cache_policy="none", update_path="inplace"),
    "navis": dict(layout="decoupled", rerank="casr", entrance="dynamic",
                  cache_policy="navis", update_path="inplace"),
}


def preset(name: str, dim: int, **overrides) -> EngineSpec:
    kw = dict(PRESETS[name])
    kw.update(overrides)
    return EngineSpec(dim=dim, **kw)


# ---------------------------------------------------------------------------
# State
# ---------------------------------------------------------------------------

@jax.tree_util.register_dataclass
@dataclasses.dataclass
class EngineState:
    store: GraphStore
    codes: jax.Array                 # [N_max, M] uint8
    ent: ent_mod.EntranceGraph
    cache: cache_mod.CacheState
    tombstone: jax.Array             # [N_max] bool — deleted vertices
    default_entries: jax.Array       # [n_entry] fallback entry ids
    ctr_search: IOCounters
    ctr_insert: IOCounters
    buf_vecs: jax.Array              # [B_max, D] FreshDiskANN memory buffer
    buf_count: jax.Array
    n_deleted: jax.Array
    free_list: jax.Array             # [N_max] reclaimed slot ids (stack)
    free_count: jax.Array            # live entries in free_list
    free_mask: jax.Array             # [N_max] bool — slot reclaimed, unused
    maint_cursor: jax.Array          # repair-sweep position (maintenance)
    young_mask: jax.Array            # [N_max] inserted since last refine
    ctr_maint: IOCounters            # consolidation I/O (SSD-model priced)

    @property
    def live_count(self):
        return self.store.count - self.n_deleted

    @property
    def live_mask(self):
        """[N_max] bool — slots holding a live (searchable) vector.  With
        deletions and slot reuse the live set is NOT the count prefix:
        callers and tests must judge ground truth against this mask."""
        return (jnp.arange(self.store.n_max) < self.store.count) & \
            ~self.tombstone


class OpStats(NamedTuple):
    """Per-operation I/O summary for latency/throughput modelling."""
    read_requests: jax.Array
    read_bytes: jax.Array
    write_requests: jax.Array
    write_bytes: jax.Array
    serial_rounds: jax.Array      # dependent I/O rounds (hops + rerank)
    cache_hits: jax.Array
    cache_misses: jax.Array
    dropped: jax.Array = jnp.zeros((), bool)   # insert rejected (capacity)


def _delta_stats(before: IOCounters, after: IOCounters,
                 rounds, dropped=None) -> OpStats:
    if dropped is None:
        dropped = jnp.zeros((), bool)
    return OpStats(
        read_requests=after.read_requests - before.read_requests,
        read_bytes=after.total_read_bytes() - before.total_read_bytes(),
        write_requests=after.write_requests - before.write_requests,
        write_bytes=after.total_write_bytes() - before.total_write_bytes(),
        serial_rounds=rounds,
        cache_hits=after.cache_hits - before.cache_hits,
        cache_misses=after.cache_misses - before.cache_misses,
        dropped=dropped)


# ---------------------------------------------------------------------------
# Engine
# ---------------------------------------------------------------------------

class Engine:
    """Composable GVS engine.  Build once, then thread `EngineState`
    through jitted ``search`` / ``insert`` / ``delete`` ops."""

    def __init__(self, spec: EngineSpec):
        self.spec = spec
        self.codec: Optional[pq_mod.PQCodec] = None
        self._sym: Optional[jax.Array] = None
        self._jit_ops()

    def install_codec(self, codec: pq_mod.PQCodec):
        """Set the PQ codec and its symmetric distance tables together;
        ``build`` keeps a codec installed before it is called."""
        self.codec = codec
        self._sym = pq_mod.sym_tables(codec)

    def _jit_ops(self):
        self.search = jax.jit(self._search)
        self.insert = jax.jit(self._insert)
        self.search_batch = jax.jit(self._search_batch)
        self.search_many = jax.jit(self._search_many)
        self.insert_batch = jax.jit(self._insert_batch)
        self.insert_many = jax.jit(self._insert_many)
        self.merge = jax.jit(self._merge)
        self.delete_many = jax.jit(self._delete_many)
        self._repair_block = jax.jit(functools.partial(
            maint_mod.repair_block, spec=self.spec.lspec,
            block=self.spec.maint_block))
        self._finalize_cycle = jax.jit(functools.partial(
            maint_mod.reclaim_and_defrag, spec=self.spec.lspec))
        self._admit_entrance_pages = jax.jit(maint_mod.admit_entrance_pages)
        self._refine_block = jax.jit(functools.partial(
            maint_mod.refine_block, spec=self.spec.lspec,
            e_pos=self.spec.e_pos, beam_width=self.spec.beam_width,
            max_hops=self.spec.max_hops, visited=self.spec.visited_impl))

    # -- construction -------------------------------------------------------

    def build(self, key: jax.Array, base_vectors: jax.Array,
              *, build_block: int = 64, build_e_pos: int = 64,
              alpha: float = 1.2, shared=None) -> EngineState:
        """Build (or adopt) the base index.

        ``shared``: an optional ``(codec, codes, store)`` bundle from a
        previous build — the proximity graph is layout-independent, so
        benchmark sweeps build it once and re-page it per engine config
        (packed vs decoupled page maps differ; edges/vectors do not).
        """
        spec = self.spec
        n_base, dim = base_vectors.shape
        assert dim == spec.dim
        n_max = spec.n_max or n_base
        k_pq, k_ent, k_build = jax.random.split(key, 3)

        if shared is not None:
            codec, codes, store0 = shared
            self.install_codec(codec)
            from repro.core.layout import assign_initial_pages
            store = assign_initial_pages(store0, spec.lspec)
        else:
            if self.codec is None:
                # PQ codec from a base sample; codes for the full capacity.
                # A pre-installed codec is kept (sharded deployments train
                # ONE codec on the global corpus — per-shard codecs would
                # make PQ distances incomparable across shards).
                sample = base_vectors[
                    jax.random.choice(k_pq, n_base, (min(n_base, 4096),),
                                      replace=False)]
                self.install_codec(pq_mod.train_pq(k_pq, sample, spec.pq_m))
            codes = jnp.zeros((n_max, spec.pq_m), jnp.uint8)
            codes = codes.at[:n_base].set(pq_mod.encode(self.codec,
                                                        base_vectors))

            store = graph_mod.build_graph(
                k_build, jnp.pad(base_vectors,
                                 ((0, n_max - n_base), (0, 0))),
                n_base, spec.lspec, self.codec, codes, n_max=n_max,
                e_pos=build_e_pos, block=build_block, alpha=alpha)

        c_max = max(int(spec.ent_frac * n_max * 2), 64)
        if spec.entrance == "none":
            ent = ent_mod.empty_entrance(c_max, spec.r_ent, n_max)
        else:
            ent = ent_mod.build_entrance(
                k_ent, codes, self._sym, n_base, c_max=c_max,
                r_ent=spec.r_ent, sample_frac=spec.ent_frac, n_max=n_max)

        cache = cache_mod.init_cache(
            store.page_live.shape[0], spec.cache_capacity_pages,
            spec.cache_policy, jax.random.fold_in(key, 7))
        med = graph_mod.medoid(base_vectors, n_base)
        default_entries = jnp.concatenate([
            med[None], jax.random.choice(
                jax.random.fold_in(key, 9), n_base,
                (spec.n_entry - 1,)).astype(jnp.int32)])

        return EngineState(
            store=store, codes=codes, ent=ent, cache=cache,
            tombstone=jnp.zeros((n_max,), bool),
            default_entries=default_entries,
            ctr_search=IOCounters.zeros(), ctr_insert=IOCounters.zeros(),
            buf_vecs=jnp.zeros((spec.buffer_max, dim), jnp.float32),
            buf_count=jnp.zeros((), jnp.int32),
            n_deleted=jnp.zeros((), jnp.int32),
            free_list=jnp.full((n_max,), -1, jnp.int32),
            free_count=jnp.zeros((), jnp.int32),
            free_mask=jnp.zeros((n_max,), bool),
            maint_cursor=jnp.zeros((), jnp.int32),
            young_mask=jnp.zeros((n_max,), bool),
            ctr_maint=IOCounters.zeros())

    def bundle(self, state: EngineState):
        """(codec, codes, store) — reusable across engine configs."""
        return (self.codec, state.codes, state.store)

    # -- entry-point selection ----------------------------------------------

    def _entries(self, state: EngineState, lut: jax.Array):
        """① entry selection.  Returns (entry_ids [n_entry], e_ent [pool])."""
        spec = self.spec
        if spec.entrance == "none":
            return state.default_entries, jnp.full(
                (spec.ent_pool,), -1, jnp.int32)

        def use_ent(_):
            entries, e_ent, _ = search_mod.entrance_search(
                state.ent, lut, state.codes, n_entry=spec.n_entry,
                pool_size=spec.ent_pool, visited=spec.visited_impl)
            return entries, e_ent

        def use_default(_):
            return state.default_entries, jnp.full(
                (spec.ent_pool,), -1, jnp.int32)

        with jax.named_scope("navis.entrance"):
            return lax.cond(state.ent.count > 0, use_ent, use_default, None)

    # -- classification (Fig 4a) --------------------------------------------

    def _reclassify(self, counters: IOCounters, q, pool_ids, store,
                    loaded_count) -> IOCounters:
        """Move the CASR-classifier 'useful' share of provisionally-wasted
        vector reads into the useful bucket (packed piggybacking & the
        decoupled-full strawman both over-charge wasted)."""
        spec = self.spec
        n_useful = casr_mod.casr_stop_point(
            q, store.vectors, pool_ids, k=spec.k, s=1)
        n_useful = jnp.minimum(n_useful, loaded_count).astype(jnp.int64)
        moved = n_useful * spec.lspec.vector_bytes
        moved = jnp.minimum(moved, counters.wasted_vec_bytes_read)
        return dataclasses.replace(
            counters,
            useful_vec_bytes_read=counters.useful_vec_bytes_read + moved,
            wasted_vec_bytes_read=counters.wasted_vec_bytes_read - moved)

    # -- search --------------------------------------------------------------

    def _search_core(self, state: EngineState, q: jax.Array, *,
                     frozen: bool):
        """Shared ②③ body of one search: traverse + rerank + buffer merge.

        ``frozen=False``: the cache threads through (sequential path).
        ``frozen=True``: the cache is a read-only snapshot and the charged
        page accesses come back as ``res.trace`` — the vmap-safe fan-out
        path.  Returns (ids, dists, stats, counters, traverse result).
        """
        spec = self.spec
        ctr0 = IOCounters.zeros()
        lut = pq_mod.adc_lut(self.codec, q)
        entries, _ = self._entries(state, lut)

        res = search_mod.disk_traverse(
            state.store, spec.lspec, lut, state.codes, state.cache, ctr0,
            entries, pool_size=spec.e_search, beam_width=spec.beam_width,
            max_hops=spec.max_hops, frozen_cache=frozen,
            visited=spec.visited_impl)
        ctr = res.counters
        dead = (res.pool_ids >= 0) & \
            state.tombstone[jnp.maximum(res.pool_ids, 0)]
        ctr = dataclasses.replace(
            ctr, tombstone_skips=ctr.tombstone_skips +
            dead.sum().astype(jnp.int64))
        pool = jnp.where(dead, -1, res.pool_ids)

        if spec.rerank == "casr":
            cres = casr_mod.casr_rerank(state.store, spec.lspec, q, pool,
                                        ctr, k=spec.k, s=spec.s_search)
            ids, dists, ctr = cres.topk_ids, cres.topk_d, cres.counters
            rounds = res.hops + cres.rerank_rounds
        else:
            sorted_ids, sorted_d, _, ctr = search_mod.full_rerank(
                state.store, spec.lspec, q, res._replace(pool_ids=pool),
                ctr, k=spec.k)
            ids, dists = sorted_ids, sorted_d
            extra = 0 if spec.layout == "packed" else 1
            rounds = res.hops + 1 + extra
            ctr = self._reclassify(ctr, q, pool, state.store,
                                   (pool >= 0).sum())

        # FreshDiskANN: merge in-memory buffer hits (exact, no I/O)
        if spec.update_path == "buffered":
            ids, dists = self._merge_buffer_hits(state, q, ids, dists)

        stats = _delta_stats(ctr0, ctr, rounds)
        return ids, dists, stats, ctr, res

    def _search(self, state: EngineState, q: jax.Array):
        """Top-k search.  Returns (ids [k], dists [k], stats, state)."""
        ids, dists, stats, ctr, res = self._search_core(state, q,
                                                        frozen=False)
        state = dataclasses.replace(
            state, cache=res.cache,
            ctr_search=merge_counters(state.ctr_search, ctr))
        return ids, dists, stats, state

    def _merge_buffer_hits(self, state, q, ids, dists):
        spec = self.spec
        bvalid = jnp.arange(spec.buffer_max) < state.buf_count
        bd = jnp.where(bvalid, kernel_ref.rerank_l2_ref(q, state.buf_vecs),
                       INF)
        # buffer ids are virtual: n_max + slot (not yet in the graph)
        bids = (state.store.n_max + jnp.arange(spec.buffer_max)).astype(
            jnp.int32)
        d, i = kernel_ref.pool_merge_ref(
            jnp.where(ids >= 0, dists, INF), ids, bd, bids)
        return jnp.where(d < INF, i, -1), d

    # -- insert ---------------------------------------------------------------

    def _insert(self, state: EngineState, v: jax.Array):
        """One insertion.  Returns (stats, state)."""
        if self.spec.update_path == "buffered":
            return self._insert_buffered(state, v)
        return self._insert_inplace(state, v)

    def _insert_inplace(self, state: EngineState, v: jax.Array,
                        page_seen=None, charge_bulk: bool = False):
        spec = self.spec

        # capacity guard: with no free (reclaimed) slot left past n_max the
        # whole insertion is masked and the stats carry ``dropped`` — an
        # unguarded insert would silently lose the scatter writes
        # (codes.at[count], vectors.at[new_id]) while count kept
        # incrementing, corrupting main_to_ent and live_count.
        full = (state.store.count >= state.store.n_max) & \
            (state.free_count <= 0)

        def do(state: EngineState):
            ctr0 = IOCounters.zeros()
            lut = pq_mod.adc_lut(self.codec, v)
            entries, e_ent = self._entries(state, lut)

            # maintenance-reclaimed slots are reused before fresh ones:
            # under sustained churn the free list is what keeps the
            # acceptance rate at 100% once count reaches n_max
            reuse = state.free_count > 0
            slot = jnp.where(
                reuse,
                state.free_list[jnp.maximum(state.free_count - 1, 0)],
                state.store.count).astype(jnp.int32)
            new_code = pq_mod.encode(self.codec, v[None])[0]
            codes = state.codes.at[slot].set(new_code)

            ires = insert_mod.insert_vertex(
                state.store, spec.lspec, self.codec, codes, self._sym,
                state.cache, ctr0, v, entries, e_pos=spec.e_pos, k=spec.k,
                s=spec.s_pos, rerank=spec.rerank,
                beam_width=spec.beam_width, max_hops=spec.max_hops,
                tombstone=state.tombstone, page_seen=page_seen,
                visited=spec.visited_impl, new_id=slot)
            ctr = ires.counters
            if spec.rerank == "full":
                ctr = self._reclassify(ctr, v, ires.pool_ids, ires.store,
                                       (ires.pool_ids >= 0).sum())

            ent = state.ent
            cache = ires.cache
            if spec.entrance == "dynamic":
                ent = ent_mod.navis_update(
                    ent, ires.new_id, new_code, ires.pool_ids, e_ent,
                    ires.store.count, codes, self._sym,
                    r_ent_frac=spec.ent_frac)
                if spec.cache_policy == "navis":
                    # entrance-aware cache hint (§7): a freshly promoted
                    # member's edgelist page seeds future traversals
                    promoted = ent.count > state.ent.count
                    page = ires.store.edge_page[slot]
                    cache = lax.cond(
                        promoted,
                        lambda c: cache_mod.priority_admit(c, page),
                        lambda c: c, cache)

            stats = _delta_stats(ctr0, ctr, ires.hops + ires.rerank_rounds)
            state = dataclasses.replace(
                state, store=ires.store, codes=codes, ent=ent,
                cache=cache,
                tombstone=state.tombstone.at[slot].set(False),
                n_deleted=state.n_deleted - reuse.astype(jnp.int32),
                free_count=state.free_count - reuse.astype(jnp.int32),
                free_mask=state.free_mask.at[slot].set(False),
                young_mask=state.young_mask.at[slot].set(True),
                ctr_insert=merge_counters(state.ctr_insert, ctr))
            return stats, state, ires.page_seen

        def skip(state: EngineState):
            stats = _delta_stats(IOCounters.zeros(), IOCounters.zeros(),
                                 jnp.zeros((), jnp.int32),
                                 dropped=jnp.ones((), bool))
            # must match the do-branch's page buffer structure: the seeded
            # buffer when given, else an empty set of the same kind/shape
            # disk_traverse would have created
            seen = (page_seen if page_seen is not None else
                    search_mod.empty_page_seen(
                        state.store, visited=spec.visited_impl,
                        max_hops=spec.max_hops,
                        beam_width=spec.beam_width))
            return stats, state, seen

        return lax.cond(full, skip, do, state)

    def _insert_buffered(self, state: EngineState, v: jax.Array):
        """FreshDiskANN path: append to the host buffer (zero storage I/O);
        the caller triggers :meth:`merge` at the 6% threshold."""
        # past capacity the insert is dropped outright: the slot write is
        # clamped AND masked (an unclamped slot would silently scatter-drop
        # while buf_count kept growing, corrupting the _merge_buffer_hits
        # validity mask and needs_merge), and the counter saturates.
        full = state.buf_count >= self.spec.buffer_max
        slot = jnp.minimum(state.buf_count, self.spec.buffer_max - 1)
        state = dataclasses.replace(
            state,
            buf_vecs=state.buf_vecs.at[slot].set(
                jnp.where(full, state.buf_vecs[slot], v)),
            buf_count=state.buf_count + jnp.where(full, 0, 1))
        zeros = jnp.zeros((), jnp.int64)
        stats = OpStats(zeros, zeros, zeros, zeros,
                        jnp.zeros((), jnp.int32), zeros, zeros,
                        dropped=full)
        return stats, state, jnp.zeros_like(state.store.page_live,
                                            dtype=bool)

    def needs_merge(self, state: EngineState) -> jax.Array:
        thresh = jnp.maximum(
            (self.spec.buffer_frac *
             state.store.count.astype(jnp.float32)).astype(jnp.int32), 1)
        return (state.buf_count >= jnp.minimum(thresh,
                                               self.spec.buffer_max)) & \
            (state.buf_count > 0)

    def _merge(self, state: EngineState):
        """FreshDiskANN StreamingMerge: position-seek every buffered vector
        (reads amortised through one shared page buffer), wire them, then
        stream-rewrite the whole on-disk index into the double buffer
        (full-index read + write — the paper's documented write overhead).
        Returns (merge_stats, state)."""
        spec = self.spec
        ctr_before = state.ctr_insert
        page_seen0 = jnp.zeros_like(state.store.page_live, dtype=bool)

        def step(carry, i):
            state, page_seen = carry

            def do(args):
                state, page_seen = args
                _, state, seen = self._insert_inplace(
                    state, state.buf_vecs[i], page_seen=page_seen)
                return state, page_seen | seen

            state, page_seen = lax.cond(
                i < state.buf_count, do, lambda a: a, (state, page_seen))
            return (state, page_seen), None

        (state, _), _ = lax.scan(step, (state, page_seen0),
                                 jnp.arange(spec.buffer_max))

        # stream-rewrite: every live page read once + written once
        lspec = spec.lspec
        per = (lspec.packed_per_page if spec.layout == "packed"
               else lspec.edgelists_per_page)
        n_pages = (-(-state.store.count // per)).astype(jnp.int64)
        stream_bytes = n_pages * PAGE_BYTES
        ctr = dataclasses.replace(
            state.ctr_insert,
            read_requests=state.ctr_insert.read_requests + n_pages,
            write_requests=state.ctr_insert.write_requests + n_pages,
            pad_bytes_read=state.ctr_insert.pad_bytes_read + stream_bytes,
            pad_bytes_written=state.ctr_insert.pad_bytes_written +
            stream_bytes)
        state = dataclasses.replace(state, ctr_insert=ctr,
                                    buf_count=jnp.zeros((), jnp.int32))
        stats = _delta_stats(ctr_before, state.ctr_insert,
                             jnp.int32(0))
        return stats, state

    # -- delete (paper §11) ---------------------------------------------------

    def delete(self, state: EngineState, vid: jax.Array) -> EngineState:
        """Tombstone ``vid``: removed from results and future wiring; the
        entrance graph drops its member.  Bulk compaction happens at the
        merge threshold (not modelled — deletion is benign per OdinANN).

        Idempotent: deleting an already-tombstoned id is a no-op (a second
        n_deleted increment would drift live_count negative-ward and
        misfire the buffered-merge threshold).  Dropping an entrance
        member also scrubs every reciprocal edge pointing at its slot —
        otherwise ``entrance_search`` could seed traversals from the dead
        vertex through the dangling references.
        """
        already = state.tombstone[vid]
        ent = state.ent
        eslot = ent.main_to_ent[vid]

        def drop_ent(ent):
            slot = jnp.maximum(eslot, 0)
            # the dead slot keeps its own outgoing edges (they point at
            # live members and let a traversal route *through* the hole),
            # but no live row may point back at it
            edges = jnp.where(ent.edges == eslot, -1, ent.edges)
            return dataclasses.replace(
                ent,
                ids=ent.ids.at[slot].set(-1),
                edges=edges,
                main_to_ent=ent.main_to_ent.at[vid].set(-1))

        ent = lax.cond((eslot >= 0) & ~already, drop_ent, lambda e: e, ent)
        return dataclasses.replace(
            state, ent=ent,
            tombstone=state.tombstone.at[vid].set(True),
            n_deleted=state.n_deleted + jnp.where(already, 0, 1))

    def _delete_many(self, state: EngineState,
                     vids: jax.Array) -> EngineState:
        """Tombstone a batch of ids ([B] int32; -1 entries are skipped)."""
        def step(state, vid):
            return lax.cond(vid >= 0,
                            lambda s: self.delete(s, vid),
                            lambda s: s, state), None

        state, _ = lax.scan(step, state, vids)
        return state

    # -- maintenance (ISSUE 4: reclamation + repair + defrag + refresh) -------

    def needs_consolidation(self, state: EngineState,
                            lookahead: int = 0) -> jax.Array:
        """True when a consolidation pass is due: the *unreclaimed*
        tombstone fraction crossed ``spec.consolidate_frac``, or capacity
        pressure — fewer than ``lookahead`` insertable slots remain
        (fresh headroom + free list) while tombstones are waiting to be
        reclaimed.  ``lookahead`` is the upcoming insert demand (e.g. the
        next wave size); 0 means "consolidate only when already full"."""
        pending = state.n_deleted - state.free_count
        count = jnp.maximum(state.store.count, 1)
        frac = pending.astype(jnp.float32) / count.astype(jnp.float32)
        headroom = (state.store.n_max - state.store.count) + \
            state.free_count
        return (pending > 0) & (
            (frac >= self.spec.consolidate_frac) |
            (headroom < jnp.maximum(lookahead, 1)))

    def maintenance_step(self, state: EngineState):
        """One bounded increment of the consolidation cycle.

        While the repair cursor is inside the vertex range, repairs the
        next ``spec.maint_block`` rows (splicing dead-vertex references
        away — :func:`repro.core.maintenance.repair_block`) and advances.
        Once the sweep is complete, finalizes the cycle: reclaim every
        tombstoned slot into the free list, clear the reclaimed rows,
        defrag the edgelist pages (invalidating moved pages in the
        cache), rebuild the entrance graph + default entries over the
        live set, priority-admit the new members' pages, and reset the
        cursor.  All I/O lands in ``state.ctr_maint``.

        Host-orchestrated (the entrance rebuild sizes its sample from the
        concrete live count); each stage is jitted.  Returns
        (state, done) — ``done`` marks cycle completion.
        """
        spec = self.spec
        cur = int(state.maint_cursor)
        if cur < int(state.store.count):
            store, cache, ctr, _ = self._repair_block(
                state.store, state.codes, self._sym, state.tombstone,
                state.cache, state.ctr_maint, jnp.asarray(cur, jnp.int32))
            state = dataclasses.replace(
                state, store=store, cache=cache, ctr_maint=ctr,
                maint_cursor=jnp.asarray(cur + spec.maint_block,
                                         jnp.int32))
            return state, False

        # -- cycle finalization ------------------------------------------
        import numpy as np

        # ①b: re-RobustPrune the vertices churn inserted since the last
        # pass — the runtime insert path wires by nearest-PQ without the
        # build's α-diversity, so without this stage a corpus whose
        # membership turns over drifts to unrefined-graph recall
        if spec.maint_refine:
            young = np.asarray(state.young_mask) & \
                (np.arange(state.store.n_max) < int(state.store.count)) & \
                ~np.asarray(state.tombstone)
            yids = np.flatnonzero(young)
            if len(yids):
                store, ctr = state.store, state.ctr_maint
                rb = 32
                for s in range(0, len(yids), rb):
                    blk = np.full((rb,), -1, np.int32)
                    blk[:len(yids[s:s + rb])] = yids[s:s + rb]
                    store, ctr, _ = self._refine_block(
                        store, state.codes, self.codec.codebooks,
                        self._sym, state.tombstone, state.cache, ctr,
                        jnp.asarray(blk), jnp.asarray(blk >= 0),
                        state.default_entries)
                state = dataclasses.replace(
                    state, store=store, ctr_maint=ctr,
                    young_mask=jnp.zeros_like(state.young_mask))

        (store, free_list, free_count, free_mask, cache, ctr,
         _) = self._finalize_cycle(
            state.store, state.tombstone, state.free_list,
            state.free_count, state.free_mask, state.cache,
            state.ctr_maint)
        state = dataclasses.replace(
            state, store=store, free_list=free_list, free_count=free_count,
            free_mask=free_mask, cache=cache, ctr_maint=ctr,
            maint_cursor=jnp.zeros((), jnp.int32))

        live_ids = jnp.asarray(np.flatnonzero(np.asarray(state.live_mask)),
                               jnp.int32)
        key = jax.random.fold_in(
            jax.random.PRNGKey(1347),
            int(store.count) * 131071 + int(state.n_deleted))
        ent = state.ent
        if spec.entrance != "none" and live_ids.shape[0] >= 2:
            # dynamic entrances top themselves back up through Algorithm 2
            # as inserts flow (navis_update's live-membership trigger);
            # static ones only ever refresh here
            ent = maint_mod.refresh_entrance(
                key, state.codes, self._sym, state.ent, state.tombstone,
                live_ids, sample_frac=spec.ent_frac, r_ent=spec.r_ent,
                n_max=store.n_max,
                top_up=spec.entrance != "dynamic")
            cache = self._admit_entrance_pages(cache, store, ent)
        default_entries = state.default_entries
        if live_ids.shape[0] > 0:
            default_entries = maint_mod.refresh_default_entries(
                jax.random.fold_in(key, 1), store.vectors, live_ids,
                spec.n_entry)
        state = dataclasses.replace(state, ent=ent, cache=cache,
                                    default_entries=default_entries)
        return state, True

    def consolidate(self, state: EngineState):
        """One full consolidation pass: repair sweep over the whole vertex
        range, then reclaim + defrag + entrance refresh.  Returns
        (OpStats, state) — the stats price the pass on the SSD model
        exactly like any foreground op (serial_rounds = sweep steps)."""
        ctr0 = state.ctr_maint
        state = dataclasses.replace(state,
                                    maint_cursor=jnp.zeros((), jnp.int32))
        steps, done = 0, False
        while not done:
            state, done = self.maintenance_step(state)
            steps += 1
        stats = _delta_stats(ctr0, state.ctr_maint,
                             jnp.asarray(steps, jnp.int32))
        return stats, state

    # -- batches --------------------------------------------------------------

    def _search_batch(self, state: EngineState, queries: jax.Array):
        """Sequential (state-threading) batch search under lax.scan."""
        def step(state, q):
            ids, dists, stats, state = self._search(state, q)
            return state, (ids, dists, stats)

        state, (ids, dists, stats) = lax.scan(step, state, queries)
        return ids, dists, stats, state

    def _search_many(self, state: EngineState, queries: jax.Array):
        """Batch-parallel search fan-out: the whole batch runs concurrently
        (vmap) against one shared snapshot of the engine state.

        Searches only *read* the graph, so a snapshot is safe: ids and
        distances are identical to :meth:`search_batch` (the cache never
        alters results, only I/O charging).  Each query probes the frozen
        cache and records its page-access trace; afterwards the traces are
        replayed in query order into one merged cache and the per-query
        counters are summed — the paper's model of concurrent readers
        sharing a single host cache.  Returns (ids [Q,k], dists [Q,k],
        per-query stats, state).
        """
        def one(q):
            ids, dists, stats, ctr, res = self._search_core(state, q,
                                                            frozen=True)
            return ids, dists, stats, ctr, res.trace

        ids, dists, stats, ctrs, traces = jax.vmap(one)(queries)
        _, cache = cache_mod.apply_traces(state.cache, traces)
        state = dataclasses.replace(
            state, cache=cache,
            ctr_search=merge_counters(state.ctr_search,
                                      sum_counters(ctrs)))
        return ids, dists, stats, state

    def _insert_batch(self, state: EngineState, vectors: jax.Array):
        def step(state, v):
            stats, state, _ = self._insert(state, v)
            return state, stats

        state, stats = lax.scan(step, state, vectors)
        return stats, state

    def _insert_many(self, state: EngineState, vectors: jax.Array,
                     valid: jax.Array | None = None):
        """Batch-parallel insert fan-out: the whole insert wave position-
        seeks concurrently, only the tiny structural commits serialise.

        Phase ① vmaps :func:`insert.position_seek` (traversal + CASR/full
        rerank + neighbor selection) against one frozen snapshot of the
        engine state — the read-heavy part that dominates update cost runs
        for all ``B`` inserts at once, each charging its own I/O counters
        and recording its page-access trace against the cache snapshot.
        The traces are then replayed in wave order into one merged cache,
        mirroring ``search_many``.

        Phase ② commits the structural updates serially under ``lax.scan``
        with conflict-aware re-validation: each commit re-checks its
        snapshot-selected neighbors against edgelists already mutated by
        earlier commits in the same wave (re-pruning by symmetric-PQ
        distance, dropping tombstoned/duplicate picks) and charges an RMW
        re-read for every neighbor edge page a prior commit dirtied — the
        snapshot copy in its staging buffer is stale — so counters stay
        honest versus the sequential path.  Commits past capacity are
        masked and flagged ``dropped``.

        ``valid`` masks padding lanes (sharded insert buckets): an invalid
        lane charges no I/O, replays no trace and commits nothing.
        Returns (per-insert OpStats [B], state).
        """
        spec = self.spec
        B = vectors.shape[0]
        ok = jnp.ones((B,), bool) if valid is None else valid

        if spec.update_path == "buffered":
            # nothing to fan out: buffered inserts do no position seeking
            def step(state, xs):
                v, keep = xs

                def do(state):
                    stats, state, _ = self._insert_buffered(state, v)
                    return stats, state

                def skip(state):
                    z = jnp.zeros((), jnp.int64)
                    return OpStats(z, z, z, z, jnp.zeros((), jnp.int32),
                                   z, z, jnp.zeros((), bool)), state

                stats, state = lax.cond(keep, do, skip, state)
                return state, stats

            state, stats = lax.scan(step, state, (vectors, ok))
            return stats, state

        # -- phase ①: concurrent position seek on the frozen snapshot -----
        with jax.named_scope("navis.encode"):
            new_codes = pq_mod.encode(self.codec, vectors)      # [B, M]

        def seek_one(v):
            ctr0 = IOCounters.zeros()
            lut = pq_mod.adc_lut(self.codec, v)
            entries, e_ent = self._entries(state, lut)
            seek = insert_mod.position_seek(
                state.store, spec.lspec, self.codec, state.codes,
                state.cache, ctr0, v, entries, e_pos=spec.e_pos,
                k=spec.k, s=spec.s_pos, rerank=spec.rerank,
                beam_width=spec.beam_width, max_hops=spec.max_hops,
                tombstone=state.tombstone, frozen_cache=True,
                visited=spec.visited_impl)
            ctr = seek.counters
            if spec.rerank == "full":
                ctr = self._reclassify(ctr, v, seek.pool_ids, state.store,
                                       (seek.pool_ids >= 0).sum())
            return (seek.nbrs, seek.pool_ids, ctr, seek.hops,
                    seek.rerank_rounds, seek.trace, e_ent)

        with jax.named_scope("navis.seek"):
            nbrs_all, pools, ctrs, hops, rounds, traces, e_ents = \
                jax.vmap(seek_one)(vectors)

        # padding lanes charge nothing and replay nothing
        ctrs = jax.tree.map(lambda x: jnp.where(ok, x, jnp.zeros_like(x)),
                            ctrs)
        hops = jnp.where(ok, hops, 0)
        rounds = jnp.where(ok, rounds, 0)
        traces = jnp.where(ok[:, None], traces, -1)

        # the wave's reads merge into the shared cache in wave order
        _, cache = cache_mod.apply_traces(state.cache, traces)

        # -- phase ②: serialized conflict-aware commits -------------------
        # commits draw reclaimed slots from the free list before fresh
        # ones, so the free structures (and the tombstone bits the reused
        # slots clear) thread through the scan carry
        n_max = state.store.n_max
        dirty0 = jnp.zeros_like(state.store.page_live, dtype=bool)

        def commit(carry, xs):
            (store, codes, ent, cache, dirty, tombstone,
             free_list, free_count, free_mask, n_deleted,
             young_mask) = carry
            v, nbrs, code, pool, e_ent, keep = xs
            can = keep & ((store.count < n_max) | (free_count > 0))

            def do(args):
                (store, codes, ent, cache, dirty, tombstone,
                 free_list, free_count, free_mask, n_deleted,
                 young_mask) = args
                reuse = free_count > 0
                new_id = jnp.where(
                    reuse, free_list[jnp.maximum(free_count - 1, 0)],
                    store.count).astype(jnp.int32)
                with jax.named_scope("navis.link"):
                    codes = codes.at[new_id].set(code)
                    nbrs2 = insert_mod.revalidate_neighbors(
                        nbrs, new_id, code, codes, self._sym, tombstone)
                    ctr, _ = insert_mod.charge_rmw_rereads(
                        IOCounters.zeros(), spec.lspec, store, nbrs2, dirty)
                    sres = insert_mod.commit_insert(
                        store, spec.lspec, cache, ctr, v, nbrs2, codes,
                        self._sym, new_id=new_id)
                    cache = sres.cache
                    dirty = insert_mod.mark_dirty_pages(
                        dirty, sres.store, new_id, nbrs2, sres.modified)
                if spec.entrance == "dynamic":
                    with jax.named_scope("navis.entrance_update"):
                        ent2 = ent_mod.navis_update(
                            ent, new_id, code, pool, e_ent,
                            sres.store.count, codes, self._sym,
                            r_ent_frac=spec.ent_frac)
                        if spec.cache_policy == "navis":
                            promoted = ent2.count > ent.count
                            page = sres.store.edge_page[new_id]
                            cache = lax.cond(
                                promoted,
                                lambda c: cache_mod.priority_admit(c, page),
                                lambda c: c, cache)
                    ent = ent2
                tombstone = tombstone.at[new_id].set(False)
                n_deleted = n_deleted - reuse.astype(jnp.int32)
                free_count = free_count - reuse.astype(jnp.int32)
                free_mask = free_mask.at[new_id].set(False)
                young_mask = young_mask.at[new_id].set(True)
                return ((sres.store, codes, ent, cache, dirty, tombstone,
                         free_list, free_count, free_mask, n_deleted,
                         young_mask),
                        sres.counters)

            def skip(args):
                return args, IOCounters.zeros()

            carry, ctr = lax.cond(
                can, do, skip,
                (store, codes, ent, cache, dirty, tombstone,
                 free_list, free_count, free_mask, n_deleted, young_mask))
            return carry, (ctr, keep & ~can)

        with jax.named_scope("navis.commit"):
            ((store, codes, ent, cache, _, tombstone, free_list, free_count,
              free_mask, n_deleted, young_mask),
             (commit_ctrs, dropped)) = lax.scan(
                commit,
                (state.store, state.codes, state.ent, cache, dirty0,
                 state.tombstone, state.free_list, state.free_count,
                 state.free_mask, state.n_deleted, state.young_mask),
                (vectors, nbrs_all, new_codes, pools, e_ents, ok))

        per = merge_counters(ctrs, commit_ctrs)            # [B]-leading
        stats = OpStats(
            read_requests=per.read_requests,
            read_bytes=per.total_read_bytes(),
            write_requests=per.write_requests,
            write_bytes=per.total_write_bytes(),
            serial_rounds=hops + rounds,
            cache_hits=per.cache_hits,
            cache_misses=per.cache_misses,
            dropped=dropped)
        state = dataclasses.replace(
            state, store=store, codes=codes, ent=ent, cache=cache,
            tombstone=tombstone, free_list=free_list,
            free_count=free_count, free_mask=free_mask,
            n_deleted=n_deleted, young_mask=young_mask,
            ctr_insert=merge_counters(state.ctr_insert,
                                      sum_counters(per)))
        return stats, state

    # -- calibration (paper §5.2 warm-up) -------------------------------------

    def calibrate(self, state: EngineState, queries: jax.Array) -> EngineSpec:
        """Set s_search / s_pos from the P25 of the vectors-to-converge
        distribution over ~100 warm-up queries.  Returns the updated spec
        (also installed on self, re-jitting the ops)."""
        spec = self.spec

        @functools.partial(jax.jit, static_argnames=("pool_size",))
        def pools(state, queries, pool_size):
            def one(q):
                lut = pq_mod.adc_lut(self.codec, q)
                entries, _ = self._entries(state, lut)
                res = search_mod.disk_traverse(
                    state.store, spec.lspec, lut, state.codes, state.cache,
                    IOCounters.zeros(), entries, pool_size=pool_size,
                    beam_width=spec.beam_width, max_hops=spec.max_hops,
                    visited=spec.visited_impl)
                return res.pool_ids
            return jax.lax.map(one, queries, batch_size=16)

        s_vals = {}
        for name, pool_size in (("s_search", spec.e_search),
                                ("s_pos", spec.e_pos)):
            ps = pools(state, queries, pool_size)
            s = casr_mod.calibrate_group_size(
                jax.random.PRNGKey(0), state.store.vectors, ps, queries,
                k=spec.k)
            s_vals[name] = max(s, 1)
        new_spec = spec.with_(**s_vals)
        self.spec = new_spec
        self._jit_ops()
        return new_spec
