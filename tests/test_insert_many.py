"""Batch-parallel insert fan-out: two-phase ``insert_many`` vs the
sequential scan (recall parity at a wide wave, counter sums, cache
merge; graph invariants per preset in test_presets.py), conflict-aware commit primitives, and the insert/delete
correctness regressions (capacity guard, idempotent delete, entrance
edge scrub)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (Engine, IOCounters, brute_force_topk,
                        check_invariants, preset, recall_at_k)
from repro.core import insert as insert_mod
from repro.core import pq as pq_mod
from repro.core.layout import LayoutSpec, empty_store, assign_initial_pages
from repro.data import insert_stream, query_stream


def _wave(dataset, n, seed=7, drift=0.2):
    return insert_stream(jax.random.PRNGKey(seed), dataset["cents"], n,
                         drift=drift)


def _recall(eng, state, queries, truth):
    ids, _, _, _ = eng.search_batch(state, queries)
    return float(recall_at_k(ids, truth))


def _assert_graph_well_formed(state):
    inv = check_invariants(state.store)
    assert all(bool(v) for v in inv.values()), inv
    n = int(state.store.count)
    edges = np.asarray(state.store.edges[:n])
    live = edges[edges >= 0]
    assert (live < n).all()                      # every edge targets a live id


def test_insert_many_single_insert_matches_sequential(navis, dataset):
    """A wave of one has no conflicts: the merged cache is bit-identical
    to the sequential insert's (same trace, same replay order, same
    eviction hints) and the new vertex gets the same neighbor set."""
    eng, state = navis
    one = _wave(dataset, 1)
    _, st_m = eng.insert_many(state, one)
    _, st_s = eng.insert_batch(state, one)
    assert int(st_m.store.count) == int(st_s.store.count)
    for a, b in zip(jax.tree.leaves(st_m.cache),
                    jax.tree.leaves(st_s.cache)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    new_id = int(state.store.count)
    e_m = sorted(np.asarray(st_m.store.edges[new_id]).tolist())
    e_s = sorted(np.asarray(st_s.store.edges[new_id]).tolist())
    assert e_m == e_s


def test_insert_many_counter_sum_invariant(navis, dataset):
    """The engine's cumulative insert counters advance by exactly the sum
    of the per-insert deltas the fan-out reports — pages are charged once
    per insert (the per-seek page buffer dedupes within an insert) and
    RMW re-reads once per dirty page per commit."""
    eng, state = navis
    newv = _wave(dataset, 10)
    stats, state2 = eng.insert_many(state, newv)
    delta = jax.tree.map(lambda a, b: a - b,
                         state2.ctr_insert, state.ctr_insert)
    assert int(np.asarray(stats.read_requests).sum()) == \
        int(delta.read_requests)
    assert int(np.asarray(stats.write_requests).sum()) == \
        int(delta.write_requests)
    assert int(np.asarray(stats.read_bytes).sum()) == \
        int(delta.total_read_bytes())
    assert int(np.asarray(stats.write_bytes).sum()) == \
        int(delta.total_write_bytes())
    assert int(np.asarray(stats.cache_hits).sum()) == int(delta.cache_hits)
    assert int(np.asarray(stats.cache_misses).sum()) == \
        int(delta.cache_misses)
    assert not np.asarray(stats.dropped).any()


def test_insert_many_replays_traces_into_shared_cache(navis, dataset):
    """Phase-① traces feed the merged cache: a search wave immediately
    after an insert wave sees cache hits on the pages the seeks read."""
    eng, state = navis
    _, state2 = eng.insert_many(state, _wave(dataset, 8))
    _, _, stats, _ = eng.search_many(state2, dataset["queries"][:8])
    assert int(np.asarray(stats.cache_hits).sum()) > 0


def test_insert_many_valid_mask_skips_padding(navis, dataset):
    """Padding lanes (sharded buckets) charge no I/O and commit nothing."""
    eng, state = navis
    newv = _wave(dataset, 8)
    ok = jnp.arange(8) < 5
    stats, st2 = jax.jit(eng._insert_many)(state, newv, ok)
    assert int(st2.store.count) == int(state.store.count) + 5
    rr = np.asarray(stats.read_requests)
    assert (rr[:5] > 0).all() and (rr[5:] == 0).all()
    assert not np.asarray(stats.dropped).any()
    _assert_graph_well_formed(st2)


# ---------------------------------------------------------------------------
# conflict-aware commit primitives
# ---------------------------------------------------------------------------

def _tiny_codec(key, dim=8, m=4, n=64):
    vecs = jax.random.normal(key, (n, dim))
    codec = pq_mod.train_pq(key, vecs, m)
    return codec, pq_mod.encode(codec, vecs), pq_mod.sym_tables(codec)


def test_revalidate_neighbors_drops_and_reprunes():
    codec, codes, sym = _tiny_codec(jax.random.PRNGKey(0))
    tomb = jnp.zeros((64,), bool).at[5].set(True)
    new_id = jnp.int32(60)
    nbrs = jnp.asarray([3, 5, 3, 60, 7, -1], jnp.int32)
    out = insert_mod.revalidate_neighbors(nbrs, new_id, codes[60], codes,
                                          sym, tomb)
    kept = np.asarray(out)
    live = kept[kept >= 0].tolist()
    # tombstoned 5, duplicate 3, self 60 and padding are gone
    assert sorted(live) == [3, 7]
    # survivors are ordered by symmetric-PQ distance to the new vertex
    d = np.asarray(pq_mod.sym_distance(sym, codes[60], codes[jnp.asarray(
        live)]))
    assert (np.diff(d) >= 0).all()
    # valid picks land at the front, padding at the tail
    assert (kept[2:] == -1).all()


def test_charge_rmw_rereads_counts_unique_dirty_pages():
    spec = LayoutSpec(kind="decoupled", dim=8, r=96)   # 10 edgelists/page
    store = assign_initial_pages(empty_store(64, 8, 96), spec)
    store_pages = np.asarray(store.edge_page)
    nbrs = jnp.asarray([0, 1, 60, -1], jnp.int32)
    # vertices 0 and 1 share an edge page; vertex 60 lives elsewhere
    assert store_pages[0] == store_pages[1] != store_pages[60]
    dirty = jnp.zeros_like(store.page_live, dtype=bool)
    dirty = dirty.at[store_pages[0]].set(True)
    ctr, n = insert_mod.charge_rmw_rereads(IOCounters.zeros(), spec, store,
                                           nbrs, dirty)
    assert int(n) == 1                        # one distinct dirty page
    assert int(ctr.read_requests) == 1
    assert int(ctr.edge_bytes_read) > 0
    # nothing dirty -> nothing charged
    ctr0, n0 = insert_mod.charge_rmw_rereads(
        IOCounters.zeros(), spec, store, nbrs,
        jnp.zeros_like(store.page_live, dtype=bool))
    assert int(n0) == 0 and int(ctr0.read_requests) == 0


def test_mark_dirty_pages_tracks_commit_writes():
    spec = LayoutSpec(kind="decoupled", dim=8, r=96)
    store = assign_initial_pages(empty_store(64, 8, 96), spec)
    dirty = jnp.zeros_like(store.page_live, dtype=bool)
    nbrs = jnp.asarray([2, 9, -1, -1], jnp.int32)
    modified = jnp.asarray([True, False, False, False])
    dirty = insert_mod.mark_dirty_pages(dirty, store, jnp.int32(30), nbrs,
                                        modified)
    d = np.asarray(dirty)
    assert d[np.asarray(store.edge_page)[30]]       # new vertex's page
    assert d[np.asarray(store.edge_page)[2]]        # rewritten neighbor
    assert d.sum() == len({int(np.asarray(store.edge_page)[30]),
                           int(np.asarray(store.edge_page)[2])})


# ---------------------------------------------------------------------------
# capacity guard (in-place insert past n_max)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def tight(dataset):
    """An engine with almost no insert headroom (n_max = count + 4)."""
    n_base = 400
    eng = Engine(preset("navis", dim=48, r=16, n_max=n_base + 4,
                        e_search=32, e_pos=40, pq_m=24, max_hops=48,
                        cache_capacity_pages=128, buffer_max=32))
    state = eng.build(jax.random.PRNGKey(3), dataset["vecs"][:n_base],
                      build_block=64, build_e_pos=32)
    return eng, state


def test_insert_inplace_capacity_guard(tight, dataset):
    """Past n_max the whole commit is masked: count saturates, the stats
    carry ``dropped``, and the graph stays well-formed (the unguarded
    path silently lost the scatter writes while count kept climbing)."""
    eng, state = tight
    n_max = state.store.n_max
    newv = _wave(dataset, 7, seed=21)
    flags = []
    for i in range(7):
        stats, state, _ = eng.insert(state, newv[i])
        flags.append(bool(stats.dropped))
    assert flags == [False] * 4 + [True] * 3
    assert int(state.store.count) == n_max
    assert int(state.live_count) == n_max
    _assert_graph_well_formed(state)
    # the accepted inserts really landed and are searchable
    ids, _, _, state = eng.search(state, newv[0])
    assert int(state.store.count) - 4 in np.asarray(ids).tolist()


def test_insert_many_capacity_guard(tight, dataset):
    """A wave overflowing capacity commits the head, drops the tail."""
    eng, state = tight
    n_max = state.store.n_max
    stats, st2 = eng.insert_many(state, _wave(dataset, 7, seed=22))
    assert int(st2.store.count) == n_max
    dropped = np.asarray(stats.dropped)
    assert dropped.tolist() == [False] * 4 + [True] * 3
    # dropped lanes still paid their position seek (phase ① ran against
    # the snapshot) but wrote nothing
    wr = np.asarray(stats.write_requests)
    assert (wr[4:] == 0).all()
    _assert_graph_well_formed(st2)


# ---------------------------------------------------------------------------
# delete correctness (idempotence + entrance edge scrub)
# ---------------------------------------------------------------------------

def test_delete_is_idempotent(navis, dataset):
    eng, state = navis
    vid = jnp.int32(17)
    live0 = int(state.live_count)
    state1 = eng.delete(state, vid)
    state2 = eng.delete(state1, vid)            # double delete: no-op
    assert int(state1.n_deleted) - int(state.n_deleted) == 1
    assert int(state2.n_deleted) == int(state1.n_deleted)
    assert int(state2.live_count) == live0 - 1
    assert bool(state2.tombstone[vid])


def test_delete_scrubs_entrance_edges(navis, dataset):
    """Dropping an entrance member leaves no reciprocal edge pointing at
    the dead slot, so entrance_search can never seed from it."""
    eng, state = navis
    ids = np.asarray(state.ent.ids)
    edges0 = np.asarray(state.ent.edges)
    # a live member some other member links back to
    slot = next(s for s in range(1, len(ids))
                if ids[s] >= 0 and (edges0 == s).sum() > 0)
    vid = int(ids[slot])
    st2 = eng.delete(state, jnp.int32(vid))
    assert int(st2.ent.ids[slot]) == -1
    assert int(st2.ent.main_to_ent[vid]) == -1
    assert (np.asarray(st2.ent.edges) == slot).sum() == 0   # scrubbed
    # deleting again must not disturb the entrance graph further
    st3 = eng.delete(st2, jnp.int32(vid))
    for a, b in zip(jax.tree.leaves(st2.ent), jax.tree.leaves(st3.ent)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_insert_wave_skips_tombstoned_neighbors(navis, dataset):
    """Wave inserts never wire to vertices deleted before the wave."""
    eng, state = navis
    victims = [3, 44, 101]
    for v in victims:
        state = eng.delete(state, jnp.int32(v))
    _, st2 = eng.insert_many(state, _wave(dataset, 6, seed=23))
    n0, n1 = int(state.store.count), int(st2.store.count)
    new_edges = np.asarray(st2.store.edges[n0:n1])
    assert not np.isin(new_edges[new_edges >= 0], victims).any()
    _assert_graph_well_formed(st2)


# ---------------------------------------------------------------------------
# ≥512-insert wave: recall parity with the sequential path (acceptance)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def midsize():
    """A dedicated corpus with enough headroom for a 512-insert wave."""
    key = jax.random.PRNGKey(5)
    from repro.data import make_clustered
    vecs, _, cents = make_clustered(key, 900, 32, n_clusters=10, scale=3.0,
                                    noise=1.0)
    eng = Engine(preset("navis", dim=32, r=16, n_max=1600, e_search=40,
                        e_pos=48, pq_m=16, max_hops=48,
                        cache_capacity_pages=256, buffer_max=64))
    state = eng.build(jax.random.PRNGKey(6), vecs, build_block=64,
                      build_e_pos=32)
    return eng, state, cents


def test_insert_many_wave512_recall_parity(midsize):
    eng, state, cents = midsize
    wave = insert_stream(jax.random.PRNGKey(7), cents, 512, drift=0.2)
    stats_m, st_m = eng.insert_many(state, wave)
    stats_s, st_s = eng.insert_batch(state, wave)
    assert int(st_m.store.count) == int(st_s.store.count)
    assert not np.asarray(stats_m.dropped).any()
    _assert_graph_well_formed(st_m)

    # per-wave counters sum consistently (no double-charged pages)
    delta = jax.tree.map(lambda a, b: a - b, st_m.ctr_insert,
                         state.ctr_insert)
    assert int(np.asarray(stats_m.read_requests).sum()) == \
        int(delta.read_requests)
    assert int(np.asarray(stats_m.write_requests).sum()) == \
        int(delta.write_requests)

    qs = query_stream(jax.random.PRNGKey(8), cents, 100)
    truth = brute_force_topk(qs, st_s.store.vectors,
                             int(st_s.store.count), 10)
    r_m = _recall(eng, st_m, qs, truth)
    r_s = _recall(eng, st_s, qs, truth)
    assert r_m >= r_s - 0.01, (r_m, r_s)      # within one recall point
