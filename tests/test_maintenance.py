"""Maintenance subsystem (ISSUE 4): tombstone reclamation into the free
list, dead-edge repair, edgelist defrag + cache invalidation, entrance
refresh, and the churn contract — inserts stop dropping once reclaimed
slots exist."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (Engine, brute_force_topk, check_invariants, preset,
                        recall_at_k)
from repro.core import cache as cache_mod
from repro.data import insert_stream, query_stream


def _delete_some(eng, state, n, seed=0, forbid=()):
    """Tombstone ``n`` random live vertices; returns (state, victims)."""
    rng = np.random.default_rng(seed)
    pool = np.setdiff1d(np.flatnonzero(np.asarray(state.live_mask)),
                        np.asarray(forbid))
    victims = rng.choice(pool, n, replace=False).astype(np.int32)
    return eng.delete_many(state, jnp.asarray(victims)), victims


# ---------------------------------------------------------------------------
# consolidation pass: repair + reclaim + refresh
# ---------------------------------------------------------------------------

def test_consolidate_reclaims_and_repairs(navis, dataset):
    eng, state = navis
    state, victims = _delete_some(eng, state, 60, seed=1)
    # pre-consolidation: live edgelists do reference the dead vertices
    inv = check_invariants(state.store, state.tombstone)
    assert not bool(inv["no_dead_refs"])

    stats, st2 = eng.consolidate(state)
    inv = check_invariants(st2.store, st2.tombstone)
    assert all(bool(v) for v in inv.values()), inv
    # every tombstoned slot was reclaimed into the free list
    assert int(st2.free_count) == len(victims)
    fl = np.asarray(st2.free_list[:int(st2.free_count)])
    assert sorted(fl.tolist()) == sorted(victims.tolist())
    assert np.asarray(st2.free_mask).sum() == len(victims)
    # reclaimed rows hold no graph state
    assert (np.asarray(st2.store.edges[victims]) == -1).all()
    assert (np.asarray(st2.store.degree[victims]) == 0).all()
    # the entrance graph only references live vertices
    ids = np.asarray(st2.ent.ids)
    assert not np.asarray(st2.tombstone)[ids[ids >= 0]].any()
    # default entries are live
    de = np.asarray(st2.default_entries)
    assert not np.asarray(st2.tombstone)[de].any()


def test_consolidate_charges_maintenance_io(navis, dataset):
    eng, state = navis
    state, _ = _delete_some(eng, state, 40, seed=2)
    ctr0 = state.ctr_maint
    stats, st2 = eng.consolidate(state)
    delta = jax.tree.map(lambda a, b: a - b, st2.ctr_maint, ctr0)
    # the pass reads the sweep + defrag stream and writes repairs + defrag
    assert int(stats.read_requests) > 0
    assert int(stats.write_requests) > 0
    assert int(stats.read_requests) == int(delta.read_requests)
    assert int(stats.write_requests) == int(delta.write_requests)
    assert int(stats.read_bytes) == int(delta.total_read_bytes())
    assert int(stats.write_bytes) == int(delta.total_write_bytes())
    # foreground counters are untouched by maintenance
    for f in ("ctr_search", "ctr_insert"):
        for a, b in zip(jax.tree.leaves(getattr(st2, f)),
                        jax.tree.leaves(getattr(state, f))):
            assert int(a) == int(b)


def test_maintenance_step_is_incremental(navis, dataset):
    eng, state = navis
    state, _ = _delete_some(eng, state, 30, seed=3)
    n_steps = 0
    done = False
    st = dataclasses.replace(state, maint_cursor=jnp.zeros((), jnp.int32))
    while not done:
        st, done = eng.maintenance_step(st)
        n_steps += 1
        assert n_steps < 100
    # sweep blocks + one finalization step
    expect = -(-int(state.store.count) // eng.spec.maint_block) + 1
    assert n_steps == expect
    assert int(st.free_count) == 30
    assert int(st.maint_cursor) == 0          # ready for the next cycle


def test_search_parity_across_consolidation(navis, dataset):
    """Live-vertex search results (ids AND dists) are preserved across a
    consolidation pass: repair only reroutes around tombstoned vertices
    the result mask already hid, defrag only moves pages, and the
    entrance refresh re-seeds traversals that converge to the same
    exact-reranked top-k."""
    eng, state = navis
    state, _ = _delete_some(eng, state, 60, seed=4)
    qs = dataset["queries"]
    ids0, d0, _, state = eng.search_many(state, qs)
    _, st2 = eng.consolidate(state)
    ids1, d1, _, _ = eng.search_many(st2, qs)
    np.testing.assert_array_equal(np.asarray(ids0), np.asarray(ids1))
    np.testing.assert_array_equal(np.asarray(d0), np.asarray(d1))


def test_consolidate_invalidates_relocated_pages(navis, dataset):
    eng, state = navis
    # warm the cache so edge pages are resident
    for q in dataset["queries"][:16]:
        _, _, _, state = eng.search(state, q)
    state, _ = _delete_some(eng, state, 40, seed=5)
    before = np.asarray(state.store.edge_page)
    _, st2 = eng.consolidate(state)
    after = np.asarray(st2.store.edge_page)
    moved = before != after
    changed = set(before[moved & (before >= 0)].tolist()) | \
        set(after[moved & (after >= 0)].tolist())
    # the entrance-aware hint re-admits live members' (fresh, post-defrag)
    # pages after the invalidation sweep — those are current, not stale
    ids = np.asarray(st2.ent.ids)
    admitted = set(after[ids[ids >= 0]].tolist())
    status = np.asarray(st2.cache.status)
    for p in changed - admitted:
        assert status[p] == 0, f"stale page {p} still cached"
    assert changed, "consolidation moved nothing?"
    # and the cache survives consistently: a fresh search still works
    ids, _, _, _ = eng.search(st2, dataset["queries"][0])
    assert (np.asarray(ids) >= 0).any()


def test_tombstone_skips_counter(navis, dataset):
    eng, state = navis
    state, _ = _delete_some(eng, state, 80, seed=6)
    ctr0 = int(state.ctr_search.tombstone_skips)
    _, _, _, state = eng.search_many(state, dataset["queries"])
    wasted = int(state.ctr_search.tombstone_skips) - ctr0
    assert wasted > 0               # dead vertices polluted explored pools
    _, st2 = eng.consolidate(state)
    ctr1 = int(st2.ctr_search.tombstone_skips)
    _, _, _, st2 = eng.search_many(st2, dataset["queries"])
    assert int(st2.ctr_search.tombstone_skips) == ctr1   # pools are clean


# ---------------------------------------------------------------------------
# free-list slot reuse (delete → consolidate → insert round trip)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def small(dataset):
    """A nearly-full engine: 400 base vectors, 4 slots of headroom."""
    n_base = 400
    eng = Engine(preset("navis", dim=48, r=16, n_max=n_base + 4,
                        e_search=32, e_pos=40, pq_m=24, max_hops=48,
                        cache_capacity_pages=128, buffer_max=32,
                        maint_block=128))
    state = eng.build(jax.random.PRNGKey(3), dataset["vecs"][:n_base],
                      build_block=64, build_e_pos=32)
    return eng, state


def test_tombstoned_slot_reuse_round_trip(small, dataset):
    eng, state = small
    vid = 123
    state = eng.delete(state, jnp.int32(vid))
    _, state = eng.consolidate(state)
    assert int(state.free_count) == 1
    count0 = int(state.store.count)
    newv = dataset["cents"][5] + 0.02
    stats, state, _ = eng.insert(state, newv)
    assert not bool(stats.dropped)
    # the insert landed in the freed slot, not a fresh one
    assert int(state.store.count) == count0
    assert int(state.free_count) == 0
    assert not bool(state.tombstone[vid])
    np.testing.assert_allclose(np.asarray(state.store.vectors[vid]),
                               np.asarray(newv), rtol=1e-6)
    # and it is searchable under its recycled id
    ids, _, _, state = eng.search(state, newv)
    assert vid in np.asarray(ids).tolist()
    inv = check_invariants(state.store, state.tombstone)
    assert all(bool(v) for v in inv.values()), inv


def test_churn_does_not_drop_inserts_at_capacity(small, dataset):
    """The production steady state: at count == n_max, delete + consolidate
    + insert keeps accepting writes — without maintenance every one of
    these inserts would drop."""
    eng, state = small
    n_max = state.store.n_max
    # fill the fresh headroom
    fill = insert_stream(jax.random.PRNGKey(31), dataset["cents"], 4)
    _, state = eng.insert_many(state, fill)
    assert int(state.store.count) == n_max

    for round_ in range(3):
        state, victims = _delete_some(eng, state, 5, seed=40 + round_)
        assert bool(eng.needs_consolidation(state, lookahead=5))
        _, state = eng.consolidate(state)
        wave = insert_stream(jax.random.PRNGKey(50 + round_),
                             dataset["cents"], 5)
        stats, state = eng.insert_many(state, wave)
        assert not np.asarray(stats.dropped).any()
        assert int(state.store.count) == n_max
        assert int(state.live_count) == n_max
    inv = check_invariants(state.store, state.tombstone)
    assert all(bool(v) for v in inv.values()), inv
    # the no-maintenance control: same wave against the full state drops
    state2, _ = _delete_some(eng, state, 5, seed=99)
    wave = insert_stream(jax.random.PRNGKey(60), dataset["cents"], 5)
    stats, _ = eng.insert_many(state2, wave)
    assert np.asarray(stats.dropped).all()


def test_churn_smoke_keeps_recall(navis, dataset):
    """Sixteen cycles that each delete 25 live vertices and insert 25
    through ``insert_many``, consolidating whenever
    ``needs_consolidation(lookahead=25)`` fires: no insert drops, every
    consolidation leaves no live edge to a deleted vertex, the graph
    stays well formed, and recall@10 against the live set ends within
    0.01 of the fresh build's."""
    eng, state = navis
    qs, batch = dataset["queries"], 25

    def hits(state):
        """Of the live set's true top-10s, how many a wave finds."""
        ids, _, _, state = eng.search_many(state, qs)
        truth = brute_force_topk(qs, state.store.vectors, state.live_mask,
                                 10)
        return round(float(recall_at_k(ids, truth)) * truth.size), state

    baseline, state = hits(state)
    rng = np.random.default_rng(0)
    dropped = consolidations = 0
    for c in range(16):
        live = np.flatnonzero(np.asarray(state.live_mask))
        victims = rng.choice(live, batch, replace=False).astype(np.int32)
        state = eng.delete_many(state, jnp.asarray(victims))
        if bool(eng.needs_consolidation(state, lookahead=batch)):
            _, state = eng.consolidate(state)
            consolidations += 1
            inv = check_invariants(state.store, state.tombstone)
            assert all(bool(v) for v in inv.values()), (c, inv)
        wave = insert_stream(jax.random.fold_in(jax.random.PRNGKey(90), c),
                             dataset["cents"], batch)
        stats, state = eng.insert_many(state, wave)
        dropped += int(np.asarray(stats.dropped).sum())
    assert dropped == 0
    assert consolidations > 0
    inv = check_invariants(state.store, state.tombstone)
    assert all(bool(v) for k, v in inv.items()
               if k != "no_dead_refs"), inv   # the last deletes still pend
    final, _ = hits(state)
    assert final >= baseline - 0.01 * qs.shape[0] * 10, (baseline, final)


def test_insert_many_draws_from_free_list(navis, dataset):
    eng, state = navis
    state, victims = _delete_some(eng, state, 5, seed=7)
    _, state = eng.consolidate(state)
    count0 = int(state.store.count)
    wave = insert_stream(jax.random.PRNGKey(70), dataset["cents"], 8)
    stats, st2 = eng.insert_many(state, wave)
    assert not np.asarray(stats.dropped).any()
    # five commits reused freed slots, three extended the prefix
    assert int(st2.store.count) == count0 + 3
    assert int(st2.free_count) == 0
    assert not np.asarray(st2.tombstone)[victims].any()
    inv = check_invariants(st2.store, st2.tombstone)
    assert all(bool(v) for v in inv.values()), inv
    # held-out recall against the live set stays healthy
    truth = brute_force_topk(dataset["queries"], st2.store.vectors,
                             st2.live_mask, 10)
    ids, _, _, _ = eng.search_batch(st2, dataset["queries"])
    assert float(recall_at_k(ids, truth)) >= 0.9


def test_needs_consolidation_trigger(navis, dataset):
    eng, state = navis
    assert not bool(eng.needs_consolidation(state))
    frac = eng.spec.consolidate_frac
    n = int(np.ceil(frac * int(state.store.count))) + 2
    state, _ = _delete_some(eng, state, n, seed=8)
    assert bool(eng.needs_consolidation(state))
    _, st2 = eng.consolidate(state)
    assert not bool(eng.needs_consolidation(st2))     # nothing pending
    # capacity-pressure clause: headroom below the upcoming wave size
    headroom = int(st2.store.n_max - st2.store.count) + int(st2.free_count)
    st3, _ = _delete_some(eng, st2, 1, seed=9)
    assert bool(eng.needs_consolidation(st3, lookahead=headroom + 10))
    assert not bool(eng.needs_consolidation(st3, lookahead=1))


# ---------------------------------------------------------------------------
# entrance-promotion cache hint (priority admission)
# ---------------------------------------------------------------------------

def test_priority_admit_pins_into_frozen():
    st_ = cache_mod.init_cache(128, 20, "navis", jax.random.PRNGKey(0))
    st_ = cache_mod.priority_admit(st_, jnp.int32(7))
    assert int(st_.status[7]) == 2                     # IN_FROZEN
    slot = int(st_.slot_of[7])
    assert int(st_.frozen_pages[slot]) == 7
    hit, _ = cache_mod.access(st_, jnp.int32(7), "navis")
    assert bool(hit)
    # a page sitting in the window is moved, not duplicated
    st_ = cache_mod.init_cache(128, 20, "navis", jax.random.PRNGKey(0))
    _, st_ = cache_mod.access(st_, jnp.int32(9), "navis")  # -> window
    st_ = cache_mod.priority_admit(st_, jnp.int32(9))
    assert int(st_.status[9]) == 2
    assert int((st_.window_pages == 9).sum()) == 0
    # single-region policies have no frozen region: no-op
    st_ = cache_mod.init_cache(128, 20, "lru", jax.random.PRNGKey(0))
    st0 = cache_mod.priority_admit(st_, jnp.int32(7))
    for a, b in zip(jax.tree.leaves(st_), jax.tree.leaves(st0)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_entrance_promotion_admits_page(navis, dataset):
    """An insert that promotes into the dynamic entrance leaves the new
    vertex's edgelist page resident in the frozen cache region."""
    eng, state = navis
    newv = insert_stream(jax.random.PRNGKey(80), dataset["cents"], 10)
    ent0 = int(state.ent.count)
    for i in range(10):
        _, state, _ = eng.insert(state, newv[i])
        if int(state.ent.count) > ent0:
            new_id = int(state.ent.ids[int(state.ent.count) - 1])
            page = int(state.store.edge_page[new_id])
            assert int(state.cache.status[page]) == 2  # IN_FROZEN
            return
    pytest.skip("entrance saturated before any promotion fired")
