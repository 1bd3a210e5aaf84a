"""Every paper-baseline preset (``engine.PRESETS``) through the fan-outs.

Each preset re-pages the one shared graph bundle, so the six engines
differ only in layout, rerank, entrance, cache and update path.  Every
case runs the batch-parallel paths (``search_many``, ``insert_many``,
``delete_many``, ``consolidate``) and holds them to a plain reference:
the sequential scans, ``brute_force_topk`` over the live set, or a copy
of what was inserted.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

from conftest import _spec
from repro.core import (Engine, PRESETS, brute_force_topk, check_invariants,
                        recall_at_k)
from repro.data import insert_stream, query_stream

NAMES = list(PRESETS)
POLICIES = ["none", "navis", "lru", "clock", "lfu"]
WAVE = 12                 # inserts a wave: one insert_many program a preset


def _build(dataset, shared_bundle, name, **overrides):
    eng = Engine(_spec(name).with_(**overrides))
    state = eng.build(jax.random.PRNGKey(2), dataset["vecs"],
                      shared=shared_bundle)
    return eng, state


@pytest.fixture(scope="module", params=NAMES)
def engine(request, dataset, shared_bundle):
    return _build(dataset, shared_bundle, request.param)


def _live_hits(eng, state, qs):
    """Of the live set's true top-10s, how many ``search_many`` finds (a
    buffered preset's virtual buffer ids count as misses)."""
    ids, _, _, _ = eng.search_many(state, qs)
    truth = brute_force_topk(qs, state.store.vectors, state.live_mask, 10)
    ids = jnp.where(ids >= state.store.n_max, -1, ids)
    return round(float(recall_at_k(ids, truth)) * truth.size)


def _assert_graph_well_formed(state):
    inv = check_invariants(state.store, state.tombstone)
    assert all(bool(v) for v in inv.values()), inv
    n = int(state.store.count)
    edges = np.asarray(state.store.edges[:n])
    assert (edges[edges >= 0] < n).all()         # every edge targets a row


# ---------------------------------------------------------------------------
# search
# ---------------------------------------------------------------------------

def test_search_many_matches_sequential(engine, dataset):
    """The vmapped fan-out returns the top-k (ids AND distances) of the
    lax.scan state-threading path on a shared snapshot."""
    eng, state = engine
    qs = dataset["queries"]
    ids_s, d_s, _, _ = eng.search_batch(state, qs)
    ids_m, d_m, _, _ = eng.search_many(state, qs)
    np.testing.assert_array_equal(np.asarray(ids_s), np.asarray(ids_m))
    np.testing.assert_allclose(np.asarray(d_s), np.asarray(d_m))


def test_recall(engine, dataset):
    eng, state = engine
    ids, _, _, _ = eng.search_many(state, dataset["queries"])
    r = float(recall_at_k(ids, dataset["truth"]))
    assert r >= 0.9, r


def test_counter_categories_are_exclusive(engine, dataset):
    """After a search wave and an insert wave, every byte read is in
    exactly one category (edgelist, useful vector, wasted vector,
    padding), and so is every byte written."""
    eng, state = engine
    _, _, _, state = eng.search_many(state, dataset["queries"])
    _, state = eng.insert_many(state, insert_stream(
        jax.random.PRNGKey(5), dataset["cents"], WAVE))
    for c in (state.ctr_search, state.ctr_insert):
        assert int(c.total_read_bytes()) == (
            int(c.edge_bytes_read) + int(c.useful_vec_bytes_read) +
            int(c.wasted_vec_bytes_read) + int(c.pad_bytes_read))
        assert int(c.total_write_bytes()) == (
            int(c.edge_bytes_written) + int(c.vec_bytes_written) +
            int(c.wasted_vec_bytes_written) + int(c.pad_bytes_written))
    assert int(state.ctr_search.total_read_bytes()) > 0


@pytest.fixture(scope="module")
def uncached(dataset, shared_bundle):
    """ids and dists of a cold NAVIS engine without a cache."""
    eng, state = _build(dataset, shared_bundle, "navis", cache_policy="none")
    ids, dists, _, _ = eng.search_many(state, dataset["queries"])
    return np.asarray(ids), np.asarray(dists)


@pytest.mark.parametrize("policy", POLICIES)
def test_cache_changes_no_result(policy, uncached, dataset, shared_bundle):
    """The cache only prices I/O: after a warm-up wave has filled it, a
    wave returns the ids and distances of an engine with no cache."""
    eng, state = _build(dataset, shared_bundle, "navis", cache_policy=policy)
    warm = query_stream(jax.random.PRNGKey(3), dataset["cents"],
                        dataset["queries"].shape[0])
    _, _, _, state = eng.search_many(state, warm)
    ids1, d1, stats, _ = eng.search_many(state, dataset["queries"])
    np.testing.assert_array_equal(uncached[0], np.asarray(ids1))
    np.testing.assert_array_equal(uncached[1], np.asarray(d1))
    if policy != "none":
        assert int(np.asarray(stats.cache_hits).sum()) > 0


# ---------------------------------------------------------------------------
# insert
# ---------------------------------------------------------------------------

@settings(max_examples=4, deadline=None)
@given(seed=st.integers(0, 2 ** 20), drift=st.floats(0.0, 0.5))
def test_insert_many_matches_batch_invariants(engine, dataset, seed, drift):
    """Same wave through the fan-out and the scan: identical final count,
    well-formed graph (no self loops, degree ≤ R, all edges live), and
    held-out search recall within tolerance of the sequential graph."""
    eng, state = engine
    newv = insert_stream(jax.random.PRNGKey(seed), dataset["cents"], WAVE,
                         drift=drift)
    _, st_m = eng.insert_many(state, newv)
    _, st_s = eng.insert_batch(state, newv)

    assert int(st_m.store.count) == int(st_s.store.count)
    assert int(st_m.buf_count) == int(st_s.buf_count)
    _assert_graph_well_formed(st_m)
    _assert_graph_well_formed(st_s)

    qs = dataset["queries"]
    h_m = _live_hits(eng, st_m, qs)
    h_s = _live_hits(eng, st_s, qs)
    assert h_m >= h_s - 0.05 * qs.shape[0] * 10, (h_m, h_s)


def test_insert_many_then_readback(engine, dataset):
    """Every vector an ``insert_many`` wave acknowledged is found by a
    search for a copy of itself, under the id it was given: the next
    prefix slot, or for a buffered preset its buffer slot's virtual id
    (``n_max`` + slot).  The copies lead a wave of the usual queries."""
    eng, state = engine
    wave = insert_stream(jax.random.PRNGKey(9), dataset["cents"], WAVE,
                         drift=0.3)
    stats, state2 = eng.insert_many(state, wave)
    assert not np.asarray(stats.dropped).any()
    if eng.spec.update_path == "buffered":
        first = int(state.store.n_max) + int(state.buf_count)
    else:
        first = int(state.store.count)
    qs = jnp.concatenate([wave, dataset["queries"][WAVE:]])
    ids, dists, _, _ = eng.search_many(state2, qs)
    ids, dists = np.asarray(ids), np.asarray(dists)
    for i in range(WAVE):
        assert first + i in ids[i].tolist(), (i, ids[i])
        assert dists[i][ids[i].tolist().index(first + i)] < 1e-3


# ---------------------------------------------------------------------------
# delete and consolidate
# ---------------------------------------------------------------------------

def _victims(state, qs_ids, n, seed):
    """Every query's top hit plus random live ids, ``n`` in all."""
    top = np.unique(np.asarray(qs_ids)[:, 0])
    live = np.setdiff1d(np.flatnonzero(np.asarray(state.live_mask)), top)
    rng = np.random.default_rng(seed)
    extra = rng.choice(live, n - len(top), replace=False)
    return np.concatenate([top, extra]).astype(np.int32)


def test_delete_many_hides_deleted(engine, dataset):
    eng, state = engine
    qs = dataset["queries"]
    ids0, _, _, state = eng.search_many(state, qs)
    victims = _victims(state, ids0, 60, seed=11)
    state = eng.delete_many(state, jnp.asarray(victims))
    ids1, _, _, _ = eng.search_many(state, qs)
    assert not np.isin(np.asarray(ids1), victims).any()
    assert int(state.live_count) == int(state.store.count) - len(victims)


def test_consolidate_keeps_recall(engine, dataset):
    """After deletes and a consolidation pass, recall@10 against the live
    set stays within 0.01 of the preset's recall before the deletes."""
    eng, state = engine
    qs = dataset["queries"]
    ids0, _, _, _ = eng.search_many(state, qs)
    h0 = _live_hits(eng, state, qs)
    victims = _victims(state, ids0, 60, seed=12)
    state = eng.delete_many(state, jnp.asarray(victims))
    _, state = eng.consolidate(state)
    _assert_graph_well_formed(state)
    assert int(state.free_count) == len(victims)
    h1 = _live_hits(eng, state, qs)
    assert h1 >= h0 - 0.01 * qs.shape[0] * 10, (h0, h1)
