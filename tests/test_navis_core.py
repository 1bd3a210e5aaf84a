"""NAVIS core behaviour: graph build, CASR, insert, entrance, engine e2e."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (Engine, brute_force_topk, check_invariants, preset,
                        robust_prune)
from repro.core import casr as casr_mod
from repro.core import entrance as ent_mod
from repro.core import pq as pq_mod
from repro.core.iomodel import IOCounters
from repro.data import insert_stream, query_stream

KEY = jax.random.PRNGKey(11)


# ---------------------------------------------------------------------------
# graph build
# ---------------------------------------------------------------------------

def test_build_invariants(navis):
    _, state = navis
    inv = check_invariants(state.store)
    assert all(bool(v) for v in inv.values()), inv


def test_build_connectivity(navis, dataset):
    _, state = navis
    n = int(state.store.count)
    E = np.asarray(state.store.edges[:n])
    seen = np.zeros(n, bool)
    seen[0] = True
    frontier = [0]
    while frontier:
        nxt = []
        for u in frontier:
            for v in E[u]:
                if v >= 0 and not seen[v]:
                    seen[v] = True
                    nxt.append(int(v))
        frontier = nxt
    assert seen.mean() > 0.98, seen.mean()


def test_robust_prune_properties():
    k = jax.random.PRNGKey(5)
    vecs = jax.random.normal(k, (100, 16))
    q = jax.random.normal(jax.random.fold_in(k, 1), (16,))
    cand = jnp.arange(50, dtype=jnp.int32)
    d = pq_mod.exact_l2(q, vecs[cand])
    kept = robust_prune(q, cand, d, vecs, alpha=1.2, r=12)
    kept_np = np.asarray(kept)
    live = kept_np[kept_np >= 0]
    # no duplicates
    assert len(live) == len(set(live.tolist()))
    # the closest candidate is always kept first
    assert live[0] == int(jnp.argmin(d))


# ---------------------------------------------------------------------------
# CASR (Algorithm 1)
# ---------------------------------------------------------------------------

def test_casr_full_load_matches_full_rerank(navis, dataset):
    """s = |pool| degenerates to a full fetch: exact top-k must equal the
    brute-force rerank of the pool."""
    eng, state = navis
    q = dataset["queries"][0]
    spec = eng.spec
    lut = pq_mod.adc_lut(eng.codec, q)
    from repro.core import search as search_mod
    entries, _ = eng._entries(state, lut)
    res = search_mod.disk_traverse(
        state.store, spec.lspec, lut, state.codes, state.cache,
        IOCounters.zeros(), entries, pool_size=spec.e_search,
        beam_width=4, max_hops=64)
    cres = casr_mod.casr_rerank(state.store, spec.lspec, q, res.pool_ids,
                                IOCounters.zeros(), k=10,
                                s=spec.e_search)
    valid = res.pool_ids >= 0
    d = jnp.where(valid, pq_mod.exact_l2(
        q, state.store.vectors[jnp.maximum(res.pool_ids, 0)]), jnp.inf)
    want = res.pool_ids[jnp.argsort(d)[:10]]
    np.testing.assert_array_equal(np.asarray(cres.topk_ids),
                                  np.asarray(want))


@pytest.mark.parametrize("s", [1, 4, 16])
def test_casr_loads_bounded_and_counted(navis, dataset, s):
    eng, state = navis
    q = dataset["queries"][1]
    spec = eng.spec
    lut = pq_mod.adc_lut(eng.codec, q)
    from repro.core import search as search_mod
    entries, _ = eng._entries(state, lut)
    res = search_mod.disk_traverse(
        state.store, spec.lspec, lut, state.codes, state.cache,
        IOCounters.zeros(), entries, pool_size=spec.e_search,
        beam_width=4, max_hops=64)
    cres = casr_mod.casr_rerank(state.store, spec.lspec, q, res.pool_ids,
                                IOCounters.zeros(), k=10, s=s)
    n_valid = int((res.pool_ids >= 0).sum())
    assert int(cres.n_loaded) <= n_valid
    assert int(cres.loaded.sum()) == int(cres.n_loaded)
    # counters agree with loads
    vb = spec.lspec.vector_bytes
    assert int(cres.counters.useful_vec_bytes_read) == \
        int(cres.n_loaded) * vb
    assert int(cres.counters.read_requests) == int(cres.n_loaded) * \
        spec.lspec.vector_pages_per_read
    assert int(cres.counters.vectors_read) == int(cres.n_loaded)


def test_casr_saves_vector_loads_vs_full(navis, dataset):
    """On a large pool, CASR must fetch strictly fewer vectors."""
    eng, state = navis
    saved = 0
    for qi in range(5):
        q = dataset["queries"][qi]
        spec = eng.spec
        lut = pq_mod.adc_lut(eng.codec, q)
        from repro.core import search as search_mod
        entries, _ = eng._entries(state, lut)
        res = search_mod.disk_traverse(
            state.store, spec.lspec, lut, state.codes, state.cache,
            IOCounters.zeros(), entries, pool_size=spec.e_pos,
            beam_width=4, max_hops=64)
        cres = casr_mod.casr_rerank(state.store, spec.lspec, q,
                                    res.pool_ids, IOCounters.zeros(),
                                    k=10, s=spec.s_pos)
        n_valid = int((res.pool_ids >= 0).sum())
        saved += n_valid - int(cres.n_loaded)
    assert saved > 0


def test_casr_stop_point_monotone_in_k(navis, dataset):
    eng, state = navis
    q = dataset["queries"][2]
    pool = brute_force_topk(q[None], state.store.vectors,
                            int(state.store.count), 48)[0]
    s5 = int(casr_mod.casr_stop_point(q, state.store.vectors, pool, k=5))
    s20 = int(casr_mod.casr_stop_point(q, state.store.vectors, pool, k=20))
    assert s5 <= s20 + 1        # bigger k needs at least as many loads


def test_calibrate_group_size_returns_positive(navis, dataset):
    eng, state = navis
    pools = brute_force_topk(dataset["queries"][:8], state.store.vectors,
                             int(state.store.count), 48)
    s = casr_mod.calibrate_group_size(KEY, state.store.vectors, pools,
                                      dataset["queries"][:8], k=10)
    assert 1 <= s <= 48


# ---------------------------------------------------------------------------
# insert + entrance
# ---------------------------------------------------------------------------

def test_insert_wires_reciprocal_and_searchable(navis, dataset):
    eng, state = navis
    new = dataset["cents"][3] + 0.01      # a fresh in-distribution vector
    stats, state, _ = eng.insert(state, new)
    new_id = int(state.store.count) - 1
    # the new vertex has edges, and appears in some neighbor's edgelist
    deg = int((state.store.edges[new_id] >= 0).sum())
    assert deg > 0
    incoming = int((state.store.edges[:int(state.store.count)] ==
                    new_id).sum())
    assert incoming > 0
    inv = check_invariants(state.store)
    assert all(bool(v) for v in inv.values())
    # a search for the exact vector finds it
    ids, dists, _, state = eng.search(state, new)
    assert new_id in np.asarray(ids).tolist()


def test_insert_write_volume_decoupled_vs_packed(dataset, shared_bundle):
    """Fig 4(b): packed structural updates co-write neighbor vectors;
    decoupling must cut write bytes."""
    results = {}
    for name in ("odinann", "sel_vec"):
        spec = preset(name, dim=48, r=16, n_max=1600, e_search=40, e_pos=48,
                      pq_m=24, max_hops=64)
        eng = Engine(spec)
        st_ = eng.build(jax.random.PRNGKey(2), dataset["vecs"],
                        shared=shared_bundle)
        newv = insert_stream(jax.random.PRNGKey(9), dataset["cents"], 10)
        stats, st_ = eng.insert_batch(st_, newv)
        results[name] = int(stats.write_bytes.sum())
    assert results["sel_vec"] < results["odinann"], results


def test_entrance_update_properties(navis, dataset):
    eng, state = navis
    ent0 = int(state.ent.count)
    newv = insert_stream(jax.random.PRNGKey(10), dataset["cents"], 15)
    _, state = eng.insert_batch(state, newv)
    ent1 = int(state.ent.count)
    assert ent1 >= ent0          # dynamic entrance may grow
    # main_to_ent is an exact inverse of ids
    ids = np.asarray(state.ent.ids)
    m2e = np.asarray(state.ent.main_to_ent)
    for slot, main in enumerate(ids):
        if main >= 0:
            assert m2e[main] == slot
    # degree cap respected
    deg = (np.asarray(state.ent.edges) >= 0).sum(1)
    assert (deg <= state.ent.r_ent).all()


def test_entrance_update_skipped_above_threshold(dataset, shared_bundle):
    spec = preset("navis", dim=48, r=16, n_max=1600, e_search=40, e_pos=48,
                  pq_m=24, max_hops=64, ent_frac=0.001)  # tiny threshold
    eng = Engine(spec)
    st_ = eng.build(jax.random.PRNGKey(2), dataset["vecs"],
                    shared=shared_bundle)
    ent0 = int(st_.ent.count)
    newv = insert_stream(jax.random.PRNGKey(11), dataset["cents"], 5)
    _, st_ = eng.insert_batch(st_, newv)
    assert int(st_.ent.count) == ent0   # already above 0.1% coverage


# ---------------------------------------------------------------------------
# engine end-to-end
# ---------------------------------------------------------------------------

def test_delete_removes_from_results(navis, dataset):
    eng, state = navis
    q = dataset["queries"][0]
    ids, _, _, state = eng.search(state, q)
    victim = int(np.asarray(ids)[0])
    state = eng.delete(state, jnp.int32(victim))
    ids2, _, _, state = eng.search(state, q)
    assert victim not in np.asarray(ids2).tolist()


def test_freshdiskann_buffer_and_merge(freshdiskann, dataset):
    eng, state = freshdiskann
    count0 = int(state.store.count)
    newv = insert_stream(jax.random.PRNGKey(12), dataset["cents"], 8)
    stats, state = eng.insert_batch(state, newv)
    # buffered: no storage writes yet, vectors searchable from the buffer
    assert int(stats.write_requests.sum()) == 0
    assert int(state.store.count) == count0
    ids, _, _, state = eng.search(state, newv[0])
    assert (np.asarray(ids) >= state.store.n_max).any()   # buffer hit
    # force a merge
    mstats, state = eng.merge(state)
    assert int(state.store.count) == count0 + 8
    assert int(state.buf_count) == 0
    assert int(mstats.write_requests) > 0                 # stream rewrite
    inv = check_invariants(state.store)
    assert all(bool(v) for v in inv.values())
