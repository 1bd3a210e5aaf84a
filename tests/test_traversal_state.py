"""O(1)-state traversal: hashed visited sets ≡ bitmap reference, overflow
saturation semantics, entrance seed guard, no Pallas on the engine path."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import Engine, pq as pq_mod
from repro.core import insert as insert_mod
from repro.core import search as search_mod
from repro.core import visited as visited_mod
from repro.core.entrance import EntranceGraph
from repro.core.iomodel import IOCounters

KEY = jax.random.PRNGKey(21)


def _counters_equal(a: IOCounters, b: IOCounters):
    for f in dataclasses.fields(IOCounters):
        va, vb = int(getattr(a, f.name)), int(getattr(b, f.name))
        assert va == vb, (f.name, va, vb)


@pytest.fixture(scope="module")
def bitmap_twin(navis):
    """Same spec/codec as the session engine, dense-bitmap visited sets.
    Runs against the *same* EngineState, so every op is an apples-to-apples
    comparison (state is engine-independent; only codec + spec matter)."""
    eng, _ = navis
    twin = Engine(eng.spec.with_(visited_impl="bitmap"))
    twin.codec = eng.codec
    twin._sym = eng._sym
    return twin


# ---------------------------------------------------------------------------
# hashed visited sets: unit properties
# ---------------------------------------------------------------------------

def test_hash_set_basics():
    vs = visited_mod.make_hash(16)
    keys = jnp.array([3, 900001, 3, -1, 77], jnp.int32)
    vs = visited_mod.add(vs, keys, jnp.ones(5, bool))
    assert int(vs.count) == 3                     # dup + invalid dropped
    got = visited_mod.contains(vs, jnp.array([3, 900001, 77, 4, -1]))
    assert got.tolist() == [True, True, True, False, False]
    assert int(visited_mod.overflow(vs)) == 0


def test_hash_set_saturates_without_corruption():
    vs = visited_mod.make_hash(2)                 # table of 8
    keys = jnp.arange(50, dtype=jnp.int32)
    vs = visited_mod.add(vs, keys, jnp.ones(50, bool))
    assert int(vs.count) == vs.keys.shape[0]      # full
    assert int(vs.overflow) == 50 - vs.keys.shape[0]
    # every key the table holds still answers membership correctly
    held = np.asarray(vs.keys)
    assert (held >= 0).all()
    assert bool(visited_mod.contains(vs, jnp.asarray(held)).all())


def test_dense_matches_hash_on_random_streams():
    k1, k2 = jax.random.split(KEY)
    keys = jax.random.randint(k1, (200,), 0, 400).astype(jnp.int32)
    mask = jax.random.bernoulli(k2, 0.8, (200,))
    hs = visited_mod.add(visited_mod.make_hash(200), keys, mask)
    ds = visited_mod.add(visited_mod.make_dense(400), keys, mask)
    probe = jnp.arange(400, dtype=jnp.int32)
    np.testing.assert_array_equal(
        np.asarray(visited_mod.contains(hs, probe)),
        np.asarray(visited_mod.contains(ds, probe)))
    assert int(visited_mod.overflow(hs)) == 0


# ---------------------------------------------------------------------------
# traversal equivalence: hash ≡ bitmap, bit for bit
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("frozen", [False, True])
def test_disk_traverse_hash_matches_bitmap(navis, dataset, frozen):
    eng, state = navis
    spec = eng.spec
    for qi in range(3):
        q = dataset["queries"][qi]
        lut = pq_mod.adc_lut(eng.codec, q)
        entries, _ = eng._entries(state, lut)
        res = {}
        for kind in ("hash", "bitmap"):
            res[kind] = search_mod.disk_traverse(
                state.store, spec.lspec, lut, state.codes, state.cache,
                IOCounters.zeros(), entries, pool_size=spec.e_search,
                beam_width=spec.beam_width, max_hops=64,
                frozen_cache=frozen, visited=kind)
        a, b = res["hash"], res["bitmap"]
        np.testing.assert_array_equal(np.asarray(a.pool_ids),
                                      np.asarray(b.pool_ids))
        np.testing.assert_array_equal(np.asarray(a.pool_dists),
                                      np.asarray(b.pool_dists))
        assert int(a.hops) == int(b.hops)
        _counters_equal(a.counters, b.counters)
        assert int(a.counters.visited_overflow) == 0
        if frozen:
            np.testing.assert_array_equal(np.asarray(a.trace),
                                          np.asarray(b.trace))
            assert int(a.trace_n) == int(b.trace_n)


def test_position_seek_hash_matches_bitmap(navis, dataset):
    eng, state = navis
    spec = eng.spec
    v = dataset["cents"][5] + 0.02
    lut = pq_mod.adc_lut(eng.codec, v)
    entries, _ = eng._entries(state, lut)
    out = {}
    for kind in ("hash", "bitmap"):
        out[kind] = insert_mod.position_seek(
            state.store, spec.lspec, eng.codec, state.codes, state.cache,
            IOCounters.zeros(), v, entries, e_pos=spec.e_pos, k=spec.k,
            s=spec.s_pos, beam_width=spec.beam_width, max_hops=64,
            tombstone=state.tombstone, frozen_cache=True, visited=kind)
    a, b = out["hash"], out["bitmap"]
    np.testing.assert_array_equal(np.asarray(a.nbrs), np.asarray(b.nbrs))
    np.testing.assert_array_equal(np.asarray(a.pool_ids),
                                  np.asarray(b.pool_ids))
    np.testing.assert_array_equal(np.asarray(a.trace), np.asarray(b.trace))
    _counters_equal(a.counters, b.counters)


def test_search_many_hash_matches_bitmap(navis, bitmap_twin, dataset):
    """The PR1 fan-out path: identical ids/dists/counters on both visited
    implementations, run against the same shared snapshot."""
    eng, state = navis
    qs = dataset["queries"][:8]
    ids_h, d_h, stats_h, st_h = eng.search_many(state, qs)
    ids_b, d_b, stats_b, st_b = bitmap_twin.search_many(state, qs)
    np.testing.assert_array_equal(np.asarray(ids_h), np.asarray(ids_b))
    np.testing.assert_array_equal(np.asarray(d_h), np.asarray(d_b))
    _counters_equal(st_h.ctr_search, st_b.ctr_search)
    assert int(st_h.ctr_search.visited_overflow) == 0


def test_insert_many_hash_matches_bitmap(navis, bitmap_twin, dataset):
    """The PR2 fan-out path: identical wave commits and I/O accounting."""
    eng, state = navis
    newv = dataset["cents"][:6] + 0.03
    stats_h, st_h = eng.insert_many(state, newv)
    stats_b, st_b = bitmap_twin.insert_many(state, newv)
    np.testing.assert_array_equal(np.asarray(st_h.store.edges),
                                  np.asarray(st_b.store.edges))
    assert int(st_h.store.count) == int(st_b.store.count)
    for f in stats_h._fields:
        np.testing.assert_array_equal(np.asarray(getattr(stats_h, f)),
                                      np.asarray(getattr(stats_b, f)))
    _counters_equal(st_h.ctr_insert, st_b.ctr_insert)


# ---------------------------------------------------------------------------
# saturation: forced-overflow traversal stays well-formed, counter bumps
# ---------------------------------------------------------------------------

def test_traversal_saturation_counted_and_correct(navis, dataset):
    eng, state = navis
    spec = eng.spec
    q = dataset["queries"][3]
    lut = pq_mod.adc_lut(eng.codec, q)
    entries, _ = eng._entries(state, lut)

    def run(**kw):
        return search_mod.disk_traverse(
            state.store, spec.lspec, lut, state.codes, state.cache,
            IOCounters.zeros(), entries, pool_size=spec.e_search,
            beam_width=spec.beam_width, max_hops=64, **kw)

    base = run(visited="bitmap")
    sat = run(visited="hash", visited_capacity=4)   # table of 8: saturates
    assert int(sat.counters.visited_overflow) > 0
    # results stay well-formed: valid unique ids, ascending distances
    ids = np.asarray(sat.pool_ids)
    live = ids[ids >= 0]
    assert len(live) == len(set(live.tolist()))
    assert (live < int(state.store.count)).all()
    d = np.asarray(sat.pool_dists)
    d = d[np.isfinite(d) & (d < 3e38)]
    assert (np.diff(d) >= 0).all()
    # saturation only re-charges I/O — never reads less than the exact run
    # spent up to the saturation point, and re-expansions burn hops
    assert int(sat.counters.hops) >= int(base.counters.hops) or \
        int(sat.counters.hops) == 64


# ---------------------------------------------------------------------------
# entrance seed guard
# ---------------------------------------------------------------------------

def test_entrance_seed_falls_back_past_dead_slot0(navis, dataset):
    """Regression: deletes can kill entrance slot 0 (the medoid-ish seed)
    and scrub edges pointing at it; the seed must fall back to the first
    live slot instead of starting (and possibly dying) on the corpse."""
    eng, state = navis
    n_max = state.store.n_max
    # dead slot 0 with fully scrubbed edges; slots 1..3 live and wired
    ids = jnp.full((8,), -1, jnp.int32).at[1].set(1).at[2].set(2).at[3].set(3)
    edges = jnp.full((8, 4), -1, jnp.int32)
    edges = edges.at[1, :2].set(jnp.array([2, 3]))
    edges = edges.at[2, :2].set(jnp.array([1, 3]))
    edges = edges.at[3, :2].set(jnp.array([1, 2]))
    m2e = jnp.full((n_max,), -1, jnp.int32)
    m2e = m2e.at[1].set(1).at[2].set(2).at[3].set(3)
    ent = EntranceGraph(ids=ids, edges=edges,
                        count=jnp.asarray(4, jnp.int32), main_to_ent=m2e)
    q = state.store.vectors[2]
    lut = pq_mod.adc_lut(eng.codec, q)
    entries, e_ent, _ = search_mod.entrance_search(
        ent, lut, state.codes, n_entry=2, pool_size=4)
    got = np.asarray(entries)
    assert (got >= 0).any()                      # pre-fix: all -1
    assert set(got[got >= 0].tolist()) <= {1, 2, 3}


def test_delete_entrance_slot0_member_search_survives(navis, dataset):
    eng, state = navis
    vid = int(state.ent.ids[0])
    assert vid >= 0
    st2 = eng.delete(state, jnp.int32(vid))
    assert int(st2.ent.ids[0]) == -1             # slot 0 now dead
    ids, dists, _, st3 = eng.search(st2, dataset["vecs"][vid])
    got = np.asarray(ids)
    assert vid not in got.tolist()
    assert (got >= 0).any()                      # seed fell back, not empty


# ---------------------------------------------------------------------------
# per-query state accounting + kernel dispatch contract
# ---------------------------------------------------------------------------

def test_traversal_state_bytes_flat_in_corpus():
    sizes = (10_000, 100_000, 1_000_000)
    kw = dict(pool_size=100, beam_width=4, max_hops=256, frozen=True)
    hashed = [search_mod.traversal_state_bytes(
        n_max=n, p_max=2 * n, visited="hash", **kw) for n in sizes]
    dense = [search_mod.traversal_state_bytes(
        n_max=n, p_max=2 * n, visited="bitmap", **kw) for n in sizes]
    assert len(set(hashed)) == 1                 # O(1) in n_max
    assert dense[0] < dense[1] < dense[2]        # O(n_max)
    assert hashed[0] < dense[0]


@pytest.mark.parametrize("op", ["search_many", "insert_many"])
def test_engine_path_has_no_pallas_call(navis, dataset, op):
    """The engine runs the jnp ops of kernels/ref.py, the TPU's one-hot
    ADC included: no Pallas kernel (and so no interpret mode) is on the
    fan-out path."""
    eng, state = navis
    fn = getattr(eng, "_" + op)
    jaxpr = jax.make_jaxpr(fn)(state, dataset["queries"][:4])
    assert "pallas_call" not in str(jaxpr)
