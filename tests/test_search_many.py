"""Batch-parallel search fan-out: counter-sum invariants, trace replay,
and the buffered-insert overflow regression (equivalence with the
sequential scan, per preset, is in test_presets.py)."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.core import Engine, IOCounters, preset
from repro.core import cache as cache_mod
from repro.core import casr as casr_mod
from repro.core.iomodel import sum_counters


# ---------------------------------------------------------------------------
# search_many counters and cache
# ---------------------------------------------------------------------------

def _batch(dataset, n=12):
    return dataset["queries"][:n]


def test_search_many_counter_sum_invariant(navis, dataset):
    """The engine's cumulative search counters advance by exactly the sum
    of the per-query deltas the fan-out reports."""
    eng, state = navis
    qs = _batch(dataset)
    _, _, stats, state2 = eng.search_many(state, qs)
    delta = jax.tree.map(lambda a, b: a - b,
                         state2.ctr_search, state.ctr_search)
    assert int(np.asarray(stats.read_requests).sum()) == \
        int(delta.read_requests)
    assert int(np.asarray(stats.read_bytes).sum()) == \
        int(delta.total_read_bytes())
    assert int(np.asarray(stats.cache_hits).sum()) == int(delta.cache_hits)
    assert int(np.asarray(stats.cache_misses).sum()) == \
        int(delta.cache_misses)


def test_search_many_batch1_cache_identical(navis, dataset):
    """Replaying a single query's trace onto the snapshot it was recorded
    against reproduces the sequential cache state bit-for-bit (same access
    sequence, same order — including the eviction PRNG key)."""
    eng, state = navis
    q = dataset["queries"][:1]
    _, _, _, st_seq = eng.search_batch(state, q)
    _, _, _, st_par = eng.search_many(state, q)
    for leaf_a, leaf_b in zip(jax.tree.leaves(st_seq.cache),
                              jax.tree.leaves(st_par.cache)):
        np.testing.assert_array_equal(np.asarray(leaf_a),
                                      np.asarray(leaf_b))


def test_search_many_warms_shared_cache(navis, dataset):
    """Trace replay actually feeds the shared cache: a second identical
    wave sees strictly more hits than the first (cold snapshot)."""
    eng, state = navis
    qs = _batch(dataset, 8)
    _, _, stats1, state2 = eng.search_many(state, qs)
    _, _, stats2, _ = eng.search_many(state2, qs)
    h1 = int(np.asarray(stats1.cache_hits).sum())
    h2 = int(np.asarray(stats2.cache_hits).sum())
    assert h2 > h1, (h1, h2)


# ---------------------------------------------------------------------------
# cache: pure lookup + trace replay primitives
# ---------------------------------------------------------------------------

def test_lookup_is_pure_and_matches_access():
    st = cache_mod.init_cache(64, 8, "lru", jax.random.PRNGKey(0))
    _, st = cache_mod.access(st, jnp.int32(3))
    before = jax.tree.leaves(st)
    assert bool(cache_mod.lookup(st, jnp.int32(3)))
    assert not bool(cache_mod.lookup(st, jnp.int32(4)))
    for a, b in zip(before, jax.tree.leaves(st)):      # no mutation
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_apply_trace_equals_sequential_access():
    pages = [5, 9, 5, 2, 9, 5]
    st0 = cache_mod.init_cache(64, 8, "navis", jax.random.PRNGKey(1))
    st_seq, hits_seq = st0, 0
    for p in pages:
        h, st_seq = cache_mod.access(st_seq, jnp.int32(p))
        hits_seq += int(h)
    trace = jnp.asarray(pages + [-1, -1], jnp.int32)   # -1 padding skipped
    hits_rep, st_rep = cache_mod.apply_trace(st0, trace)
    assert int(hits_rep) == hits_seq
    for a, b in zip(jax.tree.leaves(st_seq), jax.tree.leaves(st_rep)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# casr_rerank_many
# ---------------------------------------------------------------------------

def test_casr_rerank_many_matches_single(navis, dataset):
    eng, state = navis
    spec = eng.spec
    qs = dataset["queries"][:4]
    # PQ-sorted pools from the frozen traversal path
    ids, dists, _, _ = eng.search_batch(state, qs)
    pools = jnp.pad(ids, ((0, 0), (0, spec.e_search - ids.shape[1])),
                    constant_values=-1)
    many = casr_mod.casr_rerank_many(state.store, spec.lspec, qs, pools,
                                     IOCounters.zeros(), k=spec.k,
                                     s=spec.s_search)
    for i in range(qs.shape[0]):
        one = casr_mod.casr_rerank(state.store, spec.lspec, qs[i],
                                   pools[i], IOCounters.zeros(),
                                   k=spec.k, s=spec.s_search)
        np.testing.assert_array_equal(np.asarray(many.topk_ids[i]),
                                      np.asarray(one.topk_ids))
        np.testing.assert_allclose(np.asarray(many.topk_d[i]),
                                   np.asarray(one.topk_d))
    total = sum_counters(many.counters)
    assert int(total.read_requests) >= qs.shape[0]     # ≥1 group each


# ---------------------------------------------------------------------------
# buffered-insert overflow regression
# ---------------------------------------------------------------------------

def test_buffered_insert_saturates_at_capacity(dataset):
    """Past buffer_max the insert is dropped: buf_count saturates instead
    of growing unbounded (which corrupted the _merge_buffer_hits validity
    mask and needs_merge), and earlier buffered vectors stay intact."""
    cap = 8
    eng = Engine(preset("freshdiskann", dim=dataset["vecs"].shape[1],
                        r=16, n_max=1300, pq_m=24, e_search=24, e_pos=32,
                        max_hops=48, buffer_max=cap,
                        buffer_frac=1.0))       # merge never auto-triggers
    state = eng.build(jax.random.PRNGKey(0), dataset["vecs"][:1200],
                      build_block=64, build_e_pos=32)
    vnew = dataset["vecs"][:cap + 5] + 0.01
    for i in range(cap + 5):
        _, state, _ = eng._insert_buffered(state, vnew[i])
    assert int(state.buf_count) == cap
    # the first cap vectors are exactly what the buffer holds
    np.testing.assert_allclose(np.asarray(state.buf_vecs),
                               np.asarray(vnew[:cap]), rtol=1e-6)
    # buffer-hit merge still sees a consistent validity mask: searching
    # for buffered vector 0 surfaces its virtual id (n_max + slot)
    ids, dists, _, _ = eng.search(state, vnew[0])
    assert int(state.store.n_max) + 0 in np.asarray(ids).tolist()
