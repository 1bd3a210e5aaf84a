"""``disk_traverse`` against a plain NumPy beam search.

The traversal keeps no set of expanded ids: each pool slot carries an
"expanded" flag that the merge moves with it.  The reference here keeps
the exact Python set of expanded ids instead (Vamana's pool as a set,
expansion permanent, stable P-smallest merge, beam by stable top-W among
the unexpanded), and both must give the same pool, distances and hops.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import pq as pq_mod
from repro.core import search as search_mod
from repro.core.iomodel import IOCounters
from repro.kernels import ref as kernel_ref

INF = np.float32(3.4e38)


def reference_traverse(edges, dist, entry_ids, *, pool_size, beam_width,
                       max_hops):
    """Beam search over ``edges`` [n, R] with vertex distances ``dist`` [n].

    Returns (pool ids, pool distances, hops)."""
    r = edges.shape[1]
    e = np.asarray(entry_ids)
    e_d = np.where(e >= 0, dist[np.maximum(e, 0)], INF).astype(np.float32)
    order = np.argsort(e_d, kind="stable")
    k = min(len(e), pool_size)
    ids = np.full(pool_size, -1, np.int32)
    d = np.full(pool_size, INF, np.float32)
    ids[:k] = np.where(e[order][:k] >= 0, e[order][:k], -1)
    d[:k] = e_d[order][:k]
    expanded = set()
    hops = 0
    while hops < max_hops:
        unexp = np.array([i >= 0 and i not in expanded for i in ids])
        if not unexp.any():
            break
        cand = np.where(unexp, d, INF)
        sel = np.argsort(cand, kind="stable")[:beam_width]
        beam = [int(ids[s]) for s in sel if cand[s] < INF]
        expanded.update(beam)
        rows = [edges[v] for v in beam]
        rows += [np.full(r, -1)] * (beam_width - len(beam))
        in_pool, taken = set(ids.tolist()), set()
        new_ids, new_d = [], []
        for n in np.concatenate(rows).tolist():
            ok = (n >= 0 and n not in expanded and n not in in_pool
                  and n not in taken)
            if ok:
                taken.add(n)
            new_ids.append(n if ok else -1)
            new_d.append(dist[n] if ok else INF)
        all_d = np.concatenate([d, np.asarray(new_d, np.float32)])
        all_i = np.concatenate([ids, np.asarray(new_ids, np.int32)])
        keep = np.argsort(all_d, kind="stable")[:pool_size]
        d, ids = all_d[keep], all_i[keep]
        hops += 1
    return ids, d, hops


def _entries(eng, state, lut, dup):
    entries, _ = eng._entries(state, lut)
    entries = np.asarray(entries)
    if dup:
        entries = entries.copy()
        entries[1] = entries[0]                   # the same id twice
    return jnp.asarray(entries)


# (pool_size, max_hops): a pool that fills and converges well before the
# hop cap; the engine's pool; a pool wider than all a run can discover,
# so it never fills and the cap stops it
POOLS = [(8, 64), (40, 64), (1600, 10)]


@pytest.mark.parametrize("pool_size,max_hops", POOLS)
@pytest.mark.parametrize("frozen", [False, True])
@pytest.mark.parametrize("dup", [False, True])
@pytest.mark.parametrize("beam_width", [4, 1])
def test_disk_traverse_matches_numpy_reference(navis, dataset, pool_size,
                                               max_hops, frozen, dup,
                                               beam_width):
    """At beam width 1 the two copies of a repeated entry id are expanded
    on different hops unless the flag is set by id, not by slot."""
    eng, state = navis
    spec = eng.spec
    edges = np.asarray(state.store.edges)
    filled = 0
    for qi in range(3):
        q = dataset["queries"][qi]
        lut = pq_mod.adc_lut(eng.codec, q)
        dist = np.asarray(kernel_ref.adc_distance(lut, state.codes))
        entries = _entries(eng, state, lut, dup)
        assert int(entries[0]) >= 0
        res = search_mod.disk_traverse(
            state.store, spec.lspec, lut, state.codes, state.cache,
            IOCounters.zeros(), entries, pool_size=pool_size,
            beam_width=beam_width, max_hops=max_hops,
            frozen_cache=frozen)
        want_i, want_d, want_hops = reference_traverse(
            edges, dist, np.asarray(entries), pool_size=pool_size,
            beam_width=beam_width, max_hops=max_hops)
        np.testing.assert_array_equal(np.asarray(res.pool_ids), want_i)
        np.testing.assert_array_equal(np.asarray(res.pool_dists), want_d)
        assert int(res.hops) == want_hops
        assert int(res.counters.hops) == want_hops
        filled += int((want_i >= 0).all())
    if pool_size == 1600:
        assert filled == 0 and want_hops == max_hops
    if pool_size == 8:
        assert filled == 3 and want_hops < max_hops


def _whiles(jaxpr, depth=0):
    """(depth, carried shapes) of every while loop in ``jaxpr``, nested
    ones included."""
    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "while":
            out.append((depth, [tuple(v.aval.shape) for v in eqn.outvars]))
        for p in eqn.params.values():
            subs = p if isinstance(p, (list, tuple)) else [p]
            for sub in subs:
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    out += _whiles(inner, depth + (
                        eqn.primitive.name == "while"))
    return out


@pytest.mark.parametrize("frozen", [False, True])
def test_traversal_loop_probes_no_pool_wide_set(navis, dataset, frozen):
    """Inside the traversal's loop, no nested loop walks the pool or the
    hop's neighbour list: what remains are the page buffer's probes over
    the beam's ``beam_width`` pages (and the cache's own loops)."""
    import jax

    eng, state = navis
    spec = eng.spec
    pool, w, r = 40, spec.beam_width, state.store.edges.shape[1]
    lut = pq_mod.adc_lut(eng.codec, dataset["queries"][0])
    entries, _ = eng._entries(state, lut)

    def run(lut, entries):
        return search_mod.disk_traverse(
            state.store, spec.lspec, lut, state.codes, state.cache,
            IOCounters.zeros(), entries, pool_size=pool, beam_width=w,
            max_hops=64, frozen_cache=frozen)

    found = _whiles(jax.make_jaxpr(run)(lut, entries).jaxpr)
    outer = [s for dep, s in found if dep == 0 and (pool,) in s]
    assert len(outer) == 1
    nested = [s for dep, s in found if dep >= 1]
    assert all((pool,) not in s and (w * r,) not in s for s in nested), \
        nested
