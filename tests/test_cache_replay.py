"""The cache-trace replay against the nested-cond reference step.

``cache_ref`` keeps the earlier form of ``cache.access`` (the policy
chosen by ``lax.cond`` over the whole state, guarded writes as
``jnp.where`` over whole tables).  The straight-line NAVIS step and the
statically chosen baselines must leave every ``CacheState`` leaf, the hit
count, and the engine's results and counters bit-identical to it.
"""
import copy
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import cache_ref as R
from repro.core import cache as C

SHAPES = [(8, 40), (8, 200), (20, 40), (20, 200)]     # (capacity, p_max)
SEEDS = [0, 1]
ROWS, T = 6, 320

_replay = jax.jit(C.apply_traces, static_argnames="policy")
_replay_one = jax.jit(C.apply_trace, static_argnames="policy")
_ref_replay = jax.jit(R.apply_traces)
_ref_replay_one = jax.jit(R.apply_trace)


def _assert_same(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        assert x.dtype == y.dtype
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def _traces(p_max: int, f: int, seed: int) -> np.ndarray:
    """``[ROWS, T]`` -1-padded traces: immediate repeats (a second window
    hit, so promotions even with a one-page window), a hot set larger
    than the frozen region (frozen touches, and evictions once it is
    full), cold pages (window admissions that evict), and one all ``-1``
    row, as an ``insert_many`` padding lane replays."""
    rng = np.random.default_rng(seed)
    hot = rng.choice(p_max, size=min(f + 6, p_max), replace=False)
    out = np.full((ROWS, T), -1, np.int32)
    for r in range(ROWS):
        if r == 2:
            continue
        n = int(rng.integers(T // 2, T - 8))
        page = int(rng.integers(p_max))
        for i in range(n):
            u = rng.random()
            if u < 0.3 and i:
                pass                                  # repeat the last page
            elif u < 0.65:
                page = int(rng.choice(hot))
            else:
                page = int(rng.integers(p_max))
            out[r, i] = page
    return out


def _steps(access):
    """Thread ``access`` through ``pages`` under one jit: (hit per access,
    state before each access, final state), the states stacked."""
    def run(st, pages):
        def step(st, p):
            hit, nxt = access(st, p)
            return nxt, (hit, st)
        st, (hits, before) = jax.lax.scan(step, st, pages)
        return hits, before, st
    return jax.jit(run)


_steps_new = _steps(lambda st, p: C.access(st, p, "navis"))
_steps_ref = _steps(R.access)


@functools.lru_cache(maxsize=None)
def _case(capacity: int, p_max: int, seed: int):
    """(initial state, traces, the accesses in order, the reference's
    hits, states before each access and final state) for one case."""
    st0 = C.init_cache(p_max, capacity, "navis", jax.random.PRNGKey(seed))
    traces = _traces(p_max, st0.frozen_pages.shape[0], seed)
    pages = jnp.asarray(traces[traces >= 0])
    return (st0, traces, pages) + _steps_ref(st0, pages)


def _at(states, i):
    return jax.tree.map(lambda x: x[i], states)


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("capacity,p_max", SHAPES)
def test_replay_matches_reference(capacity, p_max, seed):
    """``apply_traces`` over the whole batch, ``apply_trace`` row by row
    and ``access`` step by step against the reference step."""
    st0, traces, pages, hits_ref, before_ref, end_ref = _case(
        capacity, p_max, seed)
    tr = jnp.asarray(traces)
    h, st = _replay(st0, tr, "navis")
    h_ref, st_ref = _ref_replay(st0, tr)
    assert int(h) == int(h_ref) == int(hits_ref.sum())
    _assert_same(st, st_ref)
    _assert_same(st, end_ref)

    st, st_ref = st0, st0
    for row in tr:
        h, st = _replay_one(st, row, "navis")
        h_ref, st_ref = _ref_replay_one(st_ref, row)
        assert int(h) == int(h_ref)
        _assert_same(st, st_ref)

    # every step from the reference's own state before it
    def one(st, p):
        hit, st = C.access(st, p, "navis")
        return hit, st
    hits, after = jax.jit(jax.vmap(one))(before_ref, pages)
    np.testing.assert_array_equal(np.asarray(hits), np.asarray(hits_ref))
    nxt = jax.tree.map(lambda b, e: jnp.concatenate([b[1:], e[None]]),
                       before_ref, end_ref)
    _assert_same(after, nxt)
    # and threaded through all of them
    hits, before, end = _steps_new(st0, pages)
    np.testing.assert_array_equal(np.asarray(hits), np.asarray(hits_ref))
    _assert_same((before, end), (before_ref, end_ref))


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("capacity,p_max", SHAPES)
def test_replay_traces_cover_every_path(capacity, p_max, seed):
    """The traces above reach every branch of the step: window admission
    with eviction, promotion, frozen eviction from a full region, frozen
    touches, repeats within a trace, padding, and probes that find an
    empty, an occupied-and-recent and a stale slot."""
    st0, traces, pages, _, before, _ = _case(capacity, p_max, seed)
    f = st0.frozen_pages.shape[0]
    b = jax.tree.map(np.asarray, before)
    seen = set()
    for i, p in enumerate(np.asarray(pages)):
        status = int(b.status[i, p])
        if status == 0:
            if b.window_pages[i, int(np.argmin(b.window_last[i]))] >= 0:
                seen.add("window eviction")
        elif status == 2:
            seen.add("frozen touch")
        elif b.hits[i, p] + 1 >= 2:
            seen.add("promotion")
            if b.frozen_fill[i] == f:
                seen.add("frozen eviction")
            _, sub = jax.random.split(jnp.asarray(b.key[i]))
            probes = np.asarray(jax.random.randint(sub, (8,), 0, f,
                                                   dtype=jnp.int32))
            occupied = b.frozen_pages[i][probes] >= 0
            age = b.clock[i] + 1 - b.frozen_last[i][probes]
            if (~occupied).any():
                seen.add("empty probe")
            if (occupied & (age < 64)).any():
                seen.add("recent probe")
            if (occupied & (age >= 64)).any():
                seen.add("stale probe")
    for row in traces:
        valid = row[row >= 0]
        if len(set(valid.tolist())) < len(valid):
            seen.add("repeat within a trace")
        if 0 < len(valid) < T:
            seen.add("padding")
        if len(valid) == 0:
            seen.add("all -1 row")
    assert seen == {"window eviction", "frozen touch", "promotion",
                    "frozen eviction", "empty probe", "recent probe",
                    "stale probe", "repeat within a trace", "padding",
                    "all -1 row"}


@pytest.mark.parametrize("capacity,p_max", SHAPES)
def test_install_frozen_matches_reference(capacity, p_max):
    """``priority_admit``'s install against the reference's, on states
    met along the traces (every page that is not frozen)."""
    _, _, pages, _, before, _ = _case(capacity, p_max, 0)
    frozen = np.asarray(before.status)[np.arange(len(pages)),
                                       np.asarray(pages)] == 2
    keep = np.flatnonzero(~frozen)
    states, pages = _at(before, keep), pages[keep]
    install = jax.jit(jax.vmap(C._install_frozen))
    ref = jax.jit(jax.vmap(R._install_frozen))
    _assert_same(install(states, pages), ref(states, pages))


@pytest.mark.parametrize("policy", sorted(C.POLICIES))
def test_replay_matches_reference_every_policy(policy):
    """The baselines keep their branches, now picked while tracing."""
    st0 = C.init_cache(40, 8, policy, jax.random.PRNGKey(3))
    tr = jnp.asarray(_traces(40, 8, 3))
    h, st = _replay(st0, tr, policy)
    h_ref, st_ref = _ref_replay(st0, tr)
    assert int(h) == int(h_ref)
    _assert_same(st, st_ref)


def _eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for p in eqn.params.values():
            for sub in (p if isinstance(p, (list, tuple)) else [p]):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _eqns(inner)


def _innermost_whiles(jaxpr):
    out = []
    for eqn in _eqns(jaxpr):
        if eqn.primitive.name == "while":
            body = eqn.params["body_jaxpr"].jaxpr
            if not any(e.primitive.name == "while" for e in _eqns(body)):
                out.append(body)
    return out


def test_navis_replay_loop_has_no_table_wide_op():
    """Inside the replay's innermost loop no ``cond`` carries a
    ``[p_max]`` table, and no select, ``where`` or copy makes one: each
    access updates single elements, in place."""
    p_max = 173
    st0 = C.init_cache(p_max, 20, "navis", jax.random.PRNGKey(0))
    jaxpr = jax.make_jaxpr(
        lambda st, tr: C.apply_traces(st, tr, "navis"))(
            st0, jnp.full((4, 16), -1, jnp.int32)).jaxpr
    bodies = _innermost_whiles(jaxpr)
    assert len(bodies) == 1
    wide = []
    for eqn in _eqns(bodies[0]):
        name = eqn.primitive.name
        ins = [tuple(v.aval.shape) for v in eqn.invars if hasattr(v, "aval")]
        outs = [tuple(v.aval.shape) for v in eqn.outvars]
        if name == "cond" and (p_max,) in ins:
            wide.append(name)
        if name in ("select_n", "copy", "copy_p") and (p_max,) in outs:
            wide.append(name)
    assert not wide, wide


# ---------------------------------------------------------------------------
# the engine: search and insert waves against the reference replay
# ---------------------------------------------------------------------------

def _with_reference(eng, monkeypatch):
    """A copy of ``eng`` whose programs trace with the reference step
    (fresh jits on a new instance, so nothing is reused)."""
    monkeypatch.setattr(C, "apply_traces",
                        lambda st, traces, policy: R.apply_traces(st, traces))
    monkeypatch.setattr(C, "access",
                        lambda st, page, policy: R.access(st, page))
    twin = copy.copy(eng)
    twin._jit_ops()
    return twin


def test_engine_waves_match_reference_replay(navis, dataset, monkeypatch):
    """Over the same waves, ``search_many`` / ``insert_many`` and the
    sequential ``search_batch`` / ``insert_batch`` return the same ids,
    distances, stats and state — cache and I/O counters included — as
    with the reference step."""
    eng, state = navis
    qs = dataset["queries"]
    newv = dataset["cents"][:8] + 0.03
    waves = []
    for e in (eng, None):
        if e is None:
            e = _with_reference(eng, monkeypatch)
        st, out = state, []
        for w in range(3):
            ids, d, stats, st = e.search_many(st, qs[8 * w:8 * w + 8])
            out.append((ids, d, stats))
            stats, st = e.insert_many(st, newv[2 * w:2 * w + 2] + 0.01 * w)
            out.append(stats)
        ids, d, stats, st = e.search_batch(st, qs[24:28])
        out.append((ids, d, stats))
        stats, st = e.insert_batch(st, newv[6:8])
        out.append(stats)
        waves.append((out, st))
        monkeypatch.undo()
    (out, st), (out_ref, st_ref) = waves
    _assert_same(out, out_ref)
    _assert_same(st, st_ref)
    assert int(st.ctr_search.cache_hits) > 0
