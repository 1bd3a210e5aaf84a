"""The 768-d text deployment (``repro.deploy.TEXT768``) on the CPU, small.

Its widths and search settings (768-d f32, r 48, pq_m 96, the wide pool
and CASR group) over about 2,000 vectors of the same clustered corpus:
``search_many`` waves against the benchmark's plain reference
(``bench/reference.py``), an ``insert_many`` wave read back, and the
benchmark harness's window end to end on a tiny checkout of this shape.
"""
import json
import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from bench import reference, run  # noqa: E402
from bench.tests.tiny import make_checkout  # noqa: E402
from repro import deploy  # noqa: E402
from repro.core import Engine, preset  # noqa: E402
from repro.data import insert_stream, query_stream  # noqa: E402

DEP = deploy.TEXT768
N, HEADROOM, WAVE = 2_000, 64, 64


@pytest.fixture(scope="module")
def built():
    """(engine, state, base vectors, centres) at the deployment's widths
    and settings, cut to N vectors."""
    # the entrance samples ent_frac of the corpus: the deployment's 1% of
    # 100,000 puts about 40 members in each of the 24 clusters, 1% of
    # 2,000 leaves clusters with none, whose queries then start in another
    # cluster and miss every neighbour; the deployment's pool of 2,800 is
    # wider than the whole cut corpus, so the pool is cut with it
    spec = preset("navis", dim=DEP.dim, n_max=N + HEADROOM,
                  **{**DEP.settings, "cache_capacity_pages": 32,
                     "ent_frac": 0.1, "e_search": 800, "s_search": 200,
                     "max_hops": 480})
    base, cents = DEP.corpus(jax.random.PRNGKey(0), N)
    eng = Engine(spec)
    state = eng.build(jax.random.PRNGKey(1), base)
    return eng, state, base, cents


def _check_wave(ids, dists, q, corpus, live, k):
    ids, dists = np.asarray(ids), np.asarray(dists)
    assert ((ids >= 0) & (ids < live)).all()
    assert all(len(set(r)) == k for r in ids)
    exact = np.asarray(reference.dist_of(q, corpus, ids))
    np.testing.assert_allclose(dists, exact, rtol=1e-4)
    want = np.asarray(reference.topk(q, corpus, live, k=k)[0])
    return (want[:, :, None] == ids[:, None, :]).any(-1).mean()


def test_search_many_meets_recall_against_the_reference(built):
    eng, state, base, cents = built
    q = query_stream(jax.random.PRNGKey(2), cents, WAVE, noise=DEP.noise)
    ids, dists, _, _ = eng.search_many(state, q)
    recall = _check_wave(ids, dists, q, base, N, eng.spec.k)
    assert recall >= 0.90, recall


def test_acknowledged_inserts_are_read_back(built):
    eng, state, base, cents = built
    v = insert_stream(jax.random.PRNGKey(3), cents, 16, noise=DEP.noise,
                      drift=0.3)
    stats, state = eng.insert_many(state, v)
    assert not np.asarray(stats.dropped).any()
    assert int(state.store.count) == N + 16
    # a copy of each insert finds it first; the wave searches the grown
    # corpus against the reference too
    corpus = jnp.concatenate([base, v])
    q = jnp.concatenate([v, v, v, v])
    ids, dists, _, _ = eng.search_many(state, q)
    assert (np.asarray(ids)[:16, 0] == N + np.arange(16)).all()
    recall = _check_wave(ids, dists, q, corpus, N + 16, eng.spec.k)
    assert recall >= 0.90, recall


def test_harness_window_is_correct_at_text_widths(tmp_path):
    """The benchmark's run, end to end, on a tiny checkout of the text
    deployment's shape: the first run builds and saves, the second
    restores; both are correct."""
    root = make_checkout(tmp_path)
    cfg_path = root / "bench" / "configs" / "tiny.json"
    cfg = json.loads(cfg_path.read_text())
    cfg.update(dim=DEP.dim, n_base=600, headroom=128, noise=DEP.noise,
               n_clusters=deploy.N_CLUSTERS)
    cfg["engine"].update(r=48, pq_m=96, e_pos=64, e_search=256,
                         s_search=64, max_hops=128)
    cfg["build"]["build_e_pos"] = 64
    cfg_path.write_text(json.dumps(cfg))
    for seed in (21, 22):
        res = run.run("tiny.mixed", seed, 1e-3, False, kind="TPU v5 lite",
                      root=root)
        assert res["correct"] is True, res["compared"]
        assert res["failed"] == 0


def test_build_keeps_edge_pages_in_range(built):
    """At r 48 an insert moves 49 edgelists onto 3 fresh pages, so
    building N vertices into N + HEADROOM slots would run the page
    allocator past its 2 (N + HEADROOM) ids; the build re-packs the
    edgelists instead, and every live vertex keeps a page in range."""
    eng, state, _, _ = built
    store = state.store
    assert eng.spec.lspec.pages_per_insert == 3
    p_max = store.page_live.shape[0]
    pages = np.asarray(store.edge_page)[:N]
    assert ((pages >= 0) & (pages < p_max)).all()
    assert int(store.next_page) <= p_max
    assert int(np.asarray(store.page_live).sum()) == N
