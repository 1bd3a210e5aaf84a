"""Shared fixtures: one small clustered dataset + pre-built engines.

Engine builds are the expensive part of the suite, so the graph bundle
(codec + codes + edges) is built once and re-paged per engine config
(``Engine.build(shared=...)``).
"""
import jax
import jax.numpy as jnp
import pytest

from repro.core import Engine, preset, brute_force_topk
from repro.data import make_clustered, query_stream


N, DIM, R = 1200, 48, 16
N_EXTRA = 400            # insert headroom


@pytest.fixture(autouse=True, scope="module")
def _clear_jax_caches():
    """XLA:CPU's LLVM ORC JIT exhausts its dylib symbol space after a few
    hundred distinct compilations in one process ("Failed to materialize
    symbols"); dropping executables between modules keeps the whole suite
    in one pytest invocation (re-tracing is cheap next to the engine
    builds, which live outside the jit cache as session fixtures)."""
    yield
    jax.clear_caches()


@pytest.fixture(scope="session")
def dataset():
    key = jax.random.PRNGKey(0)
    vecs, assign, cents = make_clustered(key, N, DIM, n_clusters=12,
                                         scale=3.0, noise=1.0)
    queries = query_stream(jax.random.PRNGKey(1), cents, 40)
    truth = brute_force_topk(queries, vecs, N, 10)
    return dict(vecs=vecs, cents=cents, queries=queries, truth=truth)


def _spec(name):
    return preset(name, dim=DIM, r=R, n_max=N + N_EXTRA, e_search=40,
                  e_pos=48, pq_m=24, cache_capacity_pages=256, max_hops=64,
                  buffer_max=128)


@pytest.fixture(scope="session")
def navis(dataset):
    eng = Engine(_spec("navis"))
    state = eng.build(jax.random.PRNGKey(2), dataset["vecs"],
                      build_block=64, build_e_pos=32)
    return eng, state


@pytest.fixture(scope="session")
def shared_bundle(navis):
    eng, state = navis
    return eng.bundle(state)


@pytest.fixture(scope="session")
def odinann(dataset, shared_bundle):
    eng = Engine(_spec("odinann"))
    state = eng.build(jax.random.PRNGKey(2), dataset["vecs"],
                      shared=shared_bundle)
    return eng, state


@pytest.fixture(scope="session")
def freshdiskann(dataset, shared_bundle):
    eng = Engine(_spec("freshdiskann"))
    state = eng.build(jax.random.PRNGKey(2), dataset["vecs"],
                      shared=shared_bundle)
    return eng, state
