"""The NAVIS stages keep their names in the compiled programs.

The engine wraps each stage in a ``jax.named_scope`` (``navis.*``); the
benchmark's trace reduction (``bench/scopes.py``) finds a stage's device
time by that name in each op's ``op_name``.  Each program is compiled
here on the CPU from shapes, at a tiny width, and every scope the
reduction reads must appear in its optimized HLO's metadata: a refactor
that drops one would silence its metric.
"""
import re

import jax
import jax.numpy as jnp
import pytest

from repro.core import Engine, preset
from repro.core import distributed as dist
from repro.core import pq as pq_mod

DIM, N_MAX = 16, 512
HOP = ["navis.fetch", "navis.score", "navis.merge"]
SEARCH = ["navis.entrance", "navis.traverse", *HOP, "navis.rerank",
          "navis.adc"]
SCOPES = {
    "search_many": SEARCH + ["navis.cache_replay"],
    "insert_many": ["navis.encode", "navis.seek", *SEARCH, "navis.select",
                    "navis.cache_replay", "navis.commit", "navis.link",
                    "navis.entrance_update", "navis.sym"],
    # the sequential path runs the same shared stages
    "search_batch": SEARCH,
}
WAVE = {"search_many": 8, "insert_many": 4, "search_batch": 2}


@pytest.fixture(scope="module")
def scope_paths():
    """program -> the ``navis.*`` scope paths of its compiled ops, each
    outermost first (``navis.traverse/navis.score/navis.adc``)."""
    eng = Engine(preset("navis", dim=DIM, n_max=N_MAX, r=8, pq_m=4,
                        e_search=16, e_pos=16, max_hops=16, r_ent=8,
                        ent_pool=16, cache_capacity_pages=8))
    key = jax.random.PRNGKey(0)
    eng.install_codec(pq_mod.train_pq(
        key, jax.random.normal(key, (256, DIM)), eng.spec.pq_m))
    st = jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape[1:], s.dtype),
                      dist.state_shapes(eng, 1, N_MAX))
    out = {}
    for name, n in WAVE.items():
        hlo = getattr(eng, name).lower(
            st, jax.ShapeDtypeStruct((n, DIM), jnp.float32)).compile()
        out[name] = {"/".join(re.findall(r"navis\.\w+", op.split(";")[0]))
                     for op in re.findall(r'op_name="([^"]*)"',
                                          hlo.as_text())}
    return out


@pytest.fixture(scope="module")
def op_names(scope_paths):
    """program -> the ``navis.*`` scope names of its compiled ops."""
    return {p: {s for path in paths for s in path.split("/") if s}
            for p, paths in scope_paths.items()}


@pytest.mark.parametrize("program,scope", [
    (p, s) for p, scopes in SCOPES.items() for s in scopes])
def test_scope_in_compiled_program(op_names, program, scope):
    assert scope in op_names[program], sorted(op_names[program])


# the leaf scopes of the ADC and of the symmetric-PQ lookups, nested under
# the stage that calls them: their readers sum every path that ends in
# the leaf, and the stage readers still count the leaf's ops
@pytest.mark.parametrize("program,outer,leaf", [
    ("search_many", "navis.traverse", "navis.adc"),
    ("search_many", "navis.entrance", "navis.adc"),
    ("insert_many", "navis.seek", "navis.adc"),
    ("insert_many", "navis.commit", "navis.sym"),
])
def test_leaf_scope_nested_under_its_stage(scope_paths, program, outer,
                                           leaf):
    nested = [p for p in scope_paths[program]
              if p.startswith(outer + "/") and p.endswith("/" + leaf)]
    assert nested, sorted(scope_paths[program])
