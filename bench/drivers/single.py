"""The one-chip driver: one ``Engine`` and one ``EngineState`` on the
default device, driven through the engine's own ``search_many`` and
``insert_many`` (traced as ``_search_many`` / ``_insert_many``).  The
engine numbers results as the harness does, so ids pass through as they
are.  The contract is in ``__init__.py``."""
from __future__ import annotations

import dataclasses
import time


def engine_spec(cfg: dict):
    from repro.core import preset

    eng = dict(cfg["engine"])
    name = eng.pop("preset")
    return preset(name, dim=cfg["dim"],
                  n_max=cfg["n_base"] + cfg["headroom"], **eng)


def open(name: str, cfg: dict, base, *, chips: int, cache, root):
    """(engine, state, build seconds or None): the saved index, or one
    built now with ``Engine.build`` and saved for later runs."""
    import jax

    from bench import index_cache
    from bench.run import log
    from repro.core import Engine

    if chips != 1:
        raise ValueError(f"the single driver runs on one chip, not {chips}")
    engine = Engine(engine_spec(cfg))
    t = time.perf_counter()
    tree = index_cache.restore(name, base, cache=cache, root=root)
    if tree is not None:
        engine.install_codec(tree["codec"])
        state = jax.block_until_ready(tree["state"])
        log("index_restore_s", time.perf_counter() - t)
        return engine, state, None
    b = cfg["build"]
    t = time.perf_counter()
    state = jax.block_until_ready(engine.build(
        jax.random.PRNGKey(cfg["data_seed"]), base,
        build_block=b["build_block"], build_e_pos=b["build_e_pos"],
        alpha=b["alpha"]))
    build_s = time.perf_counter() - t
    log("build_s", build_s)
    log("build_vectors_per_s", cfg["n_base"] / build_s)
    index_cache.save(name, {"codec": engine.codec, "state": state}, base,
                     cache=cache, root=root)
    log("index_save_s", time.perf_counter() - t - build_s)
    return engine, state, build_s


def insert(engine, state, vectors):
    stats, state = engine.insert_many(state, vectors)
    return stats.dropped, state


def search(engine, state, queries):
    ids, dists, stats, state = engine.search_many(state, queries)
    return ids, dists, stats.serial_rounds, state


def count(engine, state) -> int:
    return int(state.store.count)


def _ints(ctr) -> dict:
    return {f.name: int(getattr(ctr, f.name))
            for f in dataclasses.fields(ctr)}


def counters(engine, state) -> dict:
    return {"search": _ints(state.ctr_search),
            "insert": _ints(state.ctr_insert)}


def compiled(engine) -> int:
    return (engine.search_many._cache_size()
            + engine.insert_many._cache_size())


def k(engine) -> int:
    return engine.spec.k
