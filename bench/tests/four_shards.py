"""A test-only driver: a deployment range-sharded over ``chips`` devices,
one ``EngineState`` on each.  The harness's four-device probe
(``test_bench_harness.py``) copies it into a tiny checkout's
``bench/drivers/``; no configuration of the benchmark names it.

Shard ``s`` is built from base rows ``[s * n_per, (s + 1) * n_per)``,
with one PQ codec trained on the whole base so that the shards' distances
compare, and is then put on device ``s`` (built on the default device,
so that the build compiles once).  Row ``i`` of an insert wave
goes to shard ``i % chips``.  Beside each shard the state keeps ``gid``,
the harness's id of every local id, and ``acked``, the inserts
acknowledged so far; a search wave runs on every shard, and the host maps
each shard's ids through ``gid`` and keeps the ``k`` nearest.
"""
from __future__ import annotations

import time
from typing import NamedTuple

import jax
import numpy as np

from bench import index_cache
from bench.drivers import single


class Handle(NamedTuple):
    engine: object           # one Engine: one spec, one codec, every shard
    devices: list
    n_base: int


def open(name: str, cfg: dict, base, *, chips: int, cache, root):
    from repro.core import Engine
    from repro.core.pq import train_pq

    devices = jax.devices()[:chips]
    n_per = cfg["n_base"] // chips
    engine = Engine(single.engine_spec(
        {**cfg, "n_base": n_per, "headroom": cfg["headroom"] // chips}))
    handle = Handle(engine, devices, cfg["n_base"])

    def place(tree):
        shards = [jax.device_put(s, d)
                  for s, d in zip(tree["state"]["shards"], devices)]
        return {**tree, "state": {**tree["state"], "shards": shards}}

    tree = index_cache.restore(name, base, place=place, cache=cache,
                               root=root)
    if tree is not None:
        engine.install_codec(tree["codec"])
        return handle, tree["state"], None
    t = time.perf_counter()
    key = jax.random.PRNGKey(cfg["data_seed"])
    engine.install_codec(train_pq(key, base, engine.spec.pq_m))
    b = cfg["build"]
    shards = [engine.build(jax.random.fold_in(key, s),
                           base[s * n_per:(s + 1) * n_per],
                           build_block=b["build_block"],
                           build_e_pos=b["build_e_pos"], alpha=b["alpha"])
              for s in range(chips)]
    local = np.arange(engine.spec.n_max, dtype=np.int32)
    gid = [np.where(local < n_per, s * n_per + local, -1).astype(np.int32)
           for s in range(chips)]
    tree = {"codec": engine.codec,
            "state": {"shards": shards, "gid": gid, "acked": np.int32(0)}}
    index_cache.save(name, tree, base, cache=cache, root=root)
    state = jax.block_until_ready(place(tree)["state"])
    return handle, state, time.perf_counter() - t


def insert(h: Handle, state, vectors):
    n, n_shards = vectors.shape[0], len(h.devices)
    outs = []
    for s, d in enumerate(h.devices):
        rows = np.arange(s, n, n_shards)
        stats, new = h.engine.insert_many(
            state["shards"][s], jax.device_put(vectors[rows], d))
        outs.append((rows, stats.dropped, new))
    dropped = np.zeros(n, bool)
    for rows, drop, _ in outs:
        dropped[rows] = np.asarray(drop)
    # the harness's id of each acknowledged row of the wave
    number = h.n_base + int(state["acked"]) + np.cumsum(~dropped) - 1
    gid = []
    for s, (rows, _, _) in enumerate(outs):
        kept = rows[~dropped[rows]]
        g = np.array(state["gid"][s])
        first = int(state["shards"][s].store.count)
        g[first:first + len(kept)] = number[kept]
        gid.append(g)
    return dropped, {"shards": [new for _, _, new in outs], "gid": gid,
                     "acked": np.int32(int(state["acked"])
                                       + int((~dropped).sum()))}


def search(h: Handle, state, queries):
    ids, dists, rounds, shards = [], [], [], []
    for s, d in enumerate(h.devices):
        i, dist, stats, new = h.engine.search_many(
            state["shards"][s], jax.device_put(queries, d))
        g, i = np.asarray(state["gid"][s]), np.asarray(i)
        ids.append(np.where(i >= 0, g[np.maximum(i, 0)], -1))
        dists.append(np.asarray(dist))
        rounds.append(np.asarray(stats.serial_rounds))
        shards.append(new)
    ids, dists = np.concatenate(ids, 1), np.concatenate(dists, 1)
    best = np.argsort(dists, axis=1, kind="stable")[:, :k(h)]
    return (np.take_along_axis(ids, best, 1),
            np.take_along_axis(dists, best, 1), np.max(rounds, 0),
            {**state, "shards": shards})


def count(h: Handle, state) -> int:
    return sum(int(s.store.count) for s in state["shards"])


def counters(h: Handle, state) -> dict:
    per = [single.counters(h.engine, s) for s in state["shards"]]
    return {side: {f: sum(c[side][f] for c in per) for f in per[0][side]}
            for side in per[0]}


def compiled(h: Handle) -> int:
    return single.compiled(h.engine)


def k(h: Handle) -> int:
    return h.engine.spec.k
