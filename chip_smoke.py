"""Drive the NAVIS engine once on TPU at a DEEP1M-shaped deployment.

One chip (the default) runs the engine's main path through its public
entry points, each phase checked by the repository's own references:

  build         ``Engine.build`` over the corpus (made on the device)
  search        one ``search_many`` wave: recall@10 against
                ``brute_force_topk``, and its first queries' ids identical
                to ``search_batch``
  insert        one ``insert_many`` wave: every inserted vector is its own
                top-1 in a following ``search_many``
  delete        ``delete_many`` (one query's top hit among the ids), then a
                tombstone probe: no deleted id is returned
  maintenance   a few ``maintenance_step`` repair blocks: no live row they
                swept still points at a deleted vertex

``--four-chips`` runs only the sharded path and what it is compared with:
the corpus range-sharded over a (4,) mesh, each shard built on its own
chip, searched with ``make_sharded_search`` (recall against brute force
over the union; top-k identical to the merge of every shard's own
single-device ``search_many``) and written with ``make_sharded_insert``.

Earlier lines print measurements as ``name=value``; the last line is one
JSON object ``{"ok": true, "device": {"platform", "kind", "count"}}``.
The script exits non-zero, printing no result, when JAX finds no TPU or
any check fails.

    python chip_smoke.py                      # one chip, N_ONE_CHIP vectors
    python chip_smoke.py --n 1000000          # the full deployment size
    python chip_smoke.py --four-chips         # N_PER_SHARD vectors per shard
"""
from __future__ import annotations

import argparse
import json
import pathlib
import statistics
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))
from repro.deploy import DEEP96, N_BASE  # noqa: E402
from repro.compile_cache import enable_compile_cache  # noqa: E402
from repro.core import Engine, brute_force_topk, recall_at_k  # noqa: E402
from repro.core import distributed as dist  # noqa: E402
from repro.data import insert_stream, query_stream  # noqa: E402

WAVE_SEARCH = 64          # queries per search_many wave
WAVE_INSERT = 16          # vectors per insert_many wave
N_DELETE = 64
N_IDENTICAL = 8           # leading queries compared with search_batch
REPAIR_STEPS = 3
RECALL_FLOOR = 0.90
SHARDS = 4
# The deployment holds N_BASE = 1M base vectors; a run builds
# fewer.  Engine.build runs at about 133 vectors/s on one TPU v5e (its
# insertion and refinement passes wire one vertex at a time), so 1M would
# take about 2 h against a run's 1200 s; 20k builds in about 150 s.
# --four-chips gives every shard the one-chip size: the four shards build
# at once, one per chip.
N_ONE_CHIP = N_PER_SHARD = 20_000
CUT_REASON = ("Engine.build runs at ~133 vectors/s on one v5e, so "
              "1,000,000 vectors would not build within a 1200 s run")


class CheckFailed(Exception):
    """A phase produced a wrong result."""


def check(ok, what: str):
    if not bool(ok):
        raise CheckFailed(what)


def log(name: str, value):
    print(f"{name}={value}", flush=True)


def _compile(name: str, jitted, *args):
    t = time.perf_counter()
    compiled = jitted.lower(*args).compile()
    log(f"compile_s[{name}]", time.perf_counter() - t)
    return compiled


def _steady(name: str, fn, *args, reps: int = 5):
    """Run ``fn`` once to warm, then ``reps`` times; log the median wall
    time of a call that ends in ``block_until_ready``."""
    out = jax.block_until_ready(fn(*args))
    times = []
    for _ in range(reps):
        t = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append(time.perf_counter() - t)
    log(f"wave_s[{name}]", statistics.median(times))
    log(f"wave_s_all[{name}]", times)
    return out


def _corpus(seed: int, n: int, n_inserts: int):
    k_data, k_q, k_ins, k_build = jax.random.split(jax.random.PRNGKey(seed),
                                                   4)
    t = time.perf_counter()
    vecs, cents = DEEP96.corpus(k_data, n)
    queries = query_stream(k_q, cents, WAVE_SEARCH, noise=DEEP96.noise)
    inserts = insert_stream(k_ins, cents, n_inserts, noise=DEEP96.noise)
    jax.block_until_ready((vecs, queries, inserts))
    log("corpus_s", time.perf_counter() - t)
    return vecs, queries, inserts, k_build


def _spec(n: int):
    spec = DEEP96.spec(n)
    log("spec", {f: getattr(spec, f) for f in (
        "dim", "r", "pq_m", "n_max", "e_search", "e_pos", "max_hops",
        "s_search", "r_ent", "ent_pool", "beam_width", "k")})
    return spec


def one_chip(n: int, seed: int):
    """The one-chip main path; raises CheckFailed on a wrong result."""
    log("n_base", n)
    if n < N_BASE:
        log("n_cut", f"from {N_BASE}: {CUT_REASON}")
    vecs, queries, inserts, k_build = _corpus(seed, n, WAVE_INSERT)
    spec = _spec(n)
    eng = Engine(spec)

    # -- build ---------------------------------------------------------
    t = time.perf_counter()
    state = jax.block_until_ready(
        eng.build(k_build, vecs))
    build_s = time.perf_counter() - t
    log("build_s", build_s)
    log("build_vectors_per_s", n / build_s)

    # -- search --------------------------------------------------------
    search = _compile("search_many", eng.search_many, state, queries)
    ids, dists, _, _ = _steady("search_many", search, state, queries)
    truth = brute_force_topk(queries, vecs, n, spec.k)
    recall = float(recall_at_k(ids, truth))
    log("recall_at_10", recall)
    check(recall >= RECALL_FLOOR, f"recall@10 {recall} < {RECALL_FLOOR}")
    head = queries[:N_IDENTICAL]
    batch = _compile("search_batch", eng.search_batch, state, head)
    ids_seq = np.asarray(batch(state, head)[0])
    check((ids_seq == np.asarray(ids[:N_IDENTICAL])).all(),
          "search_many ids differ from search_batch")

    # -- insert --------------------------------------------------------
    insert = _compile("insert_many", eng.insert_many, state, inserts)
    stats, state = _steady("insert_many", insert, state, inserts)
    check(not bool(stats.dropped.any()), "an insert was dropped")
    check(int(state.store.count) == n + WAVE_INSERT, "count did not advance")
    probe = jnp.concatenate([inserts, queries[WAVE_INSERT:]])
    top1 = np.asarray(search(state, probe)[0][:WAVE_INSERT, 0])
    want = n + np.arange(WAVE_INSERT)
    log("inserts_found_top1", int((top1 == want).sum()))
    check((top1 == want).all(), f"inserted ids {want} came back as {top1}")

    # -- delete + tombstone probe --------------------------------------
    # one query's top hit, then vertices the first repair blocks' rows
    # point at, so the maintenance phase has dead edges to splice
    rows = REPAIR_STEPS * spec.maint_block
    victims = [int(ids[0, 0])]
    for v in np.asarray(state.store.edges[:rows]).ravel():
        if len(victims) == N_DELETE:
            break
        if v >= 0 and int(v) not in victims:
            victims.append(int(v))
    vids = jnp.asarray(victims, jnp.int32)
    delete = _compile("delete_many", eng.delete_many, state, vids)
    state = jax.block_until_ready(delete(state, vids))
    check(int(state.n_deleted) == N_DELETE, "delete count")
    after = np.asarray(search(state, queries)[0])
    check(not np.isin(after, victims).any(), "a deleted id was returned")

    # -- maintenance repair blocks -------------------------------------
    def dead_refs(st):
        e = st.store.edges[:rows]
        live_row = ~st.tombstone[:rows]
        return int(((e >= 0) & st.tombstone[jnp.maximum(e, 0)] &
                    live_row[:, None]).sum())

    log("dead_refs_before_repair", dead_refs(state))
    for i in range(REPAIR_STEPS):
        t = time.perf_counter()
        state, _ = eng.maintenance_step(state)
        jax.block_until_ready(state)
        log(f"maintenance_step_s[{i}]", time.perf_counter() - t)
    left = dead_refs(state)
    log("dead_refs_after_repair", left)
    check(left == 0, f"{left} swept edges still point at deleted vertices")
    after = np.asarray(search(state, queries)[0])
    check(not np.isin(after, victims).any(),
          "a deleted id was returned after repair")

    stats = jax.devices()[0].memory_stats() or {}
    log("peak_bytes_in_use", stats.get("peak_bytes_in_use", "not reported"))


def four_chips(n_per: int, seed: int):
    """The sharded path over SHARDS chips; raises CheckFailed."""
    devices = jax.devices()
    check(len(devices) >= SHARDS, f"{len(devices)} devices < {SHARDS}")
    mesh = dist.make_mesh((SHARDS,), ("shard",))
    n = SHARDS * n_per
    log("n_base", n)
    log("n_per_shard", n_per)
    if n_per < N_BASE:
        log("n_cut", f"per shard, from {N_BASE}: {CUT_REASON}")
    vecs, queries, inserts, k_build = _corpus(seed, n, SHARDS * WAVE_INSERT)
    spec = _spec(n_per)
    eng = Engine(spec)

    t = time.perf_counter()
    sstate = jax.block_until_ready(
        dist.build_sharded_state(eng, k_build, vecs, mesh))
    log("build_s", time.perf_counter() - t)
    log("shard_devices", [str(s.device) for s in
                          sstate.store.vectors.addressable_shards])

    q_all = jax.device_put(queries, NamedSharding(mesh, P()))
    search_fn = dist.make_sharded_search(eng, mesh, n_per=n_per,
                                         n_queries=WAVE_SEARCH)
    search = _compile("sharded_search", search_fn, sstate, q_all)
    gids, gd, _ = _steady("sharded_search", search, sstate, q_all)
    truth = brute_force_topk(queries, vecs, n, spec.k)
    recall = float(recall_at_k(gids, truth))
    log("recall_at_10", recall)
    check(recall >= RECALL_FLOOR, f"recall@10 {recall} < {RECALL_FLOOR}")

    def shard_state(st, s):
        """Shard ``s`` as a single-device state on device 0."""
        def piece(x):
            by_dev = {sh.device: sh.data for sh in x.addressable_shards}
            return jax.device_put(by_dev[mesh.devices.flat[s]][0],
                                  devices[0])
        return jax.tree.map(piece, st)

    # the sharded top-k against the merge of every shard's own search
    single = None
    pool_d, pool_i = [], []
    for s in range(SHARDS):
        local = shard_state(sstate, s)
        if single is None:
            single = _compile("search_many", eng.search_many, local,
                              queries)
        ids, d, _, _ = single(local, queries)
        pool_i.append(jnp.where(ids >= 0, ids + s * n_per, -1))
        pool_d.append(jnp.where(ids >= 0, d, jnp.float32(3.4e38)))
        del local
    neg, sel = lax.top_k(-jnp.concatenate(pool_d, axis=1), spec.k)
    merged = jnp.take_along_axis(jnp.concatenate(pool_i, axis=1), sel, 1)
    same_ids = bool((np.asarray(merged) == np.asarray(gids)).all())
    d_err = float(np.abs(np.asarray(-neg) - np.asarray(gd)).max())
    log("per_shard_ids_identical", same_ids)
    log("per_shard_max_abs_dist_diff", d_err)
    check(same_ids, "sharded top-k differs from the per-shard search_many")
    check(d_err == 0.0, "sharded distances differ from per-shard ones")

    insert_fn = dist.make_sharded_insert(eng, mesh, bucket=WAVE_INSERT)
    routed_np, valid = dist.route_inserts(
        inserts, jnp.arange(SHARDS * WAVE_INSERT), SHARDS, WAVE_INSERT)
    routed, valid = jax.device_put((routed_np, valid),
                                   NamedSharding(mesh, P("shard")))
    routed_np = np.asarray(routed_np)
    insert = _compile("sharded_insert", insert_fn, sstate, routed, valid)
    t = time.perf_counter()
    sstate = jax.block_until_ready(insert(sstate, routed, valid))
    log("wave_s[sharded_insert]", time.perf_counter() - t)
    counts = [int(c) for c in np.asarray(sstate.store.count)]
    log("shard_counts", counts)
    check(counts == [n_per + WAVE_INSERT] * SHARDS, "shard counts")
    want = n_per + np.arange(WAVE_INSERT)
    for s in range(SHARDS):
        probe = jnp.concatenate([jnp.asarray(routed_np[s]),
                                 queries[WAVE_INSERT:]])
        top1 = np.asarray(single(shard_state(sstate, s), probe)[0]
                          [:WAVE_INSERT, 0])
        check((top1 == want).all(),
              f"shard {s}: inserted ids {want} came back as {top1}")
    log("inserts_found_top1", SHARDS * WAVE_INSERT)

    for dev in devices[:SHARDS]:
        stats = dev.memory_stats() or {}
        log(f"peak_bytes_in_use[{dev.id}]",
            stats.get("peak_bytes_in_use", "not reported"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded path, over four chips")
    ap.add_argument("--n", type=int, default=None,
                    help="base vectors (per shard with --four-chips)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {dev.platform}); nothing run",
              file=sys.stderr)
        return 2
    log("compile_cache", enable_compile_cache())
    log("device", f"{dev.platform} {dev.device_kind} x{len(devices)}")

    t = time.perf_counter()
    try:
        if args.four_chips:
            four_chips(args.n or N_PER_SHARD, args.seed)
        else:
            one_chip(args.n or N_ONE_CHIP, args.seed)
    except CheckFailed as e:
        print(f"chip_smoke: check failed: {e}", file=sys.stderr)
        return 1
    log("total_s", time.perf_counter() - t)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
