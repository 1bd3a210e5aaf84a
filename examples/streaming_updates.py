"""Streaming updates: concurrent search+insert with a drifting corpus,
comparing NAVIS against OdinANN and FreshDiskANN — the paper's headline
scenario (Fig 10) at laptop scale, reported as exact per-op I/O counts —
followed by a sustained delete+insert churn loop that leans on the
maintenance subsystem (`Engine.consolidate` fires whenever
`needs_consolidation` trips) to keep accepting writes instead of filling
up.

    PYTHONPATH=src python examples/streaming_updates.py
"""
import jax
import jax.numpy as jnp
import numpy as np

from repro.core import Engine, brute_force_topk, preset, recall_at_k
from repro.data import insert_stream, make_clustered, query_stream

N, DIM, NOISE = 3000, 96, 0.6


def _spec(system: str, **overrides):
    kw = dict(dim=DIM, r=32, n_max=N + 1200, pq_m=32, e_search=40, e_pos=80,
              cache_capacity_pages=256, max_hops=96, buffer_max=256)
    kw.update(overrides)
    return preset(system, **kw)


def main():
    vecs, _, cents = make_clustered(jax.random.PRNGKey(0), N, DIM,
                                    n_clusters=24, noise=NOISE)
    key = jax.random.PRNGKey(42)
    base = Engine(_spec("navis"))
    bundle = base.bundle(base.build(key, vecs, build_block=64))

    print("system         reads/query  reads/insert  writes/insert  recall")
    for system in ("freshdiskann", "odinann", "navis"):
        eng = Engine(_spec(system))
        state = eng.build(key, vecs, shared=bundle)
        s_reads = i_reads = i_writes = 0
        recalls = []
        for rd in range(6):
            wave = insert_stream(jax.random.fold_in(key, 2 * rd + 1), cents,
                                 10, noise=NOISE, drift=0.3)
            istats, state = eng.insert_many(state, wave)
            if eng.spec.update_path == "buffered" and bool(
                    eng.needs_merge(state)):
                _, state = eng.merge(state)
            qs = query_stream(jax.random.fold_in(key, 2 * rd), cents, 22,
                              noise=NOISE)
            ids, _, sstats, state = eng.search_many(state, qs)
            truth = brute_force_topk(qs, state.store.vectors,
                                     int(state.store.count), 10)
            recalls.append(float(recall_at_k(
                jnp.where(ids >= state.store.n_max, -1, ids), truth)))
            s_reads += int(np.asarray(sstats.read_requests).sum())
            i_reads += int(np.asarray(istats.read_requests).sum())
            i_writes += int(np.asarray(istats.write_requests).sum())
        print(f"{system:14s} {s_reads / (6 * 22):11.1f} "
              f"{i_reads / 60:13.1f} {i_writes / 60:14.1f} "
              f"{np.mean(recalls):7.3f}")
    print("\ncounts are 4 KiB page requests (repro.core.iomodel.IOCounters); "
          "FreshDiskANN's\ninserts write nothing until its buffer merges.")
    churn_loop()


def churn_loop(cycles: int = 10, batch: int = 20):
    """Delete+insert churn at full capacity: tombstone a wave, consolidate
    when the trigger fires (reclaiming slots into the free list), insert a
    wave into the reclaimed slots — acceptance stays 100% where the
    pre-maintenance engine silently dropped every insert past n_max."""
    print(f"\nchurn loop ({cycles} cycles × {batch} delete+insert, "
          "maintenance on):")
    key = jax.random.PRNGKey(1)
    vecs, _, cents = make_clustered(key, 600, 48, n_clusters=10)
    eng = Engine(preset("navis", dim=48, r=16, n_max=900, pq_m=24,
                        e_search=32, e_pos=40, cache_capacity_pages=256,
                        max_hops=96, consolidate_frac=0.15, ent_frac=0.05))
    state = eng.build(key, vecs, build_block=64)
    # fill the fresh headroom so churn exercises reclamation, not append
    spare = int(state.store.n_max - state.store.count)
    _, state = eng.insert_many(state, insert_stream(
        jax.random.fold_in(key, 0), cents, spare))
    rng = np.random.default_rng(0)
    dropped = consolidations = 0
    for c in range(cycles):
        live = np.flatnonzero(np.asarray(state.live_mask))
        victims = rng.choice(live, batch, replace=False).astype(np.int32)
        state = eng.delete_many(state, jnp.asarray(victims))
        if bool(eng.needs_consolidation(state, lookahead=batch)):
            mstats, state = eng.consolidate(state)
            consolidations += 1
            print(f"  cycle {c}: consolidate — reclaimed "
                  f"{int(state.free_count)} slots, "
                  f"{int(mstats.read_requests)} reads / "
                  f"{int(mstats.write_requests)} writes charged")
        wave = insert_stream(jax.random.fold_in(key, c + 1), cents, batch)
        stats, state = eng.insert_many(state, wave)
        dropped += int(np.asarray(stats.dropped).sum())
    print(f"  {cycles * batch} churn inserts at count=n_max="
          f"{int(state.store.n_max)}: {dropped} dropped, "
          f"{consolidations} consolidations, live={int(state.live_count)}")


if __name__ == "__main__":
    main()
