"""Pod-scale GVS: the engine sharded over a device mesh.

The database is range-sharded over every mesh axis (16×16 single pod =
256 shards; 2×16×16 = 512): each device owns ``n_per`` vertices with a
private proximity graph, entrance graph, cache and PQ codes — exactly the
deployment the paper's single-node engine scales out to (queries fan out,
per-shard top-k merge; inserts route to their owning shard by id hash).

* ``sharded_search``: queries are replicated to every shard (one
  all-gather-free broadcast — they arrive replicated), each shard runs its
  local beam search + rerank, and the global top-k is reduced with one
  ``all_gather`` of the per-shard (k dists, k ids) pools — k·(4+4) bytes
  per shard per query, tiny next to the per-shard traversal.
* ``sharded_insert``: the host router buckets new vectors by
  ``hash(id) % n_shards``; every shard runs its bucket (padded to the
  same length — shape-static SPMD) through the ``insert_many`` fan-out:
  concurrent position seeks on the shard snapshot, serialized
  conflict-aware commits.  No cross-shard edges: the shards are
  independent graphs, which is how multi-segment deployments (Starling,
  Qdrant) scale writes.

``make_mesh`` builds the mesh the shards live on; ``dryrun()`` lowers +
compiles both ops on a mesh with ShapeDtypeStructs (no allocation).
"""
from __future__ import annotations

import concurrent.futures

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import engine as engine_mod
from repro.core import pq as pq_mod

INF = jnp.float32(3.4e38)


def make_mesh(shape, axes):
    """`jax.make_mesh` with every axis Auto (GSPMD-partitioned)."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def db_axes(mesh) -> tuple[str, ...]:
    """Every mesh axis shards the database (GVS has no tensor parallelism)."""
    return tuple(mesh.axis_names)


def n_shards(mesh) -> int:
    return int(mesh.devices.size)


# ---------------------------------------------------------------------------
# Host-side build + routing
# ---------------------------------------------------------------------------

def build_sharded_state(engine: engine_mod.Engine, key: jax.Array,
                        vectors: jax.Array, mesh):
    """Range-shard ``vectors`` over every device of ``mesh`` and build each
    shard's engine state on the device that owns it, all devices at once.
    The global state (leading shard axis, sharded over every mesh axis) is
    assembled from those per-device pieces, so no device ever holds
    another's shard.

    One PQ codec is trained on the *global* corpus and installed before
    the per-shard builds — per-shard codecs would make PQ distances (and
    the global top-k merge) incomparable across shards."""
    devices = list(mesh.devices.flat)
    n = vectors.shape[0]
    per = n // len(devices)
    sample = vectors[jax.random.choice(
        key, n, (min(n, 4096),), replace=False)]
    # installed once, before the threads: every build reads it, none
    # writes it
    engine.install_codec(pq_mod.train_pq(key, sample, engine.spec.pq_m))
    parts = [jax.device_put(vectors[s * per:(s + 1) * per], dev)
             for s, dev in enumerate(devices)]

    def build(s):
        # one host thread per device, so the shards build concurrently
        with jax.default_device(devices[s]):
            return jax.block_until_ready(
                engine.build(jax.random.fold_in(key, s), parts[s]))

    with concurrent.futures.ThreadPoolExecutor(len(devices)) as pool:
        states = list(pool.map(build, range(len(devices))))
    sharding = NamedSharding(mesh, P(db_axes(mesh)))

    def assemble(*xs):
        return jax.make_array_from_single_device_arrays(
            (len(xs),) + xs[0].shape, sharding,
            [jax.device_put(x[None], dev) for x, dev in zip(xs, devices)])

    return jax.tree.map(assemble, *states)


def route_inserts(vectors: jax.Array, ids: jax.Array, n_shards_: int,
                  bucket: int):
    """Bucket vectors by owner shard (hash = id % shards), padding every
    bucket to ``bucket`` entries.  Returns ([S, bucket, D], [S, bucket] valid).
    """
    import numpy as np
    v = np.asarray(vectors)
    idn = np.asarray(ids)
    out = np.zeros((n_shards_, bucket, v.shape[1]), np.float32)
    valid = np.zeros((n_shards_, bucket), bool)
    fill = np.zeros(n_shards_, np.int32)
    for vec, i in zip(v, idn):
        s = int(i) % n_shards_
        if fill[s] < bucket:
            out[s, fill[s]] = vec
            valid[s, fill[s]] = True
            fill[s] += 1
    return jnp.asarray(out), jnp.asarray(valid)


# ---------------------------------------------------------------------------
# SPMD ops
# ---------------------------------------------------------------------------

def make_sharded_search(engine: engine_mod.Engine, mesh, *,
                        n_per: int, n_queries: int, parallel: bool = True):
    """Jitted (stacked_state, queries [Q, D]) -> (ids [Q, k], dists [Q, k],
    stacked_state).  Global ids = shard_index * n_per + local id.

    ``parallel=True`` (default) runs each shard's query batch through the
    vmapped ``search_many`` fan-out — the per-shard analogue of the
    paper's concurrent search threads — instead of the serial
    state-threading scan; results are identical, the shard just stops
    serialising its own readers.
    """
    axes = db_axes(mesh)
    k = engine.spec.k
    search = engine._search_many if parallel else engine._search_batch

    def local(state_stk, queries):
        state = jax.tree.map(lambda x: x[0], state_stk)
        ids, dists, _, state = search(state, queries)
        # globalise ids: flatten the multi-axis shard index
        flat = jnp.zeros((), jnp.int32)
        for ax in axes:
            flat = flat * lax.axis_size(ax) + lax.axis_index(ax)
        gids = jnp.where(ids >= 0, ids + flat * n_per, -1)
        # merge: gather every shard's (dist, id) pool, reduce locally
        all_d = lax.all_gather(jnp.where(ids >= 0, dists, INF),
                               axes, tiled=False)          # [S.., Q, k]
        all_i = lax.all_gather(gids, axes, tiled=False)
        all_d = all_d.reshape(-1, queries.shape[0], k)
        all_i = all_i.reshape(-1, queries.shape[0], k)
        neg, sel = lax.top_k(-all_d.transpose(1, 0, 2).reshape(
            queries.shape[0], -1), k)
        gi = jnp.take_along_axis(
            all_i.transpose(1, 0, 2).reshape(queries.shape[0], -1),
            sel, axis=1)
        out_i = jnp.where(neg > -INF, gi, -1)
        return out_i, -neg, jax.tree.map(lambda x: x[None], state)

    spec_state = P(axes)
    fn = jax.shard_map(
        local, mesh=mesh,
        in_specs=(spec_state, P()),              # queries replicated
        out_specs=(P(), P(), spec_state),
        check_vma=False)
    return jax.jit(fn)


def make_sharded_insert(engine: engine_mod.Engine, mesh, *, bucket: int,
                        parallel: bool = True):
    """Jitted (stacked_state, routed [S, bucket, D], valid [S, bucket]) ->
    stacked_state.  Each shard inserts only its own bucket.

    ``parallel=True`` (default) routes the bucket through the shard-local
    ``insert_many`` fan-out — every shard position-seeks its whole bucket
    concurrently against its own snapshot and serialises only the
    conflict-aware commits, the write-side analogue of the parallel
    sharded search.  Padding lanes ride the ``valid`` mask.  Buffered
    engines fall back to the sequential scan (no seek to parallelise).
    """
    axes = db_axes(mesh)
    fan_out = parallel and engine.spec.update_path != "buffered"

    def local(state_stk, routed, valid):
        state = jax.tree.map(lambda x: x[0], state_stk)
        vecs, ok = routed[0], valid[0]

        if fan_out:
            _, state = engine._insert_many(state, vecs, valid=ok)
            return jax.tree.map(lambda x: x[None], state)

        def step(state, xs):
            v, keep = xs

            def do(state):
                _, state, _ = engine._insert(state, v)
                return state

            return lax.cond(keep, do, lambda s: s, state), None

        state, _ = lax.scan(step, state, (vecs, ok))
        return jax.tree.map(lambda x: x[None], state)

    fn = jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(axes), P(axes), P(axes)),
        out_specs=P(axes),
        check_vma=False)
    return jax.jit(fn, donate_argnums=(0,))


# ---------------------------------------------------------------------------
# Dry-run (production mesh, ShapeDtypeStructs only)
# ---------------------------------------------------------------------------

def state_shapes(engine: engine_mod.Engine, n_shards_: int, n_per: int):
    """ShapeDtypeStruct pytree of a stacked sharded state (no allocation)."""
    spec = engine.spec.with_(n_max=n_per)
    # mirror Engine.build's shapes without computing anything
    from repro.core import cache as cache_mod
    from repro.core import entrance as ent_mod
    from repro.core.iomodel import IOCounters
    from repro.core.layout import empty_store

    def shaped(x):
        return jax.ShapeDtypeStruct((n_shards_,) + x.shape, x.dtype)

    def empty_state():
        store = empty_store(n_per, spec.dim, spec.r)
        c_max = max(int(spec.ent_frac * n_per * 2), 64)
        ent = ent_mod.empty_entrance(c_max, spec.r_ent, n_per)
        cache = cache_mod.init_cache(store.page_live.shape[0],
                                     spec.cache_capacity_pages,
                                     spec.cache_policy, jax.random.PRNGKey(0))
        return engine_mod.EngineState(
            store=store,
            codes=jnp.zeros((n_per, spec.pq_m), jnp.uint8),
            ent=ent, cache=cache,
            tombstone=jnp.zeros((n_per,), bool),
            default_entries=jnp.zeros((spec.n_entry,), jnp.int32),
            ctr_search=IOCounters.zeros(), ctr_insert=IOCounters.zeros(),
            buf_vecs=jnp.zeros((spec.buffer_max, spec.dim), jnp.float32),
            buf_count=jnp.zeros((), jnp.int32),
            n_deleted=jnp.zeros((), jnp.int32),
            free_list=jnp.full((n_per,), -1, jnp.int32),
            free_count=jnp.zeros((), jnp.int32),
            free_mask=jnp.zeros((n_per,), bool),
            maint_cursor=jnp.zeros((), jnp.int32),
            young_mask=jnp.zeros((n_per,), bool),
            ctr_maint=IOCounters.zeros())

    return jax.tree.map(shaped, jax.eval_shape(empty_state))


def dryrun(engine: engine_mod.Engine, mesh, *, n_per: int = 65_536,
           n_queries: int = 64, bucket: int = 8):
    """Lower + compile sharded search and insert on ``mesh``.

    The engine must have a codec installed (build a tiny CPU instance or
    call :meth:`engine.Engine.build` on a small sample first); the codec
    arrays are compile-time constants, so a smoke-scale codec is fine.
    Returns {op: (lowered, compiled)}.
    """
    S = n_shards(mesh)
    sstate = state_shapes(engine, S, n_per)
    q = jax.ShapeDtypeStruct((n_queries, engine.spec.dim), jnp.float32)
    routed = jax.ShapeDtypeStruct((S, bucket, engine.spec.dim), jnp.float32)
    valid = jax.ShapeDtypeStruct((S, bucket), jnp.bool_)

    out = {}
    with mesh:
        search_fn = make_sharded_search(engine, mesh, n_per=n_per,
                                        n_queries=n_queries)
        lowered = search_fn.lower(sstate, q)
        out["search"] = (lowered, lowered.compile())
        insert_fn = make_sharded_insert(engine, mesh, bucket=bucket)
        lowered = insert_fn.lower(sstate, routed, valid)
        out["insert"] = (lowered, lowered.compile())
    return out
