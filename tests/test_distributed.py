"""Multi-device GVS: shard_map search/insert on 8 fake CPU devices.

Device count is locked at first jax init, so this runs in a subprocess
with XLA_FLAGS set (the same pattern as launch/dryrun.py) — never set the
flag in this process.  The subprocesses are pinned to the CPU: they must
never reach for an accelerator that this process may hold.
"""
import json
import os
import subprocess
import sys
import textwrap

import pytest

_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import jax, jax.numpy as jnp
    from repro.core import Engine, preset, brute_force_topk, recall_at_k
    from repro.core import distributed as dist
    from repro.data import make_clustered, query_stream

    key = jax.random.PRNGKey(0)
    N, D = 1024, 32
    vecs, _, cents = make_clustered(key, N, D, n_clusters=8, noise=1.0)
    queries = query_stream(jax.random.PRNGKey(1), cents, 16)

    n_per = N // 8 + 16
    # tiny 128-vector shards: a 1% entrance sample is 1-2 vertices and
    # mis-seeds the traversal — use 10% (13 entries) and a wider pool
    spec = preset("navis", dim=D, r=12, n_max=n_per, e_search=32,
                  e_pos=40, pq_m=16, cache_capacity_pages=64, max_hops=48,
                  buffer_max=32, ent_frac=0.10)
    eng = Engine(spec)
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((4, 2), ("data", "model"))
    sstate = dist.build_sharded_state(eng, jax.random.PRNGKey(2), vecs, mesh)
    # each shard was built on its own device, none stacked on device 0
    assert all(sh.device == d for sh, d in zip(
        sstate.store.vectors.addressable_shards, mesh.devices.flat))
    fn = dist.make_sharded_search(eng, mesh, n_per=N // 8, n_queries=16)
    with mesh:
        ids, dists, sstate = fn(sstate, queries)
    truth = brute_force_topk(queries, vecs, N, 10)
    # globalised ids from range-sharding: shard s owns [s*per, (s+1)*per)
    recall = float(recall_at_k(ids, truth))

    ins = dist.make_sharded_insert(eng, mesh, bucket=4)
    routed, valid = dist.route_inserts(vecs[:8] + 0.01, jnp.arange(8), 8, 4)
    with mesh:
        sstate = ins(sstate, routed, valid)
    counts = [int(c) for c in sstate.store.count]
    print(json.dumps({"recall": recall, "counts": counts,
                      "devices": jax.device_count()}))
""")


_MOE_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import jax, jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.models import layers as L
    from repro.launch.mesh import make_mesh

    mesh = make_mesh((4, 2), ("data", "model"))
    B, S, D, E, F, K = 8, 4, 16, 8, 32, 2
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (B, S, D), jnp.float32)
    params = {
        "router": jax.random.normal(jax.random.fold_in(key, 1), (D, E)) * 0.1,
        "up": jax.random.normal(jax.random.fold_in(key, 2), (E, D, F)) * 0.1,
        "gate": jax.random.normal(jax.random.fold_in(key, 3), (E, D, F)) * 0.1,
        "down": jax.random.normal(jax.random.fold_in(key, 4), (E, F, D)) * 0.1,
    }
    outs = {}
    with mesh:
        for name, gather in (("gather", True), ("two_d", False)):
            rules = L.ShardingRules(batch="data", tensor="model",
                                    fsdp="data", moe_gather_weights=gather)
            xs = jax.device_put(x, NamedSharding(mesh, P("data", None, None)))
            ps = jax.tree.map(lambda w: jax.device_put(
                w, NamedSharding(mesh, P("model", "data", None))
                if w.ndim == 3 else NamedSharding(mesh, P())), params)
            fn = jax.jit(lambda p, xx, r=rules: L.moe_block(
                p, xx, n_experts=E, top_k=K, capacity_factor=8.0,
                activation="silu", glu=True, mesh=mesh, rules=r))
            outs[name] = np.asarray(fn(ps, xs))
    err = float(np.abs(outs["gather"] - outs["two_d"]).max())
    rel = err / max(float(np.abs(outs["gather"]).max()), 1e-9)
    print(json.dumps({"rel_err": rel}))
""")


@pytest.mark.slow
def test_moe_2d_matches_gather_8dev():
    """The decode-path 2-D expert compute must equal the training gather
    path (capacity set high enough that no tokens drop either way)."""
    env = dict(os.environ, PYTHONPATH="src", JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", _MOE_SCRIPT], env=env,
                         capture_output=True, text=True, timeout=600,
                         cwd=os.path.dirname(os.path.dirname(
                             os.path.abspath(__file__))))
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["rel_err"] < 1e-4, res


@pytest.mark.slow
def test_sharded_search_insert_8dev():
    env = dict(os.environ, PYTHONPATH="src", JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", _SCRIPT], env=env,
                         capture_output=True, text=True, timeout=900,
                         cwd=os.path.dirname(os.path.dirname(
                             os.path.abspath(__file__))))
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["devices"] == 8
    # 8 independent 128-vector shards searched with a global merge:
    # recall is bounded by per-shard graph quality on 128 points
    assert res["recall"] >= 0.75, res
    assert sum(res["counts"]) == 1024 + 8
