"""Multi-device GVS: shard_map search/insert on 8 fake CPU devices.

Device count is locked at first jax init, so this runs in a subprocess
with XLA_FLAGS set — never set the flag in this process.  The subprocesses are pinned to the CPU: they must
never reach for an accelerator that this process may hold.
"""
import json
import os
import subprocess
import sys
import textwrap

import jax
import pytest

from repro.core import distributed as dist

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
    mesh = dist.make_mesh((4, 2), ("data", "model"))
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


def test_make_mesh_one_device():
    """``make_mesh`` lays its axes over the devices with every axis Auto,
    so GSPMD may partition along any of them."""
    mesh = dist.make_mesh((1,), ("shard",))
    assert dict(mesh.shape) == {"shard": 1}
    assert mesh.axis_types == (jax.sharding.AxisType.Auto,)
