"""A checkout with a tiny deployment, for the harness's CPU tests."""
from __future__ import annotations

import json
import pathlib
import shutil

BENCH = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH.parent

CONFIG = {
    "source": "test", "dim": 16, "dtype": "float32",
    "metric": "sqeuclidean", "n_base": 400, "headroom": 256,
    "data_seed": 3, "n_clusters": 6, "scale": 3.0, "noise": 1.0,
    "engine": {"preset": "navis", "r": 12, "pq_m": 8, "e_search": 32,
               "e_pos": 24, "k": 10, "beam_width": 4, "max_hops": 48,
               "s_search": 8, "r_ent": 16, "ent_pool": 16,
               "cache_capacity_pages": 16, "ent_frac": 0.05},
    "build": {"build_block": 32, "build_e_pos": 24, "alpha": 1.2},
    "limits": {"search_miss": 0.2, "readback_miss": 0.1,
               "dist_rel_err": 1e-4, "bad_ids": 0, "count_gap": 0,
               "dropped": 0},
}
MIX = {"loop": "closed", "clients": 1, "insert_waves": 2, "insert_wave": 8,
       "search_waves": 1, "search_wave": 16, "insert_drift": 0.0,
       "query_set": 32}


def make_checkout(dest: pathlib.Path) -> pathlib.Path:
    """A copy of the benchmark and the program at ``dest`` with one cell,
    ``tiny.mixed``, that reports every metric of ``BENCHMARK.json``."""
    ignore = shutil.ignore_patterns("__pycache__", ".index_cache",
                                    ".jax_cache", "out")
    shutil.copytree(BENCH, dest / "bench", ignore=ignore)
    shutil.copytree(ROOT / "src" / "repro", dest / "src" / "repro",
                    ignore=ignore)
    (dest / "bench" / "configs" / "tiny.json").write_text(json.dumps(CONFIG))
    (dest / "bench" / "mixes" / "tiny.json").write_text(json.dumps(MIX))
    bm = json.loads((ROOT / "BENCHMARK.json").read_text())
    bm["workloads"] = [{"name": "tiny.mixed", "config": "tiny",
                        "traffic": "tiny", "chips": 1, "why": "test"}]
    for m in bm["end_to_end"] + bm["per_layer"]:
        m.pop("workloads", None)
    (dest / "BENCHMARK.json").write_text(json.dumps(bm))
    return dest
