"""Benchmark aggregator: ``PYTHONPATH=src python -m benchmarks.run``.

One module per paper table/figure (see DESIGN.md §9); each prints CSV
rows ``name,key=value,...``.  ``--quick`` shrinks workloads ~2-3×;
``--only fig10`` runs a single module.  GVS wall-times come from the SSD
cost model over exact I/O counters (benchmarks/common.py).
"""
from __future__ import annotations

import argparse
import sys
import time
import traceback

from repro.compile_cache import enable_compile_cache

MODULES = [
    ("fig3_interference", "benchmarks.interference"),
    ("fig4_wasted_io", "benchmarks.wasted_io"),
    ("fig5_entrance_staleness", "benchmarks.entrance_staleness"),
    ("fig10_concurrent", "benchmarks.concurrent"),
    ("fig13_insert_only", "benchmarks.insert_only"),
    ("fig14_ablation", "benchmarks.ablation"),
    ("fig15_tail_latency", "benchmarks.tail_latency"),
    ("fig16_footprint", "benchmarks.footprint"),
    ("fig17_cache_policy", "benchmarks.cache_policy"),
    ("fig18_group_size", "benchmarks.group_size"),
]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--only", default=None,
                    help="substring filter on module name")
    args = ap.parse_args(argv)
    enable_compile_cache()

    failures = 0
    for name, modpath in MODULES:
        if args.only and args.only not in name:
            continue
        t0 = time.time()
        print(f"== {name} ==", flush=True)
        try:
            import importlib
            mod = importlib.import_module(modpath)
            for row in mod.run(quick=args.quick):
                print(row, flush=True)
            print(f"# {name} done in {time.time()-t0:.0f}s", flush=True)
        except Exception:                          # noqa: BLE001
            failures += 1
            print(f"# {name} FAILED:\n{traceback.format_exc()}",
                  flush=True)
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
