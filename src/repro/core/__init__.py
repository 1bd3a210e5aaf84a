"""NAVIS core: the paper's contribution as a composable JAX system.

Public API:
    EngineSpec / Engine / preset / PRESETS      (engine.py)
    GraphStore / LayoutSpec                     (layout.py)
    build_graph / brute_force_topk / recall_at_k (graph.py)
    IOCounters                                  (iomodel.py)
    Engine.consolidate / maintenance_step /
        needs_consolidation                     (engine.py + maintenance.py)
"""
from repro.core.engine import (Engine, EngineSpec, EngineState, OpStats,
                               PRESETS, preset)
from repro.core.graph import (brute_force_topk, build_graph, check_invariants,
                              medoid, recall_at_k, robust_prune)
from repro.core.iomodel import (IOCounters, PAGE_BYTES, merge_counters,
                                sum_counters)
from repro.core.layout import GraphStore, LayoutSpec, empty_store

__all__ = [
    "Engine", "EngineSpec", "EngineState", "OpStats", "PRESETS", "preset",
    "brute_force_topk", "build_graph", "check_invariants", "medoid",
    "recall_at_k", "robust_prune", "IOCounters", "PAGE_BYTES",
    "GraphStore", "LayoutSpec", "empty_store",
    "merge_counters", "sum_counters",
]
