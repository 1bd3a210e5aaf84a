"""Production mesh construction + sharding-rule derivation.

``make_production_mesh`` is a function (never a module-level constant) so
importing this module never touches jax device state.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.models.layers import ShardingRules


def make_mesh(shape, axes):
    """`jax.make_mesh` with every axis Auto (GSPMD-partitioned)."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_smoke_mesh():
    """1×1 mesh with the production axis names, for single-host tests."""
    return make_mesh((1, 1), ("data", "model"))


def batch_axes(mesh) -> tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if a in mesh.shape)


def data_size(mesh) -> int:
    return math.prod(mesh.shape[a] for a in batch_axes(mesh))


def make_rules(mesh, *, kind: str, global_batch: int,
               cfg=None) -> ShardingRules:
    """Sharding rules for one (shape-kind, batch) cell on a mesh.

    train/prefill: batch over (pod, data), TP over model, FSDP over data.
    decode: batch over (pod, data), KV-cache sequence over model
            (flash-decode style; softmax over the sharded axis is partial-
            reduced by GSPMD).
    batch=1 (long_500k): nothing batch-shards; long sequence/state dims
            spread over every mesh axis instead.
    """
    baxes = batch_axes(mesh)
    dsize = data_size(mesh)
    if global_batch >= dsize and global_batch % dsize == 0:
        b = baxes if len(baxes) > 1 else baxes[0]
    else:
        b = None
    if kind in ("train", "prefill"):
        # seq-parallel attention (§Perf, llama cell): on when gathering the
        # KV heads costs at most half of gathering the residual
        import os
        sp = os.environ.get("REPRO_SP_ATTN", "") == "1"
        if cfg is not None and getattr(cfg, "num_heads", 0):
            sp = sp or (cfg.num_kv_heads * cfg.hd * 2 <= cfg.d_model)
        return ShardingRules(batch=b, tensor="model", fsdp="data", seq=None,
                             act_seq="model", seq_parallel_attn=sp)
    # decode: MoE weights stay 2-D sharded — the per-token FSDP weight
    # gather is the dominant roofline term otherwise (§Perf, moonshot cell)
    if b is None:
        seq = tuple(a for a in ("pod", "data", "model") if a in mesh.shape)
    else:
        seq = "model"
    return ShardingRules(batch=b, tensor="model", fsdp="data", seq=seq,
                         moe_gather_weights=False)


def named(mesh, spec_tree):
    """PartitionSpec tree -> NamedSharding tree."""
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s),
        spec_tree,
        is_leaf=lambda x: isinstance(x, P))


def batch_specs(mesh, rules: ShardingRules, input_tree):
    """Sharding specs for step-fn data inputs (tokens / cross_src / pos)."""
    def spec(path, x):
        name = path[-1].key if hasattr(path[-1], "key") else str(path[-1])
        if name == "pos":
            return P()
        return P(rules.batch, *([None] * (len(x.shape) - 1)))
    return jax.tree_util.tree_map_with_path(spec, input_tree)
