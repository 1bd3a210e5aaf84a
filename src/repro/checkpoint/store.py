"""Fault-tolerant checkpointing: atomic commit, resume, elastic remesh.

Layout on disk::

    <dir>/step_000100/
        shard_00000.npz        flattened leaves (this process's shard)
        MANIFEST.json          step, leaf treedef, shapes/dtypes, n_shards
    <dir>/LATEST               text file naming the last COMMITTED step dir

Commit protocol: write into ``step_X.tmp/``, fsync, rename to ``step_X/``,
then rewrite ``LATEST`` — a crash at any point leaves either the previous
checkpoint or a complete new one (``*.tmp`` dirs are garbage-collected on
the next save).  Elastic remesh: arrays are stored unsharded per leaf, so
``load_latest`` can re-``device_put`` them under any mesh/sharding — a
state checkpointed on mesh A restarts on mesh B."""
from __future__ import annotations

import json
import os
import shutil
from pathlib import Path
from typing import Any, Optional

import jax
import numpy as np


def _leaves_with_paths(tree):
    flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
    names = ["/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                      for p in path) for path, _ in flat]
    return names, [leaf for _, leaf in flat], treedef


def save(ckpt_dir: str | Path, step: int, tree: Any, *, keep: int = 3,
         shard: int = 0) -> Path:
    """Atomically persist ``tree`` for ``step``.  Returns the commit dir."""
    ckpt_dir = Path(ckpt_dir)
    ckpt_dir.mkdir(parents=True, exist_ok=True)
    final = ckpt_dir / f"step_{step:08d}"
    tmp = ckpt_dir / f"step_{step:08d}.tmp"
    if tmp.exists():
        shutil.rmtree(tmp)
    tmp.mkdir(parents=True)

    names, leaves, _ = _leaves_with_paths(tree)
    # store raw uint8 views: numpy's npz cannot round-trip ml_dtypes
    # (bfloat16 comes back as void); the manifest holds dtype + shape
    np_leaves = [np.asarray(x) for x in leaves]
    arrays = {f"leaf_{i:05d}": np.frombuffer(x.tobytes(), np.uint8)
              for i, x in enumerate(np_leaves)}
    np.savez(tmp / f"shard_{shard:05d}.npz", **arrays)
    manifest = {
        "step": step,
        "n_leaves": len(leaves),
        "names": names,
        "shapes": [list(x.shape) for x in np_leaves],
        "dtypes": [str(x.dtype) for x in np_leaves],
    }
    (tmp / "MANIFEST.json").write_text(json.dumps(manifest))
    # fsync the shard file then atomically publish
    with open(tmp / f"shard_{shard:05d}.npz", "rb") as f:
        os.fsync(f.fileno())
    if final.exists():
        shutil.rmtree(final)
    tmp.rename(final)
    (ckpt_dir / "LATEST.tmp").write_text(final.name)
    (ckpt_dir / "LATEST.tmp").rename(ckpt_dir / "LATEST")

    _gc(ckpt_dir, keep)
    return final


def _gc(ckpt_dir: Path, keep: int) -> None:
    steps = sorted(d for d in ckpt_dir.iterdir()
                   if d.is_dir() and d.name.startswith("step_")
                   and not d.name.endswith(".tmp"))
    for d in steps[:-keep]:
        shutil.rmtree(d, ignore_errors=True)
    for d in ckpt_dir.glob("*.tmp"):
        if d.is_dir():
            shutil.rmtree(d, ignore_errors=True)


def latest_step(ckpt_dir: str | Path) -> Optional[int]:
    ckpt_dir = Path(ckpt_dir)
    latest = ckpt_dir / "LATEST"
    if not latest.exists():
        return None
    name = latest.read_text().strip()
    if not (ckpt_dir / name / "MANIFEST.json").exists():
        return None          # torn commit: fall back to scanning
    return int(name.split("_")[1])


def load(ckpt_dir: str | Path, step: int, like: Any, *,
         shard: int = 0, sharding=None) -> Any:
    """Restore the pytree saved at ``step``.  ``like`` supplies the
    treedef; ``sharding`` optionally re-places every leaf (elastic remesh:
    pass NamedShardings for the *new* mesh)."""
    import ml_dtypes  # noqa: F401 — registers bfloat16 etc. with numpy
    ckpt_dir = Path(ckpt_dir)
    d = ckpt_dir / f"step_{step:08d}"
    data = np.load(d / f"shard_{shard:05d}.npz")
    manifest = json.loads((d / "MANIFEST.json").read_text())
    leaves = [
        np.frombuffer(data[f"leaf_{i:05d}"].tobytes(),
                      dtype=np.dtype(manifest["dtypes"][i])).reshape(
                          manifest["shapes"][i])
        for i in range(manifest["n_leaves"])]
    _, like_leaves, treedef = _leaves_with_paths(like)
    assert len(leaves) == len(like_leaves), (
        f"checkpoint has {len(leaves)} leaves, expected {len(like_leaves)}")
    if sharding is not None:
        shard_leaves = jax.tree.leaves(
            sharding, is_leaf=lambda x: hasattr(x, "device_set"))
        out = [jax.device_put(x, s) for x, s in zip(leaves, shard_leaves)]
    else:
        out = [jax.numpy.asarray(x) for x in leaves]
    return jax.tree_util.tree_unflatten(treedef, out)


def load_latest(ckpt_dir: str | Path, like: Any, *, shard: int = 0,
                sharding=None):
    """(step, tree) of the newest committed checkpoint, or (None, None)."""
    step = latest_step(ckpt_dir)
    if step is None:
        return None, None
    return step, load(ckpt_dir, step, like, shard=shard, sharding=sharding)
