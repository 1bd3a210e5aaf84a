"""Build a configuration's index once per checkout; restore it afterwards.

A vector database restarts from the index it persisted, not by rebuilding
it, and ``Engine.build`` takes minutes at a deployment's size.  So the
first run of a configuration builds the index and saves it with the
program's own checkpoint store (``repro.checkpoint.store``); every later
run restores it.  What is saved is whatever pytree the configuration's
driver (``drivers/``) hands over: for ``single``, the PQ codec and the
``EngineState``.  The key is a digest of the configuration's file, of its
driver's file, of every ``src/repro/**/*.py`` and of this file and
``corpus.py``: a change to the deployment, to the way it is driven, to
the program, to the corpus or to the way the index is kept never finds an
index built by other code.

Leaves the harness can make again more cheaply than it reads them are
saved as empty arrays and put back on restore: an ``EngineState``'s
``store.vectors`` when it is the base vectors padded with zeros (which
the harness makes on the device from ``data_seed``), and an all-zero
``buf_vecs`` (the insert buffer).  A shard's own vectors, a slice of the
base, are saved as they are.
"""
from __future__ import annotations

import hashlib
import json
import pathlib
import pickle
import shutil

import jax
import jax.numpy as jnp

from bench import drivers

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
CACHE = BENCH / ".index_cache"


def digest(config_name: str, root: pathlib.Path = ROOT) -> str:
    """Hex digest of everything the built index depends on."""
    bench = root / "bench"
    config = bench / "configs" / f"{config_name}.json"
    files = [config, drivers.path(bench, json.loads(config.read_text())),
             bench / "index_cache.py", bench / "corpus.py"]
    files += sorted((root / "src" / "repro").rglob("*.py"))
    h = hashlib.sha256()
    for f in files:
        h.update(str(f.relative_to(root)).encode())
        h.update(b"\0")
        h.update(f.read_bytes())
        h.update(b"\0")
    return h.hexdigest()[:24]


def _dir(config_name: str, key: str, cache: pathlib.Path) -> pathlib.Path:
    return cache / f"{config_name}-{key}"


def padded_base(base: jax.Array, n_max: int) -> jax.Array:
    return jnp.pad(base, ((0, n_max - base.shape[0]), (0, 0)))


def _strip(tree, base):
    """(``tree`` with the leaves that ``_fill`` can remake emptied, and
    {leaf number: (what to remake, shape)} for those leaves)."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
    leaves, fills = [], {}
    for i, (path, x) in enumerate(flat):
        name = jax.tree_util.keystr(path)
        if (name.endswith(".store.vectors") and x.ndim == 2
                and x.shape[0] >= base.shape[0]
                and x.shape[1] == base.shape[1]
                and bool(jnp.array_equal(x, padded_base(base, x.shape[0])))):
            fills[i] = ("base", x.shape)
            x = x[:0]
        elif name.endswith(".buf_vecs") and not bool(jnp.any(x != 0)):
            fills[i] = ("zeros", x.shape)
            x = x[:0]
        leaves.append(x)
    return jax.tree.unflatten(treedef, leaves), fills


def _fill(tree, base, fills: dict):
    leaves, treedef = jax.tree.flatten(tree)
    for i, (what, shape) in fills.items():
        dtype = leaves[i].dtype
        leaves[i] = (padded_base(base, shape[0]).astype(dtype)
                     if what == "base" else jnp.zeros(shape, dtype))
    return jax.tree.unflatten(treedef, leaves)


def save(config_name: str, tree, base, *, cache: pathlib.Path = CACHE,
         root: pathlib.Path = ROOT) -> None:
    """Persist the driver's ``tree``; drop this configuration's indexes
    kept under other keys."""
    from repro.checkpoint import store

    key = digest(config_name, root)
    for old in cache.glob(f"{config_name}-*"):
        if old.name != f"{config_name}-{key}":
            shutil.rmtree(old, ignore_errors=True)
    d = _dir(config_name, key, cache)
    tree, fills = _strip(tree, base)
    store.save(d, 0, tree, keep=1)
    tmp = d / "treedef.pkl.tmp"
    tmp.write_bytes(pickle.dumps((jax.tree.structure(tree), fills)))
    tmp.rename(d / "treedef.pkl")


def restore(config_name: str, base, *, place=lambda tree: tree,
            cache: pathlib.Path = CACHE, root: pathlib.Path = ROOT):
    """The tree ``save`` kept under the current digest, its emptied
    leaves remade and ``place`` applied (the driver's: it puts each array
    on its device), or None when none was saved."""
    from repro.checkpoint import store

    d = _dir(config_name, digest(config_name, root), cache)
    step = store.latest_step(d)
    if step is None or not (d / "treedef.pkl").exists():
        return None
    # the bytes were written by ``save`` above, in this checkout
    treedef, fills = pickle.loads((d / "treedef.pkl").read_bytes())
    manifest = json.loads(
        (d / f"step_{step:08d}" / "MANIFEST.json").read_text())
    like = jax.tree.unflatten(treedef, [
        jax.ShapeDtypeStruct(tuple(s), jnp.dtype(t))
        for s, t in zip(manifest["shapes"], manifest["dtypes"])])
    _, tree = store.load_latest(d, like)
    return place(_fill(tree, base, fills))
