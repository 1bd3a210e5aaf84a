"""Build a configuration's index once per checkout; restore it afterwards.

A vector database restarts from the index it persisted, not by rebuilding
it, and ``Engine.build`` takes minutes at a deployment's size.  So the
first run of a configuration builds the index and saves it with the
program's own checkpoint store (``repro.checkpoint.store``); every later
run restores it.  The key is a digest of the configuration's file, of
every ``src/repro/**/*.py`` and of this file and ``corpus.py``: a change
to the deployment, to the program, to the corpus or to the way the index
is kept never finds an index built by other code.

Leaves the harness can make again more cheaply than it reads them (the
base vectors, which it makes on the device from ``data_seed``, and the
all-zero insert buffer) are saved as empty arrays and put back on
restore.  The PQ codec is saved beside the state and goes back in with
``Engine.install_codec``.
"""
from __future__ import annotations

import dataclasses
import hashlib
import pathlib
import pickle
import shutil

import jax
import jax.numpy as jnp

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
CACHE = BENCH / ".index_cache"


def digest(config_name: str, root: pathlib.Path = ROOT) -> str:
    """Hex digest of everything the built index depends on."""
    bench = root / "bench"
    files = [bench / "configs" / f"{config_name}.json",
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


def _strip(state, base):
    """The state with the leaves that ``_fill`` can remake emptied."""
    vecs = state.store.vectors
    dim = vecs.shape[1]
    empty = jnp.zeros((0, dim), vecs.dtype)
    if bool(jnp.array_equal(vecs, padded_base(base, vecs.shape[0]))):
        state = dataclasses.replace(
            state, store=dataclasses.replace(state.store, vectors=empty))
    if not bool(jnp.any(state.buf_vecs != 0)):
        state = dataclasses.replace(
            state, buf_vecs=jnp.zeros((0, state.buf_vecs.shape[1]),
                                      state.buf_vecs.dtype))
    return state


def _fill(state, base, n_max: int, buffer_max: int):
    if state.store.vectors.shape[0] == 0:
        state = dataclasses.replace(state, store=dataclasses.replace(
            state.store, vectors=padded_base(base, n_max)))
    if state.buf_vecs.shape[0] == 0:
        state = dataclasses.replace(state, buf_vecs=jnp.zeros(
            (buffer_max, state.buf_vecs.shape[1]), state.buf_vecs.dtype))
    return state


def save(config_name: str, engine, state, base, *,
         cache: pathlib.Path = CACHE, root: pathlib.Path = ROOT) -> None:
    """Persist ``engine``'s codec and ``state``; drop this configuration's
    indexes kept under other keys."""
    from repro.checkpoint import store

    key = digest(config_name, root)
    for old in cache.glob(f"{config_name}-*"):
        if old.name != f"{config_name}-{key}":
            shutil.rmtree(old, ignore_errors=True)
    d = _dir(config_name, key, cache)
    tree = {"codec": engine.codec, "state": _strip(state, base)}
    store.save(d, 0, tree, keep=1)
    tmp = d / "treedef.pkl.tmp"
    tmp.write_bytes(pickle.dumps(jax.tree.structure(tree)))
    tmp.rename(d / "treedef.pkl")


def restore(config_name: str, engine, base, *,
            cache: pathlib.Path = CACHE, root: pathlib.Path = ROOT):
    """The saved state with the codec installed in ``engine``, or None
    when no index was saved under the current digest."""
    import json

    from repro.checkpoint import store

    d = _dir(config_name, digest(config_name, root), cache)
    step = store.latest_step(d)
    if step is None or not (d / "treedef.pkl").exists():
        return None
    # the bytes were written by ``save`` above, in this checkout
    treedef = pickle.loads((d / "treedef.pkl").read_bytes())
    manifest = json.loads(
        (d / f"step_{step:08d}" / "MANIFEST.json").read_text())
    like = jax.tree.unflatten(treedef, [
        jax.ShapeDtypeStruct(tuple(s), jnp.dtype(t))
        for s, t in zip(manifest["shapes"], manifest["dtypes"])])
    _, tree = store.load_latest(d, like)
    engine.install_codec(tree["codec"])
    spec = engine.spec
    return _fill(tree["state"], base, spec.n_max, spec.buffer_max)
