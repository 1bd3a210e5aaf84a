"""Drivers: how the harness drives a deployment's engine.

A configuration (``bench/configs/<name>.json``) names its driver under the
optional key ``"driver"``; without it the driver is ``single``.  The
harness (``run.py``) loads ``bench/drivers/<driver>.py`` by path, as it
loads a metric's reader, and knows the engine only through the functions
below.  A driver for another layout (shards over several chips, replicas)
is a new file here and a configuration that names it.

A driver module provides:

``open(name, cfg, base, *, chips, cache, root) -> (handle, state, build_s)``
    The deployment ``cfg`` (configuration ``name``) over the base vectors
    ``base`` (``corpus.corpus(cfg)``, on the default device), on the first
    ``chips`` devices of ``jax.devices()``.  It restores the index saved
    under ``cache`` by ``index_cache.restore`` (passing ``root``, and a
    ``place`` that puts each restored array on the device that holds it),
    or builds the index, saves it with ``index_cache.save`` and returns
    the build's seconds as ``build_s``; ``build_s`` is None after a
    restore.  ``handle`` is whatever the other functions need (engines,
    devices) and never changes; ``state`` is a pytree that every wave
    threads through and that the harness may drop or reuse: an operation
    never alters the state it was given.

``insert(handle, state, vectors) -> (dropped, state)``
    One wave of inserts: ``vectors`` [n, dim] float32, on the default
    device.  ``dropped`` [n] bool marks the inserts that were refused;
    every other insert is acknowledged, committed to the returned state
    and found by every later search of it.

``search(handle, state, queries) -> (ids, dists, rounds, state)``
    One wave of searches: ``queries`` [Q, dim] float32.  ``ids`` [Q, k]
    int, ``dists`` [Q, k] float32 (ascending, squared L2 of each id's
    stored vector to the query), ``rounds`` [Q] int (dependent I/O
    rounds of each query: hops and rerank; read by
    ``search.lane_waste_pct``), and the state after the wave (its cache
    and counters move).

``count(handle, state) -> int``
    Vectors stored, summed over shards.

``counters(handle, state) -> {"search": {...}, "insert": {...}}``
    The state's I/O counters (``repro.core.IOCounters``' fields) as
    Python ints, summed over shards.  They may wrap at 2**32; the harness
    takes a window's increments modulo 2**32.

``compiled(handle) -> int``
    Programs compiled so far; the harness checks that none compiles in
    the window.

``k(handle) -> int``
    Results a query returns.

Numbering, which ``run.compare`` holds every driver to: row ``i`` of
``corpus.corpus(cfg)`` is id ``i``; the ``j``-th acknowledged insert
(counted from 0, over the whole run, in the order of the waves and of the
rows within a wave) is id ``n_base + j``; ``-1`` fills a row with fewer
than ``k`` answers.  A driver whose engine numbers otherwise (by shard,
``local + shard * n_per``) maps its ids to these before it returns them.

Timing: the harness reads the clock just before ``insert`` / ``search``
and after ``jax.block_until_ready`` of all they return, state included,
so a driver returns its outputs without waiting for them; outputs may
stay on the device.  Wave sizes are the mix's and are fixed for a run,
so a driver compiles nothing after the harness's warm-up.  The per-layer
readers find a driver's work in the trace by its programs' names
(``_search_many``, ``_insert_many``) and the engine's ``navis.*``
scopes.
"""
from __future__ import annotations

import pathlib

DEFAULT = "single"


def path(bench: pathlib.Path, cfg: dict) -> pathlib.Path:
    """The file of the driver that configuration ``cfg`` names."""
    return bench / "drivers" / f"{cfg.get('driver', DEFAULT)}.py"
