"""The cache's nested-``lax.cond`` access step, kept as the reference
for the straight-line NAVIS step in :mod:`repro.core.cache`.

This is the earlier form of ``access`` and its helpers, verbatim: the
policy is the traced ``st.policy``, chosen by ``lax.cond`` / ``lax.switch``
over the whole :class:`~repro.core.cache.CacheState`, and each guarded
table write is a ``jnp.where`` over the whole table.  ``test_cache_replay``
holds the production step to it leaf for leaf, for every policy.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.core.cache import (IN_FROZEN, IN_WINDOW, NOT_CACHED, POLICIES,
                              _INUSE_TICKS, _PROBES, CacheState)


def _install_frozen(st: CacheState, page) -> CacheState:
    """Move ``page`` into the frozen region (randomized eviction with
    ``_PROBES`` probes that skip recently-used entries), dropping it from
    the window if it currently sits there."""
    key, sub = jax.random.split(st.key)
    f = st.frozen_pages.shape[0]
    # int32 explicitly: under x64 the default int64 probes would downcast
    # into the int32 slot tables on every traced access (FutureWarning)
    probes = jax.random.randint(sub, (_PROBES,), 0, f, dtype=jnp.int32)
    occupied = st.frozen_pages[probes] >= 0
    recently = (st.clock - st.frozen_last[probes]) < _INUSE_TICKS
    # prefer an empty probe, else the first not-recently-used, else probe 0
    score = jnp.where(~occupied, 0, jnp.where(~recently, 1, 2))
    victim_slot = probes[jnp.argmin(score)]
    old = st.frozen_pages[victim_slot]
    status = st.status
    slot_of = st.slot_of
    status = jnp.where(old >= 0, status.at[old].set(NOT_CACHED), status)
    slot_of = jnp.where(old >= 0, slot_of.at[old].set(-1), slot_of)
    # remove from window
    in_window = st.status[page] == IN_WINDOW
    wslot = st.slot_of[page]
    window_pages = jnp.where(in_window,
                             st.window_pages.at[wslot].set(-1),
                             st.window_pages)
    window_last = jnp.where(in_window,
                            st.window_last.at[wslot].set(-1),
                            st.window_last)
    status = status.at[page].set(IN_FROZEN)
    slot_of = slot_of.at[page].set(victim_slot)
    frozen_pages = st.frozen_pages.at[victim_slot].set(page)
    frozen_last = st.frozen_last.at[victim_slot].set(st.clock)
    fill = st.frozen_fill + jnp.where(old >= 0, 0, 1)
    return dataclasses.replace(
        st, status=status, slot_of=slot_of, window_pages=window_pages,
        window_last=window_last, frozen_pages=frozen_pages,
        frozen_last=frozen_last, frozen_fill=fill, key=key)


def _navis_hit_window(st: CacheState, page) -> CacheState:
    """Second window hit ⇒ promote to frozen (randomized eviction)."""
    slot = st.slot_of[page]
    hits = st.hits.at[page].add(1)
    window_last = st.window_last.at[slot].set(st.clock)
    st = dataclasses.replace(st, hits=hits, window_last=window_last)
    return jax.lax.cond(st.hits[page] >= 2,
                        lambda s: _install_frozen(s, page), lambda s: s, st)


def _navis_miss(st: CacheState, page) -> CacheState:
    """Admit into the window, evicting the LRU window entry."""
    # empty slots have last=-1; int32 keeps the x64 scatter cast-free
    victim = jnp.argmin(st.window_last).astype(jnp.int32)
    old = st.window_pages[victim]
    status = st.status
    slot_of = st.slot_of
    hits = st.hits
    status = jnp.where(old >= 0, status.at[old].set(NOT_CACHED), status)
    slot_of = jnp.where(old >= 0, slot_of.at[old].set(-1), slot_of)
    hits = jnp.where(old >= 0, hits.at[old].set(0), hits)
    status = status.at[page].set(IN_WINDOW)
    slot_of = slot_of.at[page].set(victim)
    hits = hits.at[page].set(1)
    return dataclasses.replace(
        st, status=status, slot_of=slot_of, hits=hits,
        window_pages=st.window_pages.at[victim].set(page),
        window_last=st.window_last.at[victim].set(st.clock))


# ---------------------------------------------------------------------------
# Baseline policies (single region in the window arrays)
# ---------------------------------------------------------------------------

def _single_region_hit(st: CacheState, page) -> CacheState:
    slot = st.slot_of[page]
    window_last = st.window_last.at[slot].set(st.clock)
    hits = st.hits.at[page].add(1)
    return dataclasses.replace(st, window_last=window_last, hits=hits)


def _single_region_miss(st: CacheState, page) -> CacheState:
    def lru_victim(st):
        return jnp.argmin(st.window_last)

    def lfu_victim(st):
        occ = st.window_pages >= 0
        freq = jnp.where(occ, st.hits[jnp.maximum(st.window_pages, 0)],
                         -1)
        return jnp.argmin(jnp.where(occ, freq, -1))

    def clock_victim(st):
        # second chance: sweep from the hand; entries with a reference bit
        # (recent access) get it cleared and are skipped
        w = st.window_pages.shape[0]
        idx = (st.clock_hand + jnp.arange(w)) % w
        ref = (st.clock - st.window_last[idx]) < _INUSE_TICKS
        first_clear = jnp.argmax(~ref)
        return idx[first_clear]

    victim = jax.lax.switch(
        jnp.clip(st.policy - 1, 0, 2),
        [lru_victim, clock_victim, lfu_victim], st).astype(jnp.int32)
    old = st.window_pages[victim]
    status = st.status
    slot_of = st.slot_of
    hits = st.hits
    status = jnp.where(old >= 0, status.at[old].set(NOT_CACHED), status)
    slot_of = jnp.where(old >= 0, slot_of.at[old].set(-1), slot_of)
    hits = jnp.where(old >= 0, hits.at[old].set(0), hits)
    status = status.at[page].set(IN_WINDOW)
    slot_of = slot_of.at[page].set(victim)
    hits = hits.at[page].set(1)
    hand = jnp.where(st.policy == POLICIES["clock"],
                     ((victim + 1) % st.window_pages.shape[0]).astype(
                         st.clock_hand.dtype), st.clock_hand)
    return dataclasses.replace(
        st, status=status, slot_of=slot_of, hits=hits,
        window_pages=st.window_pages.at[victim].set(page),
        window_last=st.window_last.at[victim].set(st.clock),
        clock_hand=hand)


# ---------------------------------------------------------------------------
# Public API
# ---------------------------------------------------------------------------

def lookup(st: CacheState, page: jax.Array) -> jax.Array:
    """Pure hit test against a *frozen* cache — no mutation, no clock tick.

    This is the read half of :func:`access`, split out so a batch of
    concurrent readers — search queries or an insert wave's position
    seeks — can probe one shared snapshot under ``vmap`` (mutating
    per-access state does not vectorise; a snapshot lookup does).  The
    access sequence each reader observed is recorded as a trace and
    folded back in with :func:`apply_trace`.
    """
    return (st.status[page] != NOT_CACHED) & (st.policy != POLICIES["none"])


def apply_trace(st: CacheState, trace: jax.Array) -> tuple[jax.Array,
                                                           CacheState]:
    """Replay a page-access trace into the cache, returning (replay hit
    count, new state).  The valid entries are a contiguous prefix — the
    traversal appends charged accesses in order — so replay runs a
    dynamic-length loop that stops at the first ``-1``: cost scales with
    the accesses actually charged, not with the (heavily padded)
    ``max_hops × beam_width`` trace capacity.

    Concurrent readers share one cache: each runs against the same frozen
    snapshot, then their traces are replayed in order so the merged state
    evolves exactly as if the accesses had been issued sequentially — the
    paper's model of search threads sharing the host cache.  For a single
    trace replayed onto the snapshot it was recorded against, the result
    is bit-identical to having threaded :func:`access` through the search.
    """
    t = trace.shape[0]

    def cond(carry):
        i, _, _ = carry
        return (i < t) & (trace[jnp.minimum(i, t - 1)] >= 0)

    def body(carry):
        i, hits, st = carry
        hit, st = access(st, trace[i])
        return i + 1, hits + hit.astype(jnp.int32), st

    _, hits, st = jax.lax.while_loop(
        cond, body, (jnp.zeros((), jnp.int32), jnp.zeros((), jnp.int32),
                     st))
    return hits, st


@jax.named_scope("navis.cache_replay")
def apply_traces(st: CacheState, traces: jax.Array) -> tuple[jax.Array,
                                                             CacheState]:
    """Replay a batch of traces ([Q, T] int32, -1-padded) in wave order.

    Both fan-out paths use this merge: ``search_many`` replays its query
    wave's traces, ``insert_many`` its position-seek traces (before the
    commit scan, whose out-of-place updates may then invalidate pages —
    all wave reads precede all wave writes in the two-phase model).
    Padding lanes replay nothing: set their rows to all ``-1``.
    """
    def step(carry, trace):
        hits, st = carry
        h, st = apply_trace(st, trace)
        return (hits + h, st), None

    (hits, st), _ = jax.lax.scan(step, (jnp.zeros((), jnp.int32), st),
                                 traces)
    return hits, st


def access(st: CacheState, page: jax.Array) -> tuple[jax.Array, CacheState]:
    """One page access.  Returns (hit: bool, new state).

    The caller charges a slow-tier read on a miss.  NAVIS refreshes the
    frozen-region in-use stamp on hits (eviction protection, §7).
    """
    st = dataclasses.replace(st, clock=st.clock + 1)
    is_none = st.policy == POLICIES["none"]
    hit = lookup(st, page)

    def on_hit(st: CacheState) -> CacheState:
        def navis(st):
            def frozen_touch(st):
                slot = st.slot_of[page]
                return dataclasses.replace(
                    st, frozen_last=st.frozen_last.at[slot].set(st.clock))
            return jax.lax.cond(st.status[page] == IN_FROZEN, frozen_touch,
                                lambda s: _navis_hit_window(s, page), st)
        return jax.lax.cond(st.policy == POLICIES["navis"], navis,
                            lambda s: _single_region_hit(s, page), st)

    def on_miss(st: CacheState) -> CacheState:
        def noop(st):
            return st
        def admit(st):
            return jax.lax.cond(st.policy == POLICIES["navis"],
                                lambda s: _navis_miss(s, page),
                                lambda s: _single_region_miss(s, page), st)
        return jax.lax.cond(is_none, noop, admit, st)

    st = jax.lax.cond(hit, on_hit, on_miss, st)
    return hit, st
