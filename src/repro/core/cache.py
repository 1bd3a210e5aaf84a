"""Host-memory edgelist-page caches (NAVIS §7) + baseline policies.

NAVIS-cache: a *mostly-frozen region* (90% of capacity, randomized eviction
with up to 8 probes that skip recently-used entries) plus a *tiny admission
window* (10%, LRU).  A page must be hit **twice inside the window** to be
promoted to the frozen region — filtering one-off edgelists from long
exploration paths.  Inspired by TinyLFU/FrozenHot; parameters per the paper.

Baselines for Fig. 17(b): LRU, CLOCK (FIFO + second chance), LFU.

All policies are pure functions over a :class:`CacheState` pytree, so they
run inside jitted search/insert loops.  Lookup is O(1) via a direct-map
``status``/``slot_of`` table over page ids; evictions scan only the small
window (LRU argmin) or probe randomly (frozen region), mirroring the paper's
"no expensive tracking structures" argument.  :func:`access` and the trace
replay take the policy as a static string, so a NAVIS access traces to one
straight-line step whose table writes are single guarded elements: nothing
per access reads, selects or copies a whole ``[p_max]`` table.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

# status codes
NOT_CACHED = jnp.int8(0)
IN_WINDOW = jnp.int8(1)
IN_FROZEN = jnp.int8(2)


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class CacheState:
    policy: jax.Array          # int32 enum (POLICIES)
    status: jax.Array          # [P_max] int8
    hits: jax.Array            # [P_max] int32 (window hit count / LFU freq)
    slot_of: jax.Array         # [P_max] int32 slot index within its region
    window_pages: jax.Array    # [W] int32 page ids, -1 empty
    window_last: jax.Array     # [W] int32 last-access tick
    frozen_pages: jax.Array    # [F] int32 page ids, -1 empty
    frozen_last: jax.Array     # [F] int32 last-access tick (in-use guard)
    frozen_fill: jax.Array     # int32 number of occupied frozen slots
    clock_hand: jax.Array      # int32 (CLOCK policy)
    clock: jax.Array           # int32 global tick
    key: jax.Array             # PRNG key for randomized eviction


POLICIES = {"navis": 0, "lru": 1, "clock": 2, "lfu": 3, "none": 4}
_PROBES = 8          # randomized-eviction probe budget (paper default)
_INUSE_TICKS = 64    # "currently in use" guard for frozen eviction


def init_cache(p_max: int, capacity_pages: int, policy: str,
               key: jax.Array, window_frac: float = 0.10) -> CacheState:
    if policy == "navis":
        w = max(int(capacity_pages * window_frac), 1)
        f = max(capacity_pages - w, 1)
    elif policy == "none":
        w, f = 1, 1
    else:
        # single-region policies keep everything in the "window" arrays
        w, f = capacity_pages, 1
    return CacheState(
        policy=jnp.asarray(POLICIES[policy], jnp.int32),
        status=jnp.zeros((p_max,), jnp.int8),
        hits=jnp.zeros((p_max,), jnp.int32),
        slot_of=jnp.full((p_max,), -1, jnp.int32),
        window_pages=jnp.full((w,), -1, jnp.int32),
        window_last=jnp.full((w,), -1, jnp.int32),
        frozen_pages=jnp.full((f,), -1, jnp.int32),
        frozen_last=jnp.full((f,), -1, jnp.int32),
        frozen_fill=jnp.zeros((), jnp.int32),
        clock_hand=jnp.zeros((), jnp.int32),
        clock=jnp.zeros((), jnp.int32),
        key=key,
    )


# ---------------------------------------------------------------------------
# NAVIS policy
# ---------------------------------------------------------------------------

def _put(x: jax.Array, i, v, pred) -> jax.Array:
    """``x`` with ``x[i] = v`` where ``pred``, else with ``x[i]`` written
    back: one element write (a dynamic-update-slice), never a select or a
    copy over the whole of ``x``, so a loop-carried table updates in
    place."""
    return x.at[i].set(jnp.where(pred, v, x[i]).astype(x.dtype))


def _frozen_victim(key, frozen_pages, frozen_last, clock):
    """Randomized eviction: draw ``_PROBES`` frozen slots and prefer an
    empty probe, else the first not-recently-used, else probe 0.
    Returns (next key, victim slot)."""
    key, sub = jax.random.split(key)
    f = frozen_pages.shape[0]
    # int32 explicitly: under x64 the default int64 probes would downcast
    # into the int32 slot tables on every traced access (FutureWarning)
    probes = jax.random.randint(sub, (_PROBES,), 0, f, dtype=jnp.int32)
    occupied = frozen_pages[probes] >= 0
    recently = (clock - frozen_last[probes]) < _INUSE_TICKS
    score = jnp.where(~occupied, 0, jnp.where(~recently, 1, 2))
    return key, probes[jnp.argmin(score)]


def _install_frozen(st: CacheState, page) -> CacheState:
    """Move ``page`` into the frozen region (randomized eviction with
    ``_PROBES`` probes that skip recently-used entries), dropping it from
    the window if it currently sits there."""
    key, slot = _frozen_victim(st.key, st.frozen_pages, st.frozen_last,
                               st.clock)
    old = st.frozen_pages[slot]
    in_window = st.status[page] == IN_WINDOW
    wslot = st.slot_of[page]
    status = _put(st.status, old, NOT_CACHED, old >= 0)
    slot_of = _put(st.slot_of, old, -1, old >= 0)
    return dataclasses.replace(
        st, status=status.at[page].set(IN_FROZEN),
        slot_of=slot_of.at[page].set(slot),
        window_pages=_put(st.window_pages, wslot, -1, in_window),
        window_last=_put(st.window_last, wslot, -1, in_window),
        frozen_pages=st.frozen_pages.at[slot].set(page),
        frozen_last=st.frozen_last.at[slot].set(st.clock),
        frozen_fill=st.frozen_fill + jnp.where(old >= 0, 0, 1), key=key)


def _navis_access(st: CacheState, page) -> tuple[jax.Array, CacheState]:
    """One NAVIS access as a straight-line step.

    - miss: admit into the window, evicting its LRU entry;
    - window hit: count it and refresh the entry's stamp; a second hit in
      the window promotes the page to the frozen region (randomized
      eviction, :func:`_frozen_victim`);
    - frozen hit: refresh the in-use stamp (eviction protection, §7).

    Every path's table writes run, each guarded by its path's predicate
    (:func:`_put`), and the paths share writes: a miss and a promotion
    each evict at most one page and place ``page`` once.  All reads come
    before an optimization barrier and all writes after it, so no read of
    a table outlives a write to it and XLA updates the loop-carried
    ``[p_max]`` tables in place instead of copying them.  The probe draw
    sits in a ``lax.cond`` over the key and the ``[F]`` frozen arrays
    alone: the PRNG key advances only when a page is installed, so the
    eviction choices follow the same key sequence as when each install
    was a branch of its own.
    """
    clock = st.clock + 1
    status = st.status[page]
    slot = st.slot_of[page]
    count = st.hits[page] + 1               # window hits after this one
    hit = status != NOT_CACHED
    frozen = status == IN_FROZEN
    miss = ~hit
    promote = hit & ~frozen & (count >= 2)
    # empty window slots have last=-1; int32 keeps the x64 scatter
    # cast-free
    victim = jnp.argmin(st.window_last).astype(jnp.int32)
    key, fslot = jax.lax.cond(
        promote, _frozen_victim,
        lambda k, *_: (k, jnp.zeros((), jnp.int32)),
        st.key, st.frozen_pages, st.frozen_last, clock)
    # the page that leaves the cache to make room, if any
    old = jnp.where(miss, st.window_pages[victim], st.frozen_pages[fslot])
    evict = (miss | promote) & (old >= 0)
    wslot = jnp.where(miss, victim, slot)
    (clock, page, slot, count, miss, frozen, promote, victim, fslot, old,
     evict, wslot), st = jax.lax.optimization_barrier(
        ((clock, page, slot, count, miss, frozen, promote, victim, fslot,
          old, evict, wslot), st))
    placed = miss | promote
    return hit, dataclasses.replace(
        st, clock=clock, key=key,
        status=_put(_put(st.status, old, NOT_CACHED, evict), page,
                    jnp.where(miss, IN_WINDOW, IN_FROZEN), placed),
        slot_of=_put(_put(st.slot_of, old, -1, evict), page,
                     jnp.where(miss, victim, fslot), placed),
        hits=_put(_put(st.hits, old, 0, miss & evict), page,
                  jnp.where(miss, 1, count), ~frozen),
        window_pages=_put(st.window_pages, wslot,
                          jnp.where(miss, page, -1), placed),
        window_last=_put(st.window_last, wslot,
                         jnp.where(promote, -1, clock), ~frozen),
        frozen_pages=_put(st.frozen_pages, fslot, page, promote),
        frozen_last=_put(st.frozen_last, jnp.where(frozen, slot, fslot),
                         clock, frozen | promote),
        frozen_fill=st.frozen_fill + (promote & (old < 0)).astype(
            jnp.int32))


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


def apply_trace(st: CacheState, trace: jax.Array,
                policy: str) -> tuple[jax.Array, CacheState]:
    """Replay a page-access trace into the cache, returning (replay hit
    count, new state).  The valid entries are a contiguous prefix — the
    traversal appends charged accesses in order — so replay runs a
    dynamic-length loop that stops at the first ``-1``: cost scales with
    the accesses actually charged, not with the (heavily padded)
    ``max_hops × beam_width`` trace capacity.  Each iteration is one
    :func:`access` under the static ``policy``; under ``"navis"`` that is
    a straight-line step with no op over the ``[p_max]`` page tables.

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
        hit, st = access(st, trace[i], policy)
        return i + 1, hits + hit.astype(jnp.int32), st

    _, hits, st = jax.lax.while_loop(
        cond, body, (jnp.zeros((), jnp.int32), jnp.zeros((), jnp.int32),
                     st))
    return hits, st


@jax.named_scope("navis.cache_replay")
def apply_traces(st: CacheState, traces: jax.Array,
                 policy: str) -> tuple[jax.Array, CacheState]:
    """Replay a batch of traces ([Q, T] int32, -1-padded) in wave order
    under the static ``policy`` (the one the cache was built with).

    Both fan-out paths use this merge: ``search_many`` replays its query
    wave's traces, ``insert_many`` its position-seek traces (before the
    commit scan, whose out-of-place updates may then invalidate pages —
    all wave reads precede all wave writes in the two-phase model).
    Padding lanes replay nothing: set their rows to all ``-1``.
    """
    def step(carry, trace):
        hits, st = carry
        h, st = apply_trace(st, trace, policy)
        return (hits + h, st), None

    (hits, st), _ = jax.lax.scan(step, (jnp.zeros((), jnp.int32), st),
                                 traces)
    return hits, st


def access(st: CacheState, page: jax.Array,
           policy: str) -> tuple[jax.Array, CacheState]:
    """One page access.  Returns (hit: bool, new state).

    ``policy`` is the cache's policy as a static string (the engine's
    ``EngineSpec.cache_policy``; ``st.policy`` holds the same value,
    traced), so the policy's step is chosen while tracing.  The NAVIS
    step (:func:`_navis_access`) is straight-line: every table write is
    one guarded element write, and no ``lax.cond`` carries the
    ``[p_max]`` tables, so an access costs a few scalar reads and writes,
    not a pass over the tables.  The baselines keep their branches.

    The caller charges a slow-tier read on a miss.  NAVIS refreshes the
    frozen-region in-use stamp on hits (eviction protection, §7).
    """
    if policy == "navis":
        return _navis_access(st, page)
    st = dataclasses.replace(st, clock=st.clock + 1)
    hit = lookup(st, page)
    if policy == "none":
        return hit, st
    st = jax.lax.cond(hit, lambda s: _single_region_hit(s, page),
                      lambda s: _single_region_miss(s, page), st)
    return hit, st


def priority_admit(st: CacheState, page: jax.Array) -> CacheState:
    """Admit ``page`` straight into the frozen region, bypassing the
    two-hits-in-window filter (entrance-aware cache hint, paper §7): when
    the dynamic entrance promotes a vertex, its edgelist page is about to
    seed every traversal, so it earns frozen residency immediately.

    NAVIS policy only (single-region baselines have no frozen region to
    pin into); a page already frozen just gets its in-use stamp
    refreshed.  No I/O is charged — admission moves host memory."""
    def do(st):
        def touch(st):
            slot = st.slot_of[page]
            return dataclasses.replace(
                st, frozen_last=st.frozen_last.at[slot].set(st.clock))
        return jax.lax.cond(st.status[page] == IN_FROZEN, touch,
                            lambda s: _install_frozen(s, page), st)

    eligible = (st.policy == POLICIES["navis"]) & (page >= 0)
    return jax.lax.cond(eligible, do, lambda s: s, st)


def invalidate_page(st: CacheState, page: jax.Array) -> CacheState:
    """Eviction hint from the indirection layer when an edge page dies
    (out-of-place update invalidated every slot — §8.2)."""
    def drop(st):
        slot = st.slot_of[page]
        in_window = st.status[page] == IN_WINDOW
        window_pages = jnp.where(in_window,
                                 st.window_pages.at[slot].set(-1),
                                 st.window_pages)
        window_last = jnp.where(in_window,
                                st.window_last.at[slot].set(-1),
                                st.window_last)
        frozen_pages = jnp.where(~in_window,
                                 st.frozen_pages.at[slot].set(-1),
                                 st.frozen_pages)
        return dataclasses.replace(
            st, status=st.status.at[page].set(NOT_CACHED),
            slot_of=st.slot_of.at[page].set(-1),
            hits=st.hits.at[page].set(0),
            window_pages=window_pages, window_last=window_last,
            frozen_pages=frozen_pages)
    return jax.lax.cond(st.status[page] != NOT_CACHED, drop, lambda s: s, st)
