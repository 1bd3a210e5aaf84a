"""O(1)-state visited sets for graph traversals.

The sets these hold: in ``search.disk_traverse``, ``page_seen`` (the
pages a traversal has read) and ``vec_loaded`` (the vectors a packed page
dragged in); in ``search.entrance_search``, the entrance vertices it has
expanded.  ``disk_traverse`` keeps no expanded set: its pool carries an
"expanded" flag per slot.  As ``[n_max]`` / ``[p_max]`` bitmaps these
would make a B-query fan-out wave allocate ``B × n_max`` booleans —
per-wave memory growing with the *corpus*.  Real on-disk GVS systems
bound visited-set state by the search *frontier*: a traversal expands at
most ``beam_width`` vertices per hop for at most ``max_hops`` hops, so
the number of distinct marks is exactly bounded by
``max_hops × beam_width`` regardless of index size.

:class:`HashVisited` is a fixed-capacity open-addressing (linear-probing)
hash set sized to 2× that exact bound (load factor ≤ 0.5, power-of-two
table, Fibonacci hashing).  Probing walks the table with early exit and is
capped at the table size, so an insert fails **only when the table is
truly full** — impossible when the capacity honours the mark bound, which
makes the hashed traversal bit-identical to the bitmap one.  If a caller
forces a smaller capacity the set *saturates*: the insert is dropped,
``overflow`` increments (surfaced as ``IOCounters.visited_overflow``),
and a later membership test may miss — the traversal then charges the
page again (or re-expands an entrance vertex), which only re-charges I/O
or burns hops; results are never corrupted.

:class:`DenseVisited` wraps the original ``[n]`` bitmap behind the same
``contains`` / ``add`` API — kept as the reference implementation for the
equivalence tests and the ``visited_impl="bitmap"`` engine ablation.

All operations are pure pytree functions, safe under ``jit`` / ``vmap`` /
``lax.while_loop`` carries.
"""
from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
from jax import lax

_FIB = jnp.uint32(2654435761)          # 2^32 / golden ratio (Fibonacci hash)


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class DenseVisited:
    """Reference bitmap: O(n) state, O(1) ops."""

    bits: jax.Array            # [n] bool


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class HashVisited:
    """Open-addressing set: O(capacity) state independent of the corpus."""

    keys: jax.Array            # [table] int32, -1 = empty (power-of-two size)
    count: jax.Array           # int32 — live keys
    overflow: jax.Array        # int32 — dropped inserts (saturation events)


VisitedSet = DenseVisited | HashVisited


def table_size(capacity: int) -> int:
    """Power-of-two table ≥ 2 × capacity (load factor ≤ 0.5)."""
    cap = max(int(capacity), 1)
    return max(8, 1 << math.ceil(math.log2(2 * cap)))


def make_hash(capacity: int) -> HashVisited:
    return HashVisited(
        keys=jnp.full((table_size(capacity),), -1, jnp.int32),
        count=jnp.zeros((), jnp.int32),
        overflow=jnp.zeros((), jnp.int32))


def make_dense(n: int) -> DenseVisited:
    return DenseVisited(bits=jnp.zeros((n,), bool))


def _hash(keys: jax.Array, size: int) -> jax.Array:
    """Fibonacci hash into [0, size): multiply, keep the high bits (the low
    bits of a Fibonacci multiply are poorly mixed)."""
    lg = size.bit_length() - 1
    h = keys.astype(jnp.uint32) * _FIB
    return (h >> jnp.uint32(32 - lg)).astype(jnp.int32)


def contains(vs: VisitedSet, keys: jax.Array) -> jax.Array:
    """Membership test (negative keys are never members).  Works on any
    key shape; vectorised linear probing with early exit."""
    if isinstance(vs, DenseVisited):
        n = vs.bits.shape[0]
        return vs.bits[jnp.clip(keys, 0, n - 1)] & (keys >= 0) & (keys < n)

    size = vs.keys.shape[0]
    h = _hash(keys, size)
    found0 = jnp.zeros(jnp.shape(keys), bool)
    open0 = keys >= 0

    def cond(c):
        j, _, open_ = c
        return (j < size) & open_.any()

    def body(c):
        j, found, open_ = c
        slot = (h + j) & (size - 1)
        v = vs.keys[slot]
        found = found | (open_ & (v == keys))
        open_ = open_ & (v >= 0) & (v != keys)
        return j + 1, found, open_

    _, found, _ = lax.while_loop(cond, body,
                                 (jnp.int32(0), found0, open0))
    return found


def add(vs: VisitedSet, keys: jax.Array, mask: jax.Array) -> VisitedSet:
    """Insert ``keys[mask]`` (idempotent — present keys are no-ops).

    Hash sets probe until the key, an empty slot, or a full table; a full
    table drops the insert and bumps ``overflow`` (saturation — the caller
    may re-expand the vertex later, re-charging I/O only).
    """
    if isinstance(vs, DenseVisited):
        n = vs.bits.shape[0]
        ok = mask & (keys >= 0) & (keys < n)
        idx = jnp.where(ok, keys, n)               # out of bounds = dropped
        return DenseVisited(bits=vs.bits.at[idx].set(True))

    size = vs.keys.shape[0]
    flat_k = jnp.ravel(keys)
    flat_m = jnp.ravel(mask)

    def step(carry, i):
        table, count, overflow = carry
        k = flat_k[i]
        h = _hash(k, size)

        def insert(args):
            table, count, overflow = args
            # state: 0 = probing, 1 = found, 2 = empty slot claimed
            def cond(c):
                j, state, _ = c
                return (state == 0) & (j < size)

            def body(c):
                j, state, slot = c
                s = (h + j) & (size - 1)
                v = table[s]
                state = jnp.where(v == k, jnp.int32(1),
                                  jnp.where(v < 0, jnp.int32(2),
                                            jnp.int32(0)))
                return j + 1, state, jnp.where(state > 0, s, slot)

            _, state, slot = lax.while_loop(
                cond, body, (jnp.int32(0), jnp.int32(0), jnp.int32(0)))
            claimed = state == 2
            table = jnp.where(claimed, table.at[slot].set(k), table)
            count = count + claimed.astype(jnp.int32)
            overflow = overflow + (state == 0).astype(jnp.int32)
            return table, count, overflow

        carry = lax.cond(flat_m[i] & (k >= 0), insert, lambda a: a,
                         (table, count, overflow))
        return carry, None

    (table, count, overflow), _ = lax.scan(
        step, (vs.keys, vs.count, vs.overflow),
        jnp.arange(flat_k.shape[0]))
    return HashVisited(keys=table, count=count, overflow=overflow)


def overflow(vs: VisitedSet) -> jax.Array:
    """Saturation events so far (always 0 for the dense bitmap)."""
    if isinstance(vs, HashVisited):
        return vs.overflow
    return jnp.zeros((), jnp.int32)


def nbytes(vs: VisitedSet) -> int:
    """Per-query state footprint of this set (static — shape math only)."""
    return sum(leaf.size * leaf.dtype.itemsize
               for leaf in jax.tree.leaves(vs))
