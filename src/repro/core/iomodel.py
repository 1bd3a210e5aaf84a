"""Two-tier I/O accounting.

The paper's SSD is the *slow tier*; here it is HBM behind a gather, and
its I/O is counted, never timed.  Every traversal / rerank / structural
update primitive threads an :class:`IOCounters` pytree through, so a
caller reads exact per-category byte and request counts: the paper's SSD
metrics.

Categories follow Fig. 4(a): useful vector, wasted vector, edgelist, padding,
for both reads and writes, all at 4 KiB page granularity.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

PAGE_BYTES = 4096


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class IOCounters:
    """Per-category I/O tallies (device arrays so they live inside jit)."""

    read_requests: jax.Array
    write_requests: jax.Array
    edge_bytes_read: jax.Array
    useful_vec_bytes_read: jax.Array
    wasted_vec_bytes_read: jax.Array
    pad_bytes_read: jax.Array
    edge_bytes_written: jax.Array
    vec_bytes_written: jax.Array
    wasted_vec_bytes_written: jax.Array   # packed: co-written neighbor vecs
    pad_bytes_written: jax.Array
    cache_hits: jax.Array
    cache_misses: jax.Array
    hops: jax.Array
    # hashed-visited-set saturation events (impossible at default capacity;
    # a saturated traversal may re-expand vertices, re-charging I/O only)
    visited_overflow: jax.Array
    # explored-pool slots wasted on tombstoned vertices (the traversal
    # scored/loaded them, the result mask threw them away): the work
    # tombstones cost a traversal until a consolidation reclaims them
    tombstone_skips: jax.Array
    # full-width vectors an exact rerank read from the decoupled vector
    # file (CASR's groups, or the whole pool without it); a count of
    # vectors, which a window cannot wrap as the int32 byte counters can
    vectors_read: jax.Array

    @classmethod
    def zeros(cls) -> "IOCounters":
        z = lambda: jnp.zeros((), jnp.int64)
        return cls(*[z() for _ in dataclasses.fields(cls)])

    def total_read_bytes(self):
        return (self.edge_bytes_read + self.useful_vec_bytes_read +
                self.wasted_vec_bytes_read + self.pad_bytes_read)

    def total_write_bytes(self):
        return (self.edge_bytes_written + self.vec_bytes_written +
                self.wasted_vec_bytes_written + self.pad_bytes_written)

    def asdict(self) -> dict:
        return {f.name: int(getattr(self, f.name))
                for f in dataclasses.fields(self)}


def merge_counters(a: IOCounters, b: IOCounters) -> IOCounters:
    return jax.tree.map(lambda x, y: x + y, a, b)


def sum_counters(batched: IOCounters) -> IOCounters:
    """Reduce per-query counters ([Q]-leading leaves, e.g. from a vmapped
    search fan-out) to one scalar tally.  Concurrent readers charge I/O
    independently; the device serves the union, so counts simply add."""
    return jax.tree.map(lambda x: x.sum(axis=0), batched)

