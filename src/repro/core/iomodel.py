"""Two-tier I/O accounting + device cost models.

The paper's SSD is our *slow tier*; the TPU adaptation maps it onto
HBM-behind-a-gather (or host DRAM over PCIe for beyond-HBM corpora) — see
DESIGN.md §2.  Every traversal / rerank / structural-update primitive threads
an :class:`IOCounters` pytree through, so benchmarks read exact per-category
byte and request counts; the cost models convert them into time (the paper's
throughput/latency figures) without needing the physical device.

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
    # scored/loaded them, the result mask threw them away) — the churn
    # benchmarks read this to quantify pre-consolidation degradation
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


@dataclasses.dataclass(frozen=True)
class SSDModel:
    """NVMe cost model (defaults ≈ the paper's Crucial T705 PCIe 5.0).

    time = max(request-bound, bandwidth-bound) under a given queue depth;
    per-request latency contributes to *latency* metrics, throughput uses the
    steady-state bound.
    """

    read_iops: float = 1.40e6          # 4 KiB random read IOPS
    write_iops: float = 1.10e6
    read_bw: float = 13.6e9            # B/s sequential
    write_bw: float = 12.0e9
    request_latency: float = 55e-6     # s, single 4 KiB random read
    queue_depth: int = 256

    def read_time(self, requests: float, bytes_: float) -> float:
        return max(requests / self.read_iops, bytes_ / self.read_bw)

    def write_time(self, requests: float, bytes_: float) -> float:
        return max(requests / self.write_iops, bytes_ / self.write_bw)

    def op_latency(self, requests: float, bytes_: float,
                   serial_rounds: float) -> float:
        """Latency of one logical op whose I/O happens in ``serial_rounds``
        dependent rounds (graph hops are serial; intra-round I/O overlaps)."""
        return (serial_rounds * self.request_latency
                + self.read_time(requests, bytes_))


@dataclasses.dataclass(frozen=True)
class HBMModel:
    """TPU slow-tier analogue: gathers from HBM (819 GB/s, v5e).

    The per-request term models gather descriptor overhead — tiny, but keeps
    CASR's request-count-vs-bytes tradeoff meaningful on-TPU (DESIGN.md §2).
    """

    bw: float = 819e9
    request_latency: float = 1e-6
    read_iops: float = 50e6
    write_iops: float = 50e6
    read_bw: float = 819e9
    write_bw: float = 819e9
    queue_depth: int = 1024

    def read_time(self, requests, bytes_):
        return max(requests / self.read_iops, bytes_ / self.read_bw)

    def write_time(self, requests, bytes_):
        return max(requests / self.write_iops, bytes_ / self.write_bw)

    def op_latency(self, requests, bytes_, serial_rounds):
        return (serial_rounds * self.request_latency
                + self.read_time(requests, bytes_))
