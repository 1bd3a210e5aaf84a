"""The DEEP1M-shaped deployment the engine runs on the chip.

DEEP (Babenko & Lempitsky, 2016) is the 96-d float32 image-descriptor
corpus of the paper's Table 1, searched under squared L2.
``benchmarks/common.py``'s ``deep-like`` set mirrors it at CPU scale with
the same r, pq_m, pool sizes and cluster mixture; here it is sized as a
deployment: 1,000,000 base vectors plus 65,536 slots of insert headroom.
The vectors are a seeded clustered mixture made on the device.
"""
from __future__ import annotations

import jax

from repro.core import EngineSpec, preset
from repro.data import make_clustered

DIM = 96
N_BASE = 1_000_000
HEADROOM = 65_536
N_CLUSTERS = 24
NOISE = 0.6
# r, pq_m and e_pos are deep-like's.  Raised for recall@10, which on this
# corpus falls with n (0.39 at 50k vectors with deep-like's settings):
# - r_ent 32 -> 128: with 32 out-links the entrance graph leaves some
#   clusters without in-links, and every query of such a cluster misses
#   all ten neighbours (ent_pool 32 -> 64 with it: an insert's entrance
#   update draws its r_ent links from e_pos + ent_pool candidates);
# - e_search 40 -> 400, max_hops 96 -> 480, s_search 4 -> 100: the
#   search width and CASR group that recover the rest.
# These were checked only at 20k-50k vectors: recall@10 0.98-1.0 in CPU
# builds and on one v5e at 20k (PERF.md, section 6).  Whether they hold
# at N_BASE is not known (PERF.md, section 7).
SETTINGS = dict(r=32, pq_m=32, e_search=400, e_pos=80, max_hops=480,
                s_search=100, r_ent=128, ent_pool=64)


def spec(n_base: int = N_BASE) -> EngineSpec:
    """The NAVIS engine over ``n_base`` base vectors plus the headroom."""
    return preset("navis", dim=DIM, n_max=n_base + HEADROOM, **SETTINGS)


def corpus(key: jax.Array, n: int):
    """(base vectors [n, 96] f32, cluster centres [24, 96]) for ``key``;
    queries and inserts are drawn from the same centres with
    ``repro.data.query_stream`` / ``insert_stream`` at ``noise=NOISE``."""
    vecs, _, cents = make_clustered(key, n, DIM, n_clusters=N_CLUSTERS,
                                    noise=NOISE)
    return vecs, cents
