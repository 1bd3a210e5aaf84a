"""The deployments the engine runs on the chip.

Each is one of the paper's Table 1 corpora, sized as a deployment: its
width, dtype and squared-L2 metric, 1,000,000 base vectors plus 65,536
slots of insert headroom, and the engine settings that reach recall@10
of 0.90 on it.  The vectors are a seeded clustered mixture made on the
device, in place of the dataset's files.

- ``DEEP96``: DEEP (Babenko & Lempitsky, 2016), 96-d float32 image
  descriptors.  A 4 KiB page holds about ten of its vectors.
- ``TEXT768``: MS MARCO passages (Bajaj et al., 2016) embedded at
  BERT-base width, 768-d float32.  A packed page holds one vector.
"""
from __future__ import annotations

import dataclasses

import jax

from repro.core import EngineSpec, preset
from repro.data import make_clustered

N_BASE = 1_000_000
HEADROOM = 65_536
N_CLUSTERS = 24
SCALE = 3.0


@dataclasses.dataclass(frozen=True)
class Deployment:
    dim: int
    noise: float
    settings: dict

    def spec(self, n_base: int | None = None) -> EngineSpec:
        """The NAVIS engine over ``n_base`` base vectors plus the
        headroom."""
        n = N_BASE if n_base is None else n_base
        return preset("navis", dim=self.dim, n_max=n + HEADROOM,
                      **self.settings)

    def corpus(self, key: jax.Array, n: int):
        """(base vectors [n, dim] f32, cluster centres [n_clusters, dim])
        for ``key``; queries and inserts are drawn from the same centres
        with ``repro.data.query_stream`` / ``insert_stream`` at
        ``noise=self.noise``."""
        vecs, _, cents = make_clustered(key, n, self.dim,
                                        n_clusters=N_CLUSTERS, scale=SCALE,
                                        noise=self.noise)
        return vecs, cents


# r, pq_m and e_pos are those of the CPU-scale DEEP analogue (4,000
# vectors).  Raised for recall@10, which on this corpus falls with n (0.39
# at 50k vectors with that analogue's settings):
# - r_ent 32 -> 128: with 32 out-links the entrance graph leaves some
#   clusters without in-links, and every query of such a cluster misses
#   all ten neighbours (ent_pool 32 -> 64 with it: an insert's entrance
#   update draws its r_ent links from e_pos + ent_pool candidates);
# - e_search 40 -> 400, max_hops 96 -> 480, s_search 4 -> 100: the
#   search width and CASR group that recover the rest.
# Checked on one v5e at 100k (PERF.md): recall@10 about 0.96.
DEEP96 = Deployment(
    dim=96, noise=0.6,
    settings=dict(r=32, pq_m=32, e_search=400, e_pos=80, max_hops=480,
                  s_search=100, r_ent=128, ent_pool=64))

# r, pq_m and e_pos are those of the CPU-scale text analogue (3,000
# vectors); r_ent and ent_pool are DEEP's.  At 768-d the i.i.d. cluster
# noise crowds the distances together and PQ orders them poorly, so
# recall needs a pool that covers a large share of the query's cluster,
# which grows with the corpus, and an exact rerank of half of it: CASR
# groups of 200 stop before the true neighbours, deep in the PQ order
# (recall@10 about 0.80 at any pool at 100,000 vectors). max_hops stops
# the few lanes that go on past a pool's convergence (e_search /
# beam_width hops), which would set the wave's time.  The cheapest
# setting of a sweep at 100,000 vectors under the mixed traffic whose
# worst miss over five seeds is at most 0.06 (PERF.md).
TEXT768 = Deployment(
    dim=768, noise=1.0,
    settings=dict(r=48, pq_m=96, e_search=2800, e_pos=64, max_hops=750,
                  s_search=1400, r_ent=128, ent_pool=64))
