"""Product quantisation: codebook training (Lloyd), encoding, ADC distances.

PQ vectors live in host memory in the paper (and in VMEM-tiled form on TPU —
see kernels/pq_adc.py for the Pallas version; this module is the pure-jnp
reference used by the engine and as the kernel oracle).
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

# The codecs are f32: on TPU a DEFAULT-precision f32 matmul runs in bf16
# passes, which would train, encode and tabulate against rounded vectors.
HIGHEST = jax.lax.Precision.HIGHEST


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class PQCodec:
    codebooks: jax.Array      # [M, 256, dsub] float32

    @property
    def m(self) -> int:
        return self.codebooks.shape[0]

    @property
    def dsub(self) -> int:
        return self.codebooks.shape[2]

    @property
    def dim(self) -> int:
        return self.m * self.dsub


def train_pq(key: jax.Array, sample: jax.Array, m: int,
             iters: int = 8) -> PQCodec:
    """Lloyd k-means per subspace.  sample: [S, D]; D % m == 0."""
    s, d = sample.shape
    assert d % m == 0, (d, m)
    dsub = d // m
    sub = sample.reshape(s, m, dsub).transpose(1, 0, 2)      # [M, S, dsub]
    init_idx = jax.random.choice(key, s, (256,), replace=s < 256)
    cents = sub[:, init_idx]                                  # [M, 256, dsub]

    def step(cents, _):
        d2 = (jnp.sum(sub ** 2, -1)[:, :, None]
              - 2 * jnp.einsum("msd,mkd->msk", sub, cents, precision=HIGHEST)
              + jnp.sum(cents ** 2, -1)[:, None, :])          # [M, S, 256]
        assign = jnp.argmin(d2, -1)                           # [M, S]
        onehot = jax.nn.one_hot(assign, 256, dtype=sub.dtype)  # [M, S, 256]
        sums = jnp.einsum("msk,msd->mkd", onehot, sub, precision=HIGHEST)
        counts = onehot.sum(1)[..., None]
        new = jnp.where(counts > 0, sums / jnp.maximum(counts, 1), cents)
        return new, None

    cents, _ = jax.lax.scan(step, cents, None, length=iters)
    return PQCodec(codebooks=cents)


# bytes of one [M, rows, 256] f32 distance temporary of ``encode``: 16,384
# rows at M=32
ENCODE_BYTES = 512 * 2 ** 20


def encode_rows(m: int) -> int:
    """Rows ``encode`` takes at a time for ``m`` subspaces."""
    return max(ENCODE_BYTES // (m * 256 * 4), 1)


def encode(codec: PQCodec, x: jax.Array) -> jax.Array:
    """x: [N, D] -> codes uint8 [N, M].

    Rows are encoded ``encode_rows(M)`` at a time, so each [M, rows, 256]
    temporary holds ``ENCODE_BYTES`` whatever M is: run eagerly (as
    ``Engine.build`` does) one pass over a 1M-vector corpus would
    materialise 32 GiB of distances at M=32 and 96 GiB at M=96.  A row's
    code does not depend on the rows encoded with it."""
    rows = encode_rows(codec.m)
    if x.shape[0] > rows:
        return jnp.concatenate([encode(codec, x[i:i + rows])
                                for i in range(0, x.shape[0], rows)])
    n, d = x.shape
    sub = x.reshape(n, codec.m, codec.dsub).transpose(1, 0, 2)
    d2 = (jnp.sum(sub ** 2, -1)[:, :, None]
          - 2 * jnp.einsum("mnd,mkd->mnk", sub, codec.codebooks,
                       precision=HIGHEST)
          + jnp.sum(codec.codebooks ** 2, -1)[:, None, :])
    return jnp.argmin(d2, -1).T.astype(jnp.uint8)             # [N, M]


def adc_lut(codec: PQCodec, q: jax.Array) -> jax.Array:
    """Asymmetric-distance LUT for query q: [M, 256] of squared-L2 parts."""
    qs = q.reshape(codec.m, 1, codec.dsub)
    return jnp.sum((codec.codebooks - qs) ** 2, -1)           # [M, 256]


def adc_distance(lut: jax.Array, codes: jax.Array) -> jax.Array:
    """codes: [B, M] uint8 -> squared-L2 estimates [B]."""
    m = lut.shape[0]
    idx = codes.astype(jnp.int32)                             # [B, M]
    vals = jnp.take_along_axis(lut, idx.T, axis=1)            # [M, B]
    return vals.sum(0)


def exact_l2(q: jax.Array, x: jax.Array) -> jax.Array:
    """Squared L2 between q [D] and rows of x [B, D]."""
    diff = x - q[None]
    return jnp.sum(diff * diff, axis=-1)


def decode_codes(codec: PQCodec, codes: jax.Array) -> jax.Array:
    """Reconstruct ('deflate') PQ codes back into approximate vectors.

    codes: [N, M] uint8 -> [N, M*dsub] float32.
    """
    idx = codes.astype(jnp.int32)                             # [N, M]
    gathered = jax.vmap(lambda cb, ix: cb[ix], in_axes=(0, 1),
                        out_axes=1)(codec.codebooks, idx)      # [N, M, dsub]
    return gathered.reshape(codes.shape[0], -1)


# ---------------------------------------------------------------------------
# Symmetric (code-to-code) distances — used where no full vector is in memory
# (entrance-graph maintenance, structural-update pruning).
# ---------------------------------------------------------------------------

def sym_tables(codec: PQCodec) -> jax.Array:
    """Cross-centroid distance tables T[m, a, b] = ||c_ma - c_mb||^2."""
    cb = codec.codebooks                                      # [M, 256, dsub]
    d2 = (jnp.sum(cb ** 2, -1)[:, :, None]
          - 2 * jnp.einsum("mad,mbd->mab", cb, cb, precision=HIGHEST)
          + jnp.sum(cb ** 2, -1)[:, None, :])
    return jnp.maximum(d2, 0.0)                               # [M, 256, 256]


@jax.named_scope("navis.sym")
def sym_distance(tables: jax.Array, code_a: jax.Array,
                 code_b: jax.Array) -> jax.Array:
    """code_a: [M]; code_b: [B, M] -> approx squared L2 [B].  Its ops
    carry the scope ``navis.sym`` under their caller's stage."""
    m = tables.shape[0]
    a = code_a.astype(jnp.int32)                              # [M]
    b = code_b.astype(jnp.int32)                              # [B, M]
    rows = tables[jnp.arange(m), a]                           # [M, 256]
    return jnp.take_along_axis(rows, b.T, axis=1).sum(0)      # [B]


def sym_distance_matrix(tables: jax.Array, codes: jax.Array) -> jax.Array:
    """All-pairs symmetric PQ distances for a code set [S, M] -> [S, S].

    Rows are computed 128 at a time, so the gather temporaries are
    [128, M, S] rather than [S, M, S] (an entrance of 10k members would
    otherwise need 12 GiB of them)."""
    return jax.lax.map(lambda c: sym_distance(tables, c, codes), codes,
                       batch_size=128)
