"""A run's traffic, made on the device from ``--seed``.

``query_stream`` and ``insert_stream`` are copies of
``repro.data.pipeline``'s generators, kept here so that a change to the
program cannot move the traffic the benchmark measures.

A mix (``mixes/<name>.json``) fixes the shape of a round: ``insert_waves``
waves of ``insert_wave`` vectors, then ``search_waves`` waves of
``search_wave`` queries, and the size of the deployment's query set
(``query_set``).  The traffic of a run is a pure function of
(configuration, mix, seed): round ``r``'s inserts draw from
``fold_in(key, r)``; its queries are the next ones of the query set, which
every pass through it takes in a new order drawn from the seed.  So every
seed searches the same queries, and a window of a whole number of passes
does the same search work whatever its seed.  :func:`make_chunk` makes
``CHUNK_ROUNDS`` rounds in one jitted call, so a run draws as many rounds
as its window takes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

CHUNK_ROUNDS = 16       # rounds made per call; the traffic does not depend on it


def query_stream(key: jax.Array, cents: jax.Array, n: int, *,
                 noise: float) -> jax.Array:
    """Queries drawn from the corpus's own cluster mixture."""
    ka, kv = jax.random.split(key)
    assign = jax.random.randint(ka, (n,), 0, cents.shape[0])
    return cents[assign] + noise * jax.random.normal(
        kv, (n, cents.shape[1]), jnp.float32)


def insert_stream(key: jax.Array, cents: jax.Array, n: int, *,
                  noise: float, drift: float) -> jax.Array:
    """Fresh vectors from the mixture with every centre shifted by
    ``drift`` times a standard normal draw (new regions of the space)."""
    ka, kv, kd = jax.random.split(key, 3)
    assign = jax.random.randint(ka, (n,), 0, cents.shape[0])
    shift = drift * jax.random.normal(kd, cents.shape, jnp.float32)
    return (cents + shift)[assign] + noise * jax.random.normal(
        kv, (n, cents.shape[1]), jnp.float32)


def seed_key(seed: int) -> jax.Array:
    """A key for any whole-number seed, 64-bit ones included."""
    if seed < 0:
        raise ValueError(f"seed {seed} is negative")
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, seed >> 32)


@functools.partial(jax.jit, static_argnames=("insert_waves", "insert_wave",
                                             "search_waves", "search_wave",
                                             "noise", "drift"))
def make_chunk(key, order_key, first, cents, query_set, *, insert_waves,
               insert_wave, search_waves, search_wave, noise, drift):
    """Rounds ``first .. first + CHUNK_ROUNDS - 1`` of a run's traffic:
    inserts [CHUNK_ROUNDS, insert_waves, insert_wave, dim] and queries
    [CHUNK_ROUNDS, search_waves, search_wave, dim].  Each round draws its
    own inserts from ``key``, with its own centre shift.  The queries are
    the deployment's ``query_set``, taken pass after pass: pass ``p``
    takes every query once, in an order drawn from ``order_key`` and
    ``p``, so every seed searches the same queries."""
    dim = cents.shape[1]
    per_round = search_waves * search_wave
    n_set = query_set.shape[0]

    def one(r):
        ins = insert_stream(jax.random.fold_in(key, r), cents,
                            insert_waves * insert_wave, noise=noise,
                            drift=drift)
        p, j = jnp.divmod(r, n_set // per_round)
        order = jax.random.permutation(jax.random.fold_in(order_key, p),
                                       n_set)
        qs = query_set[jax.lax.dynamic_slice(order, (j * per_round,),
                                             (per_round,))]
        return (ins.reshape(insert_waves, insert_wave, dim),
                qs.reshape(search_waves, search_wave, dim))

    return jax.vmap(one)(first + jnp.arange(CHUNK_ROUNDS))


@functools.partial(jax.jit, static_argnames=("n", "noise"))
def make_query_set(data_seed, cents, *, n, noise):
    """The deployment's ``n`` queries, from the corpus's own mixture and
    ``data_seed``: fixed, as a dataset's query file is."""
    key = jax.random.fold_in(jax.random.PRNGKey(data_seed), 1)
    return query_stream(key, cents, n, noise=noise)


class Traffic:
    """The run's traffic, made a chunk at a time on the device."""

    def __init__(self, cfg: dict, mix: dict, seed: int, cents: jax.Array):
        self.mix = mix
        self.key, self.order_key = jax.random.split(seed_key(seed))
        self.cents = cents
        self.noise = float(cfg["noise"])
        n = mix["query_set"]
        if n % (mix["search_waves"] * mix["search_wave"]):
            raise ValueError(f"query_set {n} is not a whole number of "
                             "rounds' queries")
        self.query_set = make_query_set(cfg["data_seed"], cents, n=n,
                                        noise=self.noise)
        self._chunks: dict[int, tuple[jax.Array, jax.Array]] = {}

    def chunk(self, c: int):
        if c not in self._chunks:
            m = self.mix
            self._chunks[c] = make_chunk(
                self.key, self.order_key, jnp.int32(c * CHUNK_ROUNDS),
                self.cents, self.query_set,
                insert_waves=m["insert_waves"],
                insert_wave=m["insert_wave"],
                search_waves=m["search_waves"],
                search_wave=m["search_wave"], noise=self.noise,
                drift=float(m["insert_drift"]))
        return self._chunks[c]

    def round(self, r: int):
        """(inserts [insert_waves, insert_wave, dim],
        queries [search_waves, search_wave, dim]) of round ``r``."""
        c, i = divmod(r, CHUNK_ROUNDS)
        ins, qs = self.chunk(c)
        return ins[i], qs[i]
