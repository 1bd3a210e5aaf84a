"""Pallas TPU kernel: PQ asymmetric-distance (ADC) lookup-accumulate.

Computes d[b] = Σ_m LUT[m, codes[b, m]] for a query's per-subspace distance
LUT against a block of PQ codes — the inner loop of every traversal hop
(§2.2 ②: neighbor examination uses PQ distances, not full vectors).

TPU adaptation (DESIGN.md §2): the CPU/GPU formulation is a random gather
per (b, m), which maps poorly onto the VPU (no fast per-lane gather from
VMEM tables).  We instead materialise each subspace's selection as a
comparison mask against a broadcasted iota and reduce with a
multiply-accumulate — an elementwise [TB, 256] op that the 8×128 VPU
executes at full width, with zero gathers.  The LUT (M×256 f32 ≤ 128 KiB
for M=128) is pinned whole in VMEM; codes stream through in [TB, M] tiles
via the grid pipeline (block t+1's HBM→VMEM copy overlaps block t's
compute — automatic double buffering).

The kernel does not compile for v5e, so it is off the engine's path.
The engine runs the same one-hot formulation in plain jnp on TPU
(``ref.adc_distance_onehot``, picked by ``ref.adc_distance`` when the
program is lowered) and the gather ``ref.adc_distance_ref`` elsewhere;
the gather is this kernel's allclose oracle.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _adc_kernel(lut_ref, codes_ref, out_ref, *, m: int):
    codes = codes_ref[...].astype(jnp.int32)          # [TB, M]
    tb = codes.shape[0]
    lanes = jax.lax.broadcasted_iota(jnp.int32, (tb, 256), 1)

    def body(mi, acc):
        sel = (lanes == codes[:, mi][:, None])        # [TB, 256] one-hot
        row = lut_ref[mi, :]                          # [256]
        return acc + jnp.sum(jnp.where(sel, row[None, :], 0.0), axis=1)

    acc = jax.lax.fori_loop(0, m, body, jnp.zeros((tb,), jnp.float32))
    out_ref[...] = acc


def adc_distance_pallas(lut: jax.Array, codes: jax.Array, *,
                        block_b: int = 256,
                        interpret: bool) -> jax.Array:
    """lut: [M, 256] f32; codes: [B, M] uint8 -> [B] f32 distances."""
    m = lut.shape[0]
    b = codes.shape[0]
    nb = -(-b // block_b)
    pad = nb * block_b - b
    if pad:
        codes = jnp.pad(codes, ((0, pad), (0, 0)))

    out = pl.pallas_call(
        functools.partial(_adc_kernel, m=m),
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((m, 256), lambda i: (0, 0)),       # LUT pinned
            pl.BlockSpec((block_b, m), lambda i: (i, 0)),   # codes stream
        ],
        out_specs=pl.BlockSpec((block_b,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((nb * block_b,), jnp.float32),
        interpret=interpret,
    )(lut.astype(jnp.float32), codes)
    return out[:b]
