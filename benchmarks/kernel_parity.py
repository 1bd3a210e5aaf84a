"""Kernel parity smoke: the Pallas kernels (interpret mode) vs the oracles.

The engine runs the ``ref.py`` jnp ops (the ADC as a one-hot select on
TPU, a gather elsewhere); the three Pallas kernels are standalone code,
validated here against those oracles via the interpreter over a small
shape sweep per kernel.

Writes ``experiments/kernels/parity.json``; exits non-zero on any
mismatch.  Wired into ``scripts/ci.sh``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks import common as Cm
from repro.kernels import ref
from repro.kernels.pq_adc import adc_distance_pallas
from repro.kernels.rerank_l2 import rerank_l2_pallas
from repro.kernels.topk_pool import pool_merge_pallas

KEY = jax.random.PRNGKey(3)


def run() -> list[str]:
    rows = []
    blob = {"kernels": {}}

    cases = []
    for m, b in ((8, 33), (32, 256), (96, 500)):
        lut = jax.random.uniform(jax.random.fold_in(KEY, m), (m, 256))
        codes = jax.random.randint(jax.random.fold_in(KEY, b), (b, m),
                                   0, 256).astype(jnp.uint8)
        got = adc_distance_pallas(lut, codes, interpret=True)
        cases.append(("adc_distance", f"m{m}_b{b}", got,
                      ref.adc_distance_ref(lut, codes), 1e-4))
    for p, d, g in ((17, 48, 4), (100, 768, 8)):
        q = jax.random.normal(jax.random.fold_in(KEY, d), (d,))
        xs = jax.random.normal(jax.random.fold_in(KEY, p), (p, d))
        got = rerank_l2_pallas(q, xs, group=g, interpret=True)
        cases.append(("rerank_l2", f"p{p}_d{d}", got,
                      ref.rerank_l2_ref(q, xs), 1e-3))
    for p, n in ((16, 40), (64, 384)):
        pd = jax.random.uniform(jax.random.fold_in(KEY, p), (p,))
        nd = jax.random.uniform(jax.random.fold_in(KEY, n), (n,))
        pi = jnp.arange(p, dtype=jnp.int32)
        ni = 1000 + jnp.arange(n, dtype=jnp.int32)
        gd, gi = pool_merge_pallas(pd, pi, nd, ni, interpret=True)
        wd, wi = ref.pool_merge_ref(pd, pi, nd, ni)
        cases.append(("pool_merge_d", f"p{p}_n{n}", gd, wd, 1e-6))
        cases.append(("pool_merge_ids", f"p{p}_n{n}",
                      gi.astype(jnp.float32), wi.astype(jnp.float32), 0.0))

    ok = True
    for kernel, label, got, want, tol in cases:
        err = float(jnp.max(jnp.abs(jnp.asarray(got, jnp.float32) -
                                    jnp.asarray(want, jnp.float32))))
        passed = err <= tol if tol else err == 0.0
        ok &= passed
        blob["kernels"][f"{kernel}_{label}"] = {
            "max_abs_err": err, "tol": tol, "pass": bool(passed)}
        rows.append(Cm.fmt_row(f"parity_{kernel}_{label}",
                               max_abs_err=err, ok=int(passed)))

    path = Cm.write_json("kernels/parity.json", blob)
    rows.append(f"# wrote {path}")
    if not ok:
        raise SystemExit("kernel interpret-vs-ref parity FAILED")
    return rows


if __name__ == "__main__":
    for r in run():
        print(r)
