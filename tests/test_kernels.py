"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps + properties."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

from repro.kernels import ref
from repro.kernels.pq_adc import adc_distance_pallas
from repro.kernels.rerank_l2 import rerank_l2_pallas
from repro.kernels.topk_pool import pool_merge_pallas

KEY = jax.random.PRNGKey(7)


# ---------------------------------------------------------------------------
# pq_adc
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("m", [8, 32, 128])
@pytest.mark.parametrize("b", [1, 100, 257, 512])
def test_adc_shapes(m, b):
    lut = jax.random.uniform(KEY, (m, 256))
    codes = jax.random.randint(KEY, (b, m), 0, 256).astype(jnp.uint8)
    got = adc_distance_pallas(lut, codes, interpret=True)
    np.testing.assert_allclose(got, ref.adc_distance_ref(lut, codes),
                               rtol=1e-5)


@pytest.mark.parametrize("block_b", [32, 128, 512])
def test_adc_block_sweep(block_b):
    lut = jax.random.uniform(KEY, (16, 256))
    codes = jax.random.randint(KEY, (300, 16), 0, 256).astype(jnp.uint8)
    got = adc_distance_pallas(lut, codes, block_b=block_b, interpret=True)
    np.testing.assert_allclose(got, ref.adc_distance_ref(lut, codes),
                               rtol=1e-5)


@settings(max_examples=15, deadline=None)
@given(m=st.sampled_from([4, 16, 64]), b=st.integers(1, 80),
       seed=st.integers(0, 2 ** 16))
def test_adc_hypothesis(m, b, seed):
    k = jax.random.PRNGKey(seed)
    lut = jax.random.uniform(k, (m, 256), minval=0.0, maxval=100.0)
    codes = jax.random.randint(k, (b, m), 0, 256).astype(jnp.uint8)
    got = adc_distance_pallas(lut, codes, interpret=True)
    np.testing.assert_allclose(got, ref.adc_distance_ref(lut, codes),
                               rtol=1e-4)


# ---------------------------------------------------------------------------
# the engine's ADC: one-hot select (TPU) against the gather (elsewhere)
# ---------------------------------------------------------------------------

def _wave(dtype=jnp.float32):
    """A search wave at deep96's widths: 64 LUTs of [M=32, 256] and their
    [B=128, M] candidate codes, with the extreme codes 0 and 255 in it."""
    k1, k2 = jax.random.split(KEY)
    luts = jax.random.uniform(k1, (64, 32, 256), dtype, maxval=50.0)
    codes = jax.random.randint(k2, (64, 128, 32), 0, 256).astype(jnp.uint8)
    return luts, codes.at[:, 0].set(0).at[:, 1].set(255)


def _per_subspace(adc, luts, codes):
    """[L, M, B]: each subspace's picked LUT value, as ``adc`` gives it
    when handed that subspace alone."""
    one = jax.vmap(lambda lut, c: adc(lut[None], c[:, None]),
                   in_axes=(0, 1))
    return jax.jit(jax.vmap(one))(luts, codes)


def test_adc_onehot_picks_equal_the_gather_bit_for_bit():
    luts, codes = _wave()
    np.testing.assert_array_equal(
        _per_subspace(ref.adc_distance_onehot, luts, codes),
        _per_subspace(ref.adc_distance_ref, luts, codes))


def test_adc_onehot_sums_agree_to_f32_rounding():
    luts, codes = _wave()
    got = jax.jit(jax.vmap(ref.adc_distance_onehot))(luts, codes)
    want = jax.jit(jax.vmap(ref.adc_distance_ref))(luts, codes)
    assert got.dtype == jnp.float32
    np.testing.assert_allclose(got, want, rtol=1e-6)


def test_adc_onehot_keeps_f64_under_x64():
    with jax.enable_x64(True):
        luts, codes = _wave(jnp.float64)
        got = jax.jit(jax.vmap(ref.adc_distance_onehot))(luts[:2],
                                                         codes[:2])
        assert got.dtype == jnp.float64
        np.testing.assert_allclose(
            got, jax.vmap(ref.adc_distance_ref)(luts[:2], codes[:2]),
            rtol=1e-12)


@pytest.mark.parametrize("platform,gathers", [("cpu", True),
                                              ("tpu", False)])
def test_adc_dispatch_picks_the_form_at_lowering(platform, gathers):
    """Lowered for CPU the engine's ADC is the gather; lowered for TPU
    it is the one-hot select, with no gather left."""
    luts, codes = _wave()
    text = jax.jit(jax.vmap(ref.adc_distance)).trace(luts, codes).lower(
        lowering_platforms=(platform,)).as_text()
    assert ("gather" in text) is gathers
    assert ("iota" in text) is not gathers


# ---------------------------------------------------------------------------
# rerank_l2
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("d", [32, 96, 768])
@pytest.mark.parametrize("p,group", [(1, 1), (40, 4), (100, 8), (99, 16)])
def test_rerank_shapes(d, p, group):
    q = jax.random.normal(KEY, (d,))
    xs = jax.random.normal(jax.random.fold_in(KEY, 1), (p, d))
    got = rerank_l2_pallas(q, xs, group=group, interpret=True)
    np.testing.assert_allclose(got, ref.rerank_l2_ref(q, xs), rtol=2e-4,
                               atol=2e-3)


def test_rerank_dtype_bf16_inputs():
    q = jax.random.normal(KEY, (64,)).astype(jnp.bfloat16)
    xs = jax.random.normal(KEY, (33, 64)).astype(jnp.bfloat16)
    got = rerank_l2_pallas(q, xs, group=8, interpret=True)
    want = ref.rerank_l2_ref(q, xs)
    np.testing.assert_allclose(got, want, rtol=2e-2, atol=1e-1)


@settings(max_examples=15, deadline=None)
@given(p=st.integers(1, 60), d=st.sampled_from([8, 64, 256]),
       group=st.sampled_from([1, 4, 8]), seed=st.integers(0, 2 ** 16))
def test_rerank_hypothesis(p, d, group, seed):
    k = jax.random.PRNGKey(seed)
    q = jax.random.normal(k, (d,))
    xs = jax.random.normal(jax.random.fold_in(k, 1), (p, d))
    got = rerank_l2_pallas(q, xs, group=group, interpret=True)
    np.testing.assert_allclose(got, ref.rerank_l2_ref(q, xs), rtol=2e-4,
                               atol=2e-3)


def test_rerank_self_distance_zero():
    xs = jax.random.normal(KEY, (5, 32))
    got = rerank_l2_pallas(xs[2], xs, interpret=True)
    assert float(got[2]) < 1e-4


# ---------------------------------------------------------------------------
# topk_pool
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("p,q", [(10, 10), (40, 64), (100, 300)])
def test_merge_shapes(p, q):
    pd = jax.random.uniform(KEY, (p,))
    nd = jax.random.uniform(jax.random.fold_in(KEY, 3), (q,))
    pi = jnp.arange(p, dtype=jnp.int32)
    ni = 10_000 + jnp.arange(q, dtype=jnp.int32)
    gd, gi = pool_merge_pallas(pd, pi, nd, ni, interpret=True)
    wd, wi = ref.pool_merge_ref(pd, pi, nd, ni)
    np.testing.assert_allclose(gd, wd, rtol=1e-6)
    np.testing.assert_array_equal(gi, wi)


def test_merge_with_inf_padding():
    INF = jnp.float32(3.4e38)
    pd = jnp.array([1.0, 2.0, INF, INF])
    pi = jnp.array([5, 6, -1, -1], jnp.int32)
    nd = jnp.array([0.5, 3.0])
    ni = jnp.array([7, 8], jnp.int32)
    gd, gi = pool_merge_pallas(pd, pi, nd, ni, interpret=True)
    np.testing.assert_array_equal(gi, [7, 5, 6, 8])


@settings(max_examples=15, deadline=None)
@given(p=st.integers(2, 50), q=st.integers(1, 80),
       seed=st.integers(0, 2 ** 16))
def test_merge_hypothesis(p, q, seed):
    k = jax.random.PRNGKey(seed)
    pd = jax.random.uniform(k, (p,))
    nd = jax.random.uniform(jax.random.fold_in(k, 1), (q,))
    pi = jnp.arange(p, dtype=jnp.int32)
    ni = 1000 + jnp.arange(q, dtype=jnp.int32)
    gd, gi = pool_merge_pallas(pd, pi, nd, ni, interpret=True)
    wd, wi = ref.pool_merge_ref(pd, pi, nd, ni)
    np.testing.assert_allclose(gd, wd, rtol=1e-6)
    np.testing.assert_array_equal(gi, wi)
    # result sorted ascending
    assert bool(jnp.all(jnp.diff(gd) >= 0))


def _merge_by_argsort(pool_d, pool_ids, new_d, new_ids, *extra):
    """The merge as an argsort followed by a gather of each operand."""
    p = pool_d.shape[0]
    ops = [jnp.concatenate(pair)
           for pair in ((pool_d, new_d), (pool_ids, new_ids), *extra)]
    order = jnp.argsort(ops[0], stable=True)[:p]
    return tuple(o[order] for o in ops)


@pytest.mark.parametrize("p,q", [(4, 3), (40, 64), (400, 128),
                                 (2800, 192)])
def test_pool_merge_sort_equals_argsort_gather(p, q):
    """One multi-operand sort gives the argsort-and-gather merge bit for
    bit: ties inside the pool and between pool and new entries (distances
    drawn from a few values), INF/-1 padding on both sides, and a bool
    flag carried with its slot."""
    k = jax.random.fold_in(KEY, p * 1000 + q)
    kp, kn, kf, kpad = jax.random.split(k, 4)
    levels = jnp.float32(0.25) * jnp.arange(max(p // 8, 2))
    pd = jnp.sort(jax.random.choice(kp, levels, (p,)))
    nd = jax.random.choice(kn, levels, (q,))
    pi = jnp.arange(p, dtype=jnp.int32)
    ni = 100_000 + jnp.arange(q, dtype=jnp.int32)
    # pad the pool's tail and some new entries with INF / -1
    n_pad = p // 4
    pd = pd.at[p - n_pad:].set(ref.INF)
    pi = pi.at[p - n_pad:].set(-1)
    npad = jax.random.bernoulli(kpad, 0.3, (q,))
    nd = jnp.where(npad, ref.INF, nd)
    ni = jnp.where(npad, -1, ni)
    pf = jax.random.bernoulli(kf, 0.5, (p,)) & (pi >= 0)
    nf = jnp.zeros((q,), bool)

    assert jnp.unique(pd).shape[0] < p           # ties inside the pool
    assert bool(jnp.isin(nd, pd).any())          # ties across pool and new
    want_d, want_i, want_f = _merge_by_argsort(pd, pi, nd, ni, (pf, nf))
    got_d, got_i, got_f = ref.pool_merge_ref(pd, pi, nd, ni, (pf, nf))
    for got, want in ((got_d, want_d), (got_i, want_i), (got_f, want_f)):
        assert got.dtype == want.dtype and got.shape == (p,)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # without extra operands the merge keeps its two outputs
    two = ref.pool_merge_ref(pd, pi, nd, ni)
    assert len(two) == 2
    np.testing.assert_array_equal(np.asarray(two[1]), np.asarray(want_i))
    # the flag stays on its id: each kept pool id keeps its own flag
    flag_of = dict(zip(np.asarray(pi).tolist(), np.asarray(pf).tolist()))
    for i, f in zip(np.asarray(got_i).tolist(), np.asarray(got_f).tolist()):
        assert f == flag_of.get(i, False)         # new entries: False
