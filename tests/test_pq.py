"""Product-quantisation properties."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

from repro.core import pq

KEY = jax.random.PRNGKey(3)


@pytest.fixture(scope="module")
def codec():
    x = jax.random.normal(KEY, (800, 32))
    return pq.train_pq(KEY, x, m=16), x


def test_adc_lut_matches_decoded(codec):
    cd, x = codec
    codes = pq.encode(cd, x[:50])
    q = x[60]
    lut = pq.adc_lut(cd, q)
    d_adc = pq.adc_distance(lut, codes)
    d_dec = pq.exact_l2(q, pq.decode_codes(cd, codes))
    np.testing.assert_allclose(d_adc, d_dec, rtol=1e-4, atol=1e-3)


def test_adc_correlates_with_exact(codec):
    cd, x = codec
    codes = pq.encode(cd, x)
    q = jax.random.normal(jax.random.fold_in(KEY, 1), (32,))
    lut = pq.adc_lut(cd, q)
    d_adc = np.asarray(pq.adc_distance(lut, codes))
    d_ex = np.asarray(pq.exact_l2(q, x))
    rho = np.corrcoef(d_adc, d_ex)[0, 1]
    assert rho > 0.8, rho


def test_quantisation_error_decreases_with_m():
    x = jax.random.normal(KEY, (600, 32))
    errs = []
    for m in (4, 8, 16):
        cd = pq.train_pq(KEY, x, m=m)
        rec = pq.decode_codes(cd, pq.encode(cd, x))
        errs.append(float(jnp.mean(jnp.sum((x - rec) ** 2, -1))))
    assert errs[0] > errs[1] > errs[2], errs


def test_sym_distance_properties(codec):
    cd, x = codec
    codes = pq.encode(cd, x[:30])
    t = pq.sym_tables(cd)
    # self-distance ~zero (fp accumulation)
    d_self = pq.sym_distance(t, codes[0], codes[:1])
    assert float(d_self[0]) < 1e-5
    # symmetry
    dab = float(pq.sym_distance(t, codes[0], codes[1:2])[0])
    dba = float(pq.sym_distance(t, codes[1], codes[0:1])[0])
    assert abs(dab - dba) < 1e-3
    # non-negativity
    m = pq.sym_distance_matrix(t, codes)
    assert float(m.min()) >= 0.0


def test_sym_matches_decoded_l2(codec):
    cd, x = codec
    codes = pq.encode(cd, x[:20])
    dec = pq.decode_codes(cd, codes)
    t = pq.sym_tables(cd)
    want = jnp.sum((dec[0] - dec) ** 2, axis=1)
    got = pq.sym_distance(t, codes[0], codes)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-3)


@settings(max_examples=10, deadline=None)
@given(m=st.sampled_from([2, 4, 8]), d_per=st.sampled_from([2, 4]),
       seed=st.integers(0, 2 ** 8))
def test_encode_codes_in_range(m, d_per, seed):
    k = jax.random.PRNGKey(seed)
    x = jax.random.normal(k, (100, m * d_per))
    cd = pq.train_pq(k, x, m=m, iters=2)
    codes = pq.encode(cd, x)
    assert codes.shape == (100, m)
    assert codes.dtype == jnp.uint8


def test_encode_is_nearest_centroid(codec):
    cd, x = codec
    codes = pq.encode(cd, x[:10])
    sub = x[:10].reshape(10, cd.m, cd.dsub)
    for i in range(10):
        for mm in range(0, cd.m, 5):
            d = jnp.sum((cd.codebooks[mm] - sub[i, mm]) ** 2, -1)
            assert int(codes[i, mm]) == int(jnp.argmin(d))


def test_encode_block_holds_its_byte_budget():
    """Each [M, rows, 256] f32 temporary of ``encode`` holds at most
    ``ENCODE_BYTES``: 16,384 rows at M=32, a third of that at M=96."""
    assert pq.encode_rows(32) == 16_384
    for m in (8, 32, 96, 128):
        assert m * pq.encode_rows(m) * 256 * 4 <= pq.ENCODE_BYTES
    assert pq.encode_rows(96) == 5_461


@pytest.mark.parametrize("dim,m", [(32, 16), (768, 96)])
def test_encode_codes_do_not_depend_on_the_block(dim, m, monkeypatch):
    """Codes encoded in blocks are bit-identical to one pass over every
    row, the last block short."""
    x = jax.random.normal(KEY, (300, dim))
    cd = pq.train_pq(KEY, x, m=m, iters=2)
    whole = np.asarray(pq.encode(cd, x))
    monkeypatch.setattr(pq, "ENCODE_BYTES", m * 256 * 4 * 37)
    assert pq.encode_rows(m) == 37
    np.testing.assert_array_equal(np.asarray(pq.encode(cd, x)), whole)
