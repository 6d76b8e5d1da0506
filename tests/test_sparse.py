"""Sparse module tests: CSR construction, GSE-SEM CSR, SpMV operators."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.sparse import csr as C
from repro.sparse import generators as G
from repro.sparse import spmv as S


def _dense(a):
    rp = np.asarray(a.rowptr)
    col = np.asarray(a.col)
    val = np.asarray(a.val)
    m, n = a.shape
    d = np.zeros((m, n))
    for i in range(m):
        for j in range(rp[i], rp[i + 1]):
            d[i, col[j]] += val[j]
    return d


def test_from_coo_sums_duplicates():
    a = C.from_coo([0, 0, 1], [1, 1, 0], [2.0, 3.0, 4.0], (2, 2))
    d = _dense(a)
    np.testing.assert_array_equal(d, [[0, 5], [4, 0]])


def test_poisson2d_spd_structure():
    a = G.poisson2d(8)
    d = _dense(a)
    np.testing.assert_array_equal(d, d.T)
    w = np.linalg.eigvalsh(d)
    assert w.min() > 0  # SPD


def test_convdiff_asymmetric():
    a = G.convection_diffusion_2d(8)
    d = _dense(a)
    assert not np.allclose(d, d.T)


def test_spmv_matches_dense():
    a = G.poisson2d(10)
    x = np.random.default_rng(0).normal(size=a.shape[1])
    y = np.asarray(S.spmv(a, jnp.asarray(x)))
    np.testing.assert_allclose(y, _dense(a) @ x, rtol=1e-12)


@pytest.mark.parametrize("fmt", [jnp.float32, jnp.bfloat16, jnp.float16])
def test_spmv_low_precision_storage(fmt):
    a = G.poisson2d(10)
    x = np.ones(a.shape[1])
    y = np.asarray(S.spmv(a, jnp.asarray(x), store_dtype=fmt))
    ref = _dense(a) @ x
    # Stencil values (+-1, 4) are exact in all three formats.
    np.testing.assert_allclose(y, ref, rtol=1e-6)


@pytest.mark.parametrize("tag,rtol", [(1, 2e-4), (2, 2e-9), (3, 1e-14)])
def test_spmv_gse_precision_ladder(tag, rtol):
    a = G.random_spd(400, seed=1)
    g = C.pack_csr(a, k=8)
    x = np.random.default_rng(1).normal(size=a.shape[1])
    y = np.asarray(S.spmv_gse(g, jnp.asarray(x), tag=tag))
    ref = _dense(a) @ x
    np.testing.assert_allclose(y, ref, rtol=rtol, atol=rtol * np.abs(ref).max())


def test_gse_head_beats_fp16_bf16_on_clustered_values():
    """Paper Fig 6 claim: 16-bit GSE-SEM head error << FP16/BF16 error."""
    a = G.circuit_like(2000, seed=3)
    g = C.pack_csr(a, k=8)
    x = jnp.ones(a.shape[1], jnp.float64)  # paper sets x = 1
    ref = _dense(a) @ np.ones(a.shape[1])
    err_gse = np.abs(np.asarray(S.spmv_gse(g, x, tag=1)) - ref).max()
    err_bf16 = np.abs(np.asarray(S.spmv(a, x, store_dtype=jnp.bfloat16)) - ref).max()
    err_fp16 = np.abs(np.asarray(S.spmv(a, x, store_dtype=jnp.float16)) - ref).max()
    assert err_gse < err_bf16
    assert err_gse < err_fp16


def test_ell_roundtrip_and_spmv():
    a = G.convection_diffusion_2d(12)
    cols, vals, L = C.to_ell(a, lane=8)
    assert L % 8 == 0
    x = np.random.default_rng(2).normal(size=a.shape[1])
    y = np.asarray(S.spmv_ell(jnp.asarray(cols), jnp.asarray(vals), jnp.asarray(x)))
    np.testing.assert_allclose(y, _dense(a) @ x, rtol=1e-12)


def test_colpak_roundtrip():
    a = G.random_spd(300, seed=5)
    g = C.pack_csr(a, k=8)
    _, col = S.decode_gsecsr(g, tag=3)
    np.testing.assert_array_equal(C.csr_order(col, g.stored),
                                  np.asarray(a.col))


def test_colpak_overflow_guard():
    # 2^29 columns would collide with EI bits for k=8 -> must raise.
    big = C.CSR(
        rowptr=jnp.asarray([0, 1], jnp.int32),
        col=jnp.asarray([1 << 29], jnp.int32),
        val=jnp.asarray([1.0]),
        row_ids=jnp.asarray([0], jnp.int32),
        shape=(1, 1 << 30),
    )
    with pytest.raises(ValueError):
        C.pack_csr(big, k=8)


def test_generated_suites_have_clustered_exponents():
    from repro.core.gse import exponent_stats

    for name, a in G.spmv_suite(small=True).items():
        st = exponent_stats(np.asarray(a.val))
        # rescaled (unequilibrated) members intentionally spread exponents
        thresh = 0.25 if "_rs" in name or "overflow" in name else 0.5
        assert st["top8"] > thresh, (name, st["top8"])
