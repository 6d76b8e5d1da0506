"""Property-based SELL-C-σ sweeps (hypothesis; DESIGN.md §12).

Guarded with ``pytest.importorskip`` so tier-1 collection passes from a
clean checkout (hypothesis is optional -- see requirements.txt); the
deterministic twins of these sweeps live in tests/test_sell.py.

The properties are the pipeline's whole contract: over random row-skew,
slice/σ parameters, tags 1/2/3 and nrhs in {1, 4},

  * the packed layout is a bit-exact permutation of the CSR store
    (segment + row-permutation round trip);
  * SELL reference SpMV/SpMM are BITWISE equal to the CSR reference;
  * the bucketed Pallas kernels are BITWISE equal to the uniform-ELL
    kernels.
"""
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis")

from hypothesis import given, settings, strategies as st  # noqa: E402

from repro.kernels import ops  # noqa: E402
from repro.sparse.csr import from_coo, pack_csr, pack_sell  # noqa: E402
from repro.sparse.spmv import spmm_gse, spmv_gse  # noqa: E402


def _skew_csr(n, skew, seed):
    """Random matrix with controllable row-length skew (a few rows can be
    orders of magnitude longer than the median)."""
    rng = np.random.default_rng(seed)
    deg = np.minimum((rng.pareto(skew, n) * 3 + 1).astype(np.int64), n)
    deg[rng.integers(0, n)] = n  # at least one (near-)dense row
    rows = np.repeat(np.arange(n), deg)
    cols = np.concatenate(
        [rng.choice(n, size=d, replace=False) for d in deg]
    )
    bins = rng.choice([-2, -1, 0, 1], size=rows.size)
    vals = rng.uniform(1.0, 2.0, rows.size) * np.exp2(bins)
    vals *= rng.choice([-1.0, 1.0], size=vals.shape)
    return from_coo(rows, cols, vals, (n, n))


_case = dict(
    n=st.integers(2, 30).map(lambda k: k * 10),
    skew=st.sampled_from([0.8, 1.2, 2.0]),
    sigma=st.sampled_from([None, 16, 64]),
    tag=st.sampled_from([1, 2, 3]),
    seed=st.integers(0, 2**16),
)


@settings(max_examples=12, deadline=None)
@given(**_case)
def test_prop_sell_round_trip(n, skew, sigma, tag, seed):
    g = pack_csr(_skew_csr(n, skew, seed), k=8)
    s = pack_sell(g, sigma=sigma)
    gather = np.asarray(s.gather)
    for name in ("colpak", "head", "tail1", "tail2"):
        # A slot-major gather's padding reads one padding entry appended
        # after the buckets: zero segments reading column n.
        pad = n if name == "colpak" else 0
        flat = np.concatenate(
            [np.asarray(b).reshape(-1) for b in getattr(s, name)] + [[pad]]
        )
        np.testing.assert_array_equal(flat[gather],
                                      np.asarray(getattr(g, name)))
    perm = np.asarray(s.perm)
    np.testing.assert_array_equal(np.sort(perm[perm >= 0]), np.arange(n))
    np.testing.assert_array_equal(perm[np.asarray(s.unperm)], np.arange(n))


@settings(max_examples=12, deadline=None)
@given(**_case)
def test_prop_sell_reference_bitwise_csr(n, skew, sigma, tag, seed):
    a = _skew_csr(n, skew, seed)
    g = pack_csr(a, k=8)
    s = pack_sell(g, sigma=sigma)
    x = jnp.asarray(np.random.default_rng(seed + 1).normal(size=n))
    np.testing.assert_array_equal(np.asarray(spmv_gse(s, x, tag=tag)),
                                  np.asarray(spmv_gse(g, x, tag=tag)))


@settings(max_examples=8, deadline=None)
@given(nrhs=st.sampled_from([1, 4]), **_case)
def test_prop_sell_spmm_bitwise_csr(nrhs, n, skew, sigma, tag, seed):
    a = _skew_csr(n, skew, seed)
    g = pack_csr(a, k=8)
    s = pack_sell(g, sigma=sigma)
    x = jnp.asarray(np.random.default_rng(seed + 2).normal(size=(n, nrhs)))
    np.testing.assert_array_equal(np.asarray(spmm_gse(s, x, tag=tag)),
                                  np.asarray(spmm_gse(g, x, tag=tag)))


@settings(max_examples=8, deadline=None)
@given(nrhs=st.sampled_from([1, 4]), **_case)
def test_prop_sell_kernels_bitwise_uniform_ell(nrhs, n, skew, sigma, tag,
                                               seed):
    a = _skew_csr(n, skew, seed)
    g = pack_csr(a, k=8)
    s = pack_sell(g, sigma=sigma)
    ell = ops.ell_pack_gsecsr(g)
    rng = np.random.default_rng(seed + 3)
    x1 = jnp.asarray(rng.normal(size=n), jnp.float32)
    got = ops.gse_spmv_sell(s, x1, tag=tag)
    want = ops.gse_spmv_ell(ell, g.table, x1, g.ei_bit, tag=tag)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    xm = jnp.asarray(rng.normal(size=(n, nrhs)), jnp.float32)
    got = ops.gse_spmm_sell(s, xm, tag=tag)
    want = ops.gse_spmm_ell(ell, g.table, xm, g.ei_bit, tag=tag)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
