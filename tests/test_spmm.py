"""Tests for the tag-specialized GSE SpMM pipeline (DESIGN.md §11).

Covers the batched-subsystem kernel acceptance criteria:

  * per-tag Pallas SpMM parity vs the ``spmm_gse`` reference vs
    column-by-column ``spmv_gse`` (the multi-RHS pass must be exactly the
    per-column math, amortized);
  * the tag-1/-2 ``pallas_call``s provably omit the unused tail operands
    -- the SpMM streams the SAME matrix segment list as the SpMV however
    many right-hand sides ride along (jaxpr operand-count inspection);
  * ``iteration_stream_bytes`` nrhs generalization: nrhs=1 identity,
    matrix bytes charged once, vector bytes per extra column.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.extend import core as jcore

from repro.kernels import ops, ref
from repro.kernels.gse_spmm import gse_spmm_call, spmm_operand_names
from repro.kernels.gse_spmv import spmv_operand_names
from repro.sparse import generators as G
from repro.sparse.csr import (
    iteration_stream_bytes,
    pack_csr,
    vector_stream_bytes,
)
from repro.sparse.spmv import spmm, spmm_gse, spmv, spmv_gse


# ---------------------------------------------------------------------------
# Reference-path parity: spmm_gse == column-by-column spmv_gse, bitwise
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("tag", [1, 2, 3])
@pytest.mark.parametrize("nrhs", [1, 4])
def test_spmm_gse_matches_columnwise_spmv(tag, nrhs):
    """One decoded-value pass over nrhs columns must be numerically the
    per-column SpMV -- same gather, same segment reduction order."""
    a = G.random_spd(500, seed=tag)
    g = pack_csr(a, k=8)
    x = jnp.asarray(np.random.default_rng(tag).normal(size=(a.shape[1], nrhs)))
    y = np.asarray(spmm_gse(g, x, tag=tag))
    want = np.stack(
        [np.asarray(spmv_gse(g, x[:, j], tag=tag)) for j in range(nrhs)],
        axis=1,
    )
    np.testing.assert_array_equal(y, want)


@pytest.mark.parametrize("store", [jnp.float64, jnp.float16, jnp.bfloat16])
def test_spmm_fixed_matches_columnwise_spmv(store):
    a = G.poisson2d(16)
    x = jnp.asarray(np.random.default_rng(0).normal(size=(a.shape[1], 3)))
    y = np.asarray(spmm(a, x, store_dtype=store))
    want = np.stack(
        [np.asarray(spmv(a, x[:, j], store_dtype=store)) for j in range(3)],
        axis=1,
    )
    np.testing.assert_array_equal(y, want)


def test_spmm_rejects_1d_operand():
    a = G.poisson2d(8)
    g = pack_csr(a, k=8)
    x1 = jnp.ones((a.shape[1],))
    with pytest.raises(ValueError, match="nrhs"):
        spmm(a, x1)
    with pytest.raises(ValueError, match="nrhs"):
        spmm_gse(g, x1, tag=1)


# ---------------------------------------------------------------------------
# Pallas kernel parity vs per-column ELL reference
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("k", [2, 8])  # ei_bit 1 / 3
@pytest.mark.parametrize("tag", [1, 2, 3])
def test_spmm_kernel_parity(k, tag):
    a = G.random_spd(500, seed=10 * k + tag)
    g = pack_csr(a, k=k)
    ell = ops.ell_pack_gsecsr(g, lane=128)
    x = jnp.asarray(
        np.random.default_rng(tag).normal(size=(a.shape[1], 4)), jnp.float32
    )
    out = ops.gse_spmm_ell(ell, g.table, x, g.ei_bit, tag=tag)
    want = np.stack(
        [np.asarray(ref.spmv_ell_ref(*ell, g.table, x[:, j], g.ei_bit, tag))
         for j in range(4)],
        axis=1,
    )
    assert out.shape == (a.shape[0], 4)
    np.testing.assert_allclose(np.asarray(out), want, rtol=2e-5, atol=1e-4)


@pytest.mark.parametrize("tag", [1, 3])
def test_spmm_kernel_blocks_sweep(tag):
    """Wider tiles hit the multi-sublane-group reduction path per column."""
    a = G.poisson2d(16)
    g = pack_csr(a, k=8)
    ell = ops.ell_pack_gsecsr(g, lane=256)
    x = jnp.asarray(np.random.default_rng(0).normal(size=(a.shape[1], 2)),
                    jnp.float32)
    want = np.stack(
        [np.asarray(ref.spmv_ell_ref(*ell, g.table, x[:, j], g.ei_bit, tag))
         for j in range(2)],
        axis=1,
    )
    for blocks in [(8, 128), (8, 256), (16, 256)]:
        out = ops.gse_spmm_ell(ell, g.table, x, g.ei_bit, tag=tag,
                               blocks=blocks)
        np.testing.assert_allclose(np.asarray(out), want, rtol=2e-5,
                                   atol=1e-4)


def test_spmm_kernel_matches_spmv_kernel_at_nrhs1():
    """An (n, 1) SpMM is exactly the SpMV kernel's math."""
    a = G.poisson2d(16)
    g = pack_csr(a, k=8)
    ell = ops.ell_pack_gsecsr(g, lane=128)
    x = jnp.asarray(np.random.default_rng(1).normal(size=a.shape[1]),
                    jnp.float32)
    for tag in (1, 2, 3):
        y1 = ops.gse_spmv_ell(ell, g.table, x, g.ei_bit, tag=tag)
        y2 = ops.gse_spmm_ell(ell, g.table, x[:, None], g.ei_bit, tag=tag)
        np.testing.assert_array_equal(np.asarray(y1), np.asarray(y2[:, 0]))


def test_spmm_dispatch_cache_is_stable():
    k1 = ops.spmm_kernel_for(1, 3, (8, 128), True)
    k2 = ops.spmm_kernel_for(1, 3, (8, 128), True)
    assert k1 is k2
    assert ops.spmm_kernel_for(2, 3, (8, 128), True) is not k1
    with pytest.raises(ValueError, match="tag"):
        ops.spmm_kernel_for(4, 3, (8, 128), True)


# ---------------------------------------------------------------------------
# Operand-count inspection: unused tails never enter the pallas_call
# ---------------------------------------------------------------------------

def _iter_eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            if isinstance(v, jcore.ClosedJaxpr):
                yield from _iter_eqns(v.jaxpr)
            elif isinstance(v, jcore.Jaxpr):
                yield from _iter_eqns(v)


def _spmm_pallas_call_invars(tag, nrhs):
    m, L, n, nk, ei = 8, 128, 64, 8, 3
    colpak = jnp.zeros((m, L), jnp.uint32)
    head = jnp.zeros((m, L), jnp.uint16)
    tail1 = jnp.zeros((m, L), jnp.uint16)
    tail2 = jnp.zeros((m, L), jnp.uint32)
    x = jnp.zeros((n, nrhs), jnp.float32)
    scales = jnp.ones((1, nk), jnp.float32)
    operands = {
        1: (colpak, head, None, None),
        2: (colpak, head, tail1, None),
        3: (colpak, head, tail1, tail2),
    }[tag]
    fn = functools.partial(gse_spmm_call, *operands, x, scales,
                           ei_bit=ei, tag=tag, interpret=True)
    jaxpr = jax.make_jaxpr(fn)()
    eqns = [e for e in _iter_eqns(jaxpr.jaxpr)
            if e.primitive.name == "pallas_call"]
    assert len(eqns) == 1, "expected exactly one pallas_call"
    return eqns[0].invars


@pytest.mark.parametrize("tag,n_operands", [(1, 4), (2, 5), (3, 6)])
@pytest.mark.parametrize("nrhs", [1, 4])
def test_spmm_pallas_operand_count_per_tag(tag, n_operands, nrhs):
    """The SpMM operand list is the SpMV operand list -- the matrix
    segments are streamed once whatever the batch width; tag-1/-2 never
    stream the unused tail segments."""
    invars = _spmm_pallas_call_invars(tag, nrhs)
    assert len(invars) == n_operands
    assert spmm_operand_names(tag) == spmv_operand_names(tag)


@pytest.mark.parametrize("nrhs", [1, 4])
def test_spmm_tag1_and_tag2_omit_tail_dtypes(nrhs):
    """No u32 (M,L) tail2 operand at tags 1/2; no u16 tail at tag 1."""
    def dtypes(tag):
        return sorted(str(v.aval.dtype) for v in
                      _spmm_pallas_call_invars(tag, nrhs))

    assert dtypes(1) == ["float32", "float32", "uint16", "uint32"]
    assert dtypes(2) == ["float32", "float32", "uint16", "uint16", "uint32"]
    assert dtypes(3) == ["float32", "float32", "uint16", "uint16", "uint32",
                         "uint32"]


# ---------------------------------------------------------------------------
# iteration_stream_bytes nrhs generalization
# ---------------------------------------------------------------------------

def test_iteration_stream_bytes_nrhs1_identity():
    """nrhs=1 must reproduce the single-RHS figures exactly (the fig89
    accounting is unchanged for every existing caller)."""
    a = G.random_spd(400, seed=3)
    g = pack_csr(a, k=8)
    from repro.solvers import make_jacobi

    m = make_jacobi(a, k=8)
    for tag in (1, 2, 3):
        assert iteration_stream_bytes(g, tag, nrhs=1) == (
            iteration_stream_bytes(g, tag)
        )
        assert iteration_stream_bytes(g, tag, m, nrhs=1) == (
            iteration_stream_bytes(g, tag, m)
        )
    assert iteration_stream_bytes(a, jnp.float64, nrhs=1) == (
        iteration_stream_bytes(a, jnp.float64)
    )


def test_iteration_stream_bytes_nrhs_scaling():
    """Matrix bytes once; each extra column adds exactly one x/y stream."""
    a = G.random_spd(400, seed=3)
    g = pack_csr(a, k=8)
    vec = vector_stream_bytes(g)
    for tag in (1, 2, 3):
        one = iteration_stream_bytes(g, tag, nrhs=1)
        for nrhs in (2, 4, 8):
            got = iteration_stream_bytes(g, tag, nrhs=nrhs)
            assert got == one + (nrhs - 1) * vec
            # far below nrhs independent passes
            assert got < nrhs * one
    with pytest.raises(ValueError, match="nrhs"):
        iteration_stream_bytes(g, 1, nrhs=0)


def test_iteration_stream_bytes_nrhs4_under_2x():
    """The acceptance bound: on a stream-dominated matrix the nrhs=4
    per-iteration bytes sit under 2x the nrhs=1 figure at every tag."""
    a = G.random_spd(600, seed=5)  # ~17 nnz/row: matrix stream dominates
    g = pack_csr(a, k=8)
    for tag in (1, 2, 3):
        one = iteration_stream_bytes(g, tag, nrhs=1)
        four = iteration_stream_bytes(g, tag, nrhs=4)
        assert four < 2 * one


# ---------------------------------------------------------------------------
# Hypothesis property tests over nrhs
# ---------------------------------------------------------------------------

try:  # optional dep (see requirements.txt): guarded so tier-1 collection
    from hypothesis import given as _given, settings as _settings  # noqa
    from hypothesis import strategies as _st

    _HAVE_HYPOTHESIS = True
except ImportError:  # pragma: no cover - exercised on minimal installs
    _HAVE_HYPOTHESIS = False

if _HAVE_HYPOTHESIS:
    @_settings(max_examples=12, deadline=None)
    @_given(
        nrhs=_st.sampled_from([1, 2, 5, 8]),
        tag=_st.sampled_from([1, 2, 3]),
        seed=_st.integers(min_value=0, max_value=2**16),
    )
    def test_prop_spmm_columnwise_parity(nrhs, tag, seed):
        """For every nrhs in {1, 2, 5, 8}: spmm_gse equals the column-by-
        column spmv_gse bitwise, and the Pallas kernel agrees with the
        per-column ELL reference."""
        a = G.poisson2d(8)
        g = pack_csr(a, k=8)
        rng = np.random.default_rng(seed)
        x = jnp.asarray(rng.normal(size=(a.shape[1], nrhs)))
        y = np.asarray(spmm_gse(g, x, tag=tag))
        want = np.stack(
            [np.asarray(spmv_gse(g, x[:, j], tag=tag)) for j in range(nrhs)],
            axis=1,
        )
        np.testing.assert_array_equal(y, want)

        ell = ops.ell_pack_gsecsr(g, lane=128)
        xf = x.astype(jnp.float32)
        out = np.asarray(ops.gse_spmm_ell(ell, g.table, xf, g.ei_bit,
                                          tag=tag))
        kref = np.stack(
            [np.asarray(ref.spmv_ell_ref(*ell, g.table, xf[:, j], g.ei_bit,
                                         tag))
             for j in range(nrhs)],
            axis=1,
        )
        np.testing.assert_allclose(out, kref, rtol=2e-5, atol=1e-4)

    @_settings(max_examples=8, deadline=None)
    @_given(nrhs=_st.sampled_from([1, 2, 5, 8]))
    def test_prop_stream_bytes_monotone_in_nrhs(nrhs):
        a = G.poisson2d(8)
        g = pack_csr(a, k=8)
        prev = iteration_stream_bytes(g, 1, nrhs=nrhs)
        assert prev >= iteration_stream_bytes(g, 1)
        assert iteration_stream_bytes(g, 1, nrhs=nrhs + 1) > prev
