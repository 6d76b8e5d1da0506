"""Tests for the tag-specialized SpMV pipeline + fused stepped-CG path.

Covers the PR-1 acceptance criteria:

  * per-tag kernel parity vs kernels/ref.py across tags 1/2/3 and
    ei_bit in {1, 3} (k = 2 / 8 shared exponents);
  * the tag-1/-2 ``pallas_call``s provably omit the unused tail operands
    (jaxpr operand-count inspection);
  * fused-CG (``solve_cg`` with a ``GSECSR`` operand) agrees with the
    unfused path bit-for-bit on an SPD suite;
  * ``bytes_touched`` accounting: tag-1 < tag-2 < tag-3 and tag-1 is
    ~6 bytes/nnz (2 head + 4 colpak).
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.extend import core as jcore

from repro.core import precision as P
from repro.core.gse import pack
from repro.kernels import ops, ref
from repro.kernels.gse_spmv import (
    LANE,
    gse_spmv_call,
    spmv_operand_names,
)
from repro.sparse import generators as G
from repro.sparse.csr import pack_csr
from repro.solvers import make_gse_operator, solve_cg


# ---------------------------------------------------------------------------
# Per-tag kernel parity vs ref, across ei_bit (shared-exponent count k)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("k", [2, 8])  # ei_bit 1 / 3
@pytest.mark.parametrize("tag", [1, 2, 3])
def test_tag_specialized_kernel_parity(k, tag):
    a = G.random_spd(500, seed=10 * k + tag)
    g = pack_csr(a, k=k)
    assert g.ei_bit == {2: 1, 8: 3}[k]
    ell = ops.ell_pack_gsecsr(g, lane=128)
    x = jnp.asarray(
        np.random.default_rng(tag).normal(size=a.shape[1]), jnp.float32
    )
    out = ops.gse_spmv_ell(ell, g.table, x, g.ei_bit, tag=tag)
    want = ref.spmv_ell_ref(*ell, g.table, x, g.ei_bit, tag)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=2e-5,
                               atol=1e-4)


@pytest.mark.parametrize("tag", [1, 2, 3])
def test_kernel_lane_blocks_sweep(tag):
    """Wider BL tiles hit the multi-sublane-group reduction path."""
    a = G.poisson2d(16)
    g = pack_csr(a, k=8)
    ell = ops.ell_pack_gsecsr(g, lane=256)
    x = jnp.asarray(np.random.default_rng(0).normal(size=a.shape[1]),
                    jnp.float32)
    want = ref.spmv_ell_ref(*ell, g.table, x, g.ei_bit, tag)
    for blocks in [(8, 128), (8, 256), (16, 256)]:
        out = ops.gse_spmv_ell(ell, g.table, x, g.ei_bit, tag=tag,
                               blocks=blocks)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   rtol=2e-5, atol=1e-4)


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


def _pallas_call_invars(tag):
    m, L, n, nk, ei = 8, 128, 64, 8, 3
    colpak = jnp.zeros((m, L), jnp.uint32)
    head = jnp.zeros((m, L), jnp.uint16)
    tail1 = jnp.zeros((m, L), jnp.uint16)
    tail2 = jnp.zeros((m, L), jnp.uint32)
    x = jnp.zeros((n,), jnp.float32)
    scales = jnp.ones((1, nk), jnp.float32)
    operands = {
        1: (colpak, head, None, None),
        2: (colpak, head, tail1, None),
        3: (colpak, head, tail1, tail2),
    }[tag]
    fn = functools.partial(gse_spmv_call, *operands, x, scales,
                           ei_bit=ei, tag=tag, interpret=True)
    jaxpr = jax.make_jaxpr(fn)()
    eqns = [e for e in _iter_eqns(jaxpr.jaxpr)
            if e.primitive.name == "pallas_call"]
    assert len(eqns) == 1, "expected exactly one pallas_call"
    return eqns[0].invars


@pytest.mark.parametrize("tag,n_operands", [(1, 4), (2, 5), (3, 6)])
def test_pallas_call_operand_count_per_tag(tag, n_operands):
    """tag-1 streams scales/colpak/head/x only; tag-2 adds tail1; tag-3
    adds tail2 -- asserted on the actual pallas_call jaxpr equation."""
    invars = _pallas_call_invars(tag)
    assert len(invars) == n_operands
    assert len(spmv_operand_names(tag)) == n_operands


def test_tag1_and_tag2_omit_tail_dtypes():
    """No u32 (M,L) tail2 operand at tags 1/2; no u16 tail at tag 1.

    The segment arrays are distinguishable by dtype: colpak u32, head u16,
    tail1 u16, tail2 u32, x/scales f32.  A (8,128) u32 operand besides
    colpak would be tail2; a second u16 would be tail1.
    """
    def dtypes(tag):
        return sorted(str(v.aval.dtype) for v in _pallas_call_invars(tag))

    assert dtypes(1) == ["float32", "float32", "uint16", "uint32"]
    assert dtypes(2) == ["float32", "float32", "uint16", "uint16", "uint32"]
    assert dtypes(3) == ["float32", "float32", "uint16", "uint16", "uint32",
                         "uint32"]


def test_spmv_dispatch_cache_is_stable():
    k1 = ops.spmv_kernel_for(1, 3, (8, 128), True)
    k2 = ops.spmv_kernel_for(1, 3, (8, 128), True)
    assert k1 is k2
    assert ops.spmv_kernel_for(2, 3, (8, 128), True) is not k1


def test_output_is_lane_reduced_vector():
    """The widened (BM, LANE) accumulator reduces back to a (M,) vector."""
    a = G.poisson2d(8)
    g = pack_csr(a, k=8)
    ell = ops.ell_pack_gsecsr(g, lane=LANE)
    x = jnp.ones((a.shape[1],), jnp.float32)
    out = ops.gse_spmv_ell(ell, g.table, x, g.ei_bit, tag=1)
    assert out.shape == (a.shape[0],)


# ---------------------------------------------------------------------------
# bytes_touched accounting
# ---------------------------------------------------------------------------

def test_bytes_touched_ladder():
    a = G.random_spd(400, seed=3)
    g = pack_csr(a, k=8)
    assert g.bytes_touched(1) < g.bytes_touched(2) < g.bytes_touched(3)
    assert (g.bytes_per_nnz(1), g.bytes_per_nnz(2), g.bytes_per_nnz(3)) == (
        6, 8, 12
    )
    # tag-1 ~ 6 bytes/nnz: 2 head + 4 colpak (+ small rowptr/table overhead)
    per_nnz = g.bytes_touched(1) / g.nnz
    assert 6.0 <= per_nnz < 6.5
    # FP64 CSR baseline: 8 value + 4 colidx = 12.  Tag 3 streams the same
    # per-nnz bytes plus the (tiny) shared-exponent table.
    assert a.bytes_per_nnz(jnp.float64) == 12
    assert a.bytes_per_nnz(jnp.float16) == 6
    assert g.bytes_touched(3) == (
        a.bytes_touched(jnp.float64) + g.table.size * 4
    )


def test_gsepacked_bytes_touched_matches_nbytes():
    p = pack(np.random.default_rng(0).normal(size=256), 8)
    for tag in (1, 2, 3):
        assert p.bytes_touched(tag) == p.nbytes(tag)
    assert p.bytes_touched(1) < p.bytes_touched(2) < p.bytes_touched(3)


# ---------------------------------------------------------------------------
# Fused CG == unfused CG, bit for bit
# ---------------------------------------------------------------------------

def _fast_params(**kw):
    d = dict(t=30, l=30, m=15, rsd_limit=0.5, reldec_limit=0.45)
    d.update(kw)
    return P.MonitorParams(**d)


def _b_for(a, seed=0):
    rng = np.random.default_rng(seed)
    from repro.sparse.spmv import spmv

    return jnp.asarray(np.asarray(spmv(a, jnp.asarray(
        rng.normal(size=a.shape[1])))))


@functools.lru_cache(maxsize=1)
def _stalling_spd():
    """SPD with eigenvalues down to 1e-6: the tag-1 decode error perturbs
    the small eigenvalues, so head-only CG genuinely stalls and the
    controller must step up (same construction as test_solvers)."""
    from repro.sparse.csr import from_coo

    rng = np.random.default_rng(7)
    n = 200
    q, _ = np.linalg.qr(rng.normal(size=(n, n)))
    eigs = np.logspace(-6, 0, n)
    dense = (q * eigs) @ q.T
    dense = 0.5 * (dense + dense.T)
    rows, cols = np.nonzero(np.ones((n, n)))
    a = from_coo(rows, cols, dense[rows, cols], (n, n))
    return a, dense


def _spd_suite():
    yield "poisson2d_16", G.poisson2d(16), {}
    yield "random_spd_500", G.random_spd(500, seed=1), {}


@pytest.mark.parametrize("case", list(_spd_suite()), ids=lambda c: c[0])
def test_fused_cg_matches_unfused_trajectory(case):
    name, a, kw = case
    g = pack_csr(a, k=8)
    b = _b_for(a, seed=len(name))
    args = dict(tol=1e-8, maxiter=3000, params=_fast_params())
    args.update(kw)
    unfused = solve_cg(make_gse_operator(g), b, **args)
    fused = solve_cg(g, b, **args)
    assert int(fused.iters) == int(unfused.iters)
    assert abs(float(fused.relres) - float(unfused.relres)) <= 1e-12 * max(
        float(unfused.relres), 1.0
    )
    assert int(fused.tag) == int(unfused.tag)
    np.testing.assert_array_equal(np.asarray(fused.switch_iters),
                                  np.asarray(unfused.switch_iters))
    np.testing.assert_allclose(np.asarray(fused.x), np.asarray(unfused.x),
                               rtol=1e-12, atol=1e-14)


def test_fused_cg_steps_tags_and_matches_unfused():
    """On a genuinely stalling system the fused path must step tags at the
    same iterations as the unfused path and still converge."""
    a, dense = _stalling_spd()
    g = pack_csr(a, k=8)
    b = jnp.asarray(dense @ np.random.default_rng(7).normal(size=a.shape[1]))
    args = dict(tol=1e-8, maxiter=20000,
                params=_fast_params(t=60, l=60, m=30))
    fused = solve_cg(g, b, **args)
    assert bool(fused.converged)
    assert int(fused.tag) >= 2  # the stepped controller actually stepped
    assert int(fused.switch_iters[0]) > 0
    unfused = solve_cg(make_gse_operator(g), b, **args)
    assert int(fused.iters) == int(unfused.iters)
    np.testing.assert_array_equal(np.asarray(fused.switch_iters),
                                  np.asarray(unfused.switch_iters))


def test_fused_cg_final_correction():
    a = G.random_spd(800, seed=4)
    g = pack_csr(a, k=8)
    b = _b_for(a, seed=4)
    res = solve_cg(g, b, tol=1e-6, maxiter=6000, params=_fast_params(),
                   final_correction=True)
    true_rel = jnp.linalg.norm(b - make_gse_operator(g)(res.x, jnp.int32(3)))
    true_rel = float(true_rel / jnp.linalg.norm(b))
    assert true_rel < 5e-6
