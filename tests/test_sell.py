"""Tests for the SELL-C-σ sliced-ELL SpMV/SpMM pipeline (DESIGN.md §12).

Covers the PR-4 acceptance criteria:

  * the packed layout is a faithful permutation of the GSE-SEM CSR store
    (segment round trip + row-permutation round trip, bitwise);
  * SELL SpMV/SpMM reference paths are BITWISE equal to the CSR
    reference, and the bucketed Pallas kernels are bitwise equal to the
    uniform-ELL kernels, across tags 1/2/3 and nrhs in {1, 4};
  * per-bucket pallas_calls keep the tag-specialized operand lists
    (jaxpr operand counts, one call per width-bucket);
  * padding-honest byte model: skewed matrices show the uniform-ELL
    blowup, near-uniform (Poisson) figures are unchanged within 1%, the
    nnz-only default is untouched;
  * the operand-pack cache: repeated solves/packs against one operator
    perform ZERO host-side re-packing;
  * solver trajectories through the new layout are bit-identical to the
    CSR reference (fused CG/PCG, batched, service).
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.extend import core as jcore

from repro.core import precision as P
from repro.core.precision_table import KERNEL_SLOT_BYTES
from repro.kernels import ops
from repro.kernels.gse_spmv import gse_spmv_sell_call
from repro.sparse import generators as G
from repro.sparse.csr import (
    ELLLayout,
    GSESellC,
    ell_layout,
    iteration_stream_bytes,
    pack_csr,
    pack_sell,
    sell_slices,
    to_ell,
)
from repro.sparse.spmv import spmm_gse, spmv, spmv_gse
from repro.solvers import make_gse_operator, solve_cg, solve_pcg
from repro.solvers.batched import solve_cg_batched


def _params(**kw):
    d = dict(t=30, l=30, m=15, rsd_limit=0.5, reldec_limit=0.45)
    d.update(kw)
    return P.MonitorParams(**d)


def _skewed_small(n=320, seed=0):
    """Small skewed SPD: power-law rows + dense hubs, multiple buckets."""
    return G.skewed_spd(n, dense_rows=2, base_halfwidth=10, tail_scale=6.0,
                        seed=seed)


def _rand_skew_csr(n, seed):
    """Random row-skew (non-symmetric pattern): plain per-row degrees."""
    rng = np.random.default_rng(seed)
    deg = np.minimum((rng.pareto(1.2, n) * 4 + 1).astype(np.int64), n)
    rows = np.repeat(np.arange(n), deg)
    cols = rng.integers(0, n, size=deg.sum())
    bins = rng.choice([-2, -1, 0, 1], size=rows.size)
    vals = rng.uniform(1.0, 2.0, rows.size) * np.exp2(bins)
    vals *= rng.choice([-1.0, 1.0], size=vals.shape)
    from repro.sparse.csr import from_coo

    return from_coo(rows, cols, vals, (n, n))


# ---------------------------------------------------------------------------
# Layout round trip
# ---------------------------------------------------------------------------

def test_sell_pack_segment_round_trip():
    """Gathering the packed bucket arrays recovers every CSR-order segment
    bit-for-bit: the layout is a permutation, not a re-encoding."""
    g = pack_csr(_skewed_small(), k=8)
    s = pack_sell(g)
    gather = np.asarray(s.gather)
    for name in ("colpak", "head", "tail1", "tail2"):
        flat = np.concatenate(
            [np.asarray(b).reshape(-1) for b in getattr(s, name)]
        )
        np.testing.assert_array_equal(flat[gather],
                                      np.asarray(getattr(g, name)))


def test_sell_row_permutation_round_trip():
    g = pack_csr(_skewed_small(seed=3), k=8)
    for sigma in (None, 16, 64):
        s = pack_sell(g, sigma=sigma)
        perm = np.asarray(s.perm)
        unperm = np.asarray(s.unperm)
        m = g.shape[0]
        # Every real row appears exactly once; padding rows are -1.
        np.testing.assert_array_equal(np.sort(perm[perm >= 0]), np.arange(m))
        np.testing.assert_array_equal(perm[unperm], np.arange(m))
        assert perm.shape[0] == sum(s.bucket_rows)
        assert perm.shape[0] % s.c == 0


def test_sigma_window_sort_is_window_local():
    """σ bounds how far a row can move: the permutation stays inside its
    window, so locality (and recoverability) is controlled."""
    g = pack_csr(_rand_skew_csr(200, seed=5), k=8)
    sigma = 40
    order, _, sigma_eff = sell_slices(g.rowptr, c=8, sigma=sigma)
    assert sigma_eff == sigma
    order = np.asarray(order)
    real = order[order >= 0]
    for w0 in range(0, 200, sigma):
        win = real[(real >= w0) & (real < w0 + sigma)]
        assert win.size == min(sigma, 200 - w0)
        # rows of this window occupy contiguous positions in `order`
        pos = np.nonzero((order >= w0) & (order < w0 + sigma))[0]
        assert pos.max() - pos.min() + 1 == win.size


def test_pack_sell_rejects_bad_slice_height():
    g = pack_csr(G.poisson2d(8), k=8)
    with pytest.raises(ValueError, match="multiple of 8"):
        pack_sell(g, c=4)


# ---------------------------------------------------------------------------
# Bitwise parity: reference paths and kernels
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("tag", [1, 2, 3])
def test_sell_reference_spmv_bitwise_csr(tag):
    a = _skewed_small(seed=1)
    g = pack_csr(a, k=8)
    s = ops.sell_pack_gsecsr(g)
    x = jnp.asarray(np.random.default_rng(tag).normal(size=a.shape[1]))
    np.testing.assert_array_equal(np.asarray(spmv_gse(s, x, tag=tag)),
                                  np.asarray(spmv_gse(g, x, tag=tag)))


@pytest.mark.parametrize("nrhs", [1, 4])
@pytest.mark.parametrize("tag", [1, 2, 3])
def test_sell_reference_spmm_bitwise_csr(tag, nrhs):
    a = _rand_skew_csr(300, seed=2)
    g = pack_csr(a, k=8)
    s = ops.sell_pack_gsecsr(g)
    x = jnp.asarray(
        np.random.default_rng(10 * tag + nrhs).normal(size=(a.shape[1], nrhs))
    )
    np.testing.assert_array_equal(np.asarray(spmm_gse(s, x, tag=tag)),
                                  np.asarray(spmm_gse(g, x, tag=tag)))


@pytest.mark.parametrize("tag", [1, 2, 3])
def test_sell_kernel_bitwise_uniform_ell_kernel(tag):
    """The bucketed pallas path reproduces the uniform-ELL kernel output
    bit-for-bit: same in-row slots, same lane-group reduction order,
    trailing all-zero groups contribute exact zeros."""
    a = _skewed_small(seed=4)
    g = pack_csr(a, k=8)
    s = ops.sell_pack_gsecsr(g)
    assert s.n_buckets >= 2, "skewed case must exercise multiple buckets"
    ell = ops.ell_pack_gsecsr(g)
    x = jnp.asarray(np.random.default_rng(tag).normal(size=a.shape[1]),
                    jnp.float32)
    got = ops.gse_spmv_sell(s, x, tag=tag)
    want = ops.gse_spmv_ell(ell, g.table, x, g.ei_bit, tag=tag)
    assert got.shape == want.shape == (a.shape[0],)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("nrhs", [1, 4])
@pytest.mark.parametrize("tag", [1, 2, 3])
def test_sell_spmm_kernel_bitwise_uniform_ell_kernel(tag, nrhs):
    a = _skewed_small(seed=6)
    g = pack_csr(a, k=8)
    s = ops.sell_pack_gsecsr(g)
    ell = ops.ell_pack_gsecsr(g)
    x = jnp.asarray(
        np.random.default_rng(7 * tag + nrhs).normal(size=(a.shape[1], nrhs)),
        jnp.float32,
    )
    got = ops.gse_spmm_sell(s, x, tag=tag)
    want = ops.gse_spmm_ell(ell, g.table, x, g.ei_bit, tag=tag)
    assert got.shape == want.shape == (a.shape[0], nrhs)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_sell_kernel_rejects_incompatible_blocks():
    g = pack_csr(G.poisson2d(8), k=8)
    s = ops.sell_pack_gsecsr(g)
    x = jnp.zeros((g.shape[1],), jnp.float32)
    with pytest.raises(ValueError, match="multiple of the row block"):
        ops.gse_spmv_sell(s, x, tag=1, blocks=(16, 128))


# ---------------------------------------------------------------------------
# Jaxpr: one pallas_call per width-bucket, tag-specialized operand lists
# ---------------------------------------------------------------------------

def _iter_eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            if isinstance(v, jcore.ClosedJaxpr):
                yield from _iter_eqns(v.jaxpr)
            elif isinstance(v, jcore.Jaxpr):
                yield from _iter_eqns(v)


def _sell_pallas_eqns(s, tag):
    n = s.shape[1]
    x = jnp.zeros((n,), jnp.float32)
    scales = jnp.ones((1, int(s.table.size)), jnp.float32)
    if tag == 1:
        buckets = tuple((cp, hd, None, None)
                        for cp, hd in zip(s.colpak, s.head))
    elif tag == 2:
        buckets = tuple((cp, hd, t1, None) for cp, hd, t1 in
                        zip(s.colpak, s.head, s.tail1))
    else:
        buckets = tuple(zip(s.colpak, s.head, s.tail1, s.tail2))
    fn = functools.partial(gse_spmv_sell_call, buckets, s.unperm, x, scales,
                           ei_bit=s.ei_bit, tag=tag, interpret=True)
    jaxpr = jax.make_jaxpr(fn)()
    return [e for e in _iter_eqns(jaxpr.jaxpr)
            if e.primitive.name == "pallas_call"]


@pytest.mark.parametrize("tag,n_operands", [(1, 4), (2, 5), (3, 6)])
def test_sell_one_pallas_call_per_bucket_tag_specialized(tag, n_operands):
    """Exactly one pallas_call per width-bucket, each streaming ONLY the
    operands its tag reads (scales/colpak/head/x +tails) -- the uniform-
    ELL tag-specialization contract carried over per bucket."""
    g = pack_csr(_skewed_small(seed=8), k=8)
    s = ops.sell_pack_gsecsr(g)
    assert s.n_buckets >= 2
    eqns = _sell_pallas_eqns(s, tag)
    assert len(eqns) == s.n_buckets
    for eqn in eqns:
        assert len(eqn.invars) == n_operands


def test_sell_tag1_omits_tail_dtypes_per_bucket():
    """No u16 tail1 and no second u32 (tail2) operand in any tag-1 bucket
    call (segments are distinguishable by dtype, as in the uniform-ELL
    pipeline tests)."""
    g = pack_csr(_skewed_small(seed=8), k=8)
    s = ops.sell_pack_gsecsr(g)
    for eqn in _sell_pallas_eqns(s, 1):
        dtypes = sorted(str(v.aval.dtype) for v in eqn.invars)
        assert dtypes == ["float32", "float32", "uint16", "uint32"]


def test_sell_dispatch_cache_is_stable():
    k1 = ops.sell_kernel_for(1, 3, (8, 128), True)
    assert ops.sell_kernel_for(1, 3, (8, 128), True) is k1
    assert ops.sell_kernel_for(2, 3, (8, 128), True) is not k1
    m1 = ops.sell_spmm_kernel_for(1, 3, (8, 128), True)
    assert ops.sell_spmm_kernel_for(1, 3, (8, 128), True) is m1


# ---------------------------------------------------------------------------
# Padding-honest byte model
# ---------------------------------------------------------------------------

def test_skewed_padding_ratio_and_bytes():
    """The acceptance bar: on the skewed benchmark matrix, SELL wastes
    < 50% of uniform ELL's padded fraction and streams < 50% (actually
    ~13%) of its modeled tag-1 bytes, while staying within 10% of the
    kernel's 10 B per slot (6 B/nnz of segments + 4 B of gathered x)."""
    a = G.skewed_spd(1024)
    g = pack_csr(a, k=8)
    s = ops.sell_pack_gsecsr(g)
    ell = ell_layout(g)
    assert ell.padding_ratio > 0.8          # the blowup is real
    assert s.padding_ratio < 0.5 * ell.padding_ratio
    assert s.bytes_touched(1) < 0.5 * ell.bytes_touched(1)
    per_slot = KERNEL_SLOT_BYTES[1]
    assert abs(s.bytes_touched(1) / a.nnz - per_slot) / per_slot <= 0.10
    # effective per-nnz ladder is still monotone in the tag
    assert (s.bytes_touched(1) < s.bytes_touched(2) < s.bytes_touched(3))


def test_poisson_layout_figures_unchanged_within_1pct():
    """Near-uniform rows: SELL and uniform ELL pad identically (all
    slices at one lane width), so switching layouts moves the modeled
    figures by < 1% -- the regression bar for the non-skewed suite."""
    g = pack_csr(G.poisson2d(32), k=8)
    s = ops.sell_pack_gsecsr(g)
    ell = ell_layout(g)
    assert s.widths == (128,)
    for tag in (1, 2, 3):
        rel = abs(s.bytes_touched(tag) - ell.bytes_touched(tag))
        assert rel / ell.bytes_touched(tag) < 0.01
    assert abs(s.padding_ratio - ell.padding_ratio) < 0.01


def test_nnz_only_mode_unchanged():
    """The default byte model (no layout) is exactly the seed formula --
    the format-comparison figures (fig6) are untouched."""
    g = pack_csr(G.poisson2d(16), k=8)
    for tag in (1, 2, 3):
        want = (g.nnz * g.bytes_per_nnz(tag) + g.rowptr.size * 4
                + g.table.size * 4)
        assert g.bytes_touched(tag) == want
        assert iteration_stream_bytes(g, tag) == want


def test_bytes_touched_layout_dispatch():
    g = pack_csr(_skewed_small(), k=8)
    s = ops.sell_pack_gsecsr(g)
    ell = ell_layout(g)
    for tag in (1, 2, 3):
        assert g.bytes_touched(tag, layout=s) == s.bytes_touched(tag)
        assert g.bytes_touched(tag, layout=ell) == ell.bytes_touched(tag)
        assert iteration_stream_bytes(g, tag, layout=s) == s.bytes_touched(tag)
        # nrhs columns still add vector streams on top of the layout bytes
        from repro.sparse.csr import vector_stream_bytes

        assert iteration_stream_bytes(g, tag, nrhs=3, layout=s) == (
            s.bytes_touched(tag, nrhs=3) + 2 * vector_stream_bytes(g)
        )


def test_ell_layout_descriptor():
    g = pack_csr(_skewed_small(), k=8)
    lay = ell_layout(g)
    assert isinstance(lay, ELLLayout)
    per_row = np.diff(np.asarray(g.rowptr))
    L = -(-int(per_row.max()) // 128) * 128
    assert lay.slots == g.shape[0] * L
    assert 0.0 <= lay.padding_ratio < 1.0


# ---------------------------------------------------------------------------
# to_ell / ell_pack_gsecsr share one scatter (dedup satellite)
# ---------------------------------------------------------------------------

def test_to_ell_matches_ell_pack_layout():
    """The two packers ride one scatter helper: identical slot layout
    (ell cols == decoded colpak low bits), identical widths."""
    a = _rand_skew_csr(150, seed=9)
    g = pack_csr(a, k=8)
    cols, vals, L = to_ell(a)
    cp, hd, t1, t2 = ops.ell_pack_gsecsr(g)
    assert cp.shape == (a.shape[0], L) == cols.shape
    shift = 32 - g.ei_bit
    np.testing.assert_array_equal(
        (np.asarray(cp) & ((1 << shift) - 1)).astype(np.int64),
        cols.astype(np.int64),
    )
    # dtype discipline: cols int32, ELL segments keep their pack dtypes
    assert cols.dtype == np.int32 and vals.dtype == np.float64
    assert (cp.dtype, hd.dtype, t1.dtype, t2.dtype) == (
        jnp.uint32, jnp.uint16, jnp.uint16, jnp.uint32
    )


# ---------------------------------------------------------------------------
# Operand-pack cache
# ---------------------------------------------------------------------------

def test_pack_cache_hit_on_repeat():
    g = pack_csr(G.poisson2d(12), k=8)
    misses0 = ops.PACK_STATS["misses"]
    s1 = ops.sell_pack_gsecsr(g)
    assert ops.PACK_STATS["misses"] == misses0 + 1
    hits0 = ops.PACK_STATS["hits"]
    assert ops.sell_pack_gsecsr(g) is s1
    assert ops.PACK_STATS["hits"] == hits0 + 1
    assert ops.PACK_STATS["misses"] == misses0 + 1
    # different layout params are distinct cache entries
    s2 = ops.sell_pack_gsecsr(g, sigma=32)
    assert s2 is not s1
    # ELL packs ride the same per-instance cache
    e1 = ops.ell_pack_gsecsr(g)
    assert ops.ell_pack_gsecsr(g) is e1


def test_repeated_solves_zero_host_repacking():
    """The acceptance bar: repeated solve_cg calls on one packed operator
    perform ZERO host-side re-packing (and benchmarks sharing the
    operator reuse the same pack)."""
    a = G.poisson2d(12)
    g = pack_csr(a, k=8)
    s = ops.sell_pack_gsecsr(g)
    b = jnp.asarray(np.asarray(spmv(a, jnp.ones((a.shape[1],)))))
    misses0 = ops.PACK_STATS["misses"]
    r1 = solve_cg(s, b, tol=1e-8, maxiter=2000, params=_params())
    r2 = solve_cg(s, b, tol=1e-8, maxiter=2000, params=_params())
    assert ops.PACK_STATS["misses"] == misses0
    assert bool(r1.converged) and bool(r2.converged)
    np.testing.assert_array_equal(np.asarray(r1.x), np.asarray(r2.x))


# ---------------------------------------------------------------------------
# Solvers ride the layout bit-identically
# ---------------------------------------------------------------------------

def _b_for(a, seed=0):
    rng = np.random.default_rng(seed)
    return jnp.asarray(np.asarray(spmv(a, jnp.asarray(
        rng.normal(size=a.shape[1])))))


def test_solve_cg_sell_bit_identical_to_csr():
    a = _skewed_small(seed=11)
    g = pack_csr(a, k=8)
    s = ops.sell_pack_gsecsr(g)
    b = _b_for(a, seed=1)
    kw = dict(tol=1e-9, maxiter=4000, params=_params())
    r_csr = solve_cg(g, b, **kw)
    r_sell = solve_cg(s, b, **kw)
    r_ref = solve_cg(make_gse_operator(g), b, **kw)
    assert int(r_sell.iters) == int(r_csr.iters) == int(r_ref.iters)
    assert float(r_sell.relres) == float(r_csr.relres)
    np.testing.assert_array_equal(np.asarray(r_sell.switch_iters),
                                  np.asarray(r_csr.switch_iters))
    np.testing.assert_array_equal(np.asarray(r_sell.x), np.asarray(r_csr.x))


def test_solve_pcg_sell_bit_identical_to_csr():
    from repro.solvers import make_jacobi

    a = G.ill_conditioned_spd(16, 8.0)
    g = pack_csr(a, k=8)
    s = ops.sell_pack_gsecsr(g)
    m = make_jacobi(a, k=8)
    b = _b_for(a, seed=2)
    kw = dict(tol=1e-8, maxiter=4000, params=_params())
    r_csr = solve_pcg(g, b, m, **kw)
    r_sell = solve_pcg(s, b, m, **kw)
    assert int(r_sell.iters) == int(r_csr.iters)
    np.testing.assert_array_equal(np.asarray(r_sell.x), np.asarray(r_csr.x))


def test_solve_cg_batched_sell_bit_identical():
    a = G.random_spd(300, seed=13)
    g = pack_csr(a, k=8)
    s = ops.sell_pack_gsecsr(g)
    b = jnp.stack([_b_for(a, seed=j) for j in range(3)], axis=1)
    kw = dict(tol=1e-8, maxiter=3000, params=_params())
    r_csr = solve_cg_batched(g, b, **kw)
    r_sell = solve_cg_batched(s, b, **kw)
    np.testing.assert_array_equal(np.asarray(r_sell.iters),
                                  np.asarray(r_csr.iters))
    np.testing.assert_array_equal(np.asarray(r_sell.x), np.asarray(r_csr.x))
    np.testing.assert_array_equal(np.asarray(r_sell.switch_iters),
                                  np.asarray(r_csr.switch_iters))


def test_service_sell_layout_matches_csr_and_repacks_nothing():
    from repro.launch.solver_serve import SolverService

    a = G.poisson2d(12)

    def rhs(seed):
        rng = np.random.default_rng(seed)
        return spmv(a, jnp.asarray(rng.normal(size=a.shape[1])))

    svc_csr = SolverService(slots=2, params=_params(), maxiter=20000)
    svc_csr.register("op", a, k=8)
    svc_sell = SolverService(slots=2, params=_params(), maxiter=20000)
    svc_sell.register("op", a, k=8, layout="sell")
    misses0 = ops.PACK_STATS["misses"]

    for flush in range(2):
        ids_c = [svc_csr.submit("op", rhs(s), tol=1e-8) for s in (0, 1)]
        ids_s = [svc_sell.submit("op", rhs(s), tol=1e-8) for s in (0, 1)]
        rep_c = svc_csr.flush()
        rep_s = svc_sell.flush()
        for rc, rs in zip(ids_c, ids_s):
            # Trajectories are layout-independent...
            assert rep_s[rs].iters == rep_c[rc].iters
            assert rep_s[rs].relres == rep_c[rc].relres
            np.testing.assert_array_equal(rep_s[rs].switch_iters,
                                          rep_c[rc].switch_iters)
            # ...but the SELL reports charge actual padded slots.
            assert rep_s[rs].est_bytes > rep_c[rc].est_bytes
    # Registration packed once; flush/solve cycles re-packed NOTHING.
    assert ops.PACK_STATS["misses"] == misses0

    with pytest.raises(ValueError, match="unknown layout"):
        svc_csr.register("op2", a, layout="coo")


def test_gsesellc_is_a_pytree():
    g = pack_csr(G.poisson2d(8), k=8)
    s = pack_sell(g)
    leaves, treedef = jax.tree_util.tree_flatten(s)
    s2 = jax.tree_util.tree_unflatten(treedef, leaves)
    assert isinstance(s2, GSESellC)
    assert s2.widths == s.widths and s2.shape == s.shape
    np.testing.assert_array_equal(np.asarray(s2.gather), np.asarray(s.gather))
