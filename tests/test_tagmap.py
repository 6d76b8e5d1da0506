"""PR 10: the per-group tag-map precision axis (DESIGN.md §18).

Three contracts, each load-bearing for the refactor:

1. **Uniform identity** -- a uniform :class:`TagMap` (and the legacy int
   shim) is THE SAME precision axis as ``init_tag``: bit-identical
   trajectories across solver families, layouts, and batch widths.
2. **Per-group decode parity** -- the masked operand decoded with the
   map's MAX-tag formula is bitwise what a per-entry-tag decode
   produces (the "no new kernel bodies" claim).
3. **Blended byte model** -- ``bytes_touched(tagmap)`` and its
   distributed twins are exact hand-computable blends, with the
   redistribution identity preserved.

Property-based sweeps are guarded by ``pytest.importorskip`` so tier-1
collection never needs hypothesis.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import precision as P
from repro.core.tagmap import GROUP_SIZE, TagMap, normalize_tags
from repro.kernels import ops, ref
from repro.solvers.batched import solve_cg_batched, solve_pcg_batched
from repro.solvers.cg import solve_cg, solve_pcg
from repro.solvers.ir import solve_ir
from repro.solvers.precond import make_jacobi
from repro.sparse import generators as G
from repro.sparse.csr import iteration_stream_bytes, pack_csr
from repro.sparse.spmv import spmv


def _sys(n=10, seed=0):
    a = G.poisson2d(n)
    g = pack_csr(a, k=8)
    rng = np.random.default_rng(seed)
    b = jnp.asarray(np.asarray(spmv(a, jnp.asarray(
        rng.normal(size=a.shape[1])))))
    return a, g, b


def _fast_params(**kw):
    d = dict(t=30, l=30, m=15, rsd_limit=0.5, reldec_limit=0.45)
    d.update(kw)
    return P.MonitorParams(**d)


def _mixed_map(m, lo=1, hi=2, period=3):
    """Deterministic non-uniform map: every ``period``-th group at ``hi``."""
    ng = -(-m // GROUP_SIZE)
    tags = np.full(ng, lo, np.uint8)
    tags[::period] = hi
    return TagMap(tags)


# ---------------------------------------------------------------------------
# The legacy shim: normalize_tags
# ---------------------------------------------------------------------------

def test_normalize_tags_shim():
    m = 64
    assert normalize_tags(None) is None
    assert normalize_tags(2, m) == 2
    # A uniform map IS the int tag (the legacy fast path).
    assert normalize_tags(TagMap.for_rows(m, 3), m) == 3
    tm = _mixed_map(m)
    assert normalize_tags(tm, m) is tm
    with pytest.raises(ValueError):
        normalize_tags(0, m)
    with pytest.raises(ValueError):
        normalize_tags(4, m)
    with pytest.raises(ValueError):
        normalize_tags(TagMap.for_rows(8, 1), m)  # too few groups for m


# ---------------------------------------------------------------------------
# Contract 1: uniform TagMap / int tags == init_tag, bitwise
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("tag", [1, 2, 3])
def test_uniform_identity_cg_fused(tag):
    _, g, b = _sys()
    m = int(g.shape[0])
    ref_res = solve_cg(g, b, tol=1e-8, maxiter=2000, params=_fast_params(),
                       init_tag=tag)
    for axis in (tag, TagMap.for_rows(m, tag)):
        res = solve_cg(g, b, tol=1e-8, maxiter=2000, params=_fast_params(),
                       tags=axis)
        np.testing.assert_array_equal(np.asarray(res.x),
                                      np.asarray(ref_res.x))
        assert int(res.iters) == int(ref_res.iters)
        assert int(res.tag) == int(ref_res.tag)


def test_uniform_identity_cg_generic_operator():
    from repro.solvers import make_gse_operator

    _, g, b = _sys(seed=1)
    m = int(g.shape[0])
    op = make_gse_operator(g)
    ref_res = solve_cg(op, b, tol=1e-8, maxiter=2000, params=_fast_params(),
                       init_tag=2)
    res = solve_cg(op, b, tol=1e-8, maxiter=2000, params=_fast_params(),
                   tags=TagMap.for_rows(m, 2))
    np.testing.assert_array_equal(np.asarray(res.x), np.asarray(ref_res.x))
    assert int(res.iters) == int(ref_res.iters)


def test_uniform_identity_pcg_fused():
    a, g, b = _sys(seed=2)
    m = int(g.shape[0])
    pre = make_jacobi(a, k=8)
    ref_res = solve_pcg(g, b, pre, tol=1e-8, maxiter=2000,
                        params=_fast_params(), init_tag=2)
    for axis in (2, TagMap.for_rows(m, 2)):
        res = solve_pcg(g, b, pre, tol=1e-8, maxiter=2000,
                        params=_fast_params(), tags=axis)
        np.testing.assert_array_equal(np.asarray(res.x),
                                      np.asarray(ref_res.x))
        assert int(res.iters) == int(ref_res.iters)


def test_uniform_identity_sell_layout():
    _, g, b = _sys(seed=3)
    m = int(g.shape[0])
    sell = ops.sell_pack_gsecsr(g)
    ref_res = solve_cg(sell, b, tol=1e-8, maxiter=2000,
                       params=_fast_params(), init_tag=1)
    res = solve_cg(sell, b, tol=1e-8, maxiter=2000, params=_fast_params(),
                   tags=TagMap.for_rows(m, 1))
    np.testing.assert_array_equal(np.asarray(res.x), np.asarray(ref_res.x))
    assert int(res.iters) == int(ref_res.iters)


@pytest.mark.parametrize("nrhs", [1, 4])
def test_uniform_identity_batched(nrhs):
    a, g, _ = _sys(seed=4)
    m = int(g.shape[0])
    rng = np.random.default_rng(4)
    b = jnp.stack([jnp.asarray(np.asarray(spmv(a, jnp.asarray(
        rng.normal(size=m))))) for _ in range(nrhs)], axis=1)
    ref_res = solve_cg_batched(g, b, tol=1e-8, maxiter=2000,
                               params=_fast_params())
    res = solve_cg_batched(g, b, tol=1e-8, maxiter=2000,
                           params=_fast_params(),
                           tags=TagMap.for_rows(m, 1))
    np.testing.assert_array_equal(np.asarray(res.x), np.asarray(ref_res.x))
    np.testing.assert_array_equal(np.asarray(res.iters),
                                  np.asarray(ref_res.iters))


def test_uniform_identity_batched_pcg_int_tag():
    a, g, _ = _sys(seed=5)
    m = int(g.shape[0])
    pre = make_jacobi(a, k=8)
    rng = np.random.default_rng(5)
    b = jnp.stack([jnp.asarray(np.asarray(spmv(a, jnp.asarray(
        rng.normal(size=m))))) for _ in range(3)], axis=1)
    r2 = solve_pcg_batched(g, b, pre, tol=1e-8, maxiter=2000,
                           params=_fast_params(), tags=2)
    rm = solve_pcg_batched(g, b, pre, tol=1e-8, maxiter=2000,
                           params=_fast_params(),
                           tags=TagMap.for_rows(m, 2))
    np.testing.assert_array_equal(np.asarray(r2.x), np.asarray(rm.x))
    np.testing.assert_array_equal(np.asarray(r2.iters),
                                  np.asarray(rm.iters))


def test_uniform_identity_ir():
    _, g, b = _sys(seed=6)
    m = int(g.shape[0])
    ref_res = solve_ir(g, b, tol=1e-12, max_outer=6, inner_tol=1e-4,
                       inner_maxiter=800, params=_fast_params())
    res = solve_ir(g, b, tol=1e-12, max_outer=6, inner_tol=1e-4,
                   inner_maxiter=800, params=_fast_params(),
                   tags=TagMap.for_rows(m, 1))
    np.testing.assert_array_equal(np.asarray(res.x), np.asarray(ref_res.x))
    assert bool(res.converged)


# ---------------------------------------------------------------------------
# Contract 2: masked max-tag decode == per-entry-tag decode, bitwise
# ---------------------------------------------------------------------------

def _per_entry_reference(g, tm):
    """NumPy oracle: every entry decoded at its own symmetric induced
    tag, straight from the packed segments in CSR order."""
    g = g.in_csr_order()
    cols = (np.asarray(g.colpak, np.uint32)
            & np.uint32((1 << (32 - g.ei_bit)) - 1)).astype(np.int64)
    et = tm.entry_tags(np.asarray(g.row_ids), cols)
    decs = {t: np.asarray(ref.decode_csr_ref(
        g.colpak, g.head, g.tail1, g.tail2, g.table, g.ei_bit, t),
        np.float64) for t in (1, 2, 3)}
    out = np.zeros(et.shape[0], np.float64)
    for t in (1, 2, 3):
        out[et == t] = decs[t][et == t]
    return out, cols


@pytest.mark.parametrize("lo,hi", [(1, 2), (1, 3), (2, 3)])
def test_masked_decode_matches_per_entry_numpy(lo, hi):
    _, g, _ = _sys(seed=7)
    tm = _mixed_map(int(g.shape[0]), lo=lo, hi=hi)
    masked = ops.masked_for_tagmap(g, tm).in_csr_order()
    got = np.asarray(ref.decode_csr_ref(
        masked.colpak, masked.head, masked.tail1, masked.tail2,
        masked.table, masked.ei_bit, tm.max_tag), np.float64)
    want, _ = _per_entry_reference(g, tm)
    np.testing.assert_array_equal(got, want)


def test_masked_matvec_matches_per_entry_numpy():
    from repro.solvers import make_gse_operator

    _, g, _ = _sys(seed=8)
    m = int(g.shape[0])
    tm = _mixed_map(m)
    masked = ops.masked_for_tagmap(g, tm)
    x = np.random.default_rng(8).normal(size=m)
    got = np.asarray(make_gse_operator(masked)(jnp.asarray(x),
                                               jnp.int32(tm.max_tag)))
    vals, cols = _per_entry_reference(g, tm)
    want = np.zeros(m, np.float64)
    np.add.at(want, np.asarray(g.in_csr_order().row_ids, np.int64),
              vals * x[cols])
    np.testing.assert_allclose(got, want, rtol=1e-13, atol=1e-13)


def test_masked_operand_stays_symmetric():
    """The induced entry tag is max(row, col) BY CONSTRUCTION, so a
    masked SPD operand is exactly symmetric -- CG's contract."""
    _, g, _ = _sys(seed=9)
    m = int(g.shape[0])
    tm = _mixed_map(m, lo=1, hi=3, period=2)
    masked = ops.masked_for_tagmap(g, tm).in_csr_order()
    g = g.in_csr_order()
    vals = np.asarray(ref.decode_csr_ref(
        masked.colpak, masked.head, masked.tail1, masked.tail2,
        masked.table, masked.ei_bit, tm.max_tag), np.float64)
    cols = (np.asarray(g.colpak, np.uint32)
            & np.uint32((1 << (32 - g.ei_bit)) - 1)).astype(np.int64)
    rows = np.asarray(g.row_ids, np.int64)
    dense = np.zeros((m, m))
    dense[rows, cols] = vals
    np.testing.assert_array_equal(dense, dense.T)


# ---------------------------------------------------------------------------
# Contract 3: the blended byte model
# ---------------------------------------------------------------------------

def test_bytes_touched_blend_gsecsr():
    _, g, _ = _sys(seed=10)
    m = int(g.shape[0])
    # Uniform maps charge exactly the int-tag model.
    for t in (1, 2, 3):
        assert g.bytes_touched(TagMap.for_rows(m, t)) == g.bytes_touched(t)
    # A mixed map blends per symmetric induced entry tag, exactly.
    tm = _mixed_map(m)
    c = g.in_csr_order()
    cols = (np.asarray(c.colpak, np.uint32)
            & np.uint32((1 << (32 - g.ei_bit)) - 1)).astype(np.int64)
    et = tm.entry_tags(np.asarray(c.row_ids), cols)
    per_nnz = {1: 6, 2: 8, 3: 12}
    fixed = (np.asarray(g.rowptr).size + np.asarray(g.table).size) * 4
    want = fixed + sum(per_nnz[t] * int((et == t).sum()) for t in (1, 2, 3))
    assert g.bytes_touched(tm) == want
    # And sits strictly inside the uniform bracket.
    assert g.bytes_touched(1) < g.bytes_touched(tm) < g.bytes_touched(2)


def test_iteration_stream_bytes_tagmap():
    a, g, _ = _sys(seed=11)
    m = int(g.shape[0])
    pre = make_jacobi(a, k=8)
    tm = _mixed_map(m)
    # Vector/precond terms ride the map's MAX tag (one fused pass).
    want = (iteration_stream_bytes(g, tm.max_tag, pre, nrhs=2)
            - g.bytes_touched(tm.max_tag) + g.bytes_touched(tm))
    assert iteration_stream_bytes(g, tm, pre, nrhs=2) == want


def test_bytes_touched_blend_sell_uniform():
    _, g, _ = _sys(seed=12)
    m = int(g.shape[0])
    sell = ops.sell_pack_gsecsr(g)
    for t in (1, 2, 3):
        assert sell.bytes_touched(TagMap.for_rows(m, t)) \
            == sell.bytes_touched(t)
    tm = _mixed_map(m)
    assert sell.bytes_touched(1) <= sell.bytes_touched(tm) \
        <= sell.bytes_touched(2)


def test_partition_blend_identity():
    from repro.distributed.partition import partition_gsecsr

    _, g, _ = _sys(seed=13)
    tm = _mixed_map(int(g.shape[0]))
    for shards in (2, 4):
        part = partition_gsecsr(g, shards)
        # Redistribution identity, blended: sharding moves the stream,
        # it does not change it.
        assert (sum(part.shard_stream_bytes(tm))
                + part.shared_stream_bytes()
                == iteration_stream_bytes(g, tm)), shards
        # Uniform maps collapse to the int model on every distributed
        # byte surface.
        u2 = TagMap.for_rows(int(g.shape[0]), 2)
        assert part.halo_wire_bytes(u2, "gse") \
            == part.halo_wire_bytes(2, "gse")
        assert sum(part.shard_stream_bytes(u2)) \
            == sum(part.shard_stream_bytes(2))


def test_bnd_slot_tags_and_halo_blend():
    from repro.distributed.partition import partition_gsecsr

    _, g, _ = _sys(seed=14)
    m = int(g.shape[0])
    tm = _mixed_map(m)
    part = partition_gsecsr(g, 4)
    st = part.bnd_slot_tags(tm)
    assert st.shape == (part.n_shards, part.bnd_width)
    bnd = np.asarray(part.bnd_idx)
    row_tags = tm.row_tags(m)
    for i in range(part.n_shards):
        for s in range(part.bnd_width):
            if bnd[i, s] >= 0:
                gcol = int(bnd[i, s]) + i * part.rows_per_shard
                assert st[i, s] == row_tags[gcol], (i, s)
            else:
                # Padded slots ship (zeros) at the payload width.
                assert st[i, s] == tm.max_tag
    # The blended wire cost sits inside the uniform bracket and charges
    # the per-sender table only for shards shipping a packed slot.
    lo = part.halo_wire_bytes(tm.min_tag, "gse")
    hi = part.halo_wire_bytes(tm.max_tag, "gse")
    assert lo <= part.halo_wire_bytes(tm, "gse") <= hi
    # Exact wire ignores the map: full f64 slots either way.
    assert part.halo_wire_bytes(tm, "exact") \
        == part.halo_wire_bytes(3, "exact")


# ---------------------------------------------------------------------------
# The planner: only the limiting groups promote
# ---------------------------------------------------------------------------

def test_plan_tagmap_promotes_only_limiting_groups():
    a = G.diag_rescale(G.poisson2d(8), decades=6.0, seed=3)
    g = pack_csr(a, k=8)
    m = int(g.shape[0])
    scores = P.decode_error_scores(g, np.ones(m))
    floor1 = float(np.sqrt(scores[0].sum()))
    # A budget below the all-tag-1 floor forces promotions; the greedy
    # descent must only touch groups that dominate the floor.
    tm = P.plan_tagmap(scores, budget=floor1 / 4.0)
    promoted = np.nonzero(tm.tags > 1)[0]
    kept = np.nonzero(tm.tags == 1)[0]
    assert promoted.size > 0 and kept.size > 0
    assert scores[0][promoted].min() >= scores[0][kept].max()
    # The planned map's modeled floor fits the budget.
    assert float(np.sqrt(P.map_floor_contrib(scores, tm.tags).sum())) \
        <= floor1 / 4.0
    # A generous budget plans NO promotion at all.
    assert P.plan_tagmap(scores, budget=floor1 * 2.0).is_uniform


def test_promote_groups_touches_top_frac_only():
    tm = TagMap(np.ones(10, np.uint8))
    scores = np.arange(10, dtype=np.float64)
    out = P.promote_groups(tm, scores, frac=0.2)
    counts = {t: c for t, c in out.tag_counts().items() if c}
    assert counts == {1: 8, 2: 2}
    assert list(np.nonzero(out.tags == 2)[0]) == [8, 9]


# ---------------------------------------------------------------------------
# The adaptive driver + serve layer (light smokes; the strict byte gate
# lives in benchmarks/run.py --adaptive / BENCH_adaptive.json CI)
# ---------------------------------------------------------------------------

def test_solve_adaptive_converges_with_nonuniform_map():
    from repro.solvers.adaptive import solve_adaptive

    a = G.ill_conditioned_spd(16, decades=8.0, seed=0)
    g = pack_csr(a, k=8)
    m = int(g.shape[0])
    b = np.zeros(m)
    b[np.random.default_rng(7).choice(m, 4, replace=False)] = 1.0
    res = solve_adaptive(g, jnp.asarray(b), tol=2e-3, maxiter=4000)
    assert bool(res.converged)
    assert float(res.true_relres) <= 2e-3
    # The replan promoted SOME groups and left others cheap -- the whole
    # point of the per-group axis on this skewed-floor generator.
    assert not res.tagmap.is_uniform
    assert res.spmv_bytes > 0 and res.promotions


def test_serve_tags_axis():
    from repro.launch.solver_serve import SolverService

    a, g, b = _sys(seed=15)
    m = int(g.shape[0])
    svc = SolverService(slots=2, maxiter=3000)
    svc.register("p", a, k=8)
    r_int = svc.submit("p", b, tol=1e-8, tags=2)
    r_map = svc.submit("p", b, tol=1e-8, tags=TagMap.for_rows(m, 2))
    r_ad = svc.submit("p", b, tol=1e-8, tags="adaptive")
    reps = svc.flush()
    assert all(reps[r].converged for r in (r_int, r_map, r_ad))
    # Uniform map == int tag: same batched schedule, same iterations.
    assert reps[r_int].iters == reps[r_map].iters
    np.testing.assert_array_equal(np.asarray(svc.solution(r_int)),
                                  np.asarray(svc.solution(r_map)))
    with pytest.raises(ValueError):
        svc.register("ps", a, k=8, layout="sell", tags="adaptive")
    with pytest.raises(ValueError):
        svc.submit("p", b, tags="frobnicate")


# ---------------------------------------------------------------------------
# Property sweep (hypothesis; optional dependency)
# ---------------------------------------------------------------------------

def test_masked_decode_parity_random_maps_property():
    pytest.importorskip("hypothesis")
    from hypothesis import given, settings
    from hypothesis import strategies as st

    _, g, _ = _sys(n=8, seed=16)
    ng = -(-int(g.shape[0]) // GROUP_SIZE)

    @settings(max_examples=25, deadline=None)
    @given(st.lists(st.integers(min_value=1, max_value=3),
                    min_size=ng, max_size=ng))
    def check(tags):
        tm = TagMap(np.asarray(tags, np.uint8))
        masked = ops.masked_for_tagmap(g, tm).in_csr_order()
        got = np.asarray(ref.decode_csr_ref(
            masked.colpak, masked.head, masked.tail1, masked.tail2,
            masked.table, masked.ei_bit, tm.max_tag), np.float64)
        want, _ = _per_entry_reference(g, tm)
        np.testing.assert_array_equal(got, want)
        # The blended byte model brackets: uniform min <= map <= max.
        assert g.bytes_touched(tm.min_tag) <= g.bytes_touched(tm) \
            <= g.bytes_touched(tm.max_tag)

    check()
