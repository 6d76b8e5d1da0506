"""The SpMV's row reduction over a static row-slot map (DESIGN.md §19):
the map's layout and sentinel, its bitwise agreement with
``segment_sum``, the fallback for skewed rows, the sharded maps, and the
``spmv_row_reduction_total`` counter."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.distributed.partition import partition_gsecsr
from repro.obs import metrics as OM
from repro.solvers import solve_cg
from repro.sparse import csr as C
from repro.sparse import generators as G
from repro.sparse.spmv import spmm_gse, spmv_gse

W = 7


def _ragged(seed=0, m=211, n=211, decades=16):
    """A square operator whose rows hold 0..W entries (every length
    present, empty rows included) and whose values span ``decades``."""
    rng = np.random.default_rng(seed)
    lens = np.concatenate([np.arange(W + 1), rng.integers(0, W + 1, m - W - 1)])
    rng.shuffle(lens)
    rows = np.repeat(np.arange(m), lens)
    cols = np.concatenate([rng.choice(n, k, replace=False) for k in lens])
    vals = rng.standard_normal(rows.size) * 10.0 ** rng.uniform(
        -decades / 2, decades / 2, rows.size)
    return C.from_coo(rows, cols, vals, (m, n))


def _no_map(a):
    return dataclasses.replace(a, slot_map=None)


def test_row_slots_layout_and_sentinel():
    rowptr = np.array([0, 2, 2, 5, 6])
    sm = C.row_slots(rowptr, 3, 6)
    assert sm.dtype == np.int32 and sm.shape == (3, 4)
    np.testing.assert_array_equal(sm, [[0, 6, 2, 5],
                                       [1, 6, 3, 6],
                                       [6, 6, 4, 6]])


def test_pack_csr_builds_the_map_with_rows_on_lanes():
    a = _ragged()
    g = C.pack_csr(a, k=8)
    assert g.slot_map.shape == (W, a.shape[0])
    rowptr = np.asarray(a.rowptr)
    np.testing.assert_array_equal(
        np.asarray(g.slot_map), C.row_slots(rowptr, W, a.nnz))
    # The map rides along into the SELL pack.
    assert C.pack_sell(g).slot_map is g.slot_map


@pytest.mark.parametrize("layout", ["csr", "sell"])
@pytest.mark.parametrize("tag", [1, 2, 3])
@pytest.mark.parametrize("nrhs", [None, 3])
def test_slot_reduction_bitwise_equals_segment_sum(layout, tag, nrhs):
    a = _ragged(seed=tag)
    g = C.pack_csr(a, k=8)
    g0 = _no_map(g)
    if layout == "sell":
        g, g0 = C.pack_sell(g), C.pack_sell(g0)
    rng = np.random.default_rng(10 + tag)
    shape = (a.shape[1],) if nrhs is None else (a.shape[1], nrhs)
    x = jnp.asarray(rng.standard_normal(shape)
                    * 10.0 ** rng.uniform(-8, 8, shape))
    op = spmv_gse if nrhs is None else spmm_gse
    got, want = op(g, x, tag=tag), op(g0, x, tag=tag)
    assert got.shape == want.shape
    assert np.array_equal(np.asarray(got), np.asarray(want))


def test_one_long_row_falls_back_to_segment_sum():
    """A row as long as the matrix is wide pads every other row past
    ``MAX_SLOTS_PER_NNZ`` slots an entry: no map, and ``segment_sum``."""
    n = 64
    rows = np.concatenate([np.zeros(n, int), np.arange(1, n)])
    cols = np.concatenate([np.arange(n), np.arange(1, n)])
    a = C.from_coo(rows, cols, np.ones(rows.size), (n, n))
    assert n * n > C.MAX_SLOTS_PER_NNZ * a.nnz
    g = C.pack_csr(a, k=8)
    assert g.slot_map is None
    assert partition_gsecsr(g, 2).slot_map is None
    before = _count("segment_sum")
    x = jnp.arange(n, dtype=jnp.float64)
    y = np.asarray(spmv_gse(g, x, tag=3))
    np.testing.assert_array_equal(y, np.r_[x.sum(), x[1:]])
    assert _count("segment_sum") > before


@pytest.mark.parametrize("shards", [2, 3, 5])
def test_sharded_maps_skip_padding(shards):
    """Each shard's map addresses its own entries in CSR order; padding
    entries (whose row id is ``R``) are never in it, and padded rows read
    only the sentinel ``E``."""
    a = _ragged(seed=shards, m=203, n=203)
    part = partition_gsecsr(C.pack_csr(a, k=8), shards)
    sm = np.asarray(part.slot_map)
    E, R = part.colpak.shape[1], part.rows_per_shard
    assert sm.shape == (shards, W, R)
    rowptr = np.asarray(a.rowptr, np.int64)
    for i in range(shards):
        lo = i * R
        nz = part.nnz_per_shard[i]
        real = sm[i][sm[i] != E]
        assert real.size == nz
        assert np.array_equal(np.sort(real), np.arange(nz))
        assert (sm[i][:, part.rows_real[i]:] == E).all()
        for r in range(part.rows_real[i]):
            k = int(rowptr[lo + r + 1] - rowptr[lo + r])
            start = int(rowptr[lo + r] - rowptr[lo])
            np.testing.assert_array_equal(sm[i][:k, r],
                                          np.arange(start, start + k))


def _count(path):
    return OM.REGISTRY.get("spmv_row_reduction_total").labels(
        path=path).value


def test_counter_counts_the_traced_reduction():
    """Tracing a solve on a packed Laplacian counts each SpMV it compiles
    under ``path="slots"``, and none under ``segment_sum``."""
    jax.clear_caches()
    a = G.poisson3d(5)
    g = C.pack_csr(a, k=8)
    before = {p: _count(p) for p in ("slots", "segment_sum")}
    b = jnp.linspace(0.5, 1.5, a.shape[0])
    solve_cg(g, b, tol=1e-8, final_correction=True)
    assert _count("slots") > before["slots"]
    assert _count("segment_sum") == before["segment_sum"]
