"""Reading ``x`` through one column offset per slot (DESIGN.md §21): the
store ranked by offset, the way back to CSR order with padding inside a
row, the SpMV's bitwise agreement with the CSR ``segment_sum`` SpMV on
every store that takes it, the fallback to ``x[col]``, the solvers'
trajectories against that fallback, what a chip's program holds, and the
``spmv_x_read_total`` counter."""
import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import precision as P
from repro.core.tagmap import TagMap
from repro.distributed.partition import partition_gsecsr
from repro.kernels import ops
from repro.obs import flight as OF
from repro.obs import metrics as OM
from repro.solvers import make_jacobi, make_mg, solve_cg, solve_pcg
from repro.sparse import csr as C
from repro.sparse import generators as G
from repro.sparse.spmv import (decode_gsecsr, gather_products, spmv,
                               spmv_operand, sum_rows)

SEGMENTS = ("colpak", "head", "tail1", "tail2")


def _box27(n):
    """HPCG's 27-point operator on an ``n``-cube."""
    taps = {(dx, dy, dz): (26.0 if dx == dy == dz == 0 else -1.0)
            for dx in (-1, 0, 1) for dy in (-1, 0, 1) for dz in (-1, 0, 1)}
    return G.box_stencil((n, n, n), taps)


def _x(n, nrhs=None, seed=0):
    rng = np.random.default_rng(seed)
    shape = (n,) if nrhs is None else (n, nrhs)
    return jnp.asarray(rng.standard_normal(shape)
                       * 10.0 ** rng.uniform(-6, 6, shape))


def _csr_of(g, tag):
    """``g``'s values decoded at ``tag``, as a plain CSR in CSR order."""
    c = g.in_csr_order()
    val, col = decode_gsecsr(c, tag)
    return C.CSR(rowptr=c.rowptr, col=jnp.asarray(col), val=val,
                 row_ids=jnp.asarray(c.row_ids), shape=c.shape)


def _want(g, x, tag):
    return np.asarray(spmv(_csr_of(g, tag), x))


def _shard_spmv(part, x, tag):
    """Each shard's SpMV over its window ``x_shard ++ halo``, the halo
    read on the host, concatenated: what ``shard_map`` runs, on one
    device."""
    xs = np.pad(np.asarray(x), (0, part.n_padded - part.shape[0]))
    r, b = part.rows_per_shard, part.bnd_width
    bnd = np.asarray(part.bnd_idx)
    pool = np.concatenate([np.where(bnd[i] >= 0, xs[i * r + bnd[i]], 0.0)
                           for i in range(part.n_shards)]) if b else xs[:0]
    out = []
    for i in range(part.n_shards):
        blk = jax.tree.map(lambda v: v[i], (part.colpak, part.head,
                                            part.tail1, part.tail2,
                                            part.row_ids, part.slot_map))
        cp, hd, t1, t2, rows, sm = blk
        xcat = jnp.asarray(np.concatenate(
            [xs[i * r:(i + 1) * r], pool[np.asarray(part.halo_idx)[i]]]))
        g = C.GSECSR(rowptr=part.rowptr[i], colpak=cp, head=hd, tail1=t1,
                     tail2=t2, table=part.table, row_ids=rows,
                     ei_bit=part.ei_bit, shape=(r, xcat.shape[0]),
                     slot_map=sm)
        out.append(np.asarray(spmv_operand(g, xcat, tag)))
    return np.concatenate(out)[:part.shape[0]]


CASES = ["7-point", "27-point", "shards", "row-block", "nrhs", "sell",
         "tagmap"]


@pytest.mark.parametrize("tag", [1, 2, 3])
@pytest.mark.parametrize("case", CASES)
def test_windowed_spmv_bitwise_equals_csr_spmv(case, tag):
    """On every store that ranks its slots by offset, ``spmv_operand``
    is bitwise the CSR ``segment_sum`` SpMV of the same decoded values."""
    a = _box27(6) if case == "27-point" else G.poisson3d(7)
    g = C.pack_csr(a, k=8)
    assert g.slot_map is not None
    x = _x(a.shape[1], 3 if case == "nrhs" else None, seed=tag)
    want = _want(g, x, tag)
    if case == "shards":
        part = partition_gsecsr(g, 4)
        assert part.slot_map is not None
        got = _shard_spmv(part, x, tag)
    elif case == "row-block":
        st = C.stack_row_blocks(g, 49)
        assert st.slot_map.shape[0] == 7
        got = np.concatenate([
            np.asarray(spmv_operand(jax.tree.map(lambda v: v[b], st), x,
                                    tag)) for b in range(7)])
    elif case == "sell":
        sell = C.pack_sell(g)
        assert sell.slot_map is not None
        got = np.asarray(spmv_operand(sell, x, tag))
    elif case == "tagmap":
        tm = TagMap(np.array([1, 3, 2, 1, 3, 2, 1, 3]),
                    group_size=-(-a.shape[0] // 8))
        masked = ops.masked_for_tagmap(g, tm)
        assert masked.slot_map is not None
        got = np.asarray(spmv_operand(masked, x, tm.max_tag))
        want = _want(masked, x, tm.max_tag)
    else:
        got = np.asarray(spmv_operand(g, x, tag))
    assert got.shape == want.shape
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("grid, slots", [(7, 7), (6, 27)])
def test_stencil_store_ranks_its_offsets(grid, slots):
    """A box stencil's store holds one slot per offset, ascending; each
    stored entry of slot ``w`` sits at column ``row + slot_map[w]``, and
    padding reads the sentinel ``n``."""
    a = _box27(grid) if slots == 27 else G.poisson3d(grid)
    g = C.pack_csr(a, k=8)
    sm = np.asarray(g.slot_map)
    assert g.colpak.shape == (slots, a.shape[0])
    assert (np.diff(sm) > 0).all()
    col = (np.asarray(g.colpak) & np.uint32((1 << (32 - g.ei_bit)) - 1)
           ).astype(np.int64)
    rows = np.arange(a.shape[0])
    stored = g.stored
    assert stored.sum() == a.nnz
    np.testing.assert_array_equal(
        np.where(stored, col, -1),
        np.where(stored, rows[None, :] + sm[:, None], -1))
    assert (col[~stored] == a.shape[1]).all()


def test_csr_order_round_trips_padding_inside_rows(monkeypatch):
    """Padding that sits between a row's entries is dropped on the way
    back, and the CSR-order operand comes back bit for bit."""
    rowptr = np.array([0, 2, 4, 5])
    col = np.array([0, 1, 0, 2, 2])           # offsets 0 1 / -1 1 / 0
    sm, stored = C.offset_slots([(rowptr, col)], 3)
    np.testing.assert_array_equal(sm, [[-1, 0, 1]])
    entries = np.array([10, 11, 12, 13, 14], np.uint16)
    store = C.slot_major(entries, stored[0], 99)
    np.testing.assert_array_equal(store, [[99, 12, 99],
                                          [10, 99, 14],
                                          [11, 13, 99]])
    np.testing.assert_array_equal(C.csr_order(store, stored[0]), entries)
    a = G.poisson3d(5)
    g = C.pack_csr(a, k=8)
    with monkeypatch.context() as mp:
        mp.setattr(C, "MAX_SLOTS_PER_NNZ", 0)
        ref = C.pack_csr(a, k=8)
    assert not ref.slot_major
    back = g.in_csr_order()
    assert back.slot_map is None
    for f in SEGMENTS + ("row_ids",):
        np.testing.assert_array_equal(np.asarray(getattr(back, f)),
                                      np.asarray(getattr(ref, f)), f)


@pytest.mark.parametrize("grid", [12, 8])
def test_shard_halo_offsets_keep_each_rows_order(grid):
    """A shard reads the halo below it at offsets above its own columns':
    its slots follow an order every row keeps, not the ascending one, and
    its rows sum in CSR order.  Slabs two planes thick read the halo
    below and above at one offset, first in some rows and last in others:
    no order keeps both, and the shards keep the store by row slot."""
    g = C.pack_csr(G.poisson3d(grid), k=8)
    part = partition_gsecsr(g, 4)
    x = _x(g.shape[1])
    np.testing.assert_array_equal(_shard_spmv(part, x, 3), _want(g, x, 3))
    if grid == 8:
        assert part.slot_major and part.slot_map is None
        return
    sm = np.asarray(part.slot_map)
    assert sm.shape == (4, 9)
    assert not (np.diff(sm[1]) > 0).all()
    assert sm[1, 1] == part.rows_per_shard   # z-1 through the halo, second


@pytest.mark.parametrize("build", ["random", "long-row", "unsorted"])
def test_other_operands_keep_the_gather(build):
    """No offset ranking where the offsets do not fit the slots, or where
    a row's columns repeat: the store is by row slot, or in CSR order,
    and read by ``x[col]``."""
    if build == "random":
        a = G.random_spd(300, seed=5)
        g = C.pack_csr(a, k=8)
        assert g.slot_major
    elif build == "long-row":
        n = 64
        rows = np.concatenate([np.zeros(n, int), np.arange(1, n)])
        cols = np.concatenate([np.arange(n), np.arange(1, n)])
        a = C.from_coo(rows, cols, np.ones(rows.size), (n, n))
        g = C.pack_csr(a, k=8)
        assert not g.slot_major
    else:
        rowptr = np.array([0, 2, 3])
        col = np.array([1, 1, 0])             # row 0 holds column 1 twice
        assert C.offset_slots([(rowptr, col)], 2) is None
        return
    assert g.slot_map is None
    x = _x(a.shape[1])
    want = _want(g, x, 3)
    before = _count("window"), _count("gather")
    np.testing.assert_array_equal(np.asarray(spmv_operand(g, x, 3)), want)
    assert (_count("window"), _count("gather")) == (before[0],
                                                    before[1] + 1)


def _count(path):
    return OM.REGISTRY.get("spmv_x_read_total").labels(path=path).value


def test_counter_labels_each_traced_read():
    """Each traced SpMV counts once: a stencil operand under
    ``path="window"``, the same store stripped of its offsets under
    ``path="gather"``; a second call of the same program counts nothing."""
    jax.clear_caches()
    assert set(OM.REGISTRY.get("spmv_x_read_total").labelnames) == {"path"}
    g = C.pack_csr(G.poisson3d(5), k=8)
    x = _x(g.shape[1])
    f = jax.jit(lambda g, x: spmv_operand(g, x, 1))
    before = _count("window"), _count("gather")
    f(g, x)
    f(g, x)
    assert (_count("window"), _count("gather")) == (before[0] + 1,
                                                    before[1])
    f(dataclasses.replace(g, slot_map=None), x)
    assert (_count("window"), _count("gather")) == (before[0] + 1,
                                                    before[1] + 1)


def _tpu_text(g, x):
    return jax.jit(lambda g, x: spmv_operand(g, x, 3)).trace(g, x).lower(
        lowering_platforms=("tpu",)).as_text()


def test_windowed_lowering_holds_no_gather():
    """Lowered for a TPU, the windowed SpMV reads ``x`` by one dynamic
    slice a slot and holds no gather; the gather's lowering does."""
    g = C.pack_csr(G.poisson3d(6), k=8)
    x = _x(g.shape[1])
    text = _tpu_text(g, x)
    assert "gather" not in re.sub(r'loc\(.*?\)', "", text)
    assert text.count("stablehlo.dynamic_slice") == 7
    fallback = _tpu_text(dataclasses.replace(g, slot_map=None), x)
    assert "stablehlo.gather" in fallback


def test_window_reads_equal_the_gather():
    """``gather_products`` reads the same products through the windows
    as through ``x[col]``, padding selected to +0.0, a non-finite ``x``
    included."""
    g = C.pack_csr(G.poisson3d(6), k=8)
    x = np.array(_x(g.shape[1]))
    x[[0, 5, g.shape[1] - 1]] = [np.inf, np.nan, -np.inf]
    val, col = decode_gsecsr(g, 3)
    win = np.asarray(gather_products(val, col, jnp.asarray(x), jnp.float64,
                                     g.slot_map))
    gat = np.asarray(gather_products(val, col, jnp.asarray(x), jnp.float64))
    np.testing.assert_array_equal(win, gat)
    pad = ~g.stored
    assert (win[pad] == 0.0).all() and not np.signbit(win[pad]).any()
    np.testing.assert_array_equal(
        np.asarray(sum_rows(jnp.asarray(win), g.row_ids, g.shape[0])),
        np.asarray(sum_rows(jnp.asarray(gat), g.row_ids, g.shape[0])))


_STEP = P.MonitorParams(t=8, l=10, m=5, rsd_limit=0.0, reldec_limit=1.5,
                        ndec_limit=0)
_FP = OF.FlightParams(capacity=512)


def _same_run(a, b):
    assert int(a.iters) == int(b.iters)
    np.testing.assert_array_equal(np.asarray(a.x), np.asarray(b.x))
    np.testing.assert_array_equal(np.asarray(a.switch_iters),
                                  np.asarray(b.switch_iters))
    for fa, fb in zip(jax.tree.leaves(a.flight), jax.tree.leaves(b.flight)):
        np.testing.assert_array_equal(np.asarray(fa), np.asarray(fb))


@pytest.mark.parametrize("kind", ["cg", "pcg", "mg"])
def test_solves_follow_the_gathers_trajectory(kind):
    """CG, Jacobi-PCG and MG-PCG over the windowed operand take the
    iterations, residuals (the flight ring) and iterate of the same store
    read by ``x[col]``, the tags stepping 1 -> 2 -> 3 on the way."""
    a = _box27(16) if kind == "mg" else G.poisson3d(9)
    g = C.pack_csr(a, k=8)
    g0 = dataclasses.replace(g, slot_map=None)
    b = jnp.asarray(np.asarray(spmv(a, _x(a.shape[1], seed=3))))
    kw = dict(tol=1e-10, maxiter=400, params=_STEP, flight=_FP,
              final_correction=True)
    if kind == "cg":
        runs = [solve_cg(op, b, **kw) for op in (g, g0)]
    else:
        m = make_mg(a) if kind == "mg" else make_jacobi(a)
        runs = [solve_pcg(op, b, m, **kw) for op in (g, g0)]
    assert bool(runs[0].converged)
    _same_run(*runs)
