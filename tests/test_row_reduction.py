"""The SpMV's row reduction over a slot-major store (DESIGN.md §19): the
store's layout, padding and sentinel, the one way back to CSR order, its
bitwise agreement with ``segment_sum``, the fallback for skewed rows, the
sharded and colour-block stores, the single gather it compiles to, and
the ``spmv_row_reduction_total`` counter."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.distributed.partition import partition_gsecsr
from repro.obs import metrics as OM
from repro.solvers import solve_cg
from repro.sparse import csr as C
from repro.sparse import generators as G
from repro.sparse.spmv import decode_gsecsr, spmm_gse, spmv_gse, spmv_operand

W = 7
SEGMENTS = ("colpak", "head", "tail1", "tail2")


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
    """``a`` in CSR order, which the SpMV sums with ``segment_sum``."""
    return a.in_csr_order()


def _csr_pack(a, monkeypatch):
    """``pack_csr``'s CSR-order pack of ``a``: no store fits no slots."""
    with monkeypatch.context() as mp:
        mp.setattr(C, "MAX_SLOTS_PER_NNZ", 0)
        return C.pack_csr(a, k=8)


def _assert_slot_major(store, rowptr, entries, fill):
    """``store[k, i]`` is row ``i``'s ``k``-th entry of the CSR-order
    ``entries``, and ``fill`` past the row's length."""
    rowptr = np.asarray(rowptr, np.int64)
    store = np.asarray(store)
    for i in range(rowptr.size - 1):
        k = int(rowptr[i + 1] - rowptr[i])
        np.testing.assert_array_equal(store[:k, i],
                                      np.asarray(entries)[rowptr[i]:rowptr[i + 1]])
        assert (store[k:, i] == fill).all()


def test_row_slots_layout_and_sentinel():
    """``slot_major`` puts row ``i``'s ``k``-th entry at ``[k, i]`` and the
    fill past each row, and ``csr_order`` takes it back."""
    rowptr = np.array([0, 2, 2, 5, 6])
    entries = np.array([10, 11, 12, 13, 14, 15], np.uint32)
    stored = C.row_slots(rowptr, 3)
    sm = C.slot_major(entries, stored, 99)
    assert sm.dtype == np.uint32 and sm.shape == (3, 4)
    np.testing.assert_array_equal(sm, [[10, 99, 12, 15],
                                       [11, 99, 13, 99],
                                       [99, 99, 14, 99]])
    np.testing.assert_array_equal(C.csr_order(sm, stored), entries)
    assert C.csr_order(entries, None) is entries


def test_pack_csr_builds_the_map_with_rows_on_lanes(monkeypatch):
    """``pack_csr`` stores each segment ``(W, rows)`` by row slot; the
    padding decodes to +0.0 and reads the sentinel column ``n``; the one
    way back to CSR order gives ``pack_csr``'s CSR-order segments; and the
    SELL pack gathers the same store."""
    a = _ragged()
    g = C.pack_csr(a, k=8)
    ref = _csr_pack(a, monkeypatch)
    assert g.slot_major and not ref.slot_major
    assert g.nnz == ref.nnz == a.nnz
    rowptr = np.asarray(a.rowptr)
    for f in SEGMENTS:
        assert getattr(g, f).shape == (W, a.shape[0])
        _assert_slot_major(getattr(g, f), rowptr, getattr(ref, f),
                           a.shape[1] if f == "colpak" else 0)
    np.testing.assert_array_equal(
        np.asarray(g.row_ids),
        np.broadcast_to(np.arange(a.shape[0]), (W, a.shape[0])))
    back = g.in_csr_order()
    for f in SEGMENTS + ("row_ids",):
        np.testing.assert_array_equal(getattr(back, f),
                                      np.asarray(getattr(ref, f)), f)
    pad = np.arange(W)[:, None] >= np.diff(rowptr)[None, :]
    for tag in (1, 2, 3):
        val, col = (np.asarray(v) for v in decode_gsecsr(g, tag))
        assert (val[pad] == 0.0).all() and not np.signbit(val[pad]).any()
        assert (col[pad] == a.shape[1]).all()
        val0, col0 = decode_gsecsr(ref, tag)
        np.testing.assert_array_equal(C.csr_order(val, g.stored), val0)
        np.testing.assert_array_equal(C.csr_order(col, g.stored), col0)
    # The SELL pack gathers the slot-major store straight out of its
    # buckets.
    from repro.sparse.spmv import _sell_segments

    for f, seg in zip(SEGMENTS, _sell_segments(C.pack_sell(g))):
        np.testing.assert_array_equal(np.asarray(seg),
                                      np.asarray(getattr(g, f)), f)


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
    ``MAX_SLOTS_PER_NNZ`` slots an entry: CSR order, and ``segment_sum``."""
    n = 64
    rows = np.concatenate([np.zeros(n, int), np.arange(1, n)])
    cols = np.concatenate([np.arange(n), np.arange(1, n)])
    a = C.from_coo(rows, cols, np.ones(rows.size), (n, n))
    assert n * n > C.MAX_SLOTS_PER_NNZ * a.nnz
    g = C.pack_csr(a, k=8)
    assert not g.slot_major and g.colpak.shape == (a.nnz,)
    assert not partition_gsecsr(g, 2).slot_major
    before = _count("segment_sum")
    x = jnp.arange(n, dtype=jnp.float64)
    y = np.asarray(spmv_gse(g, x, tag=3))
    np.testing.assert_array_equal(y, np.r_[x.sum(), x[1:]])
    assert _count("segment_sum") > before


@pytest.mark.parametrize("shards", [2, 3, 5])
def test_sharded_maps_skip_padding(shards):
    """Each shard stores its rows slot-major, ``(W, R)``: entry ``[k, r]``
    is local row ``r``'s ``k``-th entry, in CSR order; padded rows hold
    padding only; padding decodes to +0.0 and reads the sentinel column
    ``R + H``, one past the halo window."""
    a = _ragged(seed=shards, m=203, n=203)
    g = C.pack_csr(a, k=8)
    part = partition_gsecsr(g, shards)
    R, H = part.rows_per_shard, part.halo_idx.shape[1]
    assert part.slot_major and part.colpak.shape == (shards, W, R)
    assert part.n_padded > a.shape[0]
    rowptr = np.asarray(a.rowptr, np.int64)
    mask = np.uint32((1 << (32 - g.ei_bit)) - 1)
    for i in range(shards):
        lo, rr = i * R, part.rows_real[i]
        local = np.pad(rowptr[lo:lo + rr + 1] - rowptr[lo], (0, R - rr),
                       mode="edge")
        np.testing.assert_array_equal(np.asarray(part.rowptr)[i], local)
        e0 = int(rowptr[lo])
        for f in ("head", "tail1", "tail2"):
            want = np.asarray(getattr(g.in_csr_order(), f))[
                e0:e0 + part.nnz_per_shard[i]]
            _assert_slot_major(np.asarray(getattr(part, f))[i], local, want,
                               0)
        col = np.asarray(part.colpak)[i] & mask
        pad = np.arange(W)[:, None] >= np.diff(local)[None, :]
        assert (col[pad] == R + H).all() and (col[~pad] < R + H).all()
        assert pad[:, rr:].all()
        np.testing.assert_array_equal(
            np.asarray(part.row_ids)[i],
            np.broadcast_to(np.arange(R), (W, R)))
        assert (np.asarray(part.colpak)[i][pad] >> (32 - g.ei_bit) == 0).all()
    # The whole store decodes back to the operand's entries.
    from repro.distributed.partition import unshard

    back = unshard(part, g)
    for f in SEGMENTS:
        np.testing.assert_array_equal(np.asarray(getattr(back, f)),
                                      np.asarray(getattr(g, f)), f)


def test_stack_row_blocks_stores_each_block_slot_major():
    """Each colour block is exactly ``(W, rows)``: its rows' entries at
    ``[k, i]``, padding reading column ``n``, local row ids and pointer."""
    a = _ragged(seed=4, m=216, n=216)
    g = C.pack_csr(a, k=8)
    rows = 27
    st = C.stack_row_blocks(g, rows)
    nb = a.shape[0] // rows
    assert st.colpak.shape == (nb, W, rows) and st.shape == (rows, a.shape[1])
    rowptr = np.asarray(a.rowptr, np.int64)
    ref = g.in_csr_order()
    for b in range(nb):
        block = jax.tree.map(lambda v: v[b], st)
        assert block.slot_major
        local = rowptr[b * rows:(b + 1) * rows + 1] - rowptr[b * rows]
        np.testing.assert_array_equal(np.asarray(block.rowptr), local)
        e0, e1 = rowptr[b * rows], rowptr[(b + 1) * rows]
        for f in SEGMENTS:
            _assert_slot_major(getattr(block, f), local,
                               np.asarray(getattr(ref, f))[e0:e1],
                               a.shape[1] if f == "colpak" else 0)
        np.testing.assert_array_equal(
            np.asarray(block.row_ids), np.broadcast_to(np.arange(rows),
                                                       (W, rows)))
        x = jnp.asarray(np.random.default_rng(b).normal(size=a.shape[1]))
        np.testing.assert_array_equal(
            np.asarray(spmv_gse(block, x, tag=3)),
            np.asarray(spmv_gse(g, x, tag=3))[b * rows:(b + 1) * rows])


@pytest.mark.parametrize("tag", [1, 2, 3])
def test_large_exponent_table_decodes_as_csr_order(tag):
    """A table of 97 shared exponents (values over 30 decades) decodes the
    slot-major store entry for entry as the CSR-order one, and the SpMV
    sums to ``segment_sum``'s bits."""
    a = _ragged(seed=20 + tag, decades=30)
    g = C.pack_csr(a, k=97)
    g0 = _no_map(g)
    assert g.slot_major and g.table.shape == (97,)
    val, _ = decode_gsecsr(g, tag)
    val0, _ = decode_gsecsr(g0, tag)
    np.testing.assert_array_equal(C.csr_order(val, g.stored), val0)
    x = jnp.asarray(np.random.default_rng(tag).standard_normal(a.shape[1]))
    np.testing.assert_array_equal(np.asarray(spmv_gse(g, x, tag=tag)),
                                  np.asarray(spmv_gse(g0, x, tag=tag)))


@pytest.mark.parametrize("layout", ["csr", "sell"])
@pytest.mark.parametrize("nrhs", [None, 2])
def test_nonfinite_x_spreads_as_segment_sum(layout, nrhs):
    """``inf`` and ``nan`` in ``x`` reach exactly the rows ``segment_sum``
    sends them to: padding reads the appended zero, never a real entry."""
    a = _ragged(seed=7)
    g = C.pack_csr(a, k=8)
    g0 = _no_map(g)
    if layout == "sell":
        g, g0 = C.pack_sell(g), C.pack_sell(g0)
    rng = np.random.default_rng(7)
    shape = (a.shape[1],) if nrhs is None else (a.shape[1], nrhs)
    x = rng.standard_normal(shape)
    flat = x.reshape(a.shape[1], -1)
    flat[3] = np.inf
    flat[11] = -np.inf
    flat[0] = np.nan           # column 0: where CSR-order padding points
    flat[a.shape[1] - 1] = np.nan
    op = spmv_gse if nrhs is None else spmm_gse
    for tag in (1, 3):
        got = np.asarray(op(g, jnp.asarray(x), tag=tag))
        want = np.asarray(op(g0, jnp.asarray(x), tag=tag))
        assert not np.isfinite(want).all() and np.isfinite(want).any()
        np.testing.assert_array_equal(got, want)


def test_spmv_compiles_to_one_gather_of_x():
    """Outside its decode, the compiled CPU SpMV over a store by row slot
    gathers once, ``x[col]``: the products are reduced where they are,
    not gathered into a row-slot layout as well."""
    g = C.pack_csr(_ragged(), k=8)
    assert g.slot_major and g.slot_map is None
    x = jnp.linspace(0.5, 1.5, g.shape[0])
    text = jax.jit(lambda g, x: spmv_operand(g, x, 3)).lower(
        g, x).compile().as_text()
    names = re.findall(r' gather\(.*?op_name="([^"]*)"', text)
    outside = [n for n in names if "/decode/" not in n]
    assert len(outside) == 1 and outside[0].endswith("spmv/gather/gather")
    assert " scatter(" not in text


def _count(path):
    return OM.REGISTRY.get("spmv_row_reduction_total").labels(
        path=path).value


def test_counter_counts_the_traced_reduction():
    """Tracing a solve on a packed Laplacian counts each SpMV it compiles
    under ``path="slot_major"``, and none under ``segment_sum``."""
    jax.clear_caches()
    a = G.poisson3d(5)
    g = C.pack_csr(a, k=8)
    before = {p: _count(p) for p in ("slot_major", "segment_sum")}
    b = jnp.linspace(0.5, 1.5, a.shape[0])
    solve_cg(g, b, tol=1e-8, final_correction=True)
    assert _count("slot_major") > before["slot_major"]
    assert _count("segment_sum") == before["segment_sum"]
