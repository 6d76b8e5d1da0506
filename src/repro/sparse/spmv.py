"""SpMV operators (paper Section III.C.2): FP64/FP32/BF16/FP16 + 3 GSE-SEM tags.

All variants follow the paper's compute discipline: values are *stored* at
the target precision but multiply-accumulate happens at high precision
(f64 on CPU; f32 or two-float on TPU -- ``acc_dtype``).

The jnp implementations sum each row of an operand stored slot-major
(``csr.slot_major``) as a reduce over its W slots: the decode yields the
``(W, rows)`` values and columns, ``x`` is read at the columns, and the
sum runs in ``segment_sum``'s order.  Where the store ranks its slots by
column offset (``csr.offset_slots``) the read is W contiguous windows of
``x``; otherwise it is the gather ``x[col]``.  An operand stored in CSR order
(plain ``CSR`` baselines, rows too skewed for slots) keeps
``segment_sum`` over precomputed row ids, which XLA lowers to a
scatter-add (DESIGN.md §19).  The Pallas blocked-ELL kernel
(``repro.kernels.gse_spmv``) is the TPU-tiled version of the same math.
Each stage runs under its device scope (``spmv/decode``, ``spmv/gather``,
``spmv/scatter``; ``obs.trace.SCOPES``), so a profile names the ops of
every SpMV the solve path runs.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.core import gse
from repro.obs import metrics as OM
from repro.obs import trace as OT
from repro.sparse.csr import CSR, GSECSR, GSESellC, is_slot_major

__all__ = ["spmv", "spmv_gse", "spmv_ell", "spmm", "spmm_gse",
           "decode_gsecsr", "decode_operand", "gather_scatter",
           "gather_products", "x_windows", "sum_rows", "spmv_operand"]


# Which row reduction each traced SpMV took: counted once a trace, so it
# says what every compiled program runs at no cost per call.
ROW_REDUCTION = OM.REGISTRY.counter(
    "spmv_row_reduction_total",
    "Traced SpMVs by row reduction: over a slot-major store or segment_sum.",
    labelnames=("path",))

# How each traced SpMV read ``x``: as contiguous windows of a store ranked
# by column offset, or by the gather ``x[col]``; counted once a trace.
X_READ = OM.REGISTRY.counter(
    "spmv_x_read_total",
    "Traced SpMVs by how x is read: contiguous windows or a gather.",
    labelnames=("path",))


def gather_scatter(val, col, x, row_ids, num_rows, acc_dtype,
                   slot_map=None):
    """``segment_sum(val * x[col], row_ids)``, the SpMV after its decode:
    ``gather_products`` then ``sum_rows``.  ``x`` is ``(n,)`` or an
    ``(n, nrhs)`` block."""
    return sum_rows(gather_products(val, col, x, acc_dtype, slot_map),
                    row_ids, num_rows)


def x_windows(x, slot_map, rows):
    """``(W, rows)`` (or ``(W, rows, nrhs)``) reads of ``x``: ``[w, i]`` is
    ``x[i + slot_map[w]]``, and zero where that falls outside ``x``.

    Slot ``w`` reads ``rows`` consecutive entries of ``x`` padded with
    ``rows`` zeros at both ends, which any offset ``col - row`` of a
    block, in ``(-rows, n)``, stays inside.  On an accelerator each slot
    is one dynamic slice, a contiguous read, and a traced ``slot_map`` (a
    shard's, a stacked block's inside a loop) reads as a constant one
    does.  On the CPU the same entries are read by one gather: behind
    contiguous loads XLA's CPU backend vectorises the fused slot sum and
    a consuming dot, and reassociates both, where behind a gather it
    sums in order (DESIGN.md §21)."""
    pad = jnp.zeros((rows,) + x.shape[1:], x.dtype)
    xp = jnp.concatenate([pad, x, pad])
    start = rows + slot_map

    def slices(xp, start):
        return jnp.stack([jax.lax.dynamic_slice_in_dim(xp, start[w], rows)
                          for w in range(start.shape[0])])

    def gather(xp, start):
        return xp[start[:, None] + jnp.arange(rows, dtype=start.dtype)]

    return jax.lax.platform_dependent(xp, start, cpu=gather,
                                      default=slices)


def gather_products(val, col, x, acc_dtype, slot_map=None):
    """``val * x[col]`` in the decode's entry order, under the ``gather``
    scope.

    For ``(W, rows)`` values and columns of a slot-major store
    (``csr.slot_major``), ``x`` is read by ``x_windows`` where the store
    ranks its slots by column offset (``slot_map``), and otherwise by
    the gather ``x[col]`` with one zero appended to ``x``, which the
    padding entries' column ``n`` reads.  Either way the padding's
    products (+0.0 already, or NaN against a non-finite window entry)
    are selected to zero: the select stands between each multiply and
    the reduce's add, which XLA's CPU backend would otherwise contract
    into an FMA, unlike ``segment_sum``, which rounds every product
    first.  On a v5e the select costs nothing measurable (DESIGN.md §19,
    §21)."""
    with OT.scope(OT.GATHER):
        xa = x.astype(acc_dtype)
        windowed = slot_map is not None
        X_READ.labels(path="window" if windowed else "gather").inc()
        if not is_slot_major(col):
            xg = xa[col]
            return val * xg if x.ndim == 1 else val[:, None] * xg
        n = xa.shape[0]
        if windowed:
            xg = x_windows(xa, slot_map, col.shape[-1])
        else:
            xa = jnp.concatenate(
                [xa, jnp.zeros((1,) + xa.shape[1:], acc_dtype)])
            xg = xa[col]
        if x.ndim == 1:
            return jnp.where(col < n, val * xg, 0.0)
        return jnp.where((col < n)[..., None], val[..., None] * xg, 0.0)


def sum_rows(prod, row_ids, num_rows):
    """Each row's sum of ``gather_products``' terms, under the ``scatter``
    scope.  ``row_ids`` is in the same entry order: ``(W, rows)`` for a
    slot-major store, whose terms are reduced over the slot axis; XLA's
    CPU backend runs that reduce from zero down the W slots, each row's
    terms in CSR order, so it is bitwise ``segment_sum``'s sum, and a
    reduce, unlike chained adds, is not fused into a consuming dot, whose
    own summation order would then change.  CSR-order ``(nnz,)`` terms
    keep ``segment_sum``, which drops entries whose row id is
    ``num_rows``."""
    with OT.scope(OT.SCATTER):
        if not is_slot_major(row_ids):
            ROW_REDUCTION.labels(path="segment_sum").inc()
            return jax.ops.segment_sum(prod, row_ids, num_segments=num_rows)
        ROW_REDUCTION.labels(path="slot_major").inc()
        return jnp.sum(prod, axis=0)


@partial(jax.jit, static_argnames=("store_dtype", "acc_dtype", "num_rows"))
def _spmv_cast(row_ids, col, val, x, store_dtype, acc_dtype, num_rows):
    with OT.scope(OT.SPMV):
        with OT.scope(OT.DECODE):
            v = val.astype(store_dtype).astype(acc_dtype)  # storage round-trip
        return gather_scatter(v, col, x, row_ids, num_rows, acc_dtype)


def spmv(a: CSR, x: jnp.ndarray, store_dtype=jnp.float64, acc_dtype=jnp.float64):
    """y = A @ x with values stored at ``store_dtype`` (paper's baselines)."""
    return _spmv_cast(
        a.row_ids, a.col, a.val, x, store_dtype, acc_dtype, a.shape[0]
    )


def _table_lookup(table, idx):
    """``table[idx]`` for the shared-exponent table, as a chain of
    selects.  On a v5e a gather from the table over a ``(W, rows)``
    slot-major index runs element by element, and so does one over the
    flat index once the table holds 97 entries; the selects are the
    cheapest read measured at every table size (DESIGN.md §19)."""
    out = jnp.broadcast_to(table[0], idx.shape)
    for j in range(1, table.shape[0]):
        out = jnp.where(idx == j, table[j], out)
    return out


@partial(jax.jit, static_argnames=("ei_bit", "tag", "acc_dtype", "num_rows"))
def _decode_gsecsr(colpak, head, tail1, tail2, table, ei_bit, tag, acc_dtype,
                   num_rows=None):
    """Decode GSE-SEM CSR values to ``acc_dtype`` (15-bit-head layout)."""
    shift = 32 - ei_bit
    exp_idx = (colpak >> shift).astype(jnp.int32)
    h = head.astype(jnp.uint32)
    sign = (h >> 15) & 0x1
    m_head = h & 0x7FFF  # all 15 bits are mantissa (expIdx is in colpak)
    if tag == 1:
        mant = m_head.astype(acc_dtype)
        bits_used = 15
    elif tag == 2:
        mant = m_head.astype(acc_dtype) * jnp.asarray(65536.0, acc_dtype) + (
            tail1.astype(acc_dtype)
        )
        bits_used = 31
    else:
        mant = (
            m_head.astype(acc_dtype) * jnp.asarray(2.0**48, acc_dtype)
            + tail1.astype(acc_dtype) * jnp.asarray(2.0**32, acc_dtype)
            + tail2.astype(acc_dtype)
        )
        bits_used = 63
    e_sh = _table_lookup(table, exp_idx).astype(jnp.int32) - 1023
    pow_ = e_sh - bits_used
    half = pow_ // 2
    sgn = 1.0 - 2.0 * sign.astype(acc_dtype)
    val = sgn * (
        (mant * gse._pow2_exact(half, acc_dtype))
        * gse._pow2_exact(pow_ - half, acc_dtype)
    )
    return val, (colpak & ((1 << shift) - 1)).astype(jnp.int32)


def decode_gsecsr(a: GSECSR, tag: int, acc_dtype=jnp.float64):
    """(values, columns) decoded from a GSE-SEM CSR at precision ``tag``,
    in its stored entry order (``csr.csr_order`` gives CSR order)."""
    return _decode_gsecsr(
        a.colpak, a.head, a.tail1, a.tail2, a.table, a.ei_bit, tag, acc_dtype
    )


def _sell_segments(a: GSESellC):
    """(colpak, head, tail1, tail2) gathered out of the packed SELL-C-σ
    bucket arrays in the ``GSECSR``'s entry order.

    The packed layout IS the value store: ``gather`` addresses every real
    entry inside the flattened width-buckets, and a slot-major gather's
    padding the one padding entry appended after them (zero segments,
    column ``shape[1]``), so the recovered segments are bit-for-bit the
    ``GSECSR`` arrays and everything downstream of this gather (decode,
    row reduction, solver iterations) is exactly the ``GSECSR`` reference
    arithmetic (DESIGN.md §12).
    """
    def take(parts, pad):
        flat = [p.reshape(-1) for p in parts]
        flat.append(jnp.full((1,), pad, parts[0].dtype))
        return jnp.concatenate(flat)[a.gather]

    return (take(a.colpak, a.shape[1]), take(a.head, 0), take(a.tail1, 0),
            take(a.tail2, 0))


def decode_operand(a, tag: int, acc_dtype=jnp.float64):
    """``(values, columns)`` decode, in the stored entry order, of a
    ``GSECSR`` OR a packed ``GSESellC`` at precision ``tag`` -- the one
    dispatch point the solver step and the reference SpMV/SpMM
    share, so every solver path rides whichever layout the caller packed,
    bit-identically.  Runs under the ``decode`` scope (the SELL segment
    gather included)."""
    with OT.scope(OT.DECODE):
        if isinstance(a, GSESellC):
            cp, hd, t1, t2 = _sell_segments(a)
            return _decode_gsecsr(cp, hd, t1, t2, a.table, a.ei_bit, tag,
                                  acc_dtype)
        return _decode_gsecsr(
            a.colpak, a.head, a.tail1, a.tail2, a.table, a.ei_bit, tag,
            acc_dtype
        )


@partial(jax.jit, static_argnames=("tag", "acc_dtype", "num_rows", "ei_bit"))
def _spmv_gse(colpak, head, tail1, tail2, table, row_ids, x, ei_bit, tag,
              acc_dtype, num_rows):
    # Reads x by the gather on every store.  The final correction's eager
    # check lowers a switch over this jaxpr once a solve, where a change
    # to what it lowers has cost a v5e host 0.1 s a solve (PERF.md §6);
    # the windows would save one SpMV's gather a solve.
    with OT.scope(OT.SPMV):
        with OT.scope(OT.DECODE):
            val, col = _decode_gsecsr(
                colpak, head, tail1, tail2, table, ei_bit, tag, acc_dtype
            )
        return gather_scatter(val, col, x, row_ids, num_rows, acc_dtype)


def spmv_operand(a, x, tag: int, acc_dtype=jnp.float64):
    """``A @ x`` of a ``GSECSR`` or ``GSESellC`` at a static ``tag``, under
    the ``spmv`` scope: one ``decode_operand``, then ``gather_scatter``,
    which reads ``x`` in windows where the operand has a ``slot_map``.
    The solver step (``fused_cg.cg_step``) inlines it; ``spmv_gse`` jits
    it for SELL."""
    with OT.scope(OT.SPMV):
        val, col = decode_operand(a, tag, acc_dtype)
        return gather_scatter(val, col, x, a.row_ids, a.shape[0], acc_dtype,
                              a.slot_map)


_spmv_gse_sell = partial(jax.jit, static_argnames=("tag", "acc_dtype"))(
    spmv_operand)


def spmv_gse(a, x: jnp.ndarray, tag: int = 1, acc_dtype=jnp.float64):
    """Paper Algorithm 2 (+tails): GSE-SEM SpMV at precision ``tag`` 1/2/3.

    ``a`` is a ``GSECSR`` or a SELL-C-σ packed ``GSESellC``; the two are
    bit-identical here (the SELL path gathers the SAME segment bits back
    to the operand's entry order before the shared decode + row
    reduction), they
    differ only in what the kernels stream and what the byte model
    charges (``a.bytes_touched(tag)``: nnz-only for ``GSECSR``, actual
    padded slots for ``GSESellC``; DESIGN.md §12).

    Bytes touched for the value stream: 2/4/8 per nnz for tags 1/2/3 plus
    4 per nnz of packed colidx -- vs 8+4 for FP64 CSR.  The TPU-tiled
    equivalents (``kernels/ops.gse_spmv_ell`` / ``gse_spmv_sell``)
    dispatch to tag-specialized Pallas kernels that provably stream only
    those segments (DESIGN.md §2.4).  Inside CG the step decodes the
    values once per iteration and folds the vector ops around this SpMV
    in one tag branch (DESIGN.md §4).
    """
    if isinstance(a, GSESellC):
        return _spmv_gse_sell(a, x, tag, acc_dtype)
    return _spmv_gse(
        a.colpak, a.head, a.tail1, a.tail2, a.table, a.row_ids, x,
        a.ei_bit, tag, acc_dtype, a.shape[0]
    )


@partial(jax.jit, static_argnames=("acc_dtype",))
def spmv_ell(cols: jnp.ndarray, vals: jnp.ndarray, x: jnp.ndarray,
             acc_dtype=jnp.float64):
    """Padded-ELL SpMV: dense (rows, L) tiles -- the TPU-shaped reference."""
    prod = vals.astype(acc_dtype) * x.astype(acc_dtype)[cols]
    return jnp.sum(prod, axis=1)


@partial(jax.jit, static_argnames=("store_dtype", "acc_dtype", "num_rows"))
def _spmm_cast(row_ids, col, val, x, store_dtype, acc_dtype, num_rows):
    v = val.astype(store_dtype).astype(acc_dtype)  # storage round-trip
    prod = v[:, None] * x.astype(acc_dtype)[col]   # (nnz, nrhs)
    return jax.ops.segment_sum(prod, row_ids, num_segments=num_rows)


def spmm(a: CSR, x: jnp.ndarray, store_dtype=jnp.float64,
         acc_dtype=jnp.float64):
    """Y = A @ X for a dense (n, nrhs) right-hand-side block.

    Multi-RHS twin of :func:`spmv` (fixed-format baselines): the value and
    colidx streams are read ONCE and amortized across all ``nrhs`` columns
    -- the memory-bound win the batched solvers build on (DESIGN.md §11).
    Column ``j`` of the result is numerically the column-by-column
    ``spmv(a, x[:, j])`` (same gather, same segment reduction order).
    """
    if x.ndim != 2:
        raise ValueError(f"spmm wants a (n, nrhs) block; got {x.shape}")
    return _spmm_cast(
        a.row_ids, a.col, a.val, x, store_dtype, acc_dtype, a.shape[0]
    )


def spmm_gse(a, x: jnp.ndarray, tag: int = 1, acc_dtype=jnp.float64):
    """GSE-SEM SpMM at precision ``tag``: Y = A @ X, X dense (n, nrhs).

    ``a`` is a ``GSECSR`` or a SELL-C-σ packed ``GSESellC`` (bit-identical
    results; the layouts differ only in streamed bytes -- DESIGN.md §12).
    One decoded-value pass feeds every column, so the modeled matrix
    traffic is ``a.bytes_touched(tag)`` ONCE per call however many
    right-hand sides ride along -- ``csr.iteration_stream_bytes(...,
    nrhs=nrhs)`` is the per-iteration account (DESIGN.md §11).  The
    TPU-tiled equivalents (``kernels/ops.gse_spmm_ell`` /
    ``gse_spmm_sell``) dispatch to tag-specialized Pallas kernels that
    provably stream only the segments ``tag`` reads, exactly like the
    SpMV pipeline.  Here it is ``spmv_gse`` on the block: one decode, one
    gather and one row reduction for every column.
    """
    if x.ndim != 2:
        raise ValueError(f"spmm_gse wants a (n, nrhs) block; got {x.shape}")
    return spmv_gse(a, x, tag, acc_dtype)
