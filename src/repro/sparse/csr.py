"""Sparse matrix containers: CSR, GSE-SEM CSR, and TPU-friendly blocked-ELL.

Paper Section III.C.1: shared-exponent *indices* are encoded into the top
``EI_BIT`` bits of the 32-bit CSR column indices (the largest SuiteSparse
column count needs only 28 bits), so the SEM head keeps all 15 non-sign
bits for mantissa... except the head must still carry the index for the
dense-tensor path; for the CSR path we free those bits.  We keep both
layouts:

  * ``GSECSR``   -- expIdx packed in ``col``; head's EI field is repurposed
                    as extra mantissa bits (M_H + EI_BIT usable bits).
  * ``GSEPacked``-- self-describing dense tensors (quant / LM path).

TPU adaptation: ``to_ell`` pads rows to a lane-aligned width so SpMV maps
onto dense (rows x lanes) tiles (DESIGN.md section 2).
"""
from __future__ import annotations

import dataclasses
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import gse, precision_table
from repro.core.tagmap import TagMap

__all__ = [
    "CSR",
    "GSECSR",
    "GSESellC",
    "ELLLayout",
    "from_coo",
    "pack_csr",
    "to_ell",
    "scatter_rows",
    "sell_slices",
    "pack_sell",
    "ell_layout",
    "iteration_stream_bytes",
    "vector_stream_bytes",
]

# Matrix-stream bytes one padded slot (or one nnz) costs at each GSE tag:
# 2/4/8 value-segment bytes + 4 packed-colidx bytes (DESIGN.md §8).
# Canonical table lives in core/precision_table.py; this is the historical
# alias other modules import.
_SLOT_BYTES = precision_table.SLOT_BYTES
_GATHERED_X_BYTES = precision_table.GATHERED_X_BYTES


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class CSR:
    rowptr: jnp.ndarray  # (m+1,) int32
    col: jnp.ndarray     # (nnz,) int32
    val: jnp.ndarray     # (nnz,) float
    row_ids: jnp.ndarray  # (nnz,) int32 -- precomputed for segment_sum
    shape: Tuple[int, int]

    @property
    def nnz(self) -> int:
        return self.col.shape[0]

    def bytes_per_nnz(self, store_dtype=jnp.float64) -> int:
        """Modeled bytes streamed per nonzero by one SpMV: value + colidx."""
        return jnp.dtype(store_dtype).itemsize + 4

    def bytes_touched(self, store_dtype=jnp.float64) -> int:
        """Modeled HBM bytes one SpMV touches in the matrix streams.

        Value + colidx per nnz plus the rowptr stream; the dense x/y vector
        traffic is format-independent and excluded so formats compare on
        what the encoding actually changes.
        """
        return self.nnz * self.bytes_per_nnz(store_dtype) + self.rowptr.size * 4

    def tree_flatten(self):
        return (self.rowptr, self.col, self.val, self.row_ids), (self.shape,)

    @classmethod
    def tree_unflatten(cls, aux, leaves):
        return cls(*leaves, shape=aux[0])


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class GSECSR:
    """CSR with GSE-SEM values; expIdx lives in the top bits of ``col``."""

    rowptr: jnp.ndarray   # (m+1,) int32
    colpak: jnp.ndarray   # (nnz,) uint32: [expIdx : EI_BIT][col : 32-EI_BIT]
    head: jnp.ndarray     # (nnz,) uint16: sign(1) | mantissa(15)
    tail1: jnp.ndarray    # (nnz,) uint16
    tail2: jnp.ndarray    # (nnz,) uint32
    table: jnp.ndarray    # (k,) int32 biased+1
    row_ids: jnp.ndarray  # (nnz,) int32
    ei_bit: int
    shape: Tuple[int, int]

    @property
    def m_h(self) -> int:
        # col carries the index -> the head spends only the sign bit.
        return 15

    @property
    def width(self) -> int:
        return self.m_h + 48

    @property
    def nnz(self) -> int:
        return self.colpak.shape[0]

    def nbytes(self, tag: int) -> int:
        n = self.colpak.shape[0]
        per = precision_table.TAG_VALUE_BYTES[tag]
        return n * per + self.table.size * 4

    def bytes_per_nnz(self, tag: int) -> int:
        """Modeled matrix-stream bytes per nonzero of the encoding at
        ``tag``: only the segments the tag reads count, 2/4/8 value bytes
        + 4 packed-colidx bytes -> 6/8/12 for tags 1/2/3, vs 12 for FP64
        CSR.  The ELL/SELL kernels add row padding and a gathered-x f32
        per slot (``ELLLayout``/``GSESellC.bytes_touched``).
        """
        pt = precision_table
        return pt.TAG_VALUE_BYTES[tag] + pt.COLIDX_BYTES

    def bytes_touched(self, tag: int, layout=None) -> int:
        """Modeled HBM bytes one tag-``tag`` SpMV touches in the matrix
        streams.  Dense x/y traffic is format-independent and excluded.

        ``layout=None`` is the nnz-only mode (per-nnz segments + rowptr +
        the shared-exponent table) used by the format-comparison figures:
        it charges what the *encoding* costs, independent of how rows are
        padded onto tiles.  Passing a packed layout (``GSESellC`` or
        ``ELLLayout``) charges the ACTUAL padded slots that layout streams
        -- ``layout.bytes_touched(tag)`` -- so skewed matrices stop
        under-reporting traffic (DESIGN.md §12).

        ``tag`` may be a per-group :class:`~repro.core.tagmap.TagMap`
        (DESIGN.md §18): the nnz-only mode then charges EACH entry at its
        symmetric induced tag (max of row/column group tags -- what the
        masked operand actually streams) -- the blended byte model the
        adaptive schedule is gated on.  A uniform map reproduces the
        scalar figure exactly.
        """
        if layout is not None:
            return layout.bytes_touched(tag)
        fixed = self.rowptr.size * 4 + self.table.size * 4
        if isinstance(tag, TagMap):
            cols = (np.asarray(self.colpak, np.uint32)
                    & np.uint32((1 << (32 - self.ei_bit)) - 1))
            et = tag.entry_tags(np.asarray(self.row_ids), cols)
            counts = np.bincount(et, minlength=4)
            return fixed + int(sum(
                int(counts[t]) * self.bytes_per_nnz(t) for t in (1, 2, 3)
            ))
        return self.nnz * self.bytes_per_nnz(tag) + fixed

    def tree_flatten(self):
        return (
            self.rowptr, self.colpak, self.head, self.tail1, self.tail2,
            self.table, self.row_ids,
        ), (self.ei_bit, self.shape)

    @classmethod
    def tree_unflatten(cls, aux, leaves):
        return cls(*leaves, ei_bit=aux[0], shape=aux[1])


@dataclasses.dataclass(frozen=True)
class ELLLayout:
    """Padding descriptor of the uniform blocked-ELL pack (DESIGN.md §12).

    Uniform ELL pads EVERY row to the longest row's lane-aligned width, so
    one dense row on a skewed matrix multiplies the streamed slots for the
    whole matrix.  This descriptor makes that cost explicit:
    ``bytes_touched(tag, nrhs)`` charges every padded slot the kernels
    actually stream (value segment + packed colidx + one gathered-x f32
    per right-hand side, plus the shared-exponent table);
    ``padding_ratio`` is the wasted fraction.
    """

    rows: int           # padded row count the kernel grid covers
    width: int          # lane-aligned uniform row width L
    nnz: int            # real stored entries
    table_entries: int  # shared-exponent table length

    @property
    def slots(self) -> int:
        return self.rows * self.width

    @property
    def padding_ratio(self) -> float:
        """Fraction of streamed slots that are padding, in [0, 1)."""
        return 1.0 - self.nnz / max(self.slots, 1)

    def bytes_touched(self, tag, nrhs: int = 1) -> int:
        """``tag`` may be a :class:`~repro.core.tagmap.TagMap`: each row's
        padded slots are then charged at the ROW's group tag (the default
        group size equals the kernels' 8-row grid block, so a per-row-
        block operand choice is physically realizable -- DESIGN.md §18).
        This is the idealized row-side model: entries promoted only via
        their COLUMN's group (symmetric induced tags) are charged at the
        row tag, so it lower-bounds the blended nnz model slightly.
        A uniform map reproduces the scalar figure exactly."""
        xg = nrhs * _GATHERED_X_BYTES
        if isinstance(tag, TagMap):
            rt = tag.row_tags(self.rows)
            per = np.array([0] + [_SLOT_BYTES[t] + xg for t in (1, 2, 3)],
                           np.int64)
            return (int(per[rt].sum()) * self.width
                    + self.table_entries * 4)
        return (self.slots * (_SLOT_BYTES[tag] + xg)
                + self.table_entries * 4)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class GSESellC:
    """Sliced-ELL (SELL-C-σ) view of a :class:`GSECSR` (DESIGN.md §12).

    Rows are sorted by descending length inside windows of ``sigma`` rows
    (σ-window sort -- the permutation is recoverable and locality-bounded),
    grouped into slices of ``c`` rows, and each slice is padded only to its
    OWN lane-aligned width instead of the global maximum.  Slices are then
    binned by width into a handful of power-of-two width-buckets; each
    bucket stores its slices' segment arrays as one dense
    ``(slices*c, width)`` block, so the SpMV/SpMM kernels run one
    ``pallas_call`` per bucket with exactly the tag-specialized operand
    list of the uniform-ELL kernels.

    Leaves (per width-bucket tuples + flat metadata):

      * ``colpak/head/tail1/tail2`` -- tuples of ``(rows_b, w_b)`` segment
        arrays, one entry per width-bucket (ascending widths);
      * ``gather``  -- (nnz,) flat index of every CSR-order entry inside the
        concatenation of the row-major bucket arrays (the packed store IS
        the value store: the reference/solver paths decode through this
        gather, bit-identical to the CSR decode);
      * ``perm``    -- (rows_padded,) original row id of each concatenated
        bucket row (-1 for slice-padding rows);
      * ``unperm``  -- (m,) position of each original row in that
        concatenation (``perm[unperm[i]] == i``);
      * ``row_ids`` -- (nnz,) CSR-order row ids (segment reduction);
      * ``table``   -- shared-exponent table.

    Static: per-bucket ``widths``, ``c``, ``sigma``, ``lane``, ``ei_bit``,
    ``shape``.  The byte model charges ACTUAL padded slots
    (``bytes_touched``); ``padding_ratio`` reports the wasted fraction.
    """

    colpak: tuple   # per-bucket (rows_b, w_b) uint32
    head: tuple     # per-bucket (rows_b, w_b) uint16
    tail1: tuple    # per-bucket (rows_b, w_b) uint16
    tail2: tuple    # per-bucket (rows_b, w_b) uint32
    gather: jnp.ndarray   # (nnz,) int32
    perm: jnp.ndarray     # (rows_padded,) int32, -1 for padding rows
    unperm: jnp.ndarray   # (m,) int32
    row_ids: jnp.ndarray  # (nnz,) int32
    table: jnp.ndarray    # (k,) int32 biased+1
    widths: Tuple[int, ...]
    c: int
    sigma: int
    lane: int
    ei_bit: int
    shape: Tuple[int, int]

    @property
    def nnz(self) -> int:
        return self.gather.shape[0]

    @property
    def n_buckets(self) -> int:
        return len(self.widths)

    @property
    def bucket_rows(self) -> Tuple[int, ...]:
        return tuple(cp.shape[0] for cp in self.colpak)

    @property
    def slots(self) -> int:
        """Padded slots actually stored/streamed, across all buckets."""
        return sum(r * w for r, w in zip(self.bucket_rows, self.widths))

    @property
    def padding_ratio(self) -> float:
        """Fraction of streamed slots that are padding, in [0, 1)."""
        return 1.0 - self.nnz / max(self.slots, 1)

    def bytes_per_nnz(self, tag: int) -> float:
        """EFFECTIVE bytes the SpMV kernel streams per nonzero: padded
        slots (segments + gathered x) amortized over the real entries (the
        honest twin of ``GSECSR.bytes_per_nnz``, which charges the
        encoding's nnz only)."""
        return ((_SLOT_BYTES[tag] + _GATHERED_X_BYTES) * self.slots
                / max(self.nnz, 1))

    def bucket_tags(self, tm: "TagMap") -> Tuple[int, ...]:
        """Per-width-bucket max INDUCED entry tag (max of row/column group
        tags over the bucket's real entries) -- the coarse unit the SELL
        kernels dispatch a per-group map at (DESIGN.md §18).  A bucket
        with no real entries charges tag 1."""
        cp_flat = np.concatenate(
            [np.asarray(cp, np.uint32).reshape(-1) for cp in self.colpak]
        ) if self.colpak else np.zeros(0, np.uint32)
        gather = np.asarray(self.gather, np.int64)
        cols = cp_flat[gather] & np.uint32((1 << (32 - self.ei_bit)) - 1)
        et = tm.entry_tags(np.asarray(self.row_ids), cols)
        sizes = np.array([cp.size for cp in self.colpak], np.int64)
        offs = np.concatenate([[0], np.cumsum(sizes)])
        bidx = np.searchsorted(offs, gather, side="right") - 1
        tags = np.ones(len(self.colpak), np.int64)
        np.maximum.at(tags, bidx, et.astype(np.int64))
        return tuple(int(t) for t in tags)

    def bytes_touched(self, tag, nrhs: int = 1) -> int:
        """Modeled HBM bytes one tag-``tag`` SpMV (``nrhs`` > 1: SpMM)
        kernel pass streams through this layout: every padded slot's value
        segment + packed colidx + one gathered-x f32 per right-hand side,
        the output row permutation, and the shared-exponent table.

        ``tag`` may be a :class:`~repro.core.tagmap.TagMap`: each width-
        bucket's slots are then charged at the bucket's MAX group tag --
        exactly what the per-bucket kernel dispatch streams (an all-tag-1
        bucket never touches tails), so this blended figure is the
        PHYSICAL model, not an optimistic nnz blend (DESIGN.md §18)."""
        fixed = self.perm.shape[0] * 4 + self.table.size * 4
        xg = nrhs * _GATHERED_X_BYTES
        if isinstance(tag, TagMap):
            return fixed + int(sum(
                r * w * (_SLOT_BYTES[t] + xg)
                for r, w, t in zip(self.bucket_rows, self.widths,
                                   self.bucket_tags(tag))
            ))
        return self.slots * (_SLOT_BYTES[tag] + xg) + fixed

    def tree_flatten(self):
        leaves = (
            self.colpak, self.head, self.tail1, self.tail2,
            self.gather, self.perm, self.unperm, self.row_ids, self.table,
        )
        aux = (self.widths, self.c, self.sigma, self.lane, self.ei_bit,
               self.shape)
        return leaves, aux

    @classmethod
    def tree_unflatten(cls, aux, leaves):
        return cls(*leaves, *aux)


def from_coo(rows, cols, vals, shape) -> CSR:
    """Build CSR from COO triplets (duplicates summed), no scipy."""
    rows = np.asarray(rows, np.int64)
    cols = np.asarray(cols, np.int64)
    vals = np.asarray(vals, np.float64)
    m, n = shape
    key = rows * n + cols
    order = np.argsort(key, kind="stable")
    key, rows, cols, vals = key[order], rows[order], cols[order], vals[order]
    # Sum duplicates.
    uniq, idx = np.unique(key, return_index=True)
    sums = np.add.reduceat(vals, idx)
    rows = rows[idx]
    cols = cols[idx]
    rowptr = np.zeros(m + 1, np.int64)
    np.add.at(rowptr, rows + 1, 1)
    rowptr = np.cumsum(rowptr)
    return CSR(
        rowptr=jnp.asarray(rowptr, jnp.int32),
        col=jnp.asarray(cols, jnp.int32),
        val=jnp.asarray(sums),
        row_ids=jnp.asarray(rows, jnp.int32),
        shape=(int(m), int(n)),
    )


def pack_csr(a: CSR, k: int = 8) -> GSECSR:
    """CSR -> GSE-SEM CSR (paper Algorithm 1 + Section III.C.1).

    The head's 15 non-sign bits are ALL mantissa: with expIdx in colpak the
    head-only precision gains ``EI_BIT`` bits over the dense-tensor layout
    (a paper-faithful benefit of the colidx trick).
    """
    vals = np.asarray(a.val, np.float64)
    table = gse.extract_shared_exponents(vals, k)
    ei = gse._ei_bit(k)
    # Pack with EI_BIT=0-equivalent layout: emulate by calling the core
    # packer with a custom head split. We reuse the generic machinery by
    # packing with k but then re-deriving a 15-bit head from (tag3) M.
    p = gse.pack_with_table(vals, table, k)
    # Recover full-width mantissa M (width = (15-ei)+48) and expIdx:
    head = np.asarray(p.head).astype(np.uint64)
    m_h_dense = 15 - ei
    sign = (head >> np.uint64(15)) & np.uint64(1)
    exp_idx = (head >> np.uint64(m_h_dense)) & np.uint64((1 << ei) - 1)
    m_dense = (
        ((head & np.uint64((1 << m_h_dense) - 1)) << np.uint64(48))
        | (np.asarray(p.tail1).astype(np.uint64) << np.uint64(32))
        | np.asarray(p.tail2).astype(np.uint64)
    )  # width m_h_dense + 48
    # Widen to 15 + 48 = 63 bits: shift left by ei.
    m_wide = m_dense << np.uint64(ei)
    w = 15 + 48
    new_head = ((sign << np.uint64(15)) | (m_wide >> np.uint64(48))).astype(np.uint16)
    new_tail1 = ((m_wide >> np.uint64(32)) & np.uint64(0xFFFF)).astype(np.uint16)
    new_tail2 = (m_wide & np.uint64(0xFFFFFFFF)).astype(np.uint32)

    col = np.asarray(a.col).astype(np.uint32)
    shift = np.uint32(32 - ei)
    max_col = int(col.max()) if col.size else 0
    if max_col >= (1 << (32 - ei)):
        raise ValueError(
            f"column count {max_col} needs > {32 - ei} bits; "
            "use the value-array encoding variant (paper III.C.1)"
        )
    colpak = (exp_idx.astype(np.uint32) << shift) | col
    return GSECSR(
        rowptr=a.rowptr,
        colpak=jnp.asarray(colpak),
        head=jnp.asarray(new_head),
        tail1=jnp.asarray(new_tail1),
        tail2=jnp.asarray(new_tail2),
        table=jnp.asarray(table, jnp.int32),
        row_ids=a.row_ids,
        ei_bit=ei,
        shape=a.shape,
    )


def vector_stream_bytes(op, dtype=jnp.float64) -> int:
    """Modeled HBM bytes ONE dense operand/result column streams: the x
    gather read plus the y write of a single SpMV/SpMM column at
    ``dtype`` (the solver vectors' precision, f64 by default)."""
    m, n = op.shape
    return (m + n) * jnp.dtype(dtype).itemsize


def iteration_stream_bytes(op, tag, precond=None, nrhs: int = 1,
                           layout=None) -> int:
    """Modeled HBM bytes ONE stepped solver iteration streams at ``tag``.

    Sums the operator's matrix streams (``op.bytes_touched``) with the
    preconditioner's stored streams at the SAME tag: in the
    preconditioned stepped solvers both reads follow the monitor's
    schedule, so a tag-1 iteration pays 2 B per stored preconditioner
    entry, not 8 (DESIGN.md §10).  Without a preconditioner ``tag`` may
    also be a ``CSR`` store dtype; charging a preconditioner requires a
    GSE tag in {1, 2, 3} (the preconditioner is always GSE-packed).

    ``nrhs`` is the number of ACTIVE right-hand-side columns the batched
    SpMM iteration feeds (DESIGN.md §11): the matrix (+preconditioner)
    segments are charged ONCE per iteration -- one streaming pass over
    the packed bytes serves every column -- while each column beyond the
    first charges its own dense x/y stream (``vector_stream_bytes``).
    The first column's vector traffic stays excluded exactly as before
    (it is format-independent and cancels in format comparisons), so
    ``nrhs=1`` reproduces the single-RHS figure identically.

    ``layout`` selects the padding-honest account (DESIGN.md §12): a
    ``GSESellC`` or ``ELLLayout`` charges the operator's ACTUAL padded
    slots instead of nnz only, with the kernels' gathered-x tile per
    active column.  Passing a ``GSESellC`` as ``op`` itself is
    equivalent -- its ``bytes_touched`` is already slot-honest.  The
    default (``layout=None``) keeps the nnz-only mode the format-
    comparison figures use, unchanged.
    """
    if nrhs < 1:
        raise ValueError(f"nrhs must be >= 1, got {nrhs}")
    if layout is None and isinstance(op, GSESellC):
        layout = op
    if layout is not None:
        total = layout.bytes_touched(tag, nrhs=nrhs)
    else:
        total = op.bytes_touched(tag)
    if precond is not None:
        # A per-group TagMap charges the preconditioner at the map's MAX
        # tag: the stepped preconditioners follow one scalar schedule, so
        # this is the conservative (never-optimistic) account.
        ptag = tag.max_tag if isinstance(tag, TagMap) else tag
        if ptag not in (1, 2, 3):
            raise ValueError(
                f"preconditioner streams need a GSE tag in {{1, 2, 3}}, "
                f"got {tag!r}"
            )
        total += precond.bytes_touched(ptag)
    total += (nrhs - 1) * vector_stream_bytes(op)
    return total


def scatter_rows(rowptr, sources, width: int, row_subset=None):
    """Scatter CSR-ordered entry streams into zero-padded (rows, width)
    arrays -- the ONE owner of the row-scatter (``to_ell``,
    ``ops.ell_pack_gsecsr`` and the SELL-C-σ bucket packer all call this;
    they used to carry drifting copies).

    ``sources`` is a sequence of ``(array, dtype)`` pairs sharing the CSR
    entry order; each comes back as its own padded array at the requested
    dtype (padding slots are zero).  ``row_subset`` selects AND orders the
    rows to scatter (a SELL bucket's permuted slice rows); ``-1`` entries
    are empty padding rows.  Default: all rows in natural order.

    Returns ``(outs, csr_pos, dest)`` where ``csr_pos`` are the CSR entry
    indices scattered (in scatter order) and ``dest`` their flat slots in
    the padded array -- packed layouts record these to recover entries
    without a rescan.
    """
    rowptr = np.asarray(rowptr, np.int64)
    per_row = np.diff(rowptr)
    if row_subset is None:
        row_subset = np.arange(per_row.size)
    row_subset = np.asarray(row_subset, np.int64)
    valid = row_subset >= 0
    safe = np.where(valid, row_subset, 0)
    lens = np.where(valid, per_row[safe], 0)
    if lens.size and int(lens.max(initial=0)) > width:
        raise ValueError(
            f"row of {int(lens.max())} entries does not fit width {width}"
        )
    total = int(lens.sum())
    starts = np.where(valid, rowptr[safe], 0)
    # Slot-within-row for every scattered entry, vectorized over rows.
    offs = np.arange(total, dtype=np.int64) - np.repeat(
        np.cumsum(lens) - lens, lens
    )
    csr_pos = np.repeat(starts, lens) + offs
    dest = np.repeat(np.arange(row_subset.size, dtype=np.int64) * width,
                     lens) + offs
    outs = []
    for src, dtype in sources:
        out = np.zeros(row_subset.size * width, dtype)
        out[dest] = np.asarray(src)[csr_pos]
        outs.append(out.reshape(row_subset.size, width))
    return outs, csr_pos, dest


def to_ell(a: CSR, lane: int = 128) -> Tuple[np.ndarray, np.ndarray, int]:
    """CSR -> padded ELL (cols[m, L], vals[m, L]); L rounded up to ``lane``.

    Padded entries have col=0, val=0 (contribute nothing).  Returns
    (cols, vals, L).  TPU kernels want lane-aligned dense tiles.
    """
    rowptr = np.asarray(a.rowptr, np.int64)
    L = int(max(1, np.diff(rowptr).max(initial=0)))
    L = ((L + lane - 1) // lane) * lane
    (cols, vals), _, _ = scatter_rows(
        rowptr, [(a.col, np.int32), (a.val, np.float64)], L
    )
    return cols, vals, L


def ell_layout(a, lane: int = 128) -> ELLLayout:
    """Padding descriptor of the uniform-ELL pack of ``a`` (a ``GSECSR``
    or ``CSR``): every row padded to the longest row's lane-aligned width.
    ``ell_layout(g).padding_ratio`` vs ``pack_sell(g).padding_ratio`` is
    the skew cost the SELL-C-σ layout removes (DESIGN.md §12)."""
    per_row = np.diff(np.asarray(a.rowptr, np.int64))
    L = int(max(1, per_row.max(initial=0)))
    L = ((L + lane - 1) // lane) * lane
    table = getattr(a, "table", None)
    return ELLLayout(
        rows=a.shape[0], width=L, nnz=a.nnz,
        table_entries=int(table.size) if table is not None else 0,
    )


def sell_slices(rowptr, c: int = 8, sigma: int | None = None,
                lane: int = 128, bucket: str = "pow2"):
    """σ-window sort + slice/bucket plan (host-side static metadata).

    Rows are sorted by DESCENDING length inside windows of ``sigma`` rows
    (stable, so equal-length rows keep their order and the permutation
    stays window-local); consecutive runs of ``c`` sorted rows form
    slices.  Each slice's width is its longest row rounded up to ``lane``;
    slices are binned into power-of-two multiples of ``lane`` so a
    pathological width spread still dispatches a handful of kernel calls.

    Returns ``(order, slice_bucket_w, sigma)``: the padded row
    permutation (length ``ceil(m/c)*c``, ``-1`` marks padding rows),
    each slice's bucket width, and the EFFECTIVE window size actually
    sorted with (``None`` -> full sort, floor ``c``) -- the one value
    callers should record.
    """
    per_row = np.diff(np.asarray(rowptr, np.int64))
    m = per_row.size
    if c < 1:
        raise ValueError(f"slice height c must be >= 1, got {c}")
    sigma = m if sigma is None else max(int(sigma), c)
    order = np.arange(m, dtype=np.int64)
    for w0 in range(0, m, sigma):
        win = order[w0:w0 + sigma]
        order[w0:w0 + sigma] = win[
            np.argsort(-per_row[win], kind="stable")
        ]
    rows_pad = -(-max(m, 1) // c) * c
    order = np.concatenate(
        [order, np.full(rows_pad - m, -1, np.int64)]
    )
    lens = np.where(order >= 0, per_row[np.clip(order, 0, None)], 0)
    slice_max = lens.reshape(-1, c).max(axis=1)
    slice_w = np.maximum(-(-slice_max // lane) * lane, lane).astype(np.int64)
    # Width-bucket granularity (plan-tunable, DESIGN.md §15): "pow2" bins
    # slice widths into power-of-two lane multiples -- bounded bucket count
    # however the widths spread, at worst <2x extra padding inside a
    # bucket; "exact" keeps every distinct lane-aligned width -- zero
    # bucket padding at the cost of one kernel call per distinct width.
    if bucket == "pow2":
        bucket_w = lane * (
            2 ** np.ceil(np.log2(slice_w / lane)).astype(np.int64)
        )
    elif bucket == "exact":
        bucket_w = slice_w
    else:
        raise ValueError(
            f"bucket must be 'pow2' or 'exact', got {bucket!r}")
    return order, bucket_w, sigma


def pack_sell(a: GSECSR, c: int = 8, sigma: int | None = None,
              lane: int = 128, bucket: str = "pow2") -> GSESellC:
    """GSE-SEM CSR -> SELL-C-σ packed layout (DESIGN.md §12).

    ``c`` must divide into the kernels' sublane block (a multiple of 8) so
    every width-bucket's row count tiles the (8, 128) grid exactly.
    Prefer :func:`repro.kernels.ops.sell_pack_gsecsr`, which memoizes the
    pack on the operator instance (solvers repack nothing per call).
    """
    if c % 8 != 0:
        raise ValueError(f"slice height c must be a multiple of 8, got {c}")
    m = a.shape[0]
    order, bucket_w, sigma_eff = sell_slices(a.rowptr, c=c, sigma=sigma,
                                             lane=lane, bucket=bucket)
    widths = tuple(int(w) for w in sorted(set(bucket_w.tolist())))
    segs = [
        (a.colpak, np.uint32),
        (a.head, np.uint16),
        (a.tail1, np.uint16),
        (a.tail2, np.uint32),
    ]
    gather = np.zeros(a.nnz, np.int64)
    perm_parts, flat_off = [], 0
    outs = {w: None for w in widths}
    for w in widths:
        slice_ids = np.nonzero(bucket_w == w)[0]
        rows_sel = np.concatenate(
            [order[s * c:(s + 1) * c] for s in slice_ids]
        ) if slice_ids.size else np.zeros(0, np.int64)
        arrs, csr_pos, dest = scatter_rows(a.rowptr, segs, int(w), rows_sel)
        outs[w] = arrs
        gather[csr_pos] = flat_off + dest
        perm_parts.append(rows_sel)
        flat_off += rows_sel.size * int(w)
    perm = (np.concatenate(perm_parts) if perm_parts
            else np.zeros(0, np.int64))
    unperm = np.zeros(m, np.int64)
    unperm[perm[perm >= 0]] = np.nonzero(perm >= 0)[0]
    return GSESellC(
        colpak=tuple(jnp.asarray(outs[w][0]) for w in widths),
        head=tuple(jnp.asarray(outs[w][1]) for w in widths),
        tail1=tuple(jnp.asarray(outs[w][2]) for w in widths),
        tail2=tuple(jnp.asarray(outs[w][3]) for w in widths),
        gather=jnp.asarray(gather, jnp.int32),
        perm=jnp.asarray(perm, jnp.int32),
        unperm=jnp.asarray(unperm, jnp.int32),
        row_ids=a.row_ids,
        table=a.table,
        widths=widths,
        c=c,
        sigma=int(sigma_eff),
        lane=lane,
        ei_bit=a.ei_bit,
        shape=a.shape,
    )
