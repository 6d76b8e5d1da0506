"""Row-sharded partitioning of a GSE-SEM CSR operator (DESIGN.md §13).

The paper's lever is bandwidth: a tag-1 iteration streams 6 B/nnz instead
of 12.  On one device PRs 1-4 exhausted that lever; the next one is to
split the byte stream across devices.  ``partition_gsecsr`` cuts a
:class:`~repro.sparse.csr.GSECSR` into ``n_shards`` contiguous row blocks:

  * each shard keeps its row slice of the packed segment streams
    (``colpak/head/tail1/tail2``), padded to the max per-shard nnz so the
    shards stack into ``(n_shards, E)`` device arrays for ``shard_map``;
  * column indices are REMAPPED to index the shard's local x window
    ``concat(x_shard, x_halo)`` -- columns owned by the shard index the
    local block directly, remote columns go through a compact halo map;
  * the halo map is the classic boundary/halo split: shard ``i`` packs the
    x entries that ANY other shard reads into a ``(B,)`` boundary buffer
    (``bnd_idx``), the buffers are ``all_gather``-ed into a ``(s*B,)``
    pool, and ``halo_idx`` gathers each shard's remote entries out of the
    pool.  Only boundary entries cross the wire -- never the full vector.

Tag-aware wire format (the GSE segmentation applied to the interconnect,
cf. Loe et al., arXiv:2109.01232 -- communication, not flops, dominates
mixed-precision Krylov on accelerators): with ``wire="gse"`` the boundary
buffer is packed through the GSE head/tail segments at the iteration's
precision tag, so a tag-1 halo exchange ships 2-byte heads (plus the
per-shard shared-exponent table), tag 2 ships head+tail1 (4 B), and tag 3
ships exact IEEE float64 (8 B -- the segmented 63-bit mantissa costs the
same bytes but loses dynamic range, so full precision rides raw bits).
``wire="exact"`` ships float64 at every tag: zero perturbation, used for
the bit/trajectory-parity contracts.

Byte model (mirrors ``csr.iteration_stream_bytes`` exactly):

  ``shard_stream_bytes(tag)[i] = nnz_i * bytes_per_nnz(tag) + rows_i * 4``
  ``shared_stream_bytes()     = 4 + table_entries * 4``

and the identity ``sum(shard_stream_bytes(tag)) + shared_stream_bytes()
== iteration_stream_bytes(gsecsr, tag)`` holds EXACTLY (asserted in
tests/test_distributed.py): sharding redistributes the single-device
matrix stream, it does not change it -- what it ADDS is the halo wire
traffic, ``halo_wire_bytes(tag, wire)``.
"""
from __future__ import annotations

import dataclasses
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from repro.core import precision_table
from repro.core.tagmap import TagMap, normalize_tags
from repro.sparse.csr import (
    _SLOT_BYTES,
    GSECSR,
    iteration_stream_bytes,
    vector_stream_bytes,
)

__all__ = [
    "PartitionedGSECSR",
    "partition_gsecsr",
    "unshard",
    "WIRE_ENTRY_BYTES",
    "AXIS",
]

# The mesh axis the stacked per-shard arrays are split along.
AXIS = "shards"

# Bytes ONE boundary x-entry costs on the wire at each tag (DESIGN.md §13):
# tag 1 ships the u16 GSE head, tag 2 head+tail1, tag 3 raw float64.
# Canonical table lives in core/precision_table.py.
WIRE_ENTRY_BYTES = precision_table.WIRE_ENTRY_BYTES


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class PartitionedGSECSR:
    """Row-sharded view of a ``GSECSR``: stacked per-shard blocks + halo map.

    All per-shard arrays carry a leading ``n_shards`` axis and are padded
    to uniform extents (max nnz ``E``, max boundary ``B``, max halo ``H``)
    so ``shard_map`` can split them along the mesh axis; with enough
    devices visible, row ``i`` of each lives on device ``i`` of the
    ``AXIS`` mesh.  Padding matrix entries decode to +0.0 and carry the
    out-of-range row id ``R``, which the local segment sum drops, so they
    perturb nothing; padded boundary slots (``bnd_idx == -1``) are masked
    to zero before the wire pack, and padded halo slots are never read by
    real matrix entries.
    """

    # -- stacked per-shard matrix blocks (leading dim n_shards) ------------
    colpak: jnp.ndarray    # (s, E) uint32: [expIdx][LOCAL col in x_shard++halo]
    head: jnp.ndarray      # (s, E) uint16
    tail1: jnp.ndarray     # (s, E) uint16
    tail2: jnp.ndarray     # (s, E) uint32
    row_ids: jnp.ndarray   # (s, E) int32 LOCAL row ids; padding -> R (dropped)
    # -- halo exchange plan ------------------------------------------------
    bnd_idx: jnp.ndarray   # (s, B) int32 local x indices this shard sends
    #                        (-1 marks padded slots: masked to 0 on the wire)
    halo_idx: jnp.ndarray  # (s, H) int32 positions in the (s*B,) gathered pool
    # -- shared -----------------------------------------------------------
    table: jnp.ndarray     # (k,) int32 shared-exponent table (replicated)
    # -- static metadata ---------------------------------------------------
    ei_bit: int
    shape: Tuple[int, int]
    n_shards: int
    rows_per_shard: int              # R: padded uniform row-block height
    nnz_per_shard: Tuple[int, ...]   # real (unpadded) nnz of each shard
    rows_real: Tuple[int, ...]       # real rows owned by each shard
    bnd_counts: Tuple[int, ...]      # real boundary entries each shard sends
    halo_counts: Tuple[int, ...]     # real halo entries each shard gathers

    # -- sizes -------------------------------------------------------------

    @property
    def nnz(self) -> int:
        return int(sum(self.nnz_per_shard))

    @property
    def n_padded(self) -> int:
        """Global padded row count ``n_shards * rows_per_shard``."""
        return self.n_shards * self.rows_per_shard

    @property
    def bnd_width(self) -> int:
        """Padded per-shard boundary-buffer width B (the all_gather slot
        count each shard broadcasts)."""
        return int(self.bnd_idx.shape[1])

    @property
    def halo_entries(self) -> int:
        """Total real remote entries gathered per SpMV, across shards."""
        return int(sum(self.halo_counts))

    # -- byte model (DESIGN.md §13) ---------------------------------------

    def bytes_per_nnz(self, tag: int) -> int:
        # The shards stream the same encoding as the unsharded container:
        # value segment + packed colidx per nnz (csr._SLOT_BYTES).
        return _SLOT_BYTES[tag]

    def _global_entries(self):
        """Per-shard (global_rows, global_cols) of the REAL entries, int64.

        Reconstructed once from the local blocks (the inverse of the
        column remap, same walk as :func:`unshard`) and memoized -- the
        per-group byte model needs global coordinates to induce entry
        tags."""
        cached = self.__dict__.get("_global_entries_memo")
        if cached is not None:
            return cached
        ei = self.ei_bit
        shift = np.uint32(32 - ei)
        r_blk = self.rows_per_shard
        s_colpak = np.asarray(self.colpak)
        s_rows = np.asarray(self.row_ids)
        halo = np.asarray(self.halo_idx)
        bnd = np.asarray(self.bnd_idx)
        out = []
        for i in range(self.n_shards):
            nz = self.nnz_per_shard[i]
            loc = (s_colpak[i, :nz]
                   & np.uint32((1 << (32 - ei)) - 1)).astype(np.int64)
            is_halo = loc >= r_blk
            pool = halo[i]
            owners = pool // max(self.bnd_width, 1)
            owner_slot = pool % max(self.bnd_width, 1)
            halo_global = (owners * r_blk + bnd[owners, owner_slot]
                           if pool.size else np.zeros(0, np.int64))
            gcol = np.where(
                is_halo,
                halo_global[np.clip(loc - r_blk, 0, None)]
                if pool.size else 0,
                loc + i * r_blk,
            )
            grow = s_rows[i, :nz].astype(np.int64) + i * r_blk
            out.append((grow, gcol))
        self.__dict__["_global_entries_memo"] = out
        return out

    def shard_stream_bytes(self, tag) -> Tuple[int, ...]:
        """Modeled HBM bytes EACH shard streams for its matrix block in one
        tag-``tag`` SpMV: real nnz at the tag's segment bytes + packed
        colidx, plus the shard's slice of the rowptr stream.  Real (not
        padded) extents are charged so the shards sum exactly to the
        single-device figure.

        ``tag`` may be a per-group :class:`~repro.core.tagmap.TagMap`:
        each entry is then charged at its SYMMETRIC induced tag (max of
        row/column group tags, global coordinates -- the same blend as
        ``GSECSR.bytes_touched(tagmap)``, so the redistribution identity
        still holds exactly)."""
        tag = normalize_tags(tag)
        if isinstance(tag, TagMap):
            per = np.array([0] + [_SLOT_BYTES[t] for t in (1, 2, 3)],
                           np.int64)
            return tuple(
                int(per[tag.entry_tags(grow, gcol)].sum()) + rr * 4
                for (grow, gcol), rr in zip(self._global_entries(),
                                            self.rows_real)
            )
        return tuple(
            nz * self.bytes_per_nnz(tag) + rr * 4
            for nz, rr in zip(self.nnz_per_shard, self.rows_real)
        )

    def shared_stream_bytes(self) -> int:
        """Once-per-iteration global terms: the rowptr terminal entry and
        the shared-exponent table (replicated on every shard but charged
        once -- it is the same single-device stream redistributed)."""
        return 4 + int(self.table.size) * 4

    def bnd_slot_tags(self, tags) -> np.ndarray:
        """(s, B) uint8 per-slot wire tags under a tag map.

        A boundary x-entry belongs to ONE row group (the row-only
        ``entry_tags`` form -- vector streams have no column partner), so
        each real slot carries its entry's group tag; padded slots
        (``bnd_idx == -1``) carry the map's MAX tag -- they ride the
        payload anyway and are charged honestly, like the SELL padding
        account.  Feed the shard's row to ``wire.halo_all_gather``'s
        ``slot_tags`` so tag-1 slots drop their tail segment on the wire.
        """
        tm = normalize_tags(tags)
        if not isinstance(tm, TagMap):
            return np.full((self.n_shards, self.bnd_width), tm, np.uint8)
        bnd = np.asarray(self.bnd_idx)
        out = np.full(bnd.shape, tm.max_tag, np.uint8)
        for i in range(self.n_shards):
            real = bnd[i] >= 0
            if real.any():
                gcol = bnd[i][real].astype(np.int64) \
                    + i * self.rows_per_shard
                out[i, real] = tm.entry_tags(gcol)
        return out

    def halo_wire_bytes(self, tag, wire: str = "exact",
                        nrhs: int = 1) -> int:
        """Modeled interconnect bytes ONE distributed SpMV/SpMM moves.

        Each shard broadcasts its padded ``B``-slot boundary buffer to the
        other ``s - 1`` shards (the all_gather payload -- padded slots are
        charged, honestly, like the SELL padding account).  With
        ``wire="gse"`` a tag-1/2 entry ships its head (+tail1) segment and
        each shard's per-iteration shared-exponent table rides along; at
        tag 3 (and for ``wire="exact"`` at every tag) entries ship raw
        float64.  ``nrhs`` columns each ship their own boundary entries
        AND (tags 1/2) their own per-shard table -- the per-column apply
        path the batched solvers run; the block ``dist_spmm`` path packs
        one table per call and is strictly cheaper than modeled.  The
        default wire matches the solvers' default (``"exact"``).
        """
        if wire not in ("exact", "gse"):
            raise ValueError(f"unknown wire mode {wire!r}; 'exact' or 'gse'")
        if self.n_shards == 1 or self.bnd_width == 0:
            return 0  # nothing remote: no collective at all
        s, b = self.n_shards, self.bnd_width
        tag = normalize_tags(tag)
        if isinstance(tag, TagMap):
            if wire == "exact":
                return (s - 1) * s * b * 8 * nrhs
            # Blended per-slot wire: each slot at its own group's entry
            # bytes; a shard's shared-exponent table rides only if ANY of
            # its slots ships a head-segmented (tag 1/2) payload.
            st = self.bnd_slot_tags(tag)
            per = np.array([0] + [WIRE_ENTRY_BYTES[t] for t in (1, 2, 3)],
                           np.int64)
            total = (s - 1) * int(per[st].sum()) * nrhs
            senders = int((st <= 2).any(axis=1).sum())
            total += (s - 1) * senders * int(self.table.size) * 4 * nrhs
            return total
        per_entry = 8 if wire == "exact" else WIRE_ENTRY_BYTES[tag]
        total = (s - 1) * s * b * per_entry * nrhs
        if wire == "gse" and tag in (1, 2):
            total += (s - 1) * s * int(self.table.size) * 4 * nrhs
        return total

    def iteration_stream_bytes(self, tag: int, wire: str = "exact",
                               nrhs: int = 1) -> int:
        """Modeled bytes one distributed stepped iteration streams: the
        exact single-device matrix stream (redistributed across shards)
        plus the halo wire traffic plus the extra columns' vector streams
        -- i.e. ``csr.iteration_stream_bytes(op, tag, nrhs=nrhs) +
        halo_wire_bytes(tag, wire, nrhs)`` (identity asserted in tests)."""
        total = sum(self.shard_stream_bytes(tag)) + self.shared_stream_bytes()
        total += (nrhs - 1) * vector_stream_bytes(self)
        return total + self.halo_wire_bytes(tag, wire, nrhs)

    # -- pytree ------------------------------------------------------------

    def tree_flatten(self):
        leaves = (self.colpak, self.head, self.tail1, self.tail2,
                  self.row_ids, self.bnd_idx, self.halo_idx, self.table)
        aux = (self.ei_bit, self.shape, self.n_shards, self.rows_per_shard,
               self.nnz_per_shard, self.rows_real, self.bnd_counts,
               self.halo_counts)
        return leaves, aux

    @classmethod
    def tree_unflatten(cls, aux, leaves):
        return cls(*leaves, *aux)


def partition_gsecsr(a: GSECSR, n_shards: int) -> PartitionedGSECSR:
    """Split a ``GSECSR`` into ``n_shards`` row blocks with a halo plan.

    Rows are cut into contiguous blocks of ``R = ceil(n / n_shards)``
    (trailing shards may own fewer real rows; the blocks are padded to
    ``R`` with empty rows).  Entry order inside every row is preserved, so
    each shard's local segment reduction reproduces the single-device
    per-row sums bit-for-bit -- the basis of the 1-shard bit-identity and
    k-shard trajectory contracts (tests/test_distributed.py).
    """
    if n_shards < 1:
        raise ValueError(f"n_shards must be >= 1, got {n_shards}")
    n = a.shape[0]
    if a.shape[0] != a.shape[1]:
        raise ValueError(
            f"row sharding wants a square operator, got {a.shape}"
        )
    rowptr = np.asarray(a.rowptr, np.int64)
    colpak = np.asarray(a.colpak, np.uint32)
    head = np.asarray(a.head, np.uint16)
    tail1 = np.asarray(a.tail1, np.uint16)
    tail2 = np.asarray(a.tail2, np.uint32)
    ei = a.ei_bit
    shift = np.uint32(32 - ei)
    col = (colpak & np.uint32((1 << (32 - ei)) - 1)).astype(np.int64)
    exp_idx = (colpak >> shift).astype(np.uint32)

    r_blk = -(-n // n_shards)  # ceil
    starts = [min(i * r_blk, n) for i in range(n_shards + 1)]

    # Pass 1: per-shard remote column sets -> per-owner boundary sets.
    shard_of = lambda c: np.minimum(c // r_blk, n_shards - 1)
    remote_cols = []           # per shard: sorted unique remote global cols
    send_sets = [set() for _ in range(n_shards)]
    for i in range(n_shards):
        lo, hi = starts[i], starts[i + 1]
        cols_i = col[rowptr[lo]:rowptr[hi]]
        rem = np.unique(cols_i[(cols_i < lo) | (cols_i >= hi)])
        remote_cols.append(rem)
        for c in rem:
            send_sets[int(shard_of(c))].add(int(c))
    bnd_cols = [np.array(sorted(s), np.int64) for s in send_sets]
    bnd_counts = tuple(len(b) for b in bnd_cols)
    # B == 0 (block-diagonal operators, or 1 shard): no exchange at all --
    # the matvec skips the collective and the wire model charges nothing.
    B = max([0] + [len(b) for b in bnd_cols]) if n_shards > 1 else 0
    # Global col -> (owner, slot in owner's boundary buffer) -> pool index.
    pool_pos = {}
    for i, cols_i in enumerate(bnd_cols):
        for slot, c in enumerate(cols_i):
            pool_pos[int(c)] = i * B + slot

    # Pass 2: per-shard blocks with locally remapped columns.
    E = max(1, max(
        int(rowptr[starts[i + 1]] - rowptr[starts[i]])
        for i in range(n_shards)
    ))
    H = max([0] + [len(r) for r in remote_cols]) if n_shards > 1 else 0
    s_colpak = np.zeros((n_shards, E), np.uint32)
    s_head = np.zeros((n_shards, E), np.uint16)
    s_tail1 = np.zeros((n_shards, E), np.uint16)
    s_tail2 = np.zeros((n_shards, E), np.uint32)
    s_rows = np.full((n_shards, E), r_blk, np.int32)  # padding -> dummy row
    # Boundary padding is -1: the matvec masks those slots to ZERO before
    # the wire pack, so a shard with fewer real boundary entries than B
    # cannot leak x values into its shared-exponent table (zeros are
    # excluded from the exponent histogram entirely).
    s_bnd = np.full((n_shards, B), -1, np.int32)
    s_halo = np.zeros((n_shards, H), np.int32)
    nnz_per_shard = []
    halo_counts = []
    max_local = r_blk + (H if n_shards > 1 else 0)
    if max_local >= (1 << (32 - ei)):
        raise ValueError(
            f"local window {max_local} needs > {32 - ei} bits; "
            "reduce shard size or halo width"
        )
    for i in range(n_shards):
        lo, hi = starts[i], starts[i + 1]
        e0, e1 = int(rowptr[lo]), int(rowptr[hi])
        nz = e1 - e0
        nnz_per_shard.append(nz)
        cols_i = col[e0:e1]
        local = (cols_i >= lo) & (cols_i < hi)
        # Remote columns -> slot in this shard's halo window [R, R + h).
        rem = remote_cols[i]
        halo_counts.append(len(rem))
        loc_col = np.where(local, cols_i - lo, 0)
        if len(rem):
            rank = np.searchsorted(rem, cols_i)
            loc_col = np.where(local, loc_col, r_blk + rank)
            s_halo[i, :len(rem)] = [pool_pos[int(c)] for c in rem]
        s_colpak[i, :nz] = (exp_idx[e0:e1] << shift) | loc_col.astype(
            np.uint32)
        s_head[i, :nz] = head[e0:e1]
        s_tail1[i, :nz] = tail1[e0:e1]
        s_tail2[i, :nz] = tail2[e0:e1]
        # Local row ids (0-based within the block), preserved entry order.
        s_rows[i, :nz] = (
            np.repeat(np.arange(hi - lo), np.diff(rowptr[lo:hi + 1])).astype(
                np.int32)
        )
        if n_shards > 1 and len(bnd_cols[i]):
            s_bnd[i, :len(bnd_cols[i])] = bnd_cols[i] - lo
    stacked, mesh = _place(
        (s_colpak, s_head, s_tail1, s_tail2, s_rows, s_bnd, s_halo),
        n_shards)
    part = PartitionedGSECSR(
        *stacked,
        table=a.table,
        ei_bit=ei,
        shape=a.shape,
        n_shards=n_shards,
        rows_per_shard=r_blk,
        nnz_per_shard=tuple(nnz_per_shard),
        rows_real=tuple(starts[i + 1] - starts[i] for i in range(n_shards)),
        bnd_counts=bnd_counts if n_shards > 1 else (0,),
        halo_counts=tuple(halo_counts) if n_shards > 1 else (0,),
    )
    if mesh is not None:
        part.__dict__["_mesh"] = mesh  # what dist_spmv.shard_mesh returns
    return part


def _place(arrays, n_shards: int):
    """Stacked (n_shards, ...) host arrays -> device arrays whose row ``i``
    lives on device ``i`` of a 1-D ``AXIS`` mesh, so the sharded solvers
    never copy the operator between devices per call.  With fewer visible
    devices than shards (byte models, host-only use) the arrays stay on
    the default device and no mesh is returned."""
    devs = jax.devices()
    if len(devs) < n_shards:
        return [jnp.asarray(x) for x in arrays], None
    mesh = Mesh(np.array(devs[:n_shards]), (AXIS,))
    spec = NamedSharding(mesh, PartitionSpec(AXIS))
    return [jax.device_put(x, spec) for x in arrays], mesh


def unshard(part: PartitionedGSECSR, a_template: GSECSR) -> GSECSR:
    """Reassemble the original ``GSECSR`` segment arrays from a partition
    (round-trip check: partitioning is a pure redistribution).

    ``a_template`` supplies the global ``rowptr``/``row_ids`` (the
    partition keeps only local forms); the returned container's packed
    segments are reconstructed from the shard blocks and must be
    bit-identical to the original's (tests/test_distributed.py).
    """
    n = part.shape[0]
    ei = part.ei_bit
    shift = np.uint32(32 - ei)
    r_blk = part.rows_per_shard
    colpak_parts, head_parts, t1_parts, t2_parts = [], [], [], []
    s_colpak = np.asarray(part.colpak)
    s_head = np.asarray(part.head)
    s_t1 = np.asarray(part.tail1)
    s_t2 = np.asarray(part.tail2)
    halo = np.asarray(part.halo_idx)
    bnd = np.asarray(part.bnd_idx)
    for i in range(part.n_shards):
        nz = part.nnz_per_shard[i]
        cp = s_colpak[i, :nz]
        loc = (cp & np.uint32((1 << (32 - ei)) - 1)).astype(np.int64)
        exp_idx = cp >> shift
        lo = i * r_blk
        is_halo = loc >= r_blk
        # Halo slot -> pool position -> (owner, owner-local idx) -> global.
        pool = halo[i]
        owners = pool // max(part.bnd_width, 1)
        owner_slot = pool % max(part.bnd_width, 1)
        halo_global = owners * r_blk + bnd[owners, owner_slot]
        gcol = np.where(is_halo,
                        halo_global[np.clip(loc - r_blk, 0, None)]
                        if pool.size else 0,
                        loc + lo)
        colpak_parts.append((exp_idx << shift) | gcol.astype(np.uint32))
        head_parts.append(s_head[i, :nz])
        t1_parts.append(s_t1[i, :nz])
        t2_parts.append(s_t2[i, :nz])
    return GSECSR(
        rowptr=a_template.rowptr,
        colpak=jnp.asarray(np.concatenate(colpak_parts)),
        head=jnp.asarray(np.concatenate(head_parts)),
        tail1=jnp.asarray(np.concatenate(t1_parts)),
        tail2=jnp.asarray(np.concatenate(t2_parts)),
        table=part.table,
        row_ids=a_template.row_ids,
        ei_bit=ei,
        shape=part.shape,
    )
