"""Single source of truth for per-tag and per-dtype byte constants.

Before PR 7 these numbers were re-declared (and re-documented) in three
places -- ``sparse/csr.py`` (``_SLOT_BYTES``), ``distributed/partition.py``
(``WIRE_ENTRY_BYTES``), and ``launch/hlo.py`` (``_DTYPE_BYTES``) -- plus
the 6/8/12 B/nnz literals scattered through docstrings.  They all derive
from one fact about the GSE-SEM encoding (paper Section III.C):

  tag 1 streams the u16 head            -> 2 value bytes / entry
  tag 2 streams head + u16 tail1        -> 4 value bytes / entry
  tag 3 streams head + tail1 + u32 tail2-> 8 value bytes / entry

and every CSR/ELL/SELL entry additionally streams a packed u32 column
index (``COLIDX_BYTES``), giving the paper's 6/8/12 B/nnz matrix-stream
figures (``SLOT_BYTES``) -- the encoding's cost.  The ELL/SELL Pallas
kernels also stream the f32 gathered-x tile ``x[col]`` per padded slot
and right-hand side (``GATHERED_X_BYTES``), so one SpMV kernel launch
streams ``KERNEL_SLOT_BYTES`` = 10/12/16 B per padded slot.  The halo wire ships only the *value* segments
(the receiving shard already knows which boundary entries it asked for),
so ``WIRE_ENTRY_BYTES == TAG_VALUE_BYTES``.

The old names remain importable from their original modules as aliases of
these tables; ``tests/test_precision_table.py`` pins the derived
``bytes_touched`` figures so a drift here cannot pass silently.
"""
from __future__ import annotations

__all__ = [
    "TAG_VALUE_BYTES",
    "COLIDX_BYTES",
    "SLOT_BYTES",
    "WIRE_ENTRY_BYTES",
    "DTYPE_BYTES",
    "TAGS",
    "TAG_SEGMENTS",
    "SEGMENT_BYTES",
    "TAG_BITS_USED",
    "tag_operand_names",
    "GATHERED_X_BYTES",
    "KERNEL_SLOT_BYTES",
]

# GSE tags in escalation order (head-only -> +tail1 -> +tail2).
TAGS = (1, 2, 3)

# Segment-array bytes per entry: u16 head, u16 tail1, u32 tail2.
SEGMENT_BYTES = {"head": 2, "tail1": 2, "tail2": 4}

# The tail segment arrays each tag streams BEYOND the always-read head.
# This is the one table the tag-specialized kernel operand lists, the
# SELL bucket tuples, and the byte models all derive from; before PR 10
# it was re-declared inline in gse_spmv.py, gse_spmm.py, and perf/ledger.
TAG_SEGMENTS = {1: (), 2: ("tail1",), 3: ("tail1", "tail2")}

# Mantissa bits a decode at each tag consumes from the 15-bit head plus
# the 16-bit tail1 / 32-bit tail2 splices: 15 / 31 / 63.  The dense
# GSEPacked path offsets these by the expIdx bits stolen from the head
# (``m_h = 15 - ei_bit``); the sparse path keeps all 15 head bits because
# expIdx rides colpak instead.
TAG_BITS_USED = {t: 15 + sum(8 * SEGMENT_BYTES[s] for s in TAG_SEGMENTS[t])
                 for t in TAGS}

# Value-segment bytes ONE matrix entry (or one wire x-entry) costs at each
# tag -- head + the tails TAG_SEGMENTS says that tag reads: 2 / 4 / 8.
TAG_VALUE_BYTES = {
    t: SEGMENT_BYTES["head"] + sum(SEGMENT_BYTES[s] for s in TAG_SEGMENTS[t])
    for t in TAGS
}


def tag_operand_names(tag: int) -> tuple:
    """The pallas_call operand list the tag-specialized kernels stream
    (``xg`` is the gathered-x tile, one f32 per padded slot and column)."""
    return ("scales", "colpak", "head") + TAG_SEGMENTS[tag] + ("xg",)

# Every stored entry also streams one packed u32 column index (expIdx in
# the top EI_BIT bits, column in the rest).
COLIDX_BYTES = 4

# Matrix-stream bytes one padded slot (or one nnz) costs at each tag:
# the paper's 6/8/12 B/nnz format promise (DESIGN.md section 8).
SLOT_BYTES = {t: TAG_VALUE_BYTES[t] + COLIDX_BYTES for t in TAGS}

# f32 bytes of the gathered-x tile (``x[col]``) the ELL/SELL kernels stream
# per padded slot and right-hand side: Mosaic lowers no 1-D gather of a
# VMEM-pinned x, so the gather runs in XLA and the kernel streams its output.
GATHERED_X_BYTES = 4

# Bytes one tag-t SpMV kernel launch streams per padded slot: 10/12/16.
KERNEL_SLOT_BYTES = {t: SLOT_BYTES[t] + GATHERED_X_BYTES for t in TAGS}

# Bytes ONE boundary x-entry costs on the halo wire at each tag
# (DESIGN.md section 13): the wire ships value segments only.
WIRE_ENTRY_BYTES = dict(TAG_VALUE_BYTES)

# HLO shape-string dtype widths for the launch/hlo.py byte estimator.
DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1,
}
