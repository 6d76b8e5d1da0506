"""Least HBM bytes of one stepped-CG iteration on a GSE-SEM CSR operator.

The work of the iteration, whatever implements it:

- per nonzero, the value segments the tag needs (2, 4 or 8 B at tags 1, 2
  and 3) and a 4 B column index;
- the row pointers, 4 B once per row (and one more);
- the SpMV's input vector once: 8 B a row;
- the float64 CG vectors ``x``, ``r`` and ``p``: one read and one write
  each, 48 B a row.

``shape`` holds ``n`` (rows), ``nnz``, ``halo`` (halo entries summed over
shards) and ``chips``; the result is summed over all chips.
"""

SEGMENT_BYTES = {1: 2, 2: 4, 3: 8}
COLUMN_BYTES = 4
ROWPTR_BYTES = 4
F64 = 8


def matrix_bytes(shape: dict, tag: int) -> int:
    return (shape["nnz"] * (SEGMENT_BYTES[tag] + COLUMN_BYTES)
            + (shape["n"] + 1) * ROWPTR_BYTES)


def vector_bytes(shape: dict) -> int:
    return shape["n"] * F64 + 3 * 2 * shape["n"] * F64


def iteration_bytes(shape: dict, tag: int) -> int:
    return matrix_bytes(shape, tag) + vector_bytes(shape)
