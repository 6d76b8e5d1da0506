"""Least HBM bytes of one stepped MG-PCG iteration, CG preconditioned by
HPCG's V-cycle, on an HPCG box, whatever implements it.

The hierarchy is derived from ``shape``: the fine level is a cube of
HPCG's 27-point stencil, ``n = nx^3`` rows and ``nnz = (3 nx - 2)^3``
entries, and each of the ``LEVELS - 1`` coarser levels the same stencil on
the half grid.  The work of the iteration:

- the CG iteration (``cg.py``);
- on each level but the coarsest, two symmetric Gauss-Seidel steps, each a
  forward and a backward sweep that reads the level's operator once (four
  reads at the tag), and the residual at the coarse points: injection
  keeps only the rows at even coordinates, ``(3 nx / 2 - 1)^3`` entries,
  so the least work reads those rows alone (HPCG's reference computes
  ``A x`` in full and drops seven eighths of it); on the coarsest level
  one symmetric step, two reads;
- each symmetric step's diagonal once, at the tag's value segments;
- the float64 vectors of a sweep: each of its 8 colour updates reads the
  whole level vector it multiplies (8 B a row), and reads its rows of
  ``r`` and ``x`` and writes ``x`` (24 B a row over the sweep); the
  residual reads ``x`` and the coarse rows of ``r`` and writes the coarse
  residual; prolongation reads the coarse correction and reads and writes
  the coarse rows of ``x``.

An operator's entries cost their value segments and a 4 B column index,
its rows a 4 B row pointer, as in ``cg.py``.
"""
from bench.work import cg

LEVELS = 4
COLOURS = 8
F64 = cg.F64


def levels(shape: dict) -> list:
    """``[{"n", "nnz", "f2c_nnz"}]`` from the fine level down; ``f2c_nnz``
    is the entries of the rows the residual keeps (0 on the coarsest)."""
    n, nnz = shape["n"], shape["nnz"]
    side = round(n ** (1 / 3))
    if side ** 3 != n or nnz != (3 * side - 2) ** 3:
        raise ValueError(f"shape {shape} is not HPCG's 27-point stencil on "
                         "a cube: n = nx^3 and nnz = (3 nx - 2)^3")
    if side % 2 ** (LEVELS - 1):
        raise ValueError(f"a {side}^3 box does not halve into {LEVELS} "
                         f"levels: nx must be divisible by {2 ** (LEVELS - 1)}")
    out = []
    for lvl in range(LEVELS):
        s = side >> lvl
        coarse = (3 * s // 2 - 1) ** 3 if lvl + 1 < LEVELS else 0
        out.append({"n": s ** 3, "nnz": (3 * s - 2) ** 3, "f2c_nnz": coarse})
    return out


def _matrix(rows: int, nnz: int, tag: int) -> int:
    return nnz * (cg.SEGMENT_BYTES[tag] + cg.COLUMN_BYTES) + (
        rows + 1) * cg.ROWPTR_BYTES


def vcycle_bytes(shape: dict, tag: int) -> int:
    total = 0
    for lvl, lv in enumerate(levels(shape)):
        n, nc = lv["n"], lv["n"] // COLOURS
        steps = 1 if lvl + 1 == LEVELS else 2
        sweeps = 2 * steps
        total += sweeps * (_matrix(n, lv["nnz"], tag)
                           + (COLOURS + 3) * n * F64)
        total += steps * n * cg.SEGMENT_BYTES[tag]
        if steps == 2:
            total += _matrix(nc, lv["f2c_nnz"], tag) + n * F64 + 2 * nc * F64
            total += 3 * nc * F64
    return total


def iteration_bytes(shape: dict, tag: int) -> int:
    return cg.iteration_bytes(shape, tag) + vcycle_bytes(shape, tag)
