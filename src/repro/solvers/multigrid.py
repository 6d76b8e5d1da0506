"""HPCG's multigrid preconditioner over GSE-packed levels (DESIGN.md §20).

``make_mg(a)`` builds the hierarchy of HPCG 3.1's reference code
(``GenerateCoarseProblem``, ``ComputeMG_ref``, ``ComputeSYMGS_ref``) for an
operator that is a constant-coefficient box stencil:

- level 0 is ``a``; each coarser level is the same stencil on the half
  grid, whose points are the fine points at even coordinates (HPCG's
  ``f2cOperator``);
- one V-cycle from a zero start: a symmetric Gauss-Seidel step, the
  residual at the coarse points (restriction by injection), the coarser
  cycle, prolongation ``x[f2c] += xc``, and a second symmetric step; the
  coarsest level takes one symmetric step alone.

Departures from the reference, each for the chip:

- The sweep is multicolour, not lexicographic.  A point's colour is its
  coordinate parity ``(ix % 2) + 2 (iy % 2) + 4 (iz % 2)``.  No two points
  of one colour are neighbours under offsets in ``{-1, 0, 1}^3``, so a
  colour's rows update at once: ``x_c += (r_c - A_c x) / d_c``.  The
  forward sweep takes colours 0..7 and the backward sweep 7..0, so the
  preconditioner stays symmetric.  It takes a few more iterations than
  the lexicographic sweep.
- Each level's vectors are held in a colour order: colour by colour, and
  within a colour in the order of the next coarser level, so that
  colour 0 (the points at even coordinates) is the coarser level itself.
  Restriction and prolongation are then slices, and the residual is
  formed at colour 0's rows alone, the only rows injection keeps.  The
  operand's vectors are permuted into that order and back at each apply.
- Each level's operator is packed once and split into 8 colour row
  blocks stacked on a leading axis, GSE-SEM ``GSECSR``s stored
  slot-major, streamed at the monitor's tag like the solve's operand; a
  symmetric step is one loop of 16 colour steps over them.  The fine
  level is thus held twice: as the solve's operand and in colour blocks.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import gse
from repro.obs import metrics as OM
from repro.obs import trace as OT
from repro.solvers.precond import _TagDispatchPrecond
from repro.sparse.csr import (CSR, GSECSR, from_coo, pack_csr,
                              stack_row_blocks)
from repro.sparse.generators import box_stencil
from repro.sparse.spmv import spmv_operand

__all__ = ["MGLevel", "MGPrecond", "find_box", "level_layouts", "make_mg"]

COLOURS = 8
# The offsets a box stencil may hold, indexed (dx + 1) + 3 (dy + 1) + 9 (dz + 1).
OFFSETS = tuple((dx, dy, dz) for dz in (-1, 0, 1) for dy in (-1, 0, 1)
                for dx in (-1, 0, 1))

# V-cycles traced into compiled programs, by the hierarchy's level count:
# counted once a trace, as ``spmv_row_reduction_total`` is.
VCYCLES = OM.REGISTRY.counter(
    "mg_vcycle_total",
    "Traced multigrid V-cycles by the number of levels.",
    labelnames=("levels",))


def _coords(idx, grid):
    nx, ny, _ = grid
    return idx % nx, (idx // nx) % ny, idx // (nx * ny)


def _lex(ix, iy, iz, grid):
    nx, ny, _ = grid
    return ix + nx * (iy + ny * iz)


def colour_of(ix, iy, iz):
    """A point's colour, ``(ix % 2) + 2 (iy % 2) + 4 (iz % 2)``."""
    return (ix % 2) + 2 * (iy % 2) + 4 * (iz % 2)


def _boxes(n: int):
    """Every ``(nx, ny, nz)`` with ``nx * ny * nz == n``."""
    divs = [d for d in range(1, int(n ** 0.5) + 1) if n % d == 0]
    divs = sorted(set(divs + [n // d for d in divs]))
    for nx in divs:
        for ny in divs:
            if (n // nx) % ny == 0:
                yield nx, ny, n // (nx * ny)


def _taps(rows, cols, vals, grid):
    """``(taps, None)`` of the entries read as a stencil on ``grid``, or
    ``(None, why)``."""
    d = [c - r for r, c in zip(_coords(rows, grid), _coords(cols, grid))]
    if any(np.abs(di).max(initial=0) > 1 for di in d):
        return None, f"on the box {grid} an entry reaches past a neighbour"
    code = (d[0] + 1) + 3 * (d[1] + 1) + 9 * (d[2] + 1)
    taps = {}
    for k in np.unique(code):
        v = vals[code == k]
        if not np.all(v == v[0]):
            return None, (f"on the box {grid} the values at offset "
                          f"{OFFSETS[k]} vary: not constant-coefficient")
        taps[OFFSETS[k]] = float(v[0])
    return taps, None


def find_box(a: CSR, divisor: int = 1):
    """``(grid, taps)`` of ``a`` read as a constant-coefficient stencil with
    offsets in ``{-1, 0, 1}^3`` on a lexicographic ``nx x ny x nz`` box, x
    fastest, every dimension divisible by ``divisor``.

    The box is read off the sparsity, and the stencil regenerated on it
    (``generators.box_stencil``) must equal ``a`` exactly.  Raises
    ``ValueError`` saying why where no box does."""
    n, m = a.shape
    if n != m:
        raise ValueError(f"multigrid needs a square operator, got {a.shape}")
    rows = np.asarray(a.row_ids, np.int64)
    cols = np.asarray(a.col, np.int64)
    vals = np.asarray(a.val, np.float64)
    offs = set(np.unique(cols - rows).tolist())
    whys, found = [], []
    for grid in _boxes(n):
        nx, ny, _ = grid
        if not offs <= {dx + nx * (dy + ny * dz) for dx, dy, dz in OFFSETS}:
            continue
        taps, bad = _taps(rows, cols, vals, grid)
        if taps is None:
            whys.append(bad)
            continue
        b = box_stencil(grid, taps)
        if not (np.array_equal(np.asarray(b.rowptr), np.asarray(a.rowptr))
                and np.array_equal(np.asarray(b.col), np.asarray(a.col))
                and np.array_equal(np.asarray(b.val), np.asarray(a.val))):
            whys.append(f"the stencil regenerated on the box {grid} differs")
            continue
        if all(g % divisor == 0 for g in grid):
            return grid, taps
        found.append(grid)
    if found:
        raise ValueError(
            f"the operator is a stencil on the box {found[0]}, but each "
            f"dimension must be divisible by {divisor} to halve it into the "
            "levels asked for")
    why = whys[0] if whys else ("its column offsets fit no box with "
                                "neighbours in {-1, 0, 1}^3")
    raise ValueError(f"multigrid needs HPCG's kind of operator, a "
                     f"constant-coefficient box stencil: {why}")


def level_layouts(grids):
    """Per level, the lexicographic index of the point held at each slot of
    the level's vectors, -1 for padding.

    The slots hold colour 0, then colour 1, ..., each colour in ``R`` slots
    (its largest size), its points first.  Within colour ``c`` the points
    follow the next coarser level's order (its point ``q`` gives the fine
    point ``2 q`` moved by ``c``'s parities), so the first ``n_{l+1}``
    slots of level ``l`` are HPCG's f2c points in the order of level
    ``l + 1``.  On the coarsest level a colour's points are lexicographic,
    and only there, where a dimension may be odd, do colours differ in
    size and leave padding."""
    last = grids[-1]
    idx = np.arange(int(np.prod(last)))
    colour = colour_of(*_coords(idx, last))
    rows = int(np.bincount(colour, minlength=COLOURS).max())
    layout = np.full(COLOURS * rows, -1)
    for c in range(COLOURS):
        pts = idx[colour == c]
        layout[c * rows:c * rows + pts.size] = pts
    layouts = [layout]
    for fine, coarse in zip(grids[-2::-1], grids[:0:-1]):
        cx, cy, cz = _coords(layouts[0][layouts[0] >= 0], coarse)
        layouts.insert(0, np.concatenate([
            _lex(2 * cx + (c & 1), 2 * cy + (c >> 1 & 1), 2 * cz + (c >> 2),
                 fine)
            for c in range(COLOURS)]))
    return layouts


def _gather0(v, idx):
    """``v`` gathered at ``idx``, where ``idx == v.size`` reads a zero."""
    return jnp.concatenate([v, jnp.zeros((1,), v.dtype)])[idx]


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(eq=False)
class MGLevel:
    """One level: its operator as 8 stacked colour row blocks of ``R`` rows
    (``csr.stack_row_blocks``), the diagonal over its ``8 R`` slots (1 on
    padding), and the gathers into and out of its slot layout where it
    differs from the order its caller holds (``None`` where not)."""

    ops: GSECSR                 # leaves (8, ...); block shape (R, 8 R)
    diag: gse.GSEPacked         # (8 R,)
    enter: jnp.ndarray | None   # (8 R,) int32: source of each slot
    leave: jnp.ndarray | None   # (n,) int32: slot of each point

    @property
    def rows(self) -> int:
        return self.ops.shape[0]

    def tree_flatten(self):
        return (self.ops, self.diag, self.enter, self.leave), None

    @classmethod
    def tree_unflatten(cls, aux, leaves):
        return cls(*leaves)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(eq=False)
class MGPrecond(_TagDispatchPrecond):
    """HPCG's V-cycle, ``z = M^{-1} r``, over GSE-packed colour blocks."""

    levels: tuple   # MGLevel, fine to coarse
    grids: tuple    # static: per level, (nx, ny, nz)

    kind = "mg"

    def apply_at(self, r: jnp.ndarray, tag: int, acc_dtype=jnp.float64):
        """One V-cycle from a zero start at a *static* tag: every level's
        operator and diagonal are decoded at ``tag``."""
        VCYCLES.labels(levels=str(len(self.levels))).inc()
        return self._cycle(0, r.astype(acc_dtype), tag, acc_dtype)

    def _cycle(self, lvl, r, tag, acc_dtype):
        lv = self.levels[lvl]
        if lv.enter is not None:
            with OT.scope(OT.TRANSFER):
                r = _gather0(r, lv.enter)
        x = self._symgs(lv, r, jnp.zeros_like(r), tag, acc_dtype)
        if lvl + 1 < len(self.levels):
            nc = lv.rows                 # colour 0 is the coarser level
            with OT.scope(OT.RESIDUAL):
                c0 = jax.tree.map(lambda v: v[0], lv.ops)
                rc = r[:nc] - spmv_operand(c0, x, tag, acc_dtype)
            xc = self._cycle(lvl + 1, rc, tag, acc_dtype)
            with OT.scope(OT.TRANSFER):
                x = jax.lax.dynamic_update_slice(x, x[:nc] + xc, (0,))
            x = self._symgs(lv, r, x, tag, acc_dtype)
        if lv.leave is not None:
            with OT.scope(OT.TRANSFER):
                x = x[lv.leave]
        return x

    @staticmethod
    def _symgs(lv, r, x, tag, acc_dtype):
        """A forward sweep over colours 0..7, then a backward one: 16
        dependent colour steps in one loop."""
        rows = lv.rows
        with OT.scope(OT.SMOOTH):
            d = gse.decode_jnp(lv.diag, tag, acc_dtype)

            def step(i, x):
                c = jnp.where(i < COLOURS, i, 2 * COLOURS - 1 - i)
                block = jax.tree.map(
                    lambda v: jax.lax.dynamic_index_in_dim(v, c, 0, False),
                    lv.ops)
                t = spmv_operand(block, x, tag, acc_dtype)
                lo = c * rows

                def part(v):
                    return jax.lax.dynamic_slice_in_dim(v, lo, rows)

                return jax.lax.dynamic_update_slice_in_dim(
                    x, part(x) + (part(r) - t) / part(d), lo, 0)

            return jax.lax.fori_loop(0, 2 * COLOURS, step, x)

    def f2c(self, lvl: int) -> np.ndarray:
        """HPCG's ``f2cOperator`` of level ``lvl + 1``: the lexicographic
        index on level ``lvl`` of each coarse point, in lexicographic
        order."""
        layouts = level_layouts(self.grids)
        coarse = layouts[lvl + 1][layouts[lvl + 1] >= 0]
        out = np.empty(coarse.size, np.int64)
        out[coarse] = layouts[lvl][:coarse.size]
        return out

    def operator(self, lvl: int):
        """Level ``lvl``'s operator decoded from its colour blocks at tag 3,
        as lexicographic COO ``(rows, cols, vals)``."""
        from repro.sparse.spmv import decode_gsecsr

        lv = self.levels[lvl]
        layout = level_layouts(self.grids)[lvl]
        out = []
        for c in range(COLOURS):
            block = jax.tree.map(lambda v: v[c], lv.ops).in_csr_order()
            val, col = decode_gsecsr(block, 3, jnp.float64)
            rows = np.asarray(block.row_ids) + c * lv.rows
            out.append((layout[rows], layout[np.asarray(col)],
                        np.asarray(val)))
        return tuple(np.concatenate(p) for p in zip(*out))

    def bytes_touched(self, tag: int) -> int:
        """Modeled HBM bytes of the stored hierarchy one apply streams: on
        each level but the coarsest, two symmetric steps (4 reads of every
        colour block) and the residual (colour 0 once more); on the
        coarsest one symmetric step; each step's diagonal once."""
        total = 0
        for lvl, lv in enumerate(self.levels):
            nnz = np.asarray(lv.ops.rowptr)[:, -1]
            per = lv.ops.bytes_per_nnz(tag)
            fixed = lv.ops.rowptr.size * 4 + lv.ops.table.size * 4
            ops = int(nnz.sum()) * per + fixed
            if lvl + 1 == len(self.levels):
                total += 2 * ops + lv.diag.nbytes(tag)
            else:
                total += (4 * ops + int(nnz[0]) * per
                          + 2 * lv.diag.nbytes(tag))
        return total

    def tree_flatten(self):
        return (self.levels,), (self.grids,)

    @classmethod
    def tree_unflatten(cls, aux, leaves):
        return cls(*leaves, *aux)


def make_mg(a: CSR, k: int = 8, levels: int = 4) -> MGPrecond:
    """HPCG's multigrid preconditioner for ``a``, a constant-coefficient box
    stencil (``find_box``), with ``levels`` levels (HPCG's
    ``numberOfMgLevels`` is 4), each level's operator packed once against
    ``k`` shared exponents.  Every grid dimension must be divisible by
    ``2 ** (levels - 1)``.  Runs under the host span ``precond.mg.setup``,
    annotated with each level's grid, rows and nonzeros."""
    if levels < 1:
        raise ValueError(f"levels must be >= 1, got {levels}")
    with OT.span("precond.mg.setup", levels=levels):
        grid, taps = find_box(a, 2 ** (levels - 1))
        centre = taps.get((0, 0, 0), 0.0)
        if centre == 0.0:
            raise ValueError("multigrid needs a nonzero diagonal: the "
                             "stencil has no centre value")
        grids = tuple(tuple(g >> lvl for g in grid) for lvl in range(levels))
        built, nnz = [], []
        for lvl, (g, layout) in enumerate(zip(grids, level_layouts(grids))):
            op = box_stencil(g, taps)
            n, slots = op.shape[0], layout.size
            real = layout >= 0
            slot_of = np.empty(n, np.int64)
            slot_of[layout[real]] = np.flatnonzero(real)
            ap = from_coo(slot_of[np.asarray(op.row_ids)],
                          slot_of[np.asarray(op.col)], np.asarray(op.val),
                          (slots, slots))
            # Where the caller's order is not the slot layout: the operand
            # (lexicographic) into level 0, the padded coarsest level.
            source = layout if lvl == 0 else np.where(
                real, np.cumsum(real) - 1, -1)
            moved = lvl == 0 or not real.all()
            built.append(MGLevel(
                ops=stack_row_blocks(pack_csr(ap, k), slots // COLOURS),
                diag=gse.pack(np.where(real, centre, 1.0), k),
                enter=(jnp.asarray(np.where(source >= 0, source, n),
                                   jnp.int32) if moved else None),
                leave=(jnp.asarray(slot_of if lvl == 0 else
                                   np.flatnonzero(real), jnp.int32)
                       if moved else None)))
            nnz.append(op.nnz)
        OT.annotate(grids=[list(g) for g in grids],
                    rows=[int(np.prod(g)) for g in grids], nnz=nnz)
    return MGPrecond(levels=tuple(built), grids=grids)
