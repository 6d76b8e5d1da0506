"""Synthetic sparse-matrix suite (SuiteSparse stand-in; DESIGN.md section 7).

The container has no network access, so the paper's 312 SuiteSparse
matrices are replaced by generators that reproduce the *roles* of the
paper's test sets:

  CG set (Table II left):  symmetric positive definite -- Poisson stencils,
      mass-like diagonal matrices, random SPD with controlled conditioning.
  GMRES set (Table II right): asymmetric -- convection-diffusion, circuit
      -like power-law, randomly perturbed stencils.

Value distributions are drawn with clustered exponents so Fig-1 statistics
(top-8 exponent coverage ~90%) hold on the synthetic suite too.
"""
from __future__ import annotations

from typing import Callable, Dict, List, Tuple

import numpy as np

from repro.sparse.csr import CSR, from_coo

__all__ = [
    "poisson2d",
    "poisson3d",
    "convection_diffusion_2d",
    "random_spd",
    "circuit_like",
    "skewed_spd",
    "diag_rescale",
    "ill_conditioned_spd",
    "mass_diagonal",
    "cg_suite",
    "gmres_suite",
    "spmv_suite",
]


def poisson2d(n: int) -> CSR:
    """5-point Laplacian on an n x n grid (SPD, like af_shell/thermal2 role)."""
    N = n * n
    idx = np.arange(N).reshape(n, n)
    rows, cols, vals = [], [], []

    def add(r, c, v):
        rows.append(r.ravel())
        cols.append(c.ravel())
        vals.append(np.full(r.size, v))

    add(idx, idx, 4.0)
    add(idx[1:, :], idx[:-1, :], -1.0)
    add(idx[:-1, :], idx[1:, :], -1.0)
    add(idx[:, 1:], idx[:, :-1], -1.0)
    add(idx[:, :-1], idx[:, 1:], -1.0)
    return from_coo(
        np.concatenate(rows), np.concatenate(cols), np.concatenate(vals), (N, N)
    )


def poisson3d(n: int) -> CSR:
    """7-point Laplacian on an n^3 grid (SPD, bone010/Queen role)."""
    N = n ** 3
    idx = np.arange(N).reshape(n, n, n)
    rows, cols, vals = [], [], []

    def add(r, c, v):
        rows.append(r.ravel())
        cols.append(c.ravel())
        vals.append(np.full(r.size, v))

    add(idx, idx, 6.0)
    for axis in range(3):
        sl_lo = [slice(None)] * 3
        sl_hi = [slice(None)] * 3
        sl_lo[axis] = slice(1, None)
        sl_hi[axis] = slice(None, -1)
        add(idx[tuple(sl_lo)], idx[tuple(sl_hi)], -1.0)
        add(idx[tuple(sl_hi)], idx[tuple(sl_lo)], -1.0)
    return from_coo(
        np.concatenate(rows), np.concatenate(cols), np.concatenate(vals), (N, N)
    )


def convection_diffusion_2d(n: int, beta: float = 20.0) -> CSR:
    """Upwind convection-diffusion (asymmetric; GMRES wang3/epb2 role)."""
    N = n * n
    h = 1.0 / (n + 1)
    idx = np.arange(N).reshape(n, n)
    rows, cols, vals = [], [], []

    def add(r, c, v):
        rows.append(np.asarray(r).ravel())
        cols.append(np.asarray(c).ravel())
        vals.append(np.broadcast_to(v, np.asarray(r).ravel().shape).copy())

    add(idx, idx, 4.0 + beta * h)
    add(idx[1:, :], idx[:-1, :], -(1.0 + beta * h))  # upwind
    add(idx[:-1, :], idx[1:, :], -1.0)
    add(idx[:, 1:], idx[:, :-1], -(1.0 + 0.5 * beta * h))
    add(idx[:, :-1], idx[:, 1:], -1.0)
    return from_coo(
        np.concatenate(rows), np.concatenate(cols), np.concatenate(vals), (N, N)
    )


def random_spd(n: int, nnz_per_row: int = 8, cond_decades: float = 3.0,
               seed: int = 0) -> CSR:
    """Random SPD: A = B + B^T + shift*I with clustered-exponent values."""
    rng = np.random.default_rng(seed)
    rows = np.repeat(np.arange(n), nnz_per_row)
    cols = rng.integers(0, n, size=n * nnz_per_row)
    # Clustered exponents: magnitudes 2^U with U from a few discrete bins.
    bins = rng.choice([-2, -1, 0, 1], size=n * nnz_per_row, p=[0.1, 0.2, 0.5, 0.2])
    vals = rng.uniform(1.0, 2.0, n * nnz_per_row) * np.exp2(bins)
    vals *= rng.choice([-1.0, 1.0], size=vals.shape)
    # Symmetrize + diagonal dominance (guarantees SPD).
    r = np.concatenate([rows, cols, np.arange(n)])
    c = np.concatenate([cols, rows, np.arange(n)])
    shift = 4.0 * nnz_per_row * np.exp2(1)
    diag = np.full(n, shift) * np.exp2(
        rng.uniform(0, cond_decades, n)  # spread the diagonal exponents
    )
    v = np.concatenate([vals, vals, diag])
    return from_coo(r, c, v, (n, n))


def circuit_like(n: int, seed: int = 0) -> CSR:
    """Power-law degree, wildly varying conductances (adder_dcop role)."""
    rng = np.random.default_rng(seed)
    deg = np.minimum((rng.pareto(1.5, n) + 1).astype(np.int64) * 2, 64)
    rows = np.repeat(np.arange(n), deg)
    cols = rng.integers(0, n, size=deg.sum())
    expo = rng.choice([-6, -3, 0, 0, 0, 3], size=deg.sum())
    vals = rng.uniform(1.0, 2.0, deg.sum()) * np.exp2(expo)
    vals *= rng.choice([-1.0, 1.0], size=vals.shape)
    r = np.concatenate([rows, np.arange(n)])
    c = np.concatenate([cols, np.arange(n)])
    v = np.concatenate([vals, np.full(n, 70.0)])  # dominant diagonal
    return from_coo(r, c, v, (n, n))


def skewed_spd(n: int = 2048, dense_rows: int = 4, base_halfwidth: int = 58,
               tail_scale: float = 3.0, seed: int = 0) -> CSR:
    """SPD with power-law row-length skew and a few DENSE rows -- the
    uniform-ELL worst case the SELL-C-σ layout exists for (DESIGN.md §12).

    Construction (R-MAT-flavored heavy hitters on a banded base):

      * a symmetric PERIODIC band whose per-row halfwidth is
        ``base_halfwidth`` plus a truncated Pareto tail -- entry
        ``(i, (i+j) mod n)`` exists iff ``j <= min(h_i, h_{(i+j) mod n})``
        (the min rule keeps the pattern symmetric without rescans; the
        wrap keeps boundary rows full-width);
      * ``dense_rows`` hub rows/columns touching EVERY column (the
        heavy-hitter tail of a power-law degree distribution);
      * clustered-exponent values + a diagonally dominant diagonal
        (strict dominance -> SPD).

    The base halfwidth keeps typical rows just under one 128-lane tile,
    so both layouts pay the same lane-quantization padding and the
    benchmark isolates the SKEW cost: uniform ELL pads every row to the
    dense rows' width (padding_ratio ~0.94 at the defaults) while
    SELL-C-σ quarantines the hubs in their own wide slice
    (padding_ratio < 0.1) -- the ``run.py --quick`` CI gate asserts the
    gap and that tag-1 modeled kernel bytes stay within 10% of 10 B/nnz
    (6 B of segments + 4 B of gathered x per slot).
    """
    rng = np.random.default_rng(seed)
    tail = np.minimum((rng.pareto(1.8, n) * tail_scale).astype(np.int64),
                      n // 2)
    h = np.minimum(base_halfwidth + tail, (n - 1) // 2)
    # Periodic-band entries (positive offsets) under the min rule,
    # vectorized; the transpose below supplies the negative offsets.
    rows = np.repeat(np.arange(n), h)
    offs = np.arange(h.sum()) - np.repeat(np.cumsum(h) - h, h) + 1
    cols = (rows + offs) % n
    keep = offs <= h[cols]
    rows, cols = rows[keep], cols[keep]
    # Dense hub rows (heavy hitters); off-diagonal only.
    hubs = rng.choice(n, size=dense_rows, replace=False)
    hr = np.repeat(hubs, n)
    hc = np.tile(np.arange(n), dense_rows)
    keep = hr != hc
    rows = np.concatenate([rows, hr[keep]])
    cols = np.concatenate([cols, hc[keep]])
    # Clustered-exponent values (Fig-1 statistics hold here too).
    bins = rng.choice([-2, -1, 0, 1], size=rows.size, p=[0.1, 0.2, 0.5, 0.2])
    vals = rng.uniform(1.0, 2.0, rows.size) * np.exp2(bins)
    vals *= rng.choice([-1.0, 1.0], size=vals.shape)
    r = np.concatenate([rows, cols])
    c = np.concatenate([cols, rows])
    v = np.concatenate([vals, vals])
    # Strictly dominant diagonal -> SPD.  Band/hub duplicates are summed
    # by from_coo; add.at counts them twice, which only strengthens the
    # dominance bound.
    abssum = np.zeros(n)
    np.add.at(abssum, r, np.abs(v))
    diag = 2.0 * abssum + 1.0
    r = np.concatenate([r, np.arange(n)])
    c = np.concatenate([c, np.arange(n)])
    v = np.concatenate([v, diag])
    return from_coo(r, c, v, (n, n))


def diag_rescale(a: CSR, decades: float = 6.0, seed: int = 0) -> CSR:
    """Symmetric diagonal rescale D A D, D = 2^U(-d/2, d/2).

    Spreads per-row/col exponents over ~``decades`` binades -- mirrors the
    *unequilibrated* matrices in SuiteSparse where the shared-exponent
    count k visibly controls the GSE-SEM truncation error (paper Fig 4b).
    SPD is preserved (congruence transform).
    """
    rng = np.random.default_rng(seed)
    n = a.shape[0]
    d = np.exp2(rng.uniform(-decades / 2, decades / 2, n))
    rows = np.asarray(a.row_ids)
    cols = np.asarray(a.col)
    vals = np.asarray(a.val) * d[rows] * d[cols]
    return from_coo(rows, cols, vals, a.shape)


def ill_conditioned_spd(n: int = 32, decades: float = 14.0, seed: int = 0) -> CSR:
    """SPD with condition number >= 1e6: 2-D Poisson congruence-rescaled.

    ``D A D`` with ``D = diag(2^U)``, ``U ~ Uniform(-decades/2, decades/2)``:
    SPD is preserved (congruence) and the Rayleigh bounds
    ``lambda_max >= max_i (DAD)_ii``, ``lambda_min <= min_i (DAD)_ii`` give
    ``cond >= (D_max/D_min)^2 ~ 2^(2*decades)`` realized spread -- ``>= 1e6``
    for ``decades >= 10`` with wide margin at the default 14.

    This is the workload where unpreconditioned stepped CG stalls for
    thousands of iterations but diagonal (Jacobi/SPAI-0) preconditioning
    undoes ``D`` exactly, restoring the stencil's conditioning -- the
    target case for the GSE-packed preconditioners (DESIGN.md §10).
    """
    return diag_rescale(poisson2d(n), decades, seed)


def mass_diagonal(n: int, seed: int = 0) -> CSR:
    """Diagonal mass matrix (bcsstm24 role)."""
    rng = np.random.default_rng(seed)
    vals = rng.uniform(0.5, 4.0, n)
    i = np.arange(n)
    return from_coo(i, i, vals, (n, n))


def cg_suite(small: bool = True) -> Dict[str, CSR]:
    """SPD suite mirroring Table II (left).  small=True keeps CI fast.

    The ``*_rs*`` members are diag-rescaled (unequilibrated, like most
    SuiteSparse matrices): exponents spread over many binades, which is
    where FP16 overflows ('/' rows in paper Table IV) and BF16's 8-bit
    significand stalls, while GSE-SEM's adaptive shared exponents cover
    the range.
    """
    s = 1 if small else 4
    return {
        "mass_diag_3k": mass_diagonal(3562 // s, seed=1),
        "poisson2d_32": poisson2d(32 * s),
        "poisson2d_64": poisson2d(64 * s),
        "poisson3d_12": poisson3d(12 * s),
        "random_spd_5k": random_spd(5000 // s, seed=2),
        "random_spd_wide_2k": random_spd(2000 // s, cond_decades=6.0, seed=3),
        "spd_rs8_2k": diag_rescale(random_spd(2000 // s, seed=21), 8.0, 21),
        "spd_overflow_2k": diag_rescale(
            random_spd(2000 // s, cond_decades=2.0, seed=22), 24.0, 22),
        "circuit_spd_4k": None,  # filled below (symmetrized circuit)
    }


def gmres_suite(small: bool = True) -> Dict[str, CSR]:
    """Asymmetric suite mirroring Table II (right)."""
    s = 1 if small else 4
    return {
        "convdiff_32": convection_diffusion_2d(32 * s),
        "convdiff_48_b50": convection_diffusion_2d(48 * s, beta=50.0),
        "circuit_2k": circuit_like(1813 if small else 8000, seed=4),
        "circuit_5k": circuit_like(4960 if small else 20000, seed=5),
        "convdiff_64": convection_diffusion_2d(64 * s, beta=5.0),
        "convdiff_rs4_32": diag_rescale(
            convection_diffusion_2d(32 * s, beta=5.0), 4.0, 23),
        "circuit_rs12_2k": diag_rescale(
            circuit_like(2000 // s, seed=24), 24.0, 24),
    }


def _symmetrize(a: CSR) -> CSR:
    import numpy as np

    rp = np.asarray(a.rowptr)
    col = np.asarray(a.col)
    val = np.asarray(a.val)
    rows = np.asarray(a.row_ids)
    r = np.concatenate([rows, col])
    c = np.concatenate([col, rows])
    v = np.concatenate([val, val]) * 0.5
    return from_coo(r, c, v, a.shape)


def spmv_suite(small: bool = True) -> Dict[str, CSR]:
    """Matrices for the SpMV-level experiments (Figs 4-6 role)."""
    cg = cg_suite(small)
    cg["circuit_spd_4k"] = _symmetrize(circuit_like(4000 if small else 16000, 6))
    out = dict(cg)
    out.update(gmres_suite(small))
    return out
