"""HPCG's multigrid preconditioner (``make_mg``) against a plain float64
numpy copy of HPCG 3.1's reference, kept here: the box stencil of
``GenerateProblem_ref``, the coarse levels and ``f2cOperator`` of
``GenerateCoarseProblem``, ``ComputeMG_ref``'s V-cycle and CG.  The
smoother is the multicolour symmetric Gauss-Seidel sweep the program runs
(colours 0..7 forward, 7..0 back), where HPCG's ``ComputeSYMGS_ref`` sweeps
lexicographically.  Nothing below the reference imports the program."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.sparse import generators as G
from repro.sparse.csr import from_coo, pack_csr

# -- the reference ------------------------------------------------------

NEIGHBOURS = [(dx, dy, dz) for dz in (-1, 0, 1) for dy in (-1, 0, 1)
              for dx in (-1, 0, 1)]


def coords(idx, grid):
    nx, ny, _ = grid
    return idx % nx, (idx // nx) % ny, idx // (nx * ny)


class Level:
    """One level: HPCG's 27-point operator on ``grid`` as float64 COO."""

    def __init__(self, grid):
        self.grid = tuple(grid)
        nx, ny, nz = grid
        self.n = nx * ny * nz
        ix, iy, iz = coords(np.arange(self.n), grid)
        rows, cols, vals = [], [], []
        for dx, dy, dz in NEIGHBOURS:
            jx, jy, jz = ix + dx, iy + dy, iz + dz
            ok = ((jx >= 0) & (jx < nx) & (jy >= 0) & (jy < ny)
                  & (jz >= 0) & (jz < nz))
            rows.append(np.arange(self.n)[ok])
            cols.append((jx + nx * (jy + ny * jz))[ok])
            vals.append(np.full(ok.sum(), 26.0 if (dx, dy, dz) == (0, 0, 0)
                                else -1.0))
        self.rows, self.cols, self.vals = (np.concatenate(v)
                                           for v in (rows, cols, vals))
        self.diag = np.full(self.n, 26.0)
        colour = (ix % 2) + 2 * (iy % 2) + 4 * (iz % 2)
        self.colours = [np.flatnonzero(colour == c) for c in range(8)]
        self.f2c = None

    def matvec(self, x, rows=None):
        y = np.bincount(self.rows, self.vals * x[self.cols], minlength=self.n)
        return y if rows is None else y[rows]

    def symgs(self, r, x):
        for c in [*range(8), *reversed(range(8))]:
            rows = self.colours[c]
            x[rows] += (r[rows] - self.matvec(x, rows)) / self.diag[rows]
        return x


def hierarchy(grid, levels=4):
    out = [Level(grid)]
    for _ in range(levels - 1):
        fine = out[-1]
        coarse = Level(tuple(g // 2 for g in fine.grid))
        cx, cy, cz = coords(np.arange(coarse.n), coarse.grid)
        nx, ny, _ = fine.grid
        coarse_f2c = 2 * cx + nx * (2 * cy + ny * 2 * cz)
        fine.f2c = coarse_f2c
        out.append(coarse)
    return out


def vcycle(levels, r, lvl=0):
    a = levels[lvl]
    x = a.symgs(r, np.zeros(a.n))
    if lvl + 1 < len(levels):
        rc = (r - a.matvec(x))[a.f2c]
        x[a.f2c] += vcycle(levels, rc, lvl + 1)
        x = a.symgs(r, x)
    return x


def pcg(levels, b, tol, maxiter=500):
    """CG preconditioned by ``vcycle``, from a zero start, stopping when the
    recursive residual reaches ``tol * ||b||``.  Returns ``(x, iters)``."""
    a = levels[0]
    x = np.zeros(a.n)
    r = b.copy()
    z = vcycle(levels, r)
    p, rz, bnorm = z.copy(), r @ z, np.linalg.norm(b)
    for it in range(1, maxiter + 1):
        ap = a.matvec(p)
        alpha = rz / (p @ ap)
        x += alpha * p
        r -= alpha * ap
        if np.linalg.norm(r) <= tol * bnorm:
            return x, it
        z = vcycle(levels, r)
        rz, rz_old = r @ z, rz
        p = z + (rz / rz_old) * p
    return x, maxiter


# -- the program against it ---------------------------------------------

GRIDS = [(8, 8, 8), (16, 8, 8)]


@pytest.fixture(scope="module", params=GRIDS, ids=lambda g: "x".join(map(str, g)))
def built(request):
    from repro.solvers import make_mg

    grid = request.param
    return make_mg(G.hpcg27(*grid)), hierarchy(grid)


def test_hierarchy_is_hpcgs(built):
    """Four levels, each the 27-point problem on the half grid, its points
    the fine points at even coordinates."""
    m, ref = built
    assert m.grids == tuple(lv.grid for lv in ref)
    for lvl, lv in enumerate(ref):
        rows, cols, vals = m.operator(lvl)
        got = sorted(zip(rows.tolist(), cols.tolist(), vals.tolist()))
        want = sorted(zip(lv.rows.tolist(), lv.cols.tolist(),
                          lv.vals.tolist()))
        assert got == want
        if lvl + 1 < len(ref):
            np.testing.assert_array_equal(m.f2c(lvl), lv.f2c)
    # An apply streams each level's entries 4 1/8 times (2 on the
    # coarsest) at the tag's value bytes and a 4 B column index.
    reads = sum((4.125 if lvl + 1 < len(ref) else 2) * lv.vals.size
                for lvl, lv in enumerate(ref))
    streams = [m.bytes_touched(tag) for tag in (1, 2, 3)]
    assert streams[0] < streams[1] < streams[2]
    assert streams[1] - streams[0] >= 2 * reads


def _r(n, seed):
    return np.random.default_rng(seed).standard_normal(n)


@pytest.mark.parametrize("tag", [1, 2, 3])
def test_apply_matches_reference(built, tag):
    """One V-cycle at each tag against the reference.  The stencil's values
    (26, -1) are exact at every tag, so the tags agree; what is left is
    the order of float64 sums within a row, a few units in the last place
    (1e-12 leaves three decades of room)."""
    m, ref = built
    apply = jax.jit(lambda r: m.apply_at(r, tag))
    for seed in range(3):
        r = _r(ref[0].n, seed)
        z = np.asarray(apply(jnp.asarray(r)))
        want = vcycle(ref, r)
        assert np.max(np.abs(z - want)) <= 1e-12 * np.max(np.abs(want))


def test_preconditioner_is_symmetric_positive(built):
    m, ref = built
    apply = jax.jit(lambda r: m.apply_at(r, 3))
    rs = [_r(ref[0].n, s) for s in range(4)]
    zs = [np.asarray(apply(jnp.asarray(r))) for r in rs]
    for i in range(len(rs)):
        assert rs[i] @ zs[i] > 0
        for j in range(i):
            a, b = rs[i] @ zs[j], rs[j] @ zs[i]
            # Rounding of two float64 V-cycles, far below the product.
            assert abs(a - b) <= 1e-12 * np.linalg.norm(rs[i]) * np.linalg.norm(zs[j])


@pytest.fixture(scope="module")
def solve16():
    from repro.solvers import make_mg, solve_pcg

    a = G.hpcg27(16)
    g, m = pack_csr(a, k=8), make_mg(a, k=8)
    return hierarchy((16, 16, 16)), lambda b: solve_pcg(
        g, jnp.asarray(b), precond=m, tol=1e-8, maxiter=500,
        final_correction=True)


@pytest.mark.parametrize("rhs", ["ones", "normal"])
def test_pcg_iterations_match_reference(solve16, rhs):
    """``solve_pcg`` with ``make_mg`` at 16^3 through the fused loop: the
    true residual meets 1e-8 within one iteration of the reference."""
    ref, solve = solve16
    n = ref[0].n
    x = np.ones(n) if rhs == "ones" else _r(n, 7)   # HPCG: b = A 1
    b = ref[0].matvec(x)
    res = solve(b)
    _, want = pcg(ref, b, 1e-8)
    assert abs(int(res.iters) - want) <= 1
    ax = ref[0].matvec(np.asarray(res.x))
    assert np.linalg.norm(b - ax) / np.linalg.norm(b) <= 1e-8


def _variable():
    a = G.hpcg27(8)
    rows = np.repeat(np.arange(a.shape[0]), np.diff(np.asarray(a.rowptr)))
    vals = np.asarray(a.val, np.float64).copy()
    vals[(rows == 100) & (np.asarray(a.col) == 101)] = -2.0
    vals[(rows == 101) & (np.asarray(a.col) == 100)] = -2.0
    return from_coo(rows, np.asarray(a.col), vals, a.shape)


@pytest.mark.parametrize("build, match", [
    pytest.param(lambda: G.random_spd(512, seed=1), "box stencil",
                 id="random_spd"),
    pytest.param(lambda: G.hpcg27(12), "divisible by 8", id="box_12"),
    pytest.param(_variable, "vary: not constant-coefficient",
                 id="variable"),
])
def test_make_mg_refuses(build, match):
    from repro.solvers import make_mg

    with pytest.raises(ValueError, match=match):
        make_mg(build())


@pytest.mark.parametrize("build, levels, grids", [
    pytest.param(lambda: G.poisson3d(8), 4,
                 ((8, 8, 8), (4, 4, 4), (2, 2, 2), (1, 1, 1)),
                 id="poisson7_8"),
    pytest.param(lambda: G.hpcg27(12), 3,
                 ((12, 12, 12), (6, 6, 6), (3, 3, 3)), id="box_12_3levels"),
    pytest.param(lambda: G.hpcg27(24, 16, 8), 4,
                 ((24, 16, 8), (12, 8, 4), (6, 4, 2), (3, 2, 1)),
                 id="box_24x16x8"),
])
def test_make_mg_reads_the_box(build, levels, grids):
    """Any constant-coefficient stencil on a box whose dimensions halve,
    the 7-point one included, with the levels asked for."""
    from repro.solvers import make_mg

    assert make_mg(build(), levels=levels).grids == grids
