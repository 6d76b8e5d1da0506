"""The generator of right-hand sides: the grid symmetries it draws are
symmetries of the operator, and every seed makes the same work."""
import numpy as np
import pytest

from bench import harness, operators, rhs
from bench.tests.helpers import run_tiny, tiny_cell

SEVEN = [[1, 0, 0, -1.0], [-1, 0, 0, -1.0], [0, 1, 0, -1.0],
         [0, -1, 0, -1.0], [0, 0, 1, -1.0], [0, 0, -1, -1.0]]
MIX = {"x": "normal", "b": "a_x", "bases": 2, "base_seed": 7,
       "vary": "symmetry", "x0": "zero", "pool": 4, "warm_stream": 1}


def _recipe(grid, neighbors=SEVEN, center=6.0):
    return {"grid": grid, "stencil": {"center": center,
                                      "neighbors": neighbors}}


@pytest.mark.parametrize("grid, neighbors, count", [
    ([5, 5, 5], SEVEN, 48),                       # the cube's whole group
    ([4, 4, 6], SEVEN, 16),                       # x and y swap, z does not
    ([3, 4, 5], SEVEN, 8),                        # reflections alone
    ([5, 5, 5], [[1, 0, 0, -1.0], [-1, 0, 0, -1.0], [0, 1, 0, -2.0],
                 [0, -1, 0, -2.0], [0, 0, 1, -2.0], [0, 0, -1, -2.0]], 16),
    ([5, 5, 5], [[1, 0, 0, -1.0], [0, 1, 0, -1.0], [0, 0, 1, -1.0]], 6),
])
def test_symmetries_commute_with_the_operator(grid, neighbors, count):
    recipe = _recipe(grid, neighbors)
    coo = operators.build(recipe)
    syms = rhs.symmetries(recipe)
    assert len(syms) == count
    assert syms[0] == ((0, 1, 2), (False, False, False))
    x = np.random.default_rng(0).standard_normal(coo.n)
    for sym in syms:
        tx = rhs.transform(x, grid, sym)
        assert sorted(tx) == sorted(x)
        np.testing.assert_allclose(coo.matvec(tx),
                                   rhs.transform(coo.matvec(x), grid, sym),
                                   rtol=0, atol=1e-12)


def test_stream_is_seeded_and_varied():
    recipe = _recipe([6, 6, 6])
    coo = operators.build(recipe)
    a, a2, b = (rhs.Stream(MIX, coo, recipe, s) for s in (2**40, 2**40, 9))
    # 96 moves of each base: two seeds rarely pick the same one.
    assert sum(not np.array_equal(a.rhs(i), b.rhs(i)) for i in range(4)) >= 3
    for i in range(4):
        np.testing.assert_array_equal(a.rhs(i), a2.rhs(i))
        # Entry i is base i % 2 moved: the same numbers, with a sign.
        xs = np.sort(np.abs(np.linalg.solve(_dense(coo), a.rhs(i))))
        np.testing.assert_allclose(xs, np.sort(np.abs(a.bases[i % 2])),
                                   atol=1e-10)
    assert a.x0(0) is None
    assert not np.array_equal(a.warm_rhs(), a.rhs(0))


def _dense(coo):
    m = np.zeros((coo.n, coo.n))
    m[coo.rows, coo.cols] = coo.vals
    return m


@pytest.mark.parametrize("key, value", [("b", "x2"), ("x0", "ones"),
                                        ("vary", "shuffle"), ("pool", 3)])
def test_stream_refuses_unknown_parameters(key, value):
    recipe = _recipe([4, 4, 4])
    with pytest.raises(ValueError):
        rhs.Stream(dict(MIX, **{key: value}), operators.build(recipe),
                   recipe, 1)


def test_every_seed_makes_the_same_work():
    """The program's iteration count of each pool entry is the same
    whatever the seed, and its answer is still correct."""
    cell = tiny_cell("lap3d_48.cg")
    dep = harness.Deployment(cell)
    counts = []
    for seed in (1, 2**40 + 5, 3**30):
        stream = rhs.Stream(cell.traffic, dep.coo, cell.config, seed)
        row = []
        for i in range(4):
            b = stream.rhs(i)
            res = dep.solve(b)
            assert dep.coo.true_relres(res.x, b) <= dep.tol
            row.append(int(res.iters))
        counts.append(row)
    assert counts[0] == counts[1] == counts[2], counts


def test_x0_normal_is_drawn_per_solve(monkeypatch):
    cell = tiny_cell("lap3d_48.cg")
    cell.traffic = dict(cell.traffic, x0="normal")
    seen = []
    real = harness.Deployment.solve

    def solve(self, b, x0=None, **over):
        seen.append(None if x0 is None else np.asarray(x0))
        return real(self, b, x0, **over)

    monkeypatch.setattr(harness.Deployment, "solve", solve)
    assert run_tiny(monkeypatch, cell, seconds=0.0)["correct"] is True
    assert all(v is not None for v in seen)
