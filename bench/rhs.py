"""The one generator of right-hand sides.  A traffic mix is a data file of
parameters, ``bench/traffic/<mix>.json``, that this module reads:

- ``x``: what a right-hand side is made from, ``"normal"`` (N(0, 1)
  entries) or ``"ones"``;
- ``b``: ``"a_x"`` for ``b = A x``, or ``"x"`` for ``b = x``;
- ``bases``: how many distinct vectors ``x`` there are, drawn once from the
  mix's own ``base_seed``.  Every run therefore solves the same set, and
  the run's seed does not change the work.  Where ``base_seed`` is null
  they are drawn from the run's seed instead;
- ``vary``: ``"symmetry"`` maps each solve's base, by the run's seed,
  through a symmetry of the operator (an axis permutation and reflections
  of the grid that carry the stencil to itself) and a sign.  The solve is
  then of other numbers but of the same Krylov difficulty: CG's iterates
  map the same way.  ``"none"`` leaves the base as it is;
- ``x0``: the initial guess, ``"zero"`` or ``"normal"`` (drawn per solve
  from the run's seed);
- ``pool``: how many right-hand sides are placed on the device before the
  window, a multiple of ``bases``; solve ``i`` takes entry ``i % pool``,
  whose base is ``i % bases``;
- ``warm_stream``: the stream of the run's seed that the warm-up's
  right-hand side is drawn from, never one of the window's.

Nothing here imports the program.
"""
from __future__ import annotations

import itertools

import numpy as np

WINDOW_STREAM = 0
X0_STREAM = 3


def _vector(kind: str, rng, n: int) -> np.ndarray:
    if kind == "normal":
        return rng.standard_normal(n)
    if kind == "ones":
        return np.ones(n)
    raise ValueError(f"unknown x {kind!r}: 'normal' or 'ones'")


def symmetries(recipe: dict) -> list:
    """The grid symmetries that carry the configuration's stencil to
    itself, as ``(axes, flips)`` of the ``(nz, ny, nx)`` array view:
    output axis ``a`` is input axis ``axes[a]``, reversed where
    ``flips[a]``.  The identity is first."""
    shape = tuple(int(g) for g in reversed(recipe["grid"]))
    st = recipe["stencil"]
    # Offsets in array-axis order (dz, dy, dx) with their values.
    taps = sorted((int(dz), int(dy), int(dx), float(v))
                  for dx, dy, dz, v in st["neighbors"])
    out = []
    for axes in itertools.permutations(range(3)):
        if any(shape[axes[a]] != shape[a] for a in range(3)):
            continue
        for flips in itertools.product((False, True), repeat=3):
            mapped = sorted(
                tuple((-1 if flips[a] else 1) * t[axes[a]] for a in range(3))
                + (t[3],) for t in taps)
            if mapped == taps:
                out.append((axes, flips))
    return out


def transform(x: np.ndarray, grid, sym) -> np.ndarray:
    """``x`` moved by one grid symmetry from :func:`symmetries`."""
    axes, flips = sym
    arr = np.transpose(x.reshape(tuple(int(g) for g in reversed(grid))), axes)
    for a, f in enumerate(flips):
        if f:
            arr = np.flip(arr, a)
    return np.ascontiguousarray(arr).ravel()


class Stream:
    """The right-hand sides of one run: a mix read against an operator
    ``coo`` (``bench/operators.Coo``) built from ``recipe``."""

    def __init__(self, mix: dict, coo, recipe: dict, seed: int):
        self.mix, self.coo, self.recipe = mix, coo, recipe
        self.seed = seed % 2**64           # any whole number keys a stream
        self.pool_size = int(mix["pool"])
        nb = int(mix["bases"])
        if self.pool_size % nb:
            raise ValueError(f"pool {self.pool_size} is not a multiple of "
                             f"bases {nb}")
        if mix["b"] not in ("a_x", "x"):
            raise ValueError(f"unknown b {mix['b']!r}: 'a_x' or 'x'")
        if mix["x0"] not in ("zero", "normal"):
            raise ValueError(f"unknown x0 {mix['x0']!r}: 'zero' or 'normal'")
        base_seed = mix.get("base_seed")
        key = [int(base_seed)] if base_seed is not None else [self.seed, 2]
        self.bases = [_vector(mix["x"], np.random.default_rng(key + [j]),
                              coo.n) for j in range(nb)]
        if mix["vary"] == "symmetry":
            self.syms = symmetries(recipe)
        elif mix["vary"] == "none":
            self.syms = None
        else:
            raise ValueError(f"unknown vary {mix['vary']!r}")

    def _b(self, x):
        return self.coo.matvec(x) if self.mix["b"] == "a_x" else x

    def rhs(self, i: int) -> np.ndarray:
        """The right-hand side of the window's ``i``-th pool entry."""
        x = self.bases[i % len(self.bases)]
        if self.syms is not None:
            rng = np.random.default_rng([self.seed, WINDOW_STREAM, i])
            sym = self.syms[int(rng.integers(len(self.syms)))]
            x = (1.0 if rng.integers(2) else -1.0) * transform(
                x, self.recipe["grid"], sym)
        return self._b(x)

    def x0(self, i: int):
        """The initial guess of pool entry ``i``; ``None`` is zero."""
        if self.mix["x0"] == "zero":
            return None
        return np.random.default_rng(
            [self.seed, X0_STREAM, i]).standard_normal(self.coo.n)

    def warm_rhs(self) -> np.ndarray:
        rng = np.random.default_rng([self.seed, int(self.mix["warm_stream"])])
        return self._b(_vector(self.mix["x"], rng, self.coo.n))
