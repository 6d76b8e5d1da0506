"""The benchmark's own operators and its plain float64 reference.

These are copies kept with the benchmark on purpose: the operator a cell
solves, and the arithmetic that judges the answer, must not move when the
program's own generators or SpMV change.  Nothing here imports the program.

An operator is data, in the keys of a configuration file:

    "stencil": {"center": 6.0, "neighbors": [[1, 0, 0, -1.0], ...]},
    "grid": [nx, ny, nz]

A stencil is its centre value and a list of ``[dx, dy, dz, value]``
neighbours; a point whose neighbour falls off the grid drops that entry
(Dirichlet boundary), as hypre's ``GenerateLaplacian`` does.
"""
from __future__ import annotations

import numpy as np


class Coo:
    """A square sparse operator as row-sorted COO triplets (float64)."""

    def __init__(self, rows, cols, vals, n: int):
        order = np.lexsort((cols, rows))
        self.rows = np.ascontiguousarray(rows[order], np.int64)
        self.cols = np.ascontiguousarray(cols[order], np.int64)
        self.vals = np.ascontiguousarray(vals[order], np.float64)
        self.n = int(n)

    @property
    def nnz(self) -> int:
        return int(self.vals.size)

    def matvec(self, x: np.ndarray) -> np.ndarray:
        """``A @ x`` in plain float64 numpy."""
        x = np.asarray(x, np.float64)
        return np.bincount(self.rows, weights=self.vals * x[self.cols],
                           minlength=self.n)

    def true_relres(self, x, b) -> float:
        """``||b - A x|| / ||b||`` in float64: the number ``correct`` is
        decided on."""
        x = np.asarray(x, np.float64)
        b = np.asarray(b, np.float64)
        return float(np.linalg.norm(b - self.matvec(x)) / np.linalg.norm(b))

    def diagonal(self) -> np.ndarray:
        d = np.zeros(self.n, np.float64)
        hit = self.rows == self.cols
        d[self.rows[hit]] = self.vals[hit]
        return d


def stencil(grid, center: float, neighbors) -> Coo:
    """The matrix of a constant-coefficient stencil on a ``grid`` box,
    lexicographic with x fastest, zero Dirichlet boundary."""
    nx, ny, nz = (int(g) for g in grid)
    n = nx * ny * nz
    ix, iy, iz = np.meshgrid(np.arange(nx), np.arange(ny), np.arange(nz),
                             indexing="ij")
    ix, iy, iz = ix.ravel(), iy.ravel(), iz.ravel()
    me = ix + nx * (iy + ny * iz)
    rows, cols, vals = [me], [me], [np.full(n, float(center))]
    for dx, dy, dz, v in neighbors:
        jx, jy, jz = ix + int(dx), iy + int(dy), iz + int(dz)
        ok = ((jx >= 0) & (jx < nx) & (jy >= 0) & (jy < ny)
              & (jz >= 0) & (jz < nz))
        rows.append(me[ok])
        cols.append((jx + nx * (jy + ny * jz))[ok])
        vals.append(np.full(int(ok.sum()), float(v)))
    return Coo(np.concatenate(rows), np.concatenate(cols),
               np.concatenate(vals), n)


def build(recipe: dict) -> Coo:
    """The operator a configuration file describes."""
    st = recipe["stencil"]
    return stencil(recipe["grid"], st["center"], st["neighbors"])


def cg_control(a: Coo, b: np.ndarray, tol: float, maxiter: int, dtype,
               jacobi: bool):
    """The reference put in the program's place at a lower precision:
    plain CG (Jacobi-preconditioned where ``jacobi``) in ``dtype`` with
    ``jax.numpy`` on the default device, stopping on the recursive
    residual as the program does.  Returns ``(x, iters)``."""
    import jax
    import jax.numpy as jnp

    rows = jnp.asarray(a.rows, jnp.int32)
    cols = jnp.asarray(a.cols, jnp.int32)
    vals = jnp.asarray(a.vals, dtype)
    dinv = jnp.asarray(1.0 / a.diagonal() if jacobi else np.ones(a.n), dtype)
    n = a.n

    def mv(v):
        return jax.ops.segment_sum(vals * v[cols], rows, num_segments=n)

    @jax.jit
    def solve(b, dinv):
        bnorm = jnp.linalg.norm(b)

        def cond(s):
            return (jnp.sqrt(s["rr"]) > tol * bnorm) & (s["it"] < maxiter)

        def body(s):
            ap = mv(s["p"])
            alpha = s["rz"] / jnp.vdot(s["p"], ap)
            x = s["x"] + alpha * s["p"]
            r = s["r"] - alpha * ap
            z = dinv * r
            rz = jnp.vdot(r, z)
            p = z + (rz / s["rz"]) * s["p"]
            return dict(x=x, r=r, p=p, rz=rz, rr=jnp.vdot(r, r),
                        it=s["it"] + 1)

        z0 = dinv * b
        s = dict(x=jnp.zeros_like(b), r=b, p=z0, rz=jnp.vdot(b, z0),
                 rr=jnp.vdot(b, b), it=jnp.int32(0))
        s = jax.lax.while_loop(cond, body, s)
        return s["x"], s["it"]

    x, it = solve(jnp.asarray(b, dtype), dinv)
    return np.asarray(x, np.float64), int(it)
