"""Chunked solver execution: bounded segments, bit-identical trajectories.

The serving layer needs solves to be *preemptible* (deadline checks,
fair scheduling across requests), *joinable* (continuous batching), and
*resumable* (checkpoint/restore across faults).  All three reduce to one
primitive: run the existing solver ``while_loop`` for at most K more
iterations and hand back the raw loop state.

The solver entry points grew three hooks for this (DESIGN.md §17):

  * ``stop_at`` -- an extra iteration bound ANDed into the loop
    condition.  Conditions never touch the update arithmetic, so a
    chunked trajectory is bit-identical to the unchunked one BY
    CONSTRUCTION, not by tolerance.
  * ``resume`` -- a previous chunk's loop-state pytree, carried verbatim
    (device arrays; the init section is skipped entirely).
  * ``return_state`` -- return that raw state alongside the result.

The drivers here wrap those hooks per solver family:

  * :class:`SolveChunks` -- single-RHS CG/PCG: the single-chip loop
    (``cg._loop``) over a packed operand, a callable, or the row-sharded
    operator as a callable.
  * :class:`BatchedChunks` -- the batched multi-RHS loop, plus
    ``join``/``drop``: a column added at a chunk boundary starts from
    the exact init a solo solve would run, and runs the exact per-column
    op sequence from there (the batched loop's per-column bit-identity
    contract, DESIGN.md §11) -- continuous batching without perturbing
    the columns already in flight.
  * :class:`IRChunks` -- iterative refinement at outer-correction
    granularity (the host loop of ``solve_ir`` re-cut; every line of
    per-correction arithmetic is shared with the unchunked driver).

Checkpointing: ``save_state``/``restore_state`` round-trip the loop
state through ``checkpoint.ckpt`` (CRC-stamped; a corrupted latest
checkpoint falls back to the previous good step -- the chunk in between
simply re-runs, which by the bit-identity contract reproduces the exact
trajectory).
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from repro.checkpoint import ckpt as CK
from repro.core import precision as P
from repro.robustness.guards import HEALTH_OK, GuardParams
from repro.solvers.batched import (
    _maybe_sharded,
    _normalize_block,
    _solve_batched,
)
from repro.solvers.cg import _loop
from repro.solvers.ir import _ir_active, _ir_result, _ir_setup, _ir_step

__all__ = ["SolveChunks", "BatchedChunks", "IRChunks"]


def _chunk_bound(it, k):
    """The stop_at bound for "k more iterations from it" as a traced
    scalar -- dynamic, so chunk advances never retrace the loop."""
    return it + jnp.int32(k)


class SolveChunks:
    """Single-RHS CG/PCG driven K iterations at a time.

    ``run_chunk(k)`` advances the solve by at most ``k`` iterations and
    returns the current ``CGResult`` snapshot; ``done`` is True when the
    unchunked loop would have exited (converged, budget exhausted, or a
    guard tripped).  The concatenation of chunks is bit-identical to one
    unchunked call with the same arguments.
    """

    def __init__(self, op, b, tol: float, maxiter: int,
                 params: P.MonitorParams,
                 guards: GuardParams | None = None,
                 x0=None, precond=None, wire: str = "exact",
                 init_tag: int = 1):
        b = jnp.asarray(b)
        if b.ndim == 2 and b.shape[1] == 1:
            b = b[:, 0]
        self.b = b
        self.x0 = jnp.zeros_like(b) if x0 is None else jnp.asarray(x0)
        self.tol = jnp.asarray(tol, b.dtype)
        self.maxiter = maxiter
        self.params = params
        self.guards = guards
        self.init_tag = init_tag
        op = _maybe_sharded(op, wire)
        self._call = lambda **kw: _loop(
            op, precond, self.b, self.x0, self.tol, self.maxiter,
            self.params, init_tag=self.init_tag, guards=self.guards, **kw)
        self._state = None
        self.res = None
        self.ckpt = None
        self.chunks = 0

    def run_chunk(self, k: int):
        """Advance at most ``k`` iterations; returns the CGResult so far."""
        if self._state is None:
            stop = jnp.int32(int(k))
            res, ckpt, st = self._call(stop_at=stop, return_state=True)
        else:
            stop = _chunk_bound(self._state["it"], int(k))
            res, ckpt, st = self._call(resume=self._state, stop_at=stop,
                                       return_state=True)
        self._state, self.res, self.ckpt = st, res, ckpt
        self.chunks += 1
        return res

    @property
    def iters(self) -> int:
        return 0 if self._state is None else int(self._state["it"])

    @property
    def done(self) -> bool:
        """True when the UNCHUNKED loop condition is false: another chunk
        would execute zero iterations."""
        if self.res is None:
            return False
        if bool(self.res.converged) or self.iters >= self.maxiter:
            return True
        if self.guards is not None and \
                int(self._state["g"]["health"]) != HEALTH_OK:
            return True
        return False

    # -- checkpoint/resume (DESIGN.md §17) --------------------------------

    def init_state(self):
        """An initialized loop state without iterating (``stop_at=0``):
        the ``like`` template restores unflatten into."""
        _, _, st = self._call(stop_at=jnp.int32(0), return_state=True)
        return st

    def save_state(self, path: str) -> str:
        """CRC-stamped checkpoint of the current loop state (one per
        chunk boundary; step = chunk index)."""
        if self._state is None:
            raise RuntimeError("no chunk has run yet; nothing to save")
        return CK.save(path, self._state, step=self.chunks,
                       extra={"iters": self.iters})

    def restore_state(self, path: str) -> list:
        """Resume from the newest VALID checkpoint under ``path``.

        Corrupt checkpoints are skipped (``ckpt.CheckpointCorrupt``) and
        the previous good one is used -- the skipped chunk re-runs from
        there, reproducing the exact trajectory.  Returns the list of
        corrupt steps passed over; raises ``FileNotFoundError`` when no
        valid checkpoint exists.
        """
        got = CK.restore_latest_valid(path, self.init_state())
        if got is None:
            raise FileNotFoundError(f"no valid checkpoint under {path}")
        st, step, _, skipped = got
        self._state = st
        self.chunks = step
        return skipped


class BatchedChunks:
    """The batched multi-RHS loop driven K iterations at a time, with
    continuous batching: ``join`` adds a column at a chunk boundary
    (its init is exactly a solo solve's init, so its trajectory matches
    a solo solve started then), ``drop`` removes one (remaining columns
    are independent per-column states -- untouched).

    ``stop_at`` is per-column (columns join at different global chunk
    counts, so each advances from its OWN iteration count).  Width
    changes retrace the loop -- the price of continuous batching; the
    service bounds width by its slot count so the retrace set is small.
    """

    def __init__(self, op, b, tol: float, maxiter: int,
                 params: P.MonitorParams,
                 guards: GuardParams | None = None,
                 x0=None, precond=None, wire: str = "exact",
                 init_tag: int = 1, tags=None):
        b, x0 = _normalize_block(b, x0)
        if tags is not None:
            # The batched precision axis (PR 10, DESIGN.md §18) resolves
            # BEFORE chunking exactly as in solve_cg_batched: an int or
            # uniform map overrides init_tag (same jaxpr), a non-uniform
            # map swaps in the masked operand and pins the monitor -- so
            # the chunked trajectory stays bit-identical to the unchunked
            # tags= call by the same construction as everything else here.
            from repro.solvers.batched import _batched_tag_axis

            init_tag, op, params = _batched_tag_axis(
                tags, op, int(b.shape[0]), params)
        self.b = b
        self.tol = jnp.asarray(tol, b.dtype)
        self.maxiter = maxiter
        self.params = params
        self.guards = guards
        self.init_tag = init_tag
        self.precond = precond
        op = _maybe_sharded(op, wire)
        self._call = lambda b_, x0_, **kw: _solve_batched(
            op, precond, b_, x0_, self.tol, self.maxiter, self.params,
            init_tag=self.init_tag, guards=self.guards, **kw)
        # Initialize every column WITHOUT iterating (per-column stop_at=0):
        # the same trick join uses, so first-wave and joined columns get
        # identical init treatment.
        res, cols = self._call(
            b, x0, stop_at=tuple(jnp.int32(0) for _ in range(b.shape[1])),
            return_state=True)
        self.cols = tuple(cols)
        self.res = res
        self.chunks = 0

    @property
    def nrhs(self) -> int:
        return len(self.cols)

    def run_chunk(self, k: int):
        """Advance every column by at most ``k`` iterations (from each
        column's OWN count); returns the BatchedCGResult snapshot."""
        stop = tuple(_chunk_bound(c["it"], int(k)) for c in self.cols)
        # x0 is dead under resume (the init section is skipped); any
        # shape-matching placeholder keeps the traced signature stable.
        res, cols = self._call(self.b, jnp.zeros_like(self.b),
                               resume=self.cols, stop_at=stop,
                               return_state=True)
        self.cols, self.res = tuple(cols), res
        self.chunks += 1
        return res

    def join(self, b_new, x0=None) -> int:
        """Add one column at the current chunk boundary; returns its
        index.  The column's state is the exact solo-solve init (one
        operator application at ``init_tag``), so from here on it runs
        the same op sequence as a solve submitted alone."""
        b1, x01 = _normalize_block(jnp.asarray(b_new), x0)
        _, cols1 = self._call(b1, x01, stop_at=(jnp.int32(0),),
                              return_state=True)
        self.cols = self.cols + tuple(cols1)
        self.b = jnp.concatenate([self.b, b1], axis=1)
        return self.nrhs - 1

    def drop(self, j: int) -> dict:
        """Remove column ``j`` (finished or expired), returning its final
        snapshot.  Other columns' states are untouched -- per-column
        independence is the batched loop's core contract."""
        snap = self.col_snapshot(j)
        self.cols = self.cols[:j] + self.cols[j + 1:]
        self.b = jnp.delete(self.b, j, axis=1)
        return snap

    def col_snapshot(self, j: int) -> dict:
        """One column's current report fields + its last-healthy x
        (``ckpt`` under guards -- what a deadline expiry returns).

        Health comes from the column's OWN guard state (finalized the
        same way the batched result does), not the cached batch result,
        which goes stale across joins/drops.
        """
        from repro.robustness.guards import finalize_health

        c = self.cols[j]
        bn = jnp.linalg.norm(self.b[:, j])
        bn = jnp.where(bn == 0, 1.0, bn)
        relres = float(jnp.sqrt(jnp.abs(c["rr"])) / bn)
        finite = bool(jnp.isfinite(jnp.vdot(c["x"], c["x"])))
        converged = relres <= float(self.tol) and finite
        h, t = finalize_health(c.get("g"), converged, relres,
                               x_finite=finite)
        g = c.get("g")
        return dict(
            x=c["x"],
            ckpt=c.get("ckpt", c["x"]),
            iters=int(c["it"]),
            relres=relres,
            tag=int(c["mon"].tag),
            switch_iters=np.asarray(c["sw"]),
            converged=converged,
            health=int(h),
            # Raw in-loop guard health: a column still iterating is OK
            # here even though finalize_health would call it "stalled"
            # (deadline expiry must not masquerade as a guard trip).
            guard_health=int(g["health"]) if g is not None else 0,
            trip_iter=int(t),
        )

    def col_done(self, j: int) -> bool:
        """Column ``j`` would execute zero further iterations."""
        c = self.cols[j]
        bn = jnp.linalg.norm(self.b[:, j])
        bn = jnp.where(bn == 0, 1.0, bn)
        relres = float(jnp.sqrt(jnp.abs(c["rr"])) / bn)
        if relres <= float(self.tol) or int(c["it"]) >= self.maxiter:
            return True
        if self.guards is not None and \
                int(c["g"]["health"]) != HEALTH_OK:
            return True
        return False

    @property
    def done(self) -> bool:
        return all(self.col_done(j) for j in range(self.nrhs))


class IRChunks:
    """Iterative refinement driven K outer corrections at a time.

    Chunk boundaries fall between corrections -- the natural restart
    point the Carson-Khan structure gives for free (each correction
    restarts the inner monitor anyway), so chunked IR shares every line
    of per-correction arithmetic with ``solve_ir`` and is trivially
    bit-identical to it.
    """

    def __init__(self, op, b, tol: float = 1e-10, max_outer: int = 10,
                 inner: str = "cg", inner_tol: float = 1e-4,
                 inner_maxiter: int = 2000,
                 params: P.MonitorParams | None = None,
                 precond=None, restart: int = 30, wire: str = "exact",
                 guards: GuardParams | None = None, flight=None):
        self.st = _ir_setup(op, jnp.asarray(b), tol=tol, max_outer=max_outer,
                            inner=inner, inner_tol=inner_tol,
                            inner_maxiter=inner_maxiter, params=params,
                            precond=precond, restart=restart, wire=wire,
                            guards=guards, flight=flight)
        self.chunks = 0

    def run_chunk(self, k: int):
        """Run at most ``k`` outer corrections; returns the IRResult so
        far (its ``converged``/``health`` reflect the current state)."""
        for _ in range(int(k)):
            if not _ir_active(self.st):
                break
            _ir_step(self.st)
        self.chunks += 1
        return _ir_result(self.st)

    @property
    def done(self) -> bool:
        return not _ir_active(self.st)

    @property
    def outer_iters(self) -> int:
        return self.st["outer"]

    def result(self):
        return _ir_result(self.st)

    # -- checkpoint/resume ------------------------------------------------

    # The IR state is host-side (closures + scalars), so checkpoints
    # carry the array leaves explicitly and the scalars in ``extra``.

    def save_state(self, path: str) -> str:
        st = self.st
        return CK.save(path, {"x": st["x"], "r": st["r"]}, step=self.chunks,
                       extra={
                           "outer": st["outer"],
                           "total_inner": st["total_inner"],
                           "relres": st["relres"],
                           "history": [float(h) for h in st["history"]],
                           "inner_health": st["inner_health"],
                           "stopped": st["stopped"],
                       })

    def restore_state(self, path: str) -> list:
        like = {"x": self.st["x"], "r": self.st["r"]}
        got = CK.restore_latest_valid(path, like)
        if got is None:
            raise FileNotFoundError(f"no valid checkpoint under {path}")
        tree, step, extra, skipped = got
        self.st["x"] = jnp.asarray(tree["x"])
        self.st["r"] = jnp.asarray(tree["r"])
        self.st["outer"] = int(extra["outer"])
        self.st["total_inner"] = int(extra["total_inner"])
        self.st["relres"] = float(extra["relres"])
        self.st["history"] = [float(h) for h in extra["history"]]
        self.st["inner_health"] = int(extra["inner_health"])
        self.st["stopped"] = bool(extra["stopped"])
        self.chunks = step
        return skipped
