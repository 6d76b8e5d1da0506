"""Stepped mixed-precision iterative refinement (Carson-Khan shape).

Outer loop at full precision, inner solves at stepped low precision:

    repeat:
        r = b - A x          # tag-3 residual (the TRUE residual)
        d ~= A^{-1} r        # stepped inner solve, starts at tag 1
        x = x + d            # full-precision correction

This is the classic three-precision iterative-refinement structure
(Carson & Higham; Carson & Khan arXiv:2307.03914 for the preconditioned
variant) mapped onto GSE-SEM's one-copy/three-precision storage: the
inner solver reads the SAME packed operand at whatever tag its residual
monitor has stepped to, and the outer loop needs no second matrix copy
for the high-precision residual -- it is a tag-3 read.

The inner solve is deliberately loose (``inner_tol``): IR converges as
long as each correction gains a constant factor, so the inner monitor
usually never needs to leave tag 1/2 -- most of the run streams 6-8
bytes/nnz instead of 12 (DESIGN.md §10).
"""
from __future__ import annotations

from typing import Callable, NamedTuple, Union

import jax.numpy as jnp
import numpy as np

from repro.core import precision as P
from repro.obs import flight as OF
from repro.obs import trace as OT
from repro.robustness.guards import (
    DEFAULT_GUARDS,
    GuardParams,
    HEALTH_NONFINITE,
    HEALTH_OK,
    HEALTH_STALLED,
)
from repro.sparse.csr import GSECSR, GSESellC
from repro.solvers.cg import solve_cg, solve_pcg
from repro.solvers.gmres import solve_gmres

__all__ = ["IRResult", "solve_ir"]

# NOTE: the serve layer (repro.serve.chunked) drives the same refinement
# loop one correction at a time via the private _ir_setup/_ir_step/
# _ir_result helpers below; solve_ir and the chunked driver share every
# line of per-correction arithmetic, so re-cutting the host loop at
# correction boundaries cannot perturb the trajectory.


class IRResult(NamedTuple):
    x: jnp.ndarray
    outer_iters: int          # correction steps taken
    inner_iters: int          # total inner-solver iterations
    relres: float             # final TRUE (tag-3) relative residual
    converged: bool
    history: np.ndarray       # (outer_iters+1,) outer residual trajectory
    # Robustness (DESIGN.md §14): HEALTH_OK when converged; otherwise the
    # failing inner solve's health code, HEALTH_NONFINITE if the outer
    # tag-3 residual itself went non-finite, or HEALTH_STALLED on plain
    # max_outer exhaustion.
    health: int = HEALTH_OK
    # Observability (DESIGN.md §16): list of per-correction flight-recorder
    # states (one per inner solve, in outer-iteration order) when a
    # ``flight`` was requested; decode each with ``FlightLog.from_state``.
    flight: object = None


def solve_ir(
    apply_a: Union[Callable, GSECSR],
    b: jnp.ndarray,
    tol: float = 1e-10,
    max_outer: int = 10,
    inner: str = "cg",
    inner_tol: float = 1e-4,
    inner_maxiter: int = 2000,
    params: P.MonitorParams | None = None,
    precond=None,
    restart: int = 30,
    wire: str = "exact",
    guards: GuardParams | None = DEFAULT_GUARDS,
    flight: OF.FlightParams | None = None,
    tags=None,
) -> IRResult:
    """Iterative refinement with a stepped inner solver.

    ``apply_a`` is a tag-dispatched operator or a ``GSECSR``.  ``inner`` selects ``"cg"`` or ``"gmres"``;
    ``precond`` (a :mod:`repro.solvers.precond` object or callable) turns
    the inner solve into PCG / right-preconditioned GMRES.  ``params``
    parameterizes the inner residual monitor (``MonitorParams``); each
    correction restarts the monitor at tag 1, so late corrections --
    whose right-hand sides are tiny -- get the cheap tags again.

    ``guards`` threads the in-loop guardrails (DESIGN.md §14) into every
    inner solve; a non-finite correction is never folded into ``x`` and
    the report's ``health`` names the failing stage.

    ``tags`` (PR 10, DESIGN.md §18) threads to the INNER CG/PCG solves:
    an int or uniform :class:`~repro.core.tagmap.TagMap` starts every
    correction's monitor there; a non-uniform map runs each correction
    on the masked per-group operand.  The OUTER tag-3 residual always
    reads the UNMASKED operand, so the refinement target stays the true
    operator.  Requires ``inner="cg"`` (GMRES keeps its scalar axis).
    """
    if tags is not None and inner != "cg":
        raise ValueError("tags= requires inner='cg' (the GMRES inner "
                         "solve keeps the legacy scalar tag axis)")
    st = _ir_setup(apply_a, b, tol=tol, max_outer=max_outer, inner=inner,
                   inner_tol=inner_tol, inner_maxiter=inner_maxiter,
                   params=params, precond=precond, restart=restart,
                   wire=wire, guards=guards, flight=flight, tags=tags)
    with OT.span("solve.ir", n=int(b.shape[0]), tol=float(tol), inner=inner):
        while _ir_active(st):
            _ir_step(st)
    return _ir_result(st)


def _ir_setup(apply_a, b, *, tol, max_outer, inner, inner_tol, inner_maxiter,
              params, precond, restart, wire, guards, flight,
              tags=None) -> dict:
    """Build the host-side refinement state for ``solve_ir``/chunked IR.

    Returns a mutable dict advanced one correction at a time by
    ``_ir_step``; ``_ir_active`` is the loop condition and ``_ir_result``
    materializes the final ``IRResult``.  The dict is host state (Python
    scalars + device arrays), not a pytree -- checkpointing extracts the
    array leaves explicitly (``repro.serve.chunked``).
    """
    if params is None:
        params = (P.MonitorParams.for_cg() if inner == "cg"
                  else P.MonitorParams.for_gmres())
    if inner not in ("cg", "gmres"):
        raise ValueError(f"inner must be 'cg' or 'gmres', got {inner}")

    from repro.solvers.batched import _maybe_sharded

    # Row-sharded operands ride the distributed operator (DESIGN.md §13):
    # the outer tag-3 residual reads and the inner solves all go through
    # the memoized sharded apply; ``wire`` picks the halo wire format
    # (ignored for non-partitioned operands, like the batched solvers).
    apply_a = _maybe_sharded(apply_a, wire)
    if isinstance(apply_a, (GSECSR, GSESellC)):
        from repro.solvers.operators import make_gse_operator

        # Memoized on the GSECSR instance: GMRES treats the operator as a
        # static jit arg, so a fresh closure per call would retrace.
        apply_tagged = make_gse_operator(apply_a)
    else:
        apply_tagged = apply_a

    def apply3(v):
        return apply_tagged(v, jnp.int32(3))

    bnorm = float(jnp.linalg.norm(b))
    bnorm = bnorm if bnorm != 0 else 1.0

    x = jnp.zeros_like(b)
    # One tag-3 residual per correction: r doubles as convergence check
    # and next inner right-hand side (the module's whole point is to
    # minimize full-precision reads).
    r = b - apply3(x)
    relres = float(jnp.linalg.norm(r)) / bnorm
    return dict(
        apply_a=apply_a, apply_tagged=apply_tagged, apply3=apply3,
        b=b, bnorm=bnorm, tol=tol, max_outer=max_outer, inner=inner,
        inner_tol=inner_tol, inner_maxiter=inner_maxiter, params=params,
        precond=precond, restart=restart, guards=guards, flight=flight,
        tags=tags, x=x, r=r, relres=relres, history=[relres], total_inner=0, outer=0,
        inner_health=HEALTH_OK, stopped=False,
        flights=[] if flight is not None else None,
    )


def _ir_active(st: dict) -> bool:
    """True while another correction step would run (solve_ir loop cond)."""
    return (not st["stopped"] and st["relres"] > st["tol"]
            and np.isfinite(st["relres"]) and st["outer"] < st["max_outer"])


def _ir_step(st: dict) -> dict:
    """One outer correction: inner solve at stepped precision, fold, re-residual.

    Exactly the body of the original ``solve_ir`` while-loop; ``stopped``
    records the early breaks (non-finite correction, zero-progress inner)
    so a re-cut host loop stops at the same correction.
    """
    if st["inner"] == "cg":
        if st["precond"] is not None:
            res = solve_pcg(st["apply_a"], st["r"], st["precond"],
                            tol=st["inner_tol"], maxiter=st["inner_maxiter"],
                            params=st["params"], guards=st["guards"],
                            flight=st["flight"], tags=st.get("tags"))
        else:
            res = solve_cg(st["apply_a"], st["r"], tol=st["inner_tol"],
                           maxiter=st["inner_maxiter"], params=st["params"],
                           guards=st["guards"], flight=st["flight"],
                           tags=st.get("tags"))
    else:
        res = solve_gmres(st["apply_tagged"], st["r"], tol=st["inner_tol"],
                          restart=st["restart"], maxiter=st["inner_maxiter"],
                          params=st["params"], precond=st["precond"],
                          guards=st["guards"], flight=st["flight"])
    st["inner_health"] = int(getattr(res, "health", HEALTH_OK))
    st["total_inner"] += int(res.iters)
    res_flight = getattr(res, "flight", None)  # adaptive results carry none
    if st["flights"] is not None and res_flight is not None:
        st["flights"].append(res_flight)
    if not bool(jnp.isfinite(jnp.vdot(res.x, res.x))):
        st["stopped"] = True  # never fold a non-finite correction into x
        return st
    st["x"] = st["x"] + res.x      # full-precision correction
    st["outer"] += 1
    # Tag-3 residual: the one-copy high read.
    st["r"] = st["b"] - st["apply3"](st["x"])
    st["relres"] = float(jnp.linalg.norm(st["r"])) / st["bnorm"]
    st["history"].append(st["relres"])
    if not bool(res.converged) and int(res.iters) == 0:
        st["stopped"] = True  # inner made no progress; avoid spinning
    return st


def _ir_result(st: dict) -> IRResult:
    """Materialize the final report from the host refinement state."""
    relres = st["relres"]
    converged = relres <= st["tol"]
    if converged:
        health = HEALTH_OK
    elif not np.isfinite(relres):
        health = HEALTH_NONFINITE
    elif st["inner_health"] != HEALTH_OK:
        health = st["inner_health"]
    else:
        health = HEALTH_STALLED
    return IRResult(
        x=st["x"],
        outer_iters=st["outer"],
        inner_iters=st["total_inner"],
        relres=relres,
        converged=converged,
        history=np.asarray(st["history"]),
        health=health,
        flight=st["flights"],
    )
