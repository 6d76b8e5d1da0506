"""Batched multi-RHS stepped solvers: per-column precision schedules over
one shared operand (DESIGN.md §11).

The paper's case is that SpMV is memory-bound, so GSE-SEM wins by
streaming fewer matrix bytes per iteration; with ``nrhs`` right-hand
sides the SAME packed segments serve every column in one pass, so the
matrix stream is charged once per iteration however wide the batch is
(``csr.iteration_stream_bytes(..., nrhs=...)``).  Loe et al.
(arXiv:2109.01232) show precision schedules must adapt per solve --
different right-hand sides converge at different rates -- so each column
here carries its OWN residual monitor, its own tag schedule, and its own
switch-iteration log, and deactivates independently on convergence.

Bit-identity contract (the subsystem's acceptance bar): column ``j`` of a
batched solve runs EXACTLY the op sequence of an independent
``solve_cg``/``solve_pcg`` on ``b[:, j]`` -- the batch body unrolls the
same per-column ``fused_cg.cg_step`` at each column's own traced tag,
and converged columns are frozen behind a per-column
``lax.cond`` -- they skip their SpMV/decode entirely instead of being
dragged further.  Columns that share a tag share one decoded-value pass
under XLA CSE (the in-jaxpr form of "tag-bucketed sub-batches"); columns
at different tags split into their own branches.  The kernels-path twin
(``kernels/ops.gse_spmm_ell``) streams the union pass explicitly.

``batched_run_bytes`` is the fig89-style account of a whole batched run:
per iteration the matrix (+preconditioner) segments are charged ONCE at
the widest tag any active column runs, and each active column beyond the
first charges its dense x/y stream.
"""
from __future__ import annotations

from functools import partial
from typing import Callable, NamedTuple, Union

import jax
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
    finalize_health,
    guard_init,
    guard_step,
)
from repro.sparse.csr import GSECSR, GSESellC, iteration_stream_bytes
from repro.solvers.cg import _as_arg, _record_switch
from repro.solvers.fused_cg import cg_step, kdot, start, static_apply
from repro.solvers.operators import make_gse_operator

__all__ = [
    "BatchedCGResult",
    "BatchedIRResult",
    "solve_cg_batched",
    "solve_pcg_batched",
    "solve_ir_batched",
    "batched_run_bytes",
    "column_tags_at",
]


def _batched_tag_axis(tags, apply_a, m, params):
    """Normalize the batched wrappers' ``tags=`` axis (PR 10).

    Returns ``(init_tag, apply_a, params)``: ints and uniform maps
    override the starting tag on the untouched operand (same jaxpr --
    the uniform fast path); a non-uniform map swaps in the MASKED
    operand decoded at its max tag with the monitor pinned there, the
    same static-schedule semantics as single-RHS ``solve_cg(tags=tm)``.
    There is no per-group recovery ladder in-batch -- flagged columns
    go through the serving layer's tag-3 retry exactly as before.
    """
    if isinstance(tags, str):
        raise ValueError(
            "the batched solvers take an int tag or a TagMap; the "
            "'adaptive' driver is single-RHS (repro.solvers.adaptive)")
    from repro.solvers.cg import _normalize_tag_axis, _pin_params

    t, tm = _normalize_tag_axis(tags, apply_a, m)
    if tm is None:
        return (1 if t is None else t), apply_a, params
    from repro.kernels.ops import masked_for_tagmap

    return tm.max_tag, masked_for_tagmap(apply_a, tm), _pin_params(
        params, tm.max_tag)


class BatchedCGResult(NamedTuple):
    x: jnp.ndarray             # (n, nrhs) solutions
    iters: jnp.ndarray         # (nrhs,) iterations executed per column
    relres: jnp.ndarray        # (nrhs,) final recursive relative residuals
    tag: jnp.ndarray           # (nrhs,) final precision tag per column
    switch_iters: jnp.ndarray  # (nrhs, 2) iteration of tag->2 / tag->3 (-1: never)
    converged: jnp.ndarray     # (nrhs,) bool
    # Robustness (DESIGN.md §14): per-column health codes
    # (robustness.guards.HEALTH_*) and first guard-trip iteration (-1:
    # never).  A tripped column freezes (stops iterating) immediately;
    # recovery for batched requests is the SERVING layer's bounded
    # tag-3 retry (launch.solver_serve), not an in-batch escalation.
    health: jnp.ndarray = HEALTH_OK    # (nrhs,) int32
    trip_iter: jnp.ndarray = -1        # (nrhs,) int32
    # Observability (DESIGN.md §16): stacked per-column flight-recorder
    # states (leading nrhs axis; None when recording is off).  Split with
    # ``obs.flight.split_batched`` and decode each column with
    # ``FlightLog.from_state``.
    flight: object = None


class BatchedIRResult(NamedTuple):
    x: jnp.ndarray             # (n, nrhs)
    outer_iters: np.ndarray    # (nrhs,) correction steps per column
    inner_iters: np.ndarray    # (nrhs,) total inner iterations per column
    relres: np.ndarray         # (nrhs,) final TRUE (tag-3) relative residuals
    converged: np.ndarray      # (nrhs,) bool
    history: list              # nrhs lists of outer residual trajectories
    # Per-column health codes, derived as in solvers.ir.IRResult.
    health: np.ndarray = None  # (nrhs,) int
    # Observability (DESIGN.md §16): list of stacked per-correction flight
    # states (one per inner batched solve), as in BatchedCGResult.flight.
    flight: object = None


def _maybe_sharded(apply_a, wire: str):
    """Swap a ``PartitionedGSECSR`` operand for its memoized distributed
    operator closure (a callable operator); anything else passes through
    untouched."""
    from repro.distributed.partition import PartitionedGSECSR

    if isinstance(apply_a, PartitionedGSECSR):
        from repro.kernels.dist_spmv import make_sharded_operator

        return make_sharded_operator(apply_a, wire)
    return apply_a


def _normalize_block(b, x0):
    """Accept ``b``/``x0`` as ``(n,)`` or ``(n, nrhs)`` blocks."""
    b = jnp.asarray(b)
    if b.ndim == 1:
        b = b[:, None]
    if b.ndim != 2:
        raise ValueError(f"b must be (n,) or (n, nrhs); got {b.shape}")
    if x0 is None:
        x0 = jnp.zeros_like(b)
    else:
        x0 = jnp.asarray(x0)
        if x0.ndim == 1:
            x0 = x0[:, None]
        if x0.shape != b.shape:
            raise ValueError(
                f"x0/b shape mismatch: {x0.shape} vs {b.shape}"
            )
        if x0.dtype != b.dtype:
            raise ValueError(f"x0/b dtype mismatch: {x0.dtype} vs {b.dtype}")
    return b, x0


def _batched_krylov_loop(b, x0, tol, maxiter, params, init_tag,
                         init_col, step_col, guards=None, flight=None,
                         resume=None, stop_at=None, return_state=False):
    """Shared batched while_loop: per-column monitors, masking, switches.

    ``init_col(b_j, x0_j, tag) -> dict`` builds one column's Krylov state
    (must contain ``rr`` = squared residual norm driving the monitor);
    ``step_col(col_state, tag) -> dict`` runs ONE iteration, the
    single-RHS ``cg_step``, at a traced per-column tag.  Everything else
    (monitor record/update, switch logging, convergence masking, per-
    column iteration counts) is identical across CG and PCG.

    With ``guards`` (a ``GuardParams``), each column also carries its own
    guard state (DESIGN.md §14): ``step_col`` surfaces the curvature
    ``denom = p.Ap`` under key ``"denom"`` (popped before the carry so the
    loop state stays fixed-shape across the guarded/unguarded cond
    branches), PCG columns flag ``z.r < 0`` via their ``"rz"`` entry, and
    a tripped column freezes exactly like a converged one.  Guards run
    AFTER the iteration ops on scalars those ops already produced, so the
    per-column bit-identity contract with single-RHS solves is untouched.

    With ``flight`` (a ``FlightParams``), each column also carries its own
    flight-recorder ring (DESIGN.md §16) -- same observation-after-update
    discipline, recorder-on stays per-column bit-identical -- and the
    result stacks the per-column states along a leading nrhs axis.

    ``resume`` (DESIGN.md §17) carries a previous chunk's cols tuple
    verbatim (the init section is skipped); ``stop_at`` is a per-column
    ``(nrhs,)`` iteration bound ANDed into each column's liveness --
    a pure extra exit condition, so chunked == unchunked bitwise.
    ``return_state`` additionally returns the raw cols tuple.
    """
    nrhs = b.shape[1]
    bnorms = []
    for j in range(nrhs):
        bn = jnp.linalg.norm(b[:, j])
        bn = jnp.where(bn == 0, 1.0, bn)
        bnorms.append(bn)
    if resume is not None:
        cols = resume
    else:
        cols = []
        for j in range(nrhs):
            mon = P.init(params, dtype=b.dtype, tag=init_tag)
            c = init_col(b[:, j], x0[:, j], mon.tag)
            if guards is not None:
                c["g"] = guard_init(jnp.sqrt(jnp.abs(c["rr"])) / bnorms[j])
            if flight is not None:
                c["fl"] = OF.flight_init(flight, b.dtype)
            c.update(
                it=jnp.int32(0),
                mon=mon,
                sw=jnp.full((2,), -1, jnp.int32),
            )
            cols.append(c)
        cols = tuple(cols)

    def col_relres(c, j):
        return jnp.sqrt(jnp.abs(c["rr"])) / bnorms[j]

    def col_active(c, j):
        alive = (col_relres(c, j) > tol) & (c["it"] < maxiter)
        if stop_at is not None:
            alive = alive & (c["it"] < stop_at[j])
        if guards is not None:
            alive = alive & (c["g"]["health"] == HEALTH_OK)
        return alive

    def cond(cols):
        alive = [col_active(c, j) for j, c in enumerate(cols)]
        return jnp.stack(alive).any()

    def step_one(j):
        def run(c):
            stepped = step_col(c, c["mon"].tag)
            denom = stepped.pop("denom")
            relres_new = jnp.sqrt(jnp.abs(stepped["rr"])) / bnorms[j]
            if guards is not None:
                breakdown = False
                finite_aux = ()
                if "rz" in stepped:
                    breakdown = stepped["rz"] < 0
                    finite_aux = (stepped["rz"],)
                stepped["g"] = guard_step(
                    c["g"], c["it"], relres_new, guards,
                    denom=denom, breakdown=breakdown, finite_aux=finite_aux,
                )
            mon1 = P.record(c["mon"], relres_new)
            mon2 = P.update_tag(mon1, params)
            sw = _record_switch(c["sw"], mon1, mon2, c["it"])
            if flight is not None:
                # Observation-only alpha/beta from the scalars the step
                # already produced (rz-recurrence under PCG, rr under CG).
                old = c["rz"] if "rz" in c else c["rr"]
                new = stepped["rz"] if "rz" in stepped else stepped["rr"]
                alpha = old / jnp.where(denom == 0, 1.0, denom)
                beta = new / jnp.where(old == 0, 1.0, old)
                g = stepped.get("g")
                stepped["fl"] = OF.flight_record(
                    c["fl"], it=c["it"], relres=relres_new,
                    tag=c["mon"].tag,
                    health=g["health"] if g is not None else None,
                    a0=alpha, a1=beta, a2=denom,
                )
            stepped.update(it=c["it"] + 1, mon=mon2, sw=sw)
            return stepped

        return run

    def body(cols):
        # lax.cond (scalar predicate -> real branch, not a select): a
        # frozen column skips its SpMV/decode entirely instead of
        # computing a result that masking would discard -- the service's
        # padding columns cost nothing while real requests iterate.
        return tuple(
            jax.lax.cond(col_active(c, j), step_one(j), lambda c: c, c)
            for j, c in enumerate(cols)
        )

    cols = jax.lax.while_loop(cond, body, cols)
    relres = jnp.stack([col_relres(c, j) for j, c in enumerate(cols)])
    if guards is not None:
        per_col = [
            finalize_health(
                c["g"],
                col_relres(c, j) <= tol,
                col_relres(c, j),
                x_finite=jnp.isfinite(jnp.vdot(c["x"], c["x"])),
            )
            for j, c in enumerate(cols)
        ]
        health = jnp.stack([h for h, _ in per_col])
        trip_iter = jnp.stack([t for _, t in per_col])
        converged = (relres <= tol) & jnp.stack(
            [jnp.isfinite(jnp.vdot(c["x"], c["x"])) for c in cols]
        )
    else:
        health = jnp.full((nrhs,), HEALTH_OK, jnp.int32)
        trip_iter = jnp.full((nrhs,), -1, jnp.int32)
        converged = relres <= tol
    res = BatchedCGResult(
        x=jnp.stack([c["x"] for c in cols], axis=1),
        iters=jnp.stack([c["it"] for c in cols]),
        relres=relres,
        tag=jnp.stack([c["mon"].tag for c in cols]),
        switch_iters=jnp.stack([c["sw"] for c in cols]),
        converged=converged,
        health=health,
        trip_iter=trip_iter,
        flight=(jax.tree_util.tree_map(lambda *xs: jnp.stack(xs),
                                       *[c["fl"] for c in cols])
                if flight is not None else None),
    )
    return (res, cols) if return_state else res


# ---------------------------------------------------------------------------
# Batched CG / PCG
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("maxiter", "params", "init_tag", "guards",
                                   "flight", "return_state"))
def _batched_jit(a, m, b, x0, tol, maxiter, params, init_tag=1, guards=None,
                 flight=None, resume=None, stop_at=None, return_state=False):
    matvec, precond = static_apply(a), static_apply(m)

    def init_col(bj, xj, tag):
        r0, p0, rz0, rr0 = start(matvec, precond, kdot, bj, xj, tag)
        c = dict(x=xj, r=r0, p=p0, rr=rr0)
        if precond is not None:
            c["rz"] = rz0
        return c

    def step_col(c, tag):
        x, r, p, rz, rr, denom = cg_step(matvec, precond, kdot, c["x"],
                                         c["r"], c["p"],
                                         c.get("rz", c["rr"]), tag)
        out = dict(x=x, r=r, p=p, rr=rr, denom=denom)
        if precond is not None:
            out["rz"] = rz
        return out

    return _batched_krylov_loop(b, x0, tol, maxiter, params, init_tag,
                                init_col, step_col, guards, flight,
                                resume=resume, stop_at=stop_at,
                                return_state=return_state)


def _solve_batched(op, precond, b, x0, tol, maxiter, params, **kw):
    """The batched loop over any operator and preconditioner form
    (``precond=None`` is CG); ``kw`` as in ``cg._loop``, with per-column
    ``stop_at``."""
    return _batched_jit(_as_arg(op), _as_arg(precond), b, x0, tol, maxiter,
                        params, **kw)


def solve_cg_batched(
    apply_a: Union[Callable, GSECSR],
    b: jnp.ndarray,
    x0: jnp.ndarray | None = None,
    tol: float = 1e-6,
    maxiter: int = 5000,
    params: P.MonitorParams | None = None,
    wire: str = "exact",
    guards: GuardParams | None = DEFAULT_GUARDS,
    flight: OF.FlightParams | None = None,
    tags=None,
) -> BatchedCGResult:
    """Stepped CG over an (n, nrhs) right-hand-side block.

    One shared operand, ``nrhs`` independent per-column precision
    schedules: each column carries its own residual monitor and steps its
    own tag, deactivating when it converges.  Column ``j``'s trajectory is
    bit-identical to ``solve_cg(apply_a, b[:, j], ...)`` with the same
    parameters -- same iterates, same iteration count, same switch
    iterations (tested in tests/test_batched.py).

    ``apply_a`` is a ``GSECSR``/``GSESellC`` or a callable, as in
    single-RHS ``solve_cg``.  A ``PartitionedGSECSR`` rides the
    row-sharded distributed operator
    (``kernels.dist_spmv.make_sharded_operator``; ``wire`` picks the halo
    wire format, DESIGN.md §13) as a callable -- column ``j`` stays
    bit-identical to the sharded single-RHS solve's operator
    applications.  The modeled per-iteration traffic of the
    batch is ``iteration_stream_bytes(a, tag, nrhs=n_active)`` -- matrix
    bytes once, vector bytes per active column; ``batched_run_bytes``
    accounts a whole run from the per-column results.

    ``guards`` attaches per-column breakdown/divergence/non-finite/stall
    detection (DESIGN.md §14); a tripped column freezes and reports its
    health code.  There is no in-batch tag escalation -- the serving
    layer retries flagged columns at tag 3 (``launch.solver_serve``).
    ``guards=None`` compiles the pre-guard loop.

    ``tags`` (PR 10, DESIGN.md §18): an int or uniform
    :class:`~repro.core.tagmap.TagMap` starts every column's monitor at
    that tag (same jaxpr, bit-identical); a NON-uniform map runs the
    static masked-operand schedule for the whole batch -- per-column
    in-loop stepping is pinned off, exactly as in single-RHS
    ``solve_cg(tags=tm)``.
    """
    b, x0 = _normalize_block(b, x0)
    if params is None:
        params = P.MonitorParams.for_cg()
    tol_ = jnp.asarray(tol, b.dtype)
    init_tag, apply_a, params = _batched_tag_axis(
        tags, apply_a, int(b.shape[0]), params)
    apply_a = _maybe_sharded(apply_a, wire)
    with OT.span("solve.cg_batched", n=int(b.shape[0]),
                 nrhs=int(b.shape[1]), tol=float(tol)):
        return _solve_batched(apply_a, None, b, x0, tol_, maxiter, params,
                              init_tag=init_tag, guards=guards, flight=flight)


def solve_pcg_batched(
    apply_a: Union[Callable, GSECSR],
    b: jnp.ndarray,
    precond,
    x0: jnp.ndarray | None = None,
    tol: float = 1e-6,
    maxiter: int = 5000,
    params: P.MonitorParams | None = None,
    wire: str = "exact",
    guards: GuardParams | None = DEFAULT_GUARDS,
    flight: OF.FlightParams | None = None,
    tags=None,
) -> BatchedCGResult:
    """Stepped preconditioned CG over an (n, nrhs) block.

    Both the operator and the GSE-packed preconditioner follow each
    column's OWN tag schedule; the stored segments of both are charged
    once per iteration however many columns ride along.  Column ``j`` is
    bit-identical to ``solve_pcg(apply_a, b[:, j], precond, ...)``.
    ``PartitionedGSECSR`` operands ride the distributed operator exactly
    as in :func:`solve_cg_batched`.  ``guards`` works as in
    :func:`solve_cg_batched`, additionally flagging ``z.r < 0``
    (indefinite-preconditioner breakdown) per column.
    ``tags`` works as in :func:`solve_cg_batched`; with a non-uniform map
    the preconditioner stream rides the map's MAX tag (the conservative
    charge ``iteration_stream_bytes`` models).
    """
    b, x0 = _normalize_block(b, x0)
    if params is None:
        params = P.MonitorParams.for_cg()
    tol_ = jnp.asarray(tol, b.dtype)
    init_tag, apply_a, params = _batched_tag_axis(
        tags, apply_a, int(b.shape[0]), params)
    apply_a = _maybe_sharded(apply_a, wire)
    with OT.span("solve.pcg_batched", n=int(b.shape[0]),
                 nrhs=int(b.shape[1]), tol=float(tol)):
        return _solve_batched(apply_a, precond, b, x0, tol_, maxiter, params,
                              init_tag=init_tag, guards=guards, flight=flight)


# ---------------------------------------------------------------------------
# Batched iterative refinement (outer loop from solvers/ir.py)
# ---------------------------------------------------------------------------

def solve_ir_batched(
    apply_a: Union[Callable, GSECSR],
    b: jnp.ndarray,
    tol: float = 1e-10,
    max_outer: int = 10,
    inner_tol: float = 1e-4,
    inner_maxiter: int = 2000,
    params: P.MonitorParams | None = None,
    precond=None,
    wire: str = "exact",
    guards: GuardParams | None = DEFAULT_GUARDS,
    flight: OF.FlightParams | None = None,
    tags=None,
) -> BatchedIRResult:
    """Batched stepped iterative refinement (the ``solve_ir`` outer loop
    over an (n, nrhs) block, inner solves batched).

    Outer loop at tag 3 per column (the one-copy high-precision read),
    inner batched stepped CG/PCG starting every correction back at tag 1.
    Each column refines until ITS true residual meets ``tol`` and then
    drops out of the correction updates; the inner batch keeps streaming
    one matrix pass for whichever columns remain.  Active columns'
    trajectories match the single-RHS ``solve_ir`` exactly (the batched
    inner solve is per-column bit-identical and the outer ops are
    per-column).

    ``tags`` threads to the INNER batched solves only (ints/uniform maps
    start the inner monitors there; a non-uniform map runs the masked
    static schedule) -- the outer tag-3 residual always reads the
    UNMASKED operand, so the refinement target stays the true operator.
    """
    b = jnp.asarray(b)
    if b.ndim == 1:
        b = b[:, None]
    if params is None:
        params = P.MonitorParams.for_cg()
    nrhs = b.shape[1]

    apply_a = _maybe_sharded(apply_a, wire)
    apply_tagged = (make_gse_operator(apply_a)
                    if isinstance(apply_a, (GSECSR, GSESellC)) else apply_a)

    def apply3_block(x_block):
        # Per-column tag-3 reads: identical arithmetic to solve_ir's apply3.
        return jnp.stack(
            [apply_tagged(x_block[:, j], jnp.int32(3)) for j in range(nrhs)],
            axis=1,
        )

    def col_norms(block):
        # Per-column 1-D norms, NOT an axis reduction: solve_ir's scalar
        # norm and jnp.linalg.norm(..., axis=0) can differ in the last
        # ulp, and the bit-identity contract extends to the history.
        return np.asarray(
            [float(jnp.linalg.norm(block[:, j])) for j in range(nrhs)]
        )

    bnorms = col_norms(b)
    bnorms = np.where(bnorms == 0, 1.0, bnorms)

    x = jnp.zeros_like(b)
    total_inner = np.zeros(nrhs, np.int64)
    outer = np.zeros(nrhs, np.int64)
    inner_health = np.zeros(nrhs, np.int64)
    r = b - apply3_block(x)
    relres = col_norms(r) / bnorms
    history = [[float(v)] for v in relres]
    flights = [] if flight is not None else None
    active = (relres > tol) & np.isfinite(relres) & (outer < max_outer)
    while active.any():
        mask = jnp.asarray(active)
        # Converged columns drop out of the inner batch NOW: zeroing their
        # residual column makes them converge at inner iteration 0 (the
        # ||b||=0 path, same trick as the service's padding columns), so
        # they stop burning inner iterations on corrections the mask
        # below would discard anyway.
        r_in = jnp.where(mask[None, :], r, 0.0)
        if precond is not None:
            res = solve_pcg_batched(apply_a, r_in, precond, tol=inner_tol,
                                    maxiter=inner_maxiter, params=params,
                                    guards=guards, flight=flight,
                                    tags=tags)
        else:
            res = solve_cg_batched(apply_a, r_in, tol=inner_tol,
                                   maxiter=inner_maxiter, params=params,
                                   guards=guards, flight=flight,
                                   tags=tags)
        if flights is not None and res.flight is not None:
            flights.append(res.flight)
        inner_health[active] = np.asarray(res.health)[active]
        # A non-finite correction column is never folded into x -- that
        # column deactivates carrying its inner health code.
        col_fin = np.asarray(jnp.isfinite(res.x).all(axis=0))
        take = mask & jnp.asarray(col_fin)
        x = jnp.where(take[None, :], x + res.x, x)
        iters = np.asarray(res.iters)
        conv = np.asarray(res.converged)
        total_inner[active] += iters[active]
        outer[active & col_fin] += 1
        r = b - apply3_block(x)
        relres = col_norms(r) / bnorms
        for j in range(nrhs):
            if active[j] and col_fin[j]:
                history[j].append(float(relres[j]))
        stalled = (~conv) & (iters == 0)  # no-progress guard, per column
        active = (active & (relres > tol) & np.isfinite(relres) & ~stalled
                  & col_fin & (outer < max_outer))
    converged = (relres <= tol) & np.isfinite(relres)
    health = np.where(
        converged, HEALTH_OK,
        np.where(~np.isfinite(relres), HEALTH_NONFINITE,
                 np.where(inner_health != HEALTH_OK, inner_health,
                          HEALTH_STALLED)),
    ).astype(np.int64)
    return BatchedIRResult(
        x=x,
        outer_iters=outer,
        inner_iters=total_inner,
        relres=relres,
        converged=converged,
        history=[np.asarray(h) for h in history],
        health=health,
        flight=flights,
    )


# ---------------------------------------------------------------------------
# Byte accounting for a whole batched run (fig89-style)
# ---------------------------------------------------------------------------

def column_tags_at(iters, switch_iters, it: int) -> np.ndarray:
    """Per-column tag at 0-based iteration ``it`` (0 for finished columns).

    Uses the ``switch_iters`` semantics of the single-RHS byte model
    (``benchmarks.fig89``): iterations ``[0, sw0)`` run at tag 1,
    ``[sw0, sw1)`` at tag 2, ``[sw1, iters)`` at tag 3; ``-1`` means the
    step never happened.
    """
    iters = np.asarray(iters)
    sw = np.asarray(switch_iters)
    nrhs = iters.shape[0]
    tags = np.zeros(nrhs, np.int64)
    for j in range(nrhs):
        if it >= iters[j]:
            continue  # column already converged: streams nothing
        t2 = sw[j, 0] if sw[j, 0] >= 0 else iters[j]
        t3 = sw[j, 1] if sw[j, 1] >= 0 else iters[j]
        tags[j] = 1 if it < t2 else (2 if it < t3 else 3)
    return tags


def batched_run_bytes(op, iters, switch_iters, precond=None) -> int:
    """Modeled HBM bytes a whole batched stepped run streams.

    Per iteration, the matrix (+preconditioner) segments are charged ONCE
    at the WIDEST tag any active column runs -- the shared streaming pass
    must read the union of the segments its columns need -- and every
    active column beyond the first charges its dense x/y stream
    (``iteration_stream_bytes(..., nrhs=n_active)``).  Converged columns
    stream nothing.  With ``nrhs == 1`` this reduces exactly to the
    single-RHS trajectory account of ``benchmarks.fig89``.
    """
    iters = np.asarray(iters)
    total = 0
    for it in range(int(iters.max(initial=0))):
        tags = column_tags_at(iters, switch_iters, it)
        n_active = int((tags > 0).sum())
        if n_active == 0:
            continue
        total += iteration_stream_bytes(
            op, int(tags.max()), precond, nrhs=n_active
        )
    return total
