"""In-loop solver guardrails and tag-escalation recovery (DESIGN.md §14).

Every Krylov loop in the repo is a ``jax.lax.while_loop``; a tag-1
breakdown used to mean one of two silent failure modes:

  * ``p.Ap <= 0`` (indefinite low-tag perturbation) -- alpha's
    divide-guard kicks in and the loop spins to ``maxiter`` on garbage;
  * a NaN residual -- ``NaN > tol`` is False, so the loop EXITS EARLY and
    returns an unflagged non-finite x that looks "converged by maxiter".

The guard runs alongside the update (never inside it -- the update
arithmetic is bit-identical with guards on or off, which is what keeps
the operand-vs-callable, SELL-vs-CSR and 1-shard-vs-``solve_cg`` contracts
intact).  Each iteration classifies the new state into one of five
health codes and the loop condition adds ``health == OK``, so a tripped
guard stops the loop at the trip iteration instead of burning budget.

Recovery is a HOST-side driver (:func:`run_with_recovery`): the loops
also carry the last known-finite x as a checkpoint; on a trip at
tag < 3 the driver rolls back to the checkpoint, promotes the tag
(rebuilding the monitor window from scratch, so NaNs can never poison
the C1/C2 metrics), records the promotion into ``switch_iters`` at the
GLOBAL iteration (fig89's byte model splits the trajectory by those
switch points -- recovery stays byte-accounted), and resumes with the
remaining budget.  The terminal rung is the exact tag-3 path: the same
resume machinery ``_finish_with_correction`` uses.
"""
from __future__ import annotations

import dataclasses

import jax.numpy as jnp
import numpy as np

__all__ = [
    "HEALTH_OK",
    "HEALTH_BREAKDOWN",
    "HEALTH_DIVERGED",
    "HEALTH_NONFINITE",
    "HEALTH_STALLED",
    "HEALTH_NAMES",
    "health_name",
    "GuardParams",
    "DEFAULT_GUARDS",
    "guard_init",
    "guard_step",
    "finalize_health",
    "run_with_recovery",
    "run_with_recovery_map",
]

# Health codes, carried as int32 scalars through the jitted loops so the
# structured status survives jit/shard_map boundaries.  Order encodes
# severity: when several conditions fire in one iteration the LARGEST
# diagnosable code wins (nonfinite > diverged/breakdown > stalled).
HEALTH_OK = 0
HEALTH_BREAKDOWN = 1   # p.Ap <= 0 (or z.r < 0 under PCG, lucky-zero GMRES)
HEALTH_DIVERGED = 2    # relres blew past div_factor * best-seen
HEALTH_NONFINITE = 3   # NaN/Inf in the residual recurrence
HEALTH_STALLED = 4     # no new best residual for stall_window iterations

HEALTH_NAMES = ("ok", "breakdown", "diverged", "nonfinite", "stalled")


def health_name(code) -> str:
    """Human-readable name for a health code (accepts traced/np scalars)."""
    i = int(code)
    if 0 <= i < len(HEALTH_NAMES):
        return HEALTH_NAMES[i]
    return f"unknown({i})"


@dataclasses.dataclass(frozen=True)
class GuardParams:
    """Static (hashable) guard thresholds -- a jit static arg, like
    ``MonitorParams``.

    ``div_factor``: trip DIVERGED when the recursive relative residual
    exceeds ``div_factor *`` the best residual seen so far.  CG residuals
    legitimately oscillate orders of magnitude on ill-conditioned
    problems, so this is deliberately loose (1e4).

    ``stall_window``: trip STALLED after this many iterations without a
    new best residual.  Must comfortably exceed the precision monitor's
    decision window (``MonitorParams.t``/``l``), otherwise the guard
    steals breakdowns the monitor would have resolved by stepping the
    tag on its own.
    """
    div_factor: float = 1e4
    stall_window: int = 1000


DEFAULT_GUARDS = GuardParams()


def guard_init(relres0):
    """Guard state for a loop whose initial relative residual is
    ``relres0``.

    A non-finite INITIAL residual (b or x0 poisoned, or an operator that
    NaNs at the starting tag) trips immediately with ``trip = 0``: the
    while_loop would otherwise exit before iteration 0 (``NaN > tol`` is
    False) and report an unflagged "converged" garbage x.
    """
    relres0 = jnp.asarray(relres0)
    finite = jnp.isfinite(relres0)
    big = jnp.asarray(jnp.finfo(relres0.dtype).max, relres0.dtype)
    return {
        "health": jnp.where(finite, HEALTH_OK, HEALTH_NONFINITE).astype(jnp.int32),
        "best": jnp.where(finite, relres0, big),
        "best_it": jnp.int32(0),
        "trip": jnp.where(finite, -1, 0).astype(jnp.int32),
    }


def guard_step(g, it, relres, params: GuardParams, *, denom=None,
               breakdown=False, finite_aux=()):
    """One guard update, evaluated AFTER the iteration's arithmetic.

    ``it`` is the (0-based) iteration that just ran; ``relres`` its new
    recursive relative residual.  ``denom`` (optional) is the curvature
    ``p.Ap`` -- ``denom <= 0`` is the classic CG breakdown.  ``breakdown``
    folds in extra solver-specific breakdown predicates (e.g. ``z.r < 0``
    under PCG).  ``finite_aux`` lists extra scalars that must stay finite
    (recurrence coefficients whose NaN may precede the residual's).

    Only the FIRST trip is latched: health and trip-iteration freeze once
    set, so the loop condition (``health == OK``) exits on the next check
    and the report names the iteration that actually failed.
    """
    relres = jnp.asarray(relres)
    finite = jnp.isfinite(relres)
    for a in finite_aux:
        finite = finite & jnp.isfinite(jnp.asarray(a))

    code = jnp.where(
        (it - g["best_it"]) >= params.stall_window,
        HEALTH_STALLED, HEALTH_OK,
    )
    code = jnp.where(relres > params.div_factor * g["best"],
                     HEALTH_DIVERGED, code)
    bad = jnp.asarray(breakdown)
    if denom is not None:
        denom = jnp.asarray(denom)
        bad = bad | (denom <= 0)
        finite = finite & jnp.isfinite(denom)
    code = jnp.where(bad, HEALTH_BREAKDOWN, code)
    code = jnp.where(finite, code, HEALTH_NONFINITE).astype(jnp.int32)

    was_ok = g["health"] == HEALTH_OK
    health = jnp.where(was_ok, code, g["health"])
    trip = jnp.where(was_ok & (code != HEALTH_OK),
                     jnp.asarray(it, jnp.int32), g["trip"])
    improved = finite & (relres < g["best"])
    return {
        "health": health,
        "best": jnp.where(improved, relres, g["best"]),
        "best_it": jnp.where(improved, jnp.asarray(it, jnp.int32),
                             g["best_it"]),
        "trip": trip,
    }


def finalize_health(g, converged, relres, x_finite=True):
    """Map the end-of-loop state to the reported ``(health, trip_iter)``.

    Convergence overrides everything: a ``denom == 0`` on the very
    iteration that reached tol is exact convergence, not breakdown (the
    alpha divide-guard already handles the arithmetic).  An unconverged
    clean exit is maxiter exhaustion -> STALLED with ``trip = -1`` (no
    in-loop trip; recovery keys off ``trip >= 0`` so plain budget
    exhaustion is reported, not "recovered").  ``x_finite`` folds in a
    final finiteness certificate on the solution vector for solvers
    (GMRES) whose x is assembled after the guarded loop.

    ``g`` may be ``None`` (guards disabled): the classification is then
    purely post-hoc -- converged / nonfinite / stalled.
    """
    relres = jnp.asarray(relres)
    ok_exit = jnp.isfinite(relres) & jnp.asarray(x_finite)
    base = jnp.where(ok_exit, HEALTH_STALLED, HEALTH_NONFINITE)
    trip = jnp.int32(-1)
    if g is not None:
        base = jnp.where(g["health"] != HEALTH_OK, g["health"], base)
        trip = g["trip"]
    health = jnp.where(converged, HEALTH_OK, base).astype(jnp.int32)
    trip = jnp.where(converged, jnp.int32(-1), trip)
    return health, trip


def run_with_recovery(run, x0, maxiter: int, init_tag: int = 1,
                      recover: bool = True, max_tag: int = 3):
    """Host-side escalation driver around a guarded solver run.

    ``run(x_start, budget, tag)`` must execute the solver from
    ``x_start`` with at most ``budget`` iterations, the monitor starting
    at ``tag``, and return ``(res, ckpt)`` where ``res`` carries
    ``health`` / ``trip_iter`` / ``iters`` / ``switch_iters`` and
    ``ckpt`` is the last known-finite iterate (== ``res.x`` on a clean
    run).

    On a trip at tag < ``max_tag`` the driver restarts from ``ckpt`` at
    the next tag with the REMAINING budget and a fresh monitor (the
    paper's window metrics are rebuilt from scratch -- a NaN residual
    from the failed segment can never poison C1/C2).  Each escalation is
    written into ``switch_iters`` at the global iteration it happened,
    so ``iteration_stream_bytes``/fig89 charge the pre-escalation
    segment at the cheap tag and the resumed segment at the promoted
    tag -- recovery stays byte-accounted.  The final rung is tag 3: the
    exact path, same machinery ``_finish_with_correction`` resumes on.

    The merged result reports cumulative ``iters``, the FIRST global
    trip iteration (``health == ok`` with ``trip_iter >= 0`` therefore
    reads "tripped, recovered"), and the last run's health otherwise.
    """
    res, ckpt = run(x0, maxiter, init_tag)
    if not recover:
        return res
    health = int(res.health)
    trip = int(res.trip_iter)
    if health == HEALTH_OK or trip < 0:
        return res

    total = int(res.iters)
    first_trip = trip
    sw = np.asarray(res.switch_iters, dtype=np.int64).copy()
    tag = max(int(res.tag), init_tag)
    while health != HEALTH_OK and trip >= 0 and tag < max_tag:
        tag += 1
        # The escalation IS a tag switch: record it at the global
        # iteration so the byte model bills segments honestly.
        if sw[tag - 2] < 0:
            sw[tag - 2] = total
        budget = max(maxiter - total, 1)
        res, ckpt = run(ckpt, budget, tag)
        inner_sw = np.asarray(res.switch_iters, dtype=np.int64)
        for s in range(sw.shape[0]):
            if inner_sw[s] >= 0 and sw[s] < 0:
                sw[s] = total + inner_sw[s]
        total += int(res.iters)
        health = int(res.health)
        trip = int(res.trip_iter)
        tag = max(int(res.tag), tag)
    return res._replace(
        iters=jnp.asarray(total, jnp.int32),
        switch_iters=jnp.asarray(sw, jnp.int32),
        trip_iter=jnp.asarray(first_trip, jnp.int32),
    )


def run_with_recovery_map(run, x0, maxiter: int, tm, recover: bool = True):
    """Per-group twin of :func:`run_with_recovery` (PR 10, DESIGN.md §18).

    ``run(x_start, budget, floor)`` must execute the solver with the
    static :class:`~repro.core.tagmap.TagMap` FLOORED at ``floor``
    (``TagMap.floored``: every group raised to at least the floor) and
    return ``(res, ckpt)`` like the scalar driver's ``run``.

    A trip escalates the floor one rung instead of the whole operator:
    only the groups BELOW the floor promote -- the already-promoted
    high-sensitivity groups keep their tags and the recovery cost is the
    cheapest map that is one rung safer everywhere.  The final rung
    (floor 3) is the uniform exact path, the same termination guarantee
    as the scalar ladder.  Each escalation is billed into
    ``switch_iters`` at its global iteration; inner runs never step
    in-loop (the monitor is pinned at the map's max tag), so there is no
    inner switch record to merge.
    """
    floor = tm.min_tag
    res, ckpt = run(x0, maxiter, floor)
    if not recover:
        return res
    health = int(res.health)
    trip = int(res.trip_iter)
    if health == HEALTH_OK or trip < 0:
        return res

    total = int(res.iters)
    first_trip = trip
    sw = np.asarray(res.switch_iters, dtype=np.int64).copy()
    while health != HEALTH_OK and trip >= 0 and floor < 3:
        floor += 1
        if sw[floor - 2] < 0:
            sw[floor - 2] = total
        budget = max(maxiter - total, 1)
        res, ckpt = run(ckpt, budget, floor)
        total += int(res.iters)
        health = int(res.health)
        trip = int(res.trip_iter)
    return res._replace(
        iters=jnp.asarray(total, jnp.int32),
        switch_iters=jnp.asarray(sw, jnp.int32),
        trip_iter=jnp.asarray(first_trip, jnp.int32),
    )
