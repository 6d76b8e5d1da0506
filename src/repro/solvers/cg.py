"""Conjugate Gradient with stepped mixed precision (paper Alg. 3 + Sec IV).

Pure ``lax.while_loop``; the operator is called with the current precision
tag each iteration, and the residual monitor (core.precision) steps the tag
up when convergence stalls.  Faithful to the paper: the switch happens
in-place (no restart, no residual recomputation at the switch), matching
Algorithm 3.

Two equivalent hot paths (bit-identical trajectories):

  * generic: ``apply_a(x, tag)`` is any callable (fixed-precision
    baselines, dense operators, preconditioned wrappers);
  * fused:   pass a ``GSECSR`` directly as the operator and each iteration
    runs ``solvers.fused_cg.fused_cg_step`` -- one decoded-value pass with
    the dots/axpys folded around the SpMV (DESIGN.md §4).
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Callable, NamedTuple, Union

import jax
import jax.numpy as jnp

from repro.core import precision as P
from repro.core.tagmap import TagMap, normalize_tags
from repro.obs import flight as OF
from repro.obs import trace as OT
from repro.robustness.guards import (
    DEFAULT_GUARDS,
    GuardParams,
    HEALTH_OK,
    finalize_health,
    guard_init,
    guard_step,
    run_with_recovery,
    run_with_recovery_map,
)
from repro.solvers.fused_cg import kdot
from repro.sparse.csr import GSECSR, GSESellC

__all__ = ["CGResult", "solve_cg", "solve_pcg"]


def _normalize_tag_axis(tags, apply_a, m):
    """Normalize the public ``tags=`` axis (PR 10, DESIGN.md §18).

    Returns ``(init_tag_override, tm)`` -- at most one non-None:

      * ``None``            -> ``(None, None)``: legacy ``init_tag`` path;
      * int / uniform map   -> ``(tag, None)``: the SAME jaxpr as today's
        scalar ``tag=int`` API (the uniform fast path the bit-identity
        acceptance criterion pins);
      * non-uniform map     -> ``(None, tm)``: masked-operand path --
        requires a packed GSE operand whose tail segments can be zeroed.
    """
    norm = normalize_tags(tags, m)
    if norm is None or isinstance(norm, int):
        return norm, None
    tm = norm
    from repro.distributed.partition import PartitionedGSECSR

    if isinstance(apply_a, PartitionedGSECSR):
        raise NotImplementedError(
            "non-uniform TagMap schedules on sharded (PartitionedGSECSR) "
            "operands are not supported yet; int tags and uniform maps are"
        )
    if not isinstance(apply_a, (GSECSR, GSESellC)):
        raise ValueError(
            "a non-uniform TagMap needs a packed GSE operand (GSECSR/"
            "GSESellC) whose tail segments it can mask; got a generic "
            f"apply_a of type {type(apply_a).__name__}"
        )
    return None, tm


def _normalize_b_x0(b, x0):
    """Accept ``b``/``x0`` as ``(n,)`` or ``(n, 1)``; reject anything else.

    Returns ``(b_1d, x0_1d_or_None, orig_shape)`` -- the solvers run on the
    1-D view and reshape the solution back to the caller's layout, so the
    batched wrappers (``solvers.batched``) can delegate single columns
    without special cases.  Mismatched shapes or dtypes between ``b`` and
    ``x0`` raise a ``ValueError`` up front instead of a shape error deep
    inside a jitted ``while_loop``.
    """
    b = jnp.asarray(b)
    orig_shape = b.shape
    if b.ndim == 2 and b.shape[1] == 1:
        b = b[:, 0]
    elif b.ndim != 1:
        raise ValueError(
            f"b must be (n,) or (n, 1); got {orig_shape} -- for multi-RHS "
            "blocks use repro.solvers.batched"
        )
    if x0 is not None:
        x0 = jnp.asarray(x0)
        x0_shape = x0.shape
        if x0.ndim == 2 and x0.shape[1] == 1:
            x0 = x0[:, 0]
        elif x0.ndim != 1:
            raise ValueError(f"x0 must be (n,) or (n, 1); got {x0_shape}")
        if x0.shape[0] != b.shape[0]:
            raise ValueError(
                f"x0/b shape mismatch: x0 has {x0.shape[0]} rows, "
                f"b has {b.shape[0]}"
            )
        if x0.dtype != b.dtype:
            raise ValueError(
                f"x0/b dtype mismatch: {x0.dtype} vs {b.dtype}"
            )
    return b, x0, orig_shape


def _restore_shape(res, orig_shape):
    """Reshape the solution back to the caller's ``b`` layout."""
    if res.x.shape != orig_shape:
        res = res._replace(x=res.x.reshape(orig_shape))
    return res


class CGResult(NamedTuple):
    x: jnp.ndarray
    iters: jnp.ndarray       # iterations executed
    relres: jnp.ndarray      # final recursive relative residual
    tag: jnp.ndarray         # final precision tag
    switch_iters: jnp.ndarray  # (2,) iteration of tag->2 and tag->3 (-1: never)
    converged: jnp.ndarray
    # Robustness (DESIGN.md §14): structured health code
    # (robustness.guards.HEALTH_*, name via ``health_name``) and the first
    # iteration a guard tripped (-1: never; >= 0 with health == ok means
    # "tripped, then recovered via tag escalation").
    health: jnp.ndarray = HEALTH_OK
    trip_iter: jnp.ndarray = -1
    # Observability (DESIGN.md §16): raw flight-recorder ring state (None
    # when recording is off); decode with ``obs.flight.FlightLog.from_state``.
    flight: object = None
    # Iterations the final correction's resumed segment ran (0 where the
    # true residual already met ``tol``; None without final_correction).
    # Included in ``iters``.
    correction_iters: object = None


def _guarded_init(state, relres0, guards):
    """Attach guard state + last-finite checkpoint to a loop state dict."""
    if guards is not None:
        state["g"] = guard_init(relres0)
        state["ckpt"] = state["x"]
    return state


def _guarded_cond(s, ok, guards):
    """AND the guard's health into a loop condition (no-op with guards off)."""
    if guards is not None:
        ok = ok & (s["g"]["health"] == HEALTH_OK)
    return ok


def _guarded_body(s, out, relres_new, guards, *, denom=None, breakdown=False,
                  finite_aux=()):
    """Run the guard over an iteration's new state and roll the checkpoint.

    Called AFTER the update arithmetic (which is identical with guards on
    or off -- the bit-identity contracts); records health/trip and keeps
    ``ckpt`` at the last state the guard judged healthy, which is what
    tag-escalation recovery rolls back to.
    """
    if guards is None:
        return out
    g = guard_step(s["g"], s["it"], relres_new, guards, denom=denom,
                   breakdown=breakdown, finite_aux=finite_aux)
    out["g"] = g
    out["ckpt"] = jnp.where(g["health"] == HEALTH_OK, out["x"], s["ckpt"])
    return out


def _guarded_result(out, relres, tol, guards, make):
    """Finalize health/trip and build ``(result, ckpt)`` from a loop exit."""
    conv = relres <= tol
    g = out.get("g") if guards is not None else None
    health, trip = finalize_health(g, conv, relres)
    res = make(conv, health, trip)
    ckpt = out["ckpt"] if guards is not None else out["x"]
    return res, ckpt


def _flight_init(state, flight, dtype):
    """Attach a flight-recorder ring buffer to a loop state dict."""
    if flight is not None:
        state["fl"] = OF.flight_init(flight, dtype)
    return state


def _flight_body(s, out, relres_new, flight, a0=None, a1=None, a2=None):
    """Append this iteration's flight row (pure observation, after the
    guard ran so the row carries the guard's verdict on this iteration).

    Same discipline as ``_guarded_body``: nothing here feeds back into the
    solver recurrence, so recorder-on stays bit-identical to recorder-off.
    """
    if flight is None:
        return out
    g = out.get("g")
    out["fl"] = OF.flight_record(
        s["fl"],
        it=s["it"],
        relres=relres_new,
        tag=s["mon"].tag,
        health=g["health"] if g is not None else None,
        a0=a0, a1=a1, a2=a2,
    )
    return out


@partial(jax.jit, static_argnames=("apply_a", "maxiter", "params", "init_tag",
                                   "guards", "flight", "return_ckpt",
                                   "return_state"))
def _solve_cg(apply_a, b, x0, tol, maxiter, params: P.MonitorParams,
              init_tag: int = 1, guards: GuardParams | None = None,
              flight: OF.FlightParams | None = None,
              return_ckpt: bool = False, resume=None, stop_at=None,
              return_state: bool = False):
    dtype = b.dtype
    bnorm = jnp.linalg.norm(b)
    bnorm = jnp.where(bnorm == 0, 1.0, bnorm)

    def relres(s):
        return jnp.sqrt(jnp.abs(s["rs"])) / bnorm

    # ``resume`` (DESIGN.md §17) carries a previous chunk's loop state
    # verbatim: the init section is skipped entirely, so a resumed loop
    # continues the EXACT op sequence the unchunked loop would have run.
    if resume is not None:
        state = resume
    else:
        mon = P.init(params, dtype=dtype, tag=init_tag)
        r0 = _residual(b, apply_a(x0, mon.tag))
        state = dict(
            x=x0,
            r=r0,
            p=r0,
            rs=kdot(r0, r0),
            it=jnp.int32(0),
            mon=mon,
            switches=jnp.full((2,), -1, jnp.int32),
        )
        state = _guarded_init(state, relres(state), guards)
        state = _flight_init(state, flight, dtype)

    def cond(s):
        ok = (relres(s) > tol) & (s["it"] < maxiter)
        if stop_at is not None:
            # Chunk boundary: a pure extra exit condition -- the body
            # arithmetic is untouched, so chunked == unchunked bitwise.
            ok = ok & (s["it"] < stop_at)
        return _guarded_cond(s, ok, guards)

    def body(s):
        tag = s["mon"].tag
        ap = apply_a(s["p"], tag)
        denom = kdot(s["p"], ap)
        with OT.scope(OT.KRYLOV, OT.UPDATE):
            alpha = s["rs"] / jnp.where(denom == 0, 1.0, denom)
            x = s["x"] + alpha * s["p"]
            r = s["r"] - alpha * ap
        rs_new = kdot(r, r)
        with OT.scope(OT.MONITOR):
            mon = P.record(s["mon"], jnp.sqrt(jnp.abs(rs_new)) / bnorm)
            mon2 = P.update_tag(mon, params)
            switches = _record_switch(s["switches"], mon, mon2, s["it"])
        with OT.scope(OT.KRYLOV, OT.UPDATE):
            beta = rs_new / jnp.where(s["rs"] == 0, 1.0, s["rs"])
            p = r + beta * s["p"]
        out = dict(
            x=x, r=r, p=p, rs=rs_new, it=s["it"] + 1, mon=mon2, switches=switches
        )
        with OT.scope(OT.MONITOR):
            out = _guarded_body(s, out, jnp.sqrt(jnp.abs(rs_new)) / bnorm,
                                guards, denom=denom)
            return _flight_body(s, out, jnp.sqrt(jnp.abs(rs_new)) / bnorm,
                                flight, a0=alpha, a1=beta, a2=denom)

    out = jax.lax.while_loop(cond, body, state)
    res, ckpt = _guarded_result(
        out, relres(out), tol, guards,
        lambda conv, health, trip: CGResult(
            x=out["x"],
            iters=out["it"],
            relres=relres(out),
            tag=out["mon"].tag,
            switch_iters=out["switches"],
            converged=conv,
            health=health,
            trip_iter=trip,
            flight=out.get("fl"),
        ),
    )
    if return_state:
        return res, ckpt, out
    return (res, ckpt) if return_ckpt else res


def _record_switch(switches, mon, mon2, it):
    """Log the iteration of a tag step-up into its slot (0: ->2, 1: ->3).

    The slot write happens ONLY when a step actually occurred; writing
    unconditionally would re-target slot 1 with a self-assignment on every
    post-switch tag-3 iteration (and corrupt it if the slot indexing ever
    drifts from the tag clip).
    """
    stepped = mon2.tag > mon.tag
    slot = jnp.clip(mon.tag - 1, 0, 1)
    return jnp.where(stepped, switches.at[slot].set(it + 1), switches)


def _residual(b, ax):
    """``b - A x`` of the loop's start, under the ``krylov/update`` scope
    (the SpMV that made ``ax`` carries its own)."""
    with OT.scope(OT.KRYLOV, OT.UPDATE):
        return b - ax


@partial(jax.jit, static_argnames=("maxiter", "params", "init_tag", "guards",
                                   "flight", "return_ckpt", "return_state"))
def _solve_cg_fused(a, b, x0, tol, maxiter, params: P.MonitorParams,
                    init_tag: int = 1, guards: GuardParams | None = None,
                    flight: OF.FlightParams | None = None,
                    return_ckpt: bool = False, resume=None, stop_at=None,
                    return_state: bool = False):
    """Fused-path CG over a ``GSECSR`` operand (DESIGN.md §4).

    Same trajectory as ``_solve_cg`` with the GSE operator -- each
    iteration is one ``fused_cg_step``: the values are decoded once at the
    monitor's current tag and the dots/axpys/residual norm ride the same
    sweep as the SpMV.  With guards or the flight recorder the step also
    surfaces the curvature ``p.Ap`` it already computed
    (``fused_cg_step_g``) -- the update arithmetic is unchanged either way.

    ``resume``/``stop_at``/``return_state``: chunked execution hooks
    (DESIGN.md §17), as in :func:`_solve_cg`.
    """
    from repro.solvers.fused_cg import fused_cg_step, fused_cg_step_g, gse_matvec

    dtype = b.dtype
    bnorm = jnp.linalg.norm(b)
    bnorm = jnp.where(bnorm == 0, 1.0, bnorm)

    def relres(s):
        return jnp.sqrt(jnp.abs(s["rs"])) / bnorm

    if resume is not None:
        state = resume
    else:
        mon = P.init(params, dtype=dtype, tag=init_tag)
        r0 = _residual(b, gse_matvec(a, x0, mon.tag))
        state = dict(
            x=x0,
            r=r0,
            p=r0,
            rs=kdot(r0, r0),
            it=jnp.int32(0),
            mon=mon,
            switches=jnp.full((2,), -1, jnp.int32),
        )
        state = _guarded_init(state, relres(state), guards)
        state = _flight_init(state, flight, dtype)

    def cond(s):
        ok = (relres(s) > tol) & (s["it"] < maxiter)
        if stop_at is not None:
            ok = ok & (s["it"] < stop_at)
        return _guarded_cond(s, ok, guards)

    def body(s):
        if guards is None and flight is None:
            x, r, p, rs_new = fused_cg_step(
                a, s["x"], s["r"], s["p"], s["rs"], s["mon"].tag
            )
            denom = None
        else:
            x, r, p, rs_new, denom = fused_cg_step_g(
                a, s["x"], s["r"], s["p"], s["rs"], s["mon"].tag
            )
        with OT.scope(OT.MONITOR):
            mon = P.record(s["mon"], jnp.sqrt(jnp.abs(rs_new)) / bnorm)
            mon2 = P.update_tag(mon, params)
            switches = _record_switch(s["switches"], mon, mon2, s["it"])
            out = dict(
                x=x, r=r, p=p, rs=rs_new, it=s["it"] + 1, mon=mon2,
                switches=switches
            )
            out = _guarded_body(s, out, jnp.sqrt(jnp.abs(rs_new)) / bnorm,
                                guards, denom=denom)
            if flight is not None:
                # Observation-only recomputation of the step scalars from
                # the surfaced curvature (the fused step consumed them
                # internally).
                alpha = s["rs"] / jnp.where(denom == 0, 1.0, denom)
                beta = rs_new / jnp.where(s["rs"] == 0, 1.0, s["rs"])
                out = _flight_body(s, out, jnp.sqrt(jnp.abs(rs_new)) / bnorm,
                                   flight, a0=alpha, a1=beta, a2=denom)
        return out

    out = jax.lax.while_loop(cond, body, state)
    res, ckpt = _guarded_result(
        out, relres(out), tol, guards,
        lambda conv, health, trip: CGResult(
            x=out["x"],
            iters=out["it"],
            relres=relres(out),
            tag=out["mon"].tag,
            switch_iters=out["switches"],
            converged=conv,
            health=health,
            trip_iter=trip,
            flight=out.get("fl"),
        ),
    )
    if return_state:
        return res, ckpt, out
    return (res, ckpt) if return_ckpt else res


@partial(jax.jit, static_argnames=("apply_a", "apply_m", "maxiter", "params",
                                   "init_tag", "guards", "flight",
                                   "return_ckpt", "return_state"))
def _solve_pcg(apply_a, apply_m, b, x0, tol, maxiter, params: P.MonitorParams,
               init_tag: int = 1, guards: GuardParams | None = None,
               flight: OF.FlightParams | None = None,
               return_ckpt: bool = False, resume=None, stop_at=None,
               return_state: bool = False):
    """Preconditioned CG: ``z = M^{-1} r`` at the monitor's current tag.

    The recurrence runs on ``rz = r.z``; the monitor sees the plain
    residual norm ``sqrt(r.r)/||b||`` -- the same quantity the paper's
    controller watches in unpreconditioned CG.
    """
    dtype = b.dtype
    bnorm = jnp.linalg.norm(b)
    bnorm = jnp.where(bnorm == 0, 1.0, bnorm)

    def relres(s):
        return jnp.sqrt(jnp.abs(s["rr"])) / bnorm

    if resume is not None:
        state = resume
    else:
        mon = P.init(params, dtype=dtype, tag=init_tag)
        r0 = _residual(b, apply_a(x0, mon.tag))
        with OT.scope(OT.PRECOND):
            z0 = apply_m(r0, mon.tag)
        state = dict(
            x=x0,
            r=r0,
            p=z0,
            rz=kdot(r0, z0),
            rr=kdot(r0, r0),
            it=jnp.int32(0),
            mon=mon,
            switches=jnp.full((2,), -1, jnp.int32),
        )
        state = _guarded_init(state, relres(state), guards)
        state = _flight_init(state, flight, dtype)

    def cond(s):
        ok = (relres(s) > tol) & (s["it"] < maxiter)
        if stop_at is not None:
            ok = ok & (s["it"] < stop_at)
        return _guarded_cond(s, ok, guards)

    def body(s):
        tag = s["mon"].tag
        ap = apply_a(s["p"], tag)
        denom = kdot(s["p"], ap)
        with OT.scope(OT.KRYLOV, OT.UPDATE):
            alpha = s["rz"] / jnp.where(denom == 0, 1.0, denom)
            x = s["x"] + alpha * s["p"]
            r = s["r"] - alpha * ap
        with OT.scope(OT.PRECOND):
            z = apply_m(r, tag)
        rz_new = kdot(r, z)
        rr_new = kdot(r, r)
        with OT.scope(OT.MONITOR):
            mon = P.record(s["mon"], jnp.sqrt(jnp.abs(rr_new)) / bnorm)
            mon2 = P.update_tag(mon, params)
            switches = _record_switch(s["switches"], mon, mon2, s["it"])
        with OT.scope(OT.KRYLOV, OT.UPDATE):
            beta = rz_new / jnp.where(s["rz"] == 0, 1.0, s["rz"])
            p = z + beta * s["p"]
        out = dict(
            x=x, r=r, p=p, rz=rz_new, rr=rr_new, it=s["it"] + 1, mon=mon2,
            switches=switches,
        )
        with OT.scope(OT.MONITOR):
            # z.r < 0 breaks PCG's M-SPD contract: an extra breakdown
            # predicate.
            out = _guarded_body(s, out, jnp.sqrt(jnp.abs(rr_new)) / bnorm,
                                guards, denom=denom, breakdown=rz_new < 0,
                                finite_aux=(rz_new,))
            return _flight_body(s, out, jnp.sqrt(jnp.abs(rr_new)) / bnorm,
                                flight, a0=alpha, a1=beta, a2=denom)

    out = jax.lax.while_loop(cond, body, state)
    res, ckpt = _guarded_result(
        out, relres(out), tol, guards,
        lambda conv, health, trip: CGResult(
            x=out["x"],
            iters=out["it"],
            relres=relres(out),
            tag=out["mon"].tag,
            switch_iters=out["switches"],
            converged=conv,
            health=health,
            trip_iter=trip,
            flight=out.get("fl"),
        ),
    )
    if return_state:
        return res, ckpt, out
    return (res, ckpt) if return_ckpt else res


@partial(jax.jit, static_argnames=("maxiter", "params", "init_tag", "guards",
                                   "flight", "return_ckpt", "return_state"))
def _solve_pcg_fused(a, m, b, x0, tol, maxiter, params: P.MonitorParams,
                     init_tag: int = 1, guards: GuardParams | None = None,
                     flight: OF.FlightParams | None = None,
                     return_ckpt: bool = False, resume=None, stop_at=None,
                     return_state: bool = False):
    """Fused-path PCG over a ``GSECSR`` operand and a pytree preconditioner.

    Each iteration is one ``fused_pcg_step``: operator decode and
    preconditioner apply ride the same tag branch (DESIGN.md §10), with
    the exact arithmetic of ``_solve_pcg`` -- bit-identical trajectories.
    """
    from repro.solvers.fused_cg import (fused_pcg_step, fused_pcg_step_g,
                                        gse_matvec)

    dtype = b.dtype
    bnorm = jnp.linalg.norm(b)
    bnorm = jnp.where(bnorm == 0, 1.0, bnorm)

    def relres(s):
        return jnp.sqrt(jnp.abs(s["rr"])) / bnorm

    if resume is not None:
        state = resume
    else:
        mon = P.init(params, dtype=dtype, tag=init_tag)
        r0 = _residual(b, gse_matvec(a, x0, mon.tag))
        with OT.scope(OT.PRECOND):
            z0 = m.apply(r0, mon.tag)
        state = dict(
            x=x0,
            r=r0,
            p=z0,
            rz=kdot(r0, z0),
            rr=kdot(r0, r0),
            it=jnp.int32(0),
            mon=mon,
            switches=jnp.full((2,), -1, jnp.int32),
        )
        state = _guarded_init(state, relres(state), guards)
        state = _flight_init(state, flight, dtype)

    def cond(s):
        ok = (relres(s) > tol) & (s["it"] < maxiter)
        if stop_at is not None:
            ok = ok & (s["it"] < stop_at)
        return _guarded_cond(s, ok, guards)

    def body(s):
        if guards is None and flight is None:
            x, r, p, rz_new, rr_new = fused_pcg_step(
                a, m, s["x"], s["r"], s["p"], s["rz"], s["mon"].tag
            )
            denom = None
        else:
            x, r, p, rz_new, rr_new, denom = fused_pcg_step_g(
                a, m, s["x"], s["r"], s["p"], s["rz"], s["mon"].tag
            )
        with OT.scope(OT.MONITOR):
            mon = P.record(s["mon"], jnp.sqrt(jnp.abs(rr_new)) / bnorm)
            mon2 = P.update_tag(mon, params)
            switches = _record_switch(s["switches"], mon, mon2, s["it"])
            out = dict(
                x=x, r=r, p=p, rz=rz_new, rr=rr_new, it=s["it"] + 1, mon=mon2,
                switches=switches,
            )
            out = _guarded_body(s, out, jnp.sqrt(jnp.abs(rr_new)) / bnorm,
                                guards, denom=denom, breakdown=rz_new < 0,
                                finite_aux=(rz_new,))
            if flight is not None:
                alpha = s["rz"] / jnp.where(denom == 0, 1.0, denom)
                beta = rz_new / jnp.where(s["rz"] == 0, 1.0, s["rz"])
                out = _flight_body(s, out, jnp.sqrt(jnp.abs(rr_new)) / bnorm,
                                   flight, a0=alpha, a1=beta, a2=denom)
        return out

    out = jax.lax.while_loop(cond, body, state)
    res, ckpt = _guarded_result(
        out, relres(out), tol, guards,
        lambda conv, health, trip: CGResult(
            x=out["x"],
            iters=out["it"],
            relres=relres(out),
            tag=out["mon"].tag,
            switch_iters=out["switches"],
            converged=conv,
            health=health,
            trip_iter=trip,
            flight=out.get("fl"),
        ),
    )
    if return_state:
        return res, ckpt, out
    return (res, ckpt) if return_ckpt else res


def _finish_with_correction(res, b, tol, maxiter, apply3, resume):
    """Shared final-correction epilogue (``solve_cg`` / ``solve_pcg`` /
    ``solve_gmres`` -- ``CGResult`` and ``GMRESResult`` share fields):
    verify the TRUE tag-3 residual and, when the recursive convergence was
    optimistic, resume at full precision.  The resume budget is clamped to
    >= 1 -- the first solve may have exhausted ``maxiter`` exactly at
    tolerance, and a non-positive budget would run zero iterations and
    report a stale result.

    Runs under the host span ``solve.correction`` (annotated with the
    ``true_relres`` the check read), with the children
    ``solve.correction.check`` and, when it resumes,
    ``solve.correction.resume``.  The resumed segment's iteration count
    lands on the result as ``correction_iters`` (a device scalar; 0 when
    the check passes), so no wait is added for it."""
    with OT.span("solve.correction"):
        with OT.span("solve.correction.check"):
            bnorm = jnp.linalg.norm(b)
            bnorm = jnp.where(bnorm == 0, 1.0, bnorm)
            true_rel = jnp.linalg.norm(b - apply3(res.x)) / bnorm
            rel = float(true_rel) if bool(res.converged) else None
        if rel is not None:
            OT.annotate(true_relres=rel)
        if rel is None or not rel > tol:
            return res._replace(correction_iters=jnp.zeros_like(res.iters))
        with OT.span("solve.correction.resume"):
            res2 = resume(res.x, max(maxiter - int(res.iters), 1))
        return type(res)(
            x=res2.x,
            iters=res.iters + res2.iters,
            relres=res2.relres,
            tag=res2.tag,
            switch_iters=res.switch_iters,
            converged=res2.converged,
            health=res2.health,
            trip_iter=jnp.where(res2.trip_iter >= 0,
                                res2.trip_iter + res.iters, res.trip_iter),
            # The resumed segment's recording (its `it` restarts at 0); fall
            # back to the first run's when the resume didn't record.
            flight=res2.flight if res2.flight is not None else res.flight,
            correction_iters=res2.iters,
        )


def _pin_params(params: P.MonitorParams, max_tag: int) -> P.MonitorParams:
    """Pin the in-loop monitor at the map's max tag: with
    ``init_tag == max_tag`` the step predicate (``tag < max_tag``) is
    statically false, so a static TagMap IS the schedule -- no in-loop
    whole-operator stepping underneath a per-group map."""
    if params.max_tag == max_tag:
        return params
    return dataclasses.replace(params, max_tag=max_tag)


def _pack_map_flight(res, tme: TagMap):
    """Restamp a TagMap segment's flight rows with the packed (min, max)
    active tag pair (obs.flight satellite; schema unchanged for uniform
    maps)."""
    if res.flight is None:
        return res
    return res._replace(flight=OF.pack_state_tags(
        res.flight, tme.min_tag, tme.max_tag))


def _tagmap_run_cg(a, b, tol_, params, guards, flight, tm: TagMap):
    """Build the ``run(x_start, budget, floor)`` closure the per-group
    recovery ladder drives for CG: mask the operand at the floored map,
    decode at its max tag, monitor pinned (DESIGN.md §18)."""
    from repro.kernels.ops import masked_for_tagmap

    def run(x_start, budget, floor):
        tme = tm.floored(floor)
        res, ckpt = _solve_cg_fused(
            masked_for_tagmap(a, tme), b, x_start, tol_, budget,
            _pin_params(params, tme.max_tag), init_tag=tme.max_tag,
            guards=guards, flight=flight, return_ckpt=True)
        return _pack_map_flight(res, tme), ckpt

    return run


def _tagmap_run_pcg(a, precond, b, tol_, params, guards, flight,
                    fused: bool, tm: TagMap):
    """PCG twin of :func:`_tagmap_run_cg` -- the preconditioner stream
    runs at the map's MAX tag (the conservative charge
    ``iteration_stream_bytes`` models)."""
    from repro.kernels.ops import masked_for_tagmap

    if fused:
        def run(x_start, budget, floor):
            tme = tm.floored(floor)
            res, ckpt = _solve_pcg_fused(
                masked_for_tagmap(a, tme), precond, b, x_start, tol_,
                budget, _pin_params(params, tme.max_tag),
                init_tag=tme.max_tag, guards=guards, flight=flight,
                return_ckpt=True)
            return _pack_map_flight(res, tme), ckpt
    else:
        apply_m = precond if callable(precond) else precond.apply

        def run(x_start, budget, floor):
            tme = tm.floored(floor)
            res, ckpt = _solve_pcg(
                _gsecsr_operator(masked_for_tagmap(a, tme)), apply_m, b,
                x_start, tol_, budget, _pin_params(params, tme.max_tag),
                init_tag=tme.max_tag, guards=guards, flight=flight,
                return_ckpt=True)
            return _pack_map_flight(res, tme), ckpt

    return run


def _gsecsr_operator(a) -> Callable:
    """Tag-dispatched operator view of a GSECSR/GSESellC, memoized on the instance
    so repeated solves reuse one closure (the closure is a static jit
    argument -- a fresh one per call would retrace the whole solver)."""
    op = a.__dict__.get("_tag_operator")
    if op is None:
        from repro.solvers.fused_cg import gse_matvec

        def op(v, tag):
            return gse_matvec(a, v, tag)

        a.__dict__["_tag_operator"] = op
    return op


def solve_pcg(
    apply_a: Union[Callable, GSECSR],
    b: jnp.ndarray,
    precond,
    x0: jnp.ndarray | None = None,
    tol: float = 1e-6,
    maxiter: int = 5000,
    params: P.MonitorParams | None = None,
    final_correction: bool = False,
    wire: str = "exact",
    guards: GuardParams | None = DEFAULT_GUARDS,
    recover: bool = True,
    init_tag: int = 1,
    flight: OF.FlightParams | None = None,
    tags=None,
) -> CGResult:
    """Preconditioned CG for SPD systems with stepped mixed precision.

    ``precond`` is a preconditioner from :mod:`repro.solvers.precond`
    (exposing ``apply``/``apply_at``) or any callable ``apply_m(r, tag)``.
    Both the operator and the preconditioner are applied at the monitor's
    current tag, so the preconditioner stream follows the same precision
    schedule without a second stored copy.

    Passing a ``GSECSR`` as ``apply_a`` together with a precond *object*
    selects the fused iteration path (``fused_pcg_step``) -- bit-identical
    to the generic path, fewer kernel launches.  Passing a
    ``PartitionedGSECSR`` selects the fully-sharded distributed loop
    (``solvers.sharded``; ``wire`` picks the halo wire format and is
    ignored otherwise).

    ``guards`` (a :class:`repro.robustness.GuardParams`, default on; pass
    ``None`` to compile the pre-guard loop) adds in-loop breakdown/
    divergence/non-finite/stall detection; with ``recover`` a trip at
    tag < 3 rolls back to the last finite checkpoint and escalates the
    tag (DESIGN.md §14).  ``init_tag`` starts the monitor above tag 1
    (e.g. 3 = the exact path -- the serving layer's fallback).

    ``flight`` (a :class:`repro.obs.FlightParams`; default off) carries a
    device-side per-iteration flight recorder through the loop, returned
    raw on ``CGResult.flight`` -- decode with
    ``obs.flight.FlightLog.from_state``.  Bit-identical trajectories
    either way (DESIGN.md §16).

    ``tags`` (PR 10, DESIGN.md §18) selects the precision axis: an int or
    a uniform :class:`~repro.core.tagmap.TagMap` overrides ``init_tag``
    (same jaxpr, bit-identical); a NON-uniform map runs the masked-operand
    per-group schedule (the map IS the schedule -- the in-loop monitor is
    pinned, and recovery escalates the map's FLOOR instead of the whole
    operator); ``"adaptive"`` hands off to
    :func:`repro.solvers.adaptive.solve_adaptive`.

    ``b``/``x0`` may be ``(n,)`` or ``(n, 1)``; the solution comes back in
    ``b``'s layout.
    """
    from repro.distributed.partition import PartitionedGSECSR

    if isinstance(tags, str):
        if tags != "adaptive":
            raise ValueError(
                f"tags= accepts an int tag, a TagMap, or 'adaptive'; "
                f"got {tags!r}")
        from repro.solvers.adaptive import solve_adaptive

        return solve_adaptive(apply_a, b, precond=precond, x0=x0, tol=tol,
                              maxiter=maxiter, params=params)
    t_override, tm = _normalize_tag_axis(tags, apply_a,
                                         int(jnp.asarray(b).shape[0]))
    if t_override is not None:
        init_tag = t_override

    if isinstance(apply_a, PartitionedGSECSR):
        from repro.solvers.sharded import solve_pcg_sharded

        return solve_pcg_sharded(apply_a, b, precond, x0=x0, tol=tol,
                                 maxiter=maxiter, params=params, wire=wire,
                                 final_correction=final_correction,
                                 guards=guards, recover=recover,
                                 init_tag=init_tag, flight=flight)
    b, x0, orig_shape = _normalize_b_x0(b, x0)
    if x0 is None:
        x0 = jnp.zeros_like(b)
    if params is None:
        params = P.MonitorParams.for_cg()
    tol_ = jnp.asarray(tol, b.dtype)
    fused = (isinstance(apply_a, (GSECSR, GSESellC))
             and hasattr(precond, "apply_at"))

    if tm is not None:
        run = _tagmap_run_pcg(apply_a, precond, b, tol_, params, guards,
                              flight, fused, tm)
        with OT.span("solve.pcg", n=int(b.shape[0]), tol=float(tol),
                     init_tag=tm.max_tag, fused=fused):
            res = run_with_recovery_map(
                run, x0, maxiter, tm,
                recover=recover and guards is not None)
            if not final_correction:
                return _restore_shape(res, orig_shape)
            apply3_op = _gsecsr_operator(apply_a)

            def apply3(v):
                return apply3_op(v, jnp.int32(3))

            def resume(xr, budget):
                return run(xr, budget, 3)[0]

            return _restore_shape(
                _finish_with_correction(res, b, tol, maxiter, apply3, resume),
                orig_shape,
            )

    if fused:
        def run(x_start, budget, tag):
            return _solve_pcg_fused(apply_a, precond, b, x_start, tol_,
                                    budget, params, init_tag=tag,
                                    guards=guards, flight=flight,
                                    return_ckpt=True)
    else:
        apply_m = precond if callable(precond) else precond.apply
        if isinstance(apply_a, (GSECSR, GSESellC)):
            apply_a = _gsecsr_operator(apply_a)

        def run(x_start, budget, tag):
            return _solve_pcg(apply_a, apply_m, b, x_start, tol_, budget,
                              params, init_tag=tag, guards=guards,
                              flight=flight, return_ckpt=True)

    with OT.span("solve.pcg", n=int(b.shape[0]), tol=float(tol),
                 init_tag=init_tag, fused=fused):
        res = run_with_recovery(run, x0, maxiter, init_tag=init_tag,
                                recover=recover and guards is not None)
        if not final_correction:
            return _restore_shape(res, orig_shape)
        apply3_op = _gsecsr_operator(apply_a) if fused else apply_a

        def apply3(v):
            return apply3_op(v, jnp.int32(3))

        def resume(xr, budget):
            return run(xr, budget, 3)[0]

        return _restore_shape(
            _finish_with_correction(res, b, tol, maxiter, apply3, resume),
            orig_shape,
        )


def solve_cg(
    apply_a: Union[Callable, GSECSR],
    b: jnp.ndarray,
    x0: jnp.ndarray | None = None,
    tol: float = 1e-6,
    maxiter: int = 5000,
    params: P.MonitorParams | None = None,
    final_correction: bool = False,
    wire: str = "exact",
    guards: GuardParams | None = DEFAULT_GUARDS,
    recover: bool = True,
    init_tag: int = 1,
    flight: OF.FlightParams | None = None,
    tags=None,
) -> CGResult:
    """CG for SPD systems.  ``apply_a(x, tag)`` is the (possibly multi-
    precision) operator; fixed-precision baselines ignore ``tag``.

    Passing a ``GSECSR`` directly as ``apply_a`` selects the fused
    iteration path (``fused_cg_step``): one decoded-value pass per
    iteration with the vector ops folded around the SpMV.  Trajectories
    are bit-identical to ``solve_cg(make_gse_operator(a), ...)``; only the
    kernel-launch structure differs.  Passing a ``PartitionedGSECSR``
    selects the fully-sharded distributed loop (``solvers.sharded``;
    ``wire`` picks the halo wire format and is ignored otherwise).

    ``final_correction`` (beyond-paper safeguard): the recursive residual of
    a stepped run converges against the *perturbed* low-precision operator;
    the true residual can sit above ``tol``.  When enabled, the driver
    verifies the tag-3 residual after convergence and, if needed, resumes
    at full precision until the TRUE residual meets ``tol``.

    ``guards``/``recover``/``init_tag``/``flight``: see :func:`solve_pcg`
    -- in-loop guardrails plus checkpoint-rollback tag-escalation recovery
    (DESIGN.md §14) and the per-iteration flight recorder (DESIGN.md §16).
    ``tags``: the per-group precision axis (PR 10) -- also documented
    there.

    ``b``/``x0`` may be ``(n,)`` or ``(n, 1)``; the solution comes back in
    ``b``'s layout.
    """
    from repro.distributed.partition import PartitionedGSECSR

    if isinstance(tags, str):
        if tags != "adaptive":
            raise ValueError(
                f"tags= accepts an int tag, a TagMap, or 'adaptive'; "
                f"got {tags!r}")
        from repro.solvers.adaptive import solve_adaptive

        return solve_adaptive(apply_a, b, x0=x0, tol=tol, maxiter=maxiter,
                              params=params)
    t_override, tm = _normalize_tag_axis(tags, apply_a,
                                         int(jnp.asarray(b).shape[0]))
    if t_override is not None:
        init_tag = t_override

    if isinstance(apply_a, PartitionedGSECSR):
        from repro.solvers.sharded import solve_cg_sharded

        return solve_cg_sharded(apply_a, b, x0=x0, tol=tol, maxiter=maxiter,
                                params=params, wire=wire,
                                final_correction=final_correction,
                                guards=guards, recover=recover,
                                init_tag=init_tag, flight=flight)
    b, x0, orig_shape = _normalize_b_x0(b, x0)
    if x0 is None:
        x0 = jnp.zeros_like(b)
    if params is None:
        params = P.MonitorParams.for_cg()
    tol_ = jnp.asarray(tol, b.dtype)
    fused = isinstance(apply_a, (GSECSR, GSESellC))
    solve = _solve_cg_fused if fused else _solve_cg

    if tm is not None:
        run = _tagmap_run_cg(apply_a, b, tol_, params, guards, flight, tm)
        with OT.span("solve.cg", n=int(b.shape[0]), tol=float(tol),
                     init_tag=tm.max_tag, fused=True):
            res = run_with_recovery_map(
                run, x0, maxiter, tm,
                recover=recover and guards is not None)
            if not final_correction:
                return _restore_shape(res, orig_shape)
            apply3_op = _gsecsr_operator(apply_a)

            def apply3(v):
                return apply3_op(v, jnp.int32(3))

            def resume(xr, budget):
                return run(xr, budget, 3)[0]

            return _restore_shape(
                _finish_with_correction(res, b, tol, maxiter, apply3, resume),
                orig_shape,
            )

    def run(x_start, budget, tag):
        return solve(apply_a, b, x_start, tol_, budget, params,
                     init_tag=tag, guards=guards, flight=flight,
                     return_ckpt=True)

    with OT.span("solve.cg", n=int(b.shape[0]), tol=float(tol),
                 init_tag=init_tag, fused=fused):
        res = run_with_recovery(run, x0, maxiter, init_tag=init_tag,
                                recover=recover and guards is not None)
        if not final_correction:
            return _restore_shape(res, orig_shape)
        apply3_op = _gsecsr_operator(apply_a) if fused else apply_a

        def apply3(v):
            return apply3_op(v, jnp.int32(3))

        def resume(xr, budget):
            return run(xr, budget, 3)[0]

        return _restore_shape(
            _finish_with_correction(res, b, tol, maxiter, apply3, resume),
            orig_shape,
        )
