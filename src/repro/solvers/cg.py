"""Conjugate Gradient with stepped mixed precision (paper Alg. 3 + Sec IV).

Pure ``lax.while_loop``; the operator is applied at the current precision
tag each iteration, and the residual monitor (core.precision) steps the tag
up when convergence stalls.  Faithful to the paper: the switch happens
in-place (no restart, no residual recomputation at the switch), matching
Algorithm 3.

One iteration, one loop, one entry (DESIGN.md §4):

  * ``fused_cg.cg_step`` is the iteration, the tag switch around all of it;
  * ``krylov_loop`` is the ``while_loop`` around it, with the monitor, the
    switch log, the guards, the flight recorder and the chunk hooks -- the
    single-chip loop here and the sharded body inside ``shard_map``
    (``solvers.sharded``) both run it;
  * ``_drive`` is the entry behind ``solve_cg``, ``solve_pcg``,
    ``solve_cg_sharded`` and ``solve_pcg_sharded``: the ``tags=``
    hand-off, the sharded dispatch, recovery and the final correction.

The operator is a packed ``GSECSR``/``GSESellC`` or any callable
``apply(x, tag)`` (fixed-precision baselines, dense operators, the
distributed operator); the preconditioner a ``solvers.precond`` /
``solvers.multigrid`` object or any callable ``apply_m(r, tag)``.  Either
form runs the same loop.
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
from repro.solvers.fused_cg import cg_step, kdot, start, static_apply
from repro.solvers.operators import make_gse_operator
from repro.sparse.csr import GSECSR, GSESellC

__all__ = ["CGResult", "krylov_loop", "solve_cg", "solve_pcg"]


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


def _relres(s, bnorm):
    """A loop state's recursive relative residual: ``sqrt(r.r)/||b||``
    (``rr`` under PCG, ``rs`` under CG)."""
    return jnp.sqrt(jnp.abs(s["rr"] if "rr" in s else s["rs"])) / bnorm


def krylov_loop(matvec, precond, dot, b, x0, tol, maxiter, params, bnorm, *,
                init_tag=1, guards: GuardParams | None = None,
                flight: OF.FlightParams | None = None, resume=None,
                stop_at=None, apply0=None):
    """The stepped CG (``precond=None``) or PCG ``while_loop`` around
    ``fused_cg.cg_step``; returns the loop's exit state.

    ``matvec``/``precond``/``dot``/``apply0`` are as in ``cg_step`` and
    ``fused_cg.start``.  The state carries, once for every loop:

      * the residual monitor ``mon`` and its switch log ``switches``;
      * with ``guards`` (DESIGN.md §14) the guard state ``g`` and
        ``ckpt``, the last iterate the guard judged healthy, which
        tag-escalation recovery rolls back to;
      * with ``flight`` (DESIGN.md §16) the flight-recorder ring ``fl``.

    Guards and recorder run after the update arithmetic, on scalars the
    step already produced, so neither changes the trajectory.  Chunk
    hooks (DESIGN.md §17): ``resume`` is a previous chunk's state,
    carried verbatim (the start is skipped, so a resumed loop continues
    the exact op sequence), and ``stop_at`` an extra iteration bound
    ANDed into the condition.  The state's keys are CG's
    ``x r p rs it mon switches`` and PCG's ``x r p rz rr it mon
    switches``, plus ``g``/``ckpt``/``fl``: the chunk checkpoints of
    ``serve.chunked`` restore into them.
    """
    pcg = precond is not None
    rz_key = "rz" if pcg else "rs"

    if resume is not None:
        state = resume
    else:
        mon = P.init(params, dtype=b.dtype, tag=init_tag)
        r0, p0, rz0, rr0 = start(matvec, precond, dot, b, x0, mon.tag,
                                 apply0)
        state = dict(x=x0, r=r0, p=p0, it=jnp.int32(0), mon=mon,
                     switches=jnp.full((2,), -1, jnp.int32))
        state.update(dict(rz=rz0, rr=rr0) if pcg else dict(rs=rr0))
        if guards is not None:
            state["g"] = guard_init(_relres(state, bnorm))
            state["ckpt"] = x0
        if flight is not None:
            state["fl"] = OF.flight_init(flight, b.dtype)

    def cond(s):
        ok = (_relres(s, bnorm) > tol) & (s["it"] < maxiter)
        if stop_at is not None:
            ok = ok & (s["it"] < stop_at)
        if guards is not None:
            ok = ok & (s["g"]["health"] == HEALTH_OK)
        return ok

    def body(s):
        rz_old = s[rz_key]
        x, r, p, rz, rr, denom = cg_step(matvec, precond, dot, s["x"],
                                         s["r"], s["p"], rz_old,
                                         s["mon"].tag)
        with OT.scope(OT.MONITOR):
            rel = jnp.sqrt(jnp.abs(rr)) / bnorm
            mon = P.record(s["mon"], rel)
            mon2 = P.update_tag(mon, params)
            out = dict(x=x, r=r, p=p, it=s["it"] + 1, mon=mon2,
                       switches=_record_switch(s["switches"], mon, mon2,
                                               s["it"]))
            out.update(dict(rz=rz, rr=rr) if pcg else dict(rs=rr))
            if guards is not None:
                # z.r < 0 breaks PCG's M-SPD contract: an extra breakdown
                # predicate.
                out["g"] = guard_step(
                    s["g"], s["it"], rel, guards, denom=denom,
                    breakdown=(rz < 0) if pcg else False,
                    finite_aux=(rz,) if pcg else ())
                out["ckpt"] = jnp.where(out["g"]["health"] == HEALTH_OK, x,
                                        s["ckpt"])
            if flight is not None:
                out["fl"] = OF.flight_record(
                    s["fl"], it=s["it"], relres=rel, tag=s["mon"].tag,
                    health=out["g"]["health"] if guards is not None else None,
                    a0=rz_old / jnp.where(denom == 0, 1.0, denom),
                    a1=rz / jnp.where(rz_old == 0, 1.0, rz_old), a2=denom)
        return out

    return jax.lax.while_loop(cond, body, state)


def _loop_result(out, bnorm, tol):
    """``(CGResult, ckpt)`` of a loop's exit state; ``ckpt`` is the last
    healthy iterate (``x`` itself with guards off)."""
    relres = _relres(out, bnorm)
    conv = relres <= tol
    health, trip = finalize_health(out.get("g"), conv, relres)
    res = CGResult(x=out["x"], iters=out["it"], relres=relres,
                   tag=out["mon"].tag, switch_iters=out["switches"],
                   converged=conv, health=health, trip_iter=trip,
                   flight=out.get("fl"))
    return res, out.get("ckpt", out["x"])


@partial(jax.jit, static_argnames=("maxiter", "params", "init_tag", "guards",
                                   "flight", "return_ckpt", "return_state"))
def _loop_jit(a, m, b, x0, tol, maxiter, params: P.MonitorParams,
              init_tag: int = 1, guards: GuardParams | None = None,
              flight: OF.FlightParams | None = None,
              return_ckpt: bool = False, resume=None, stop_at=None,
              return_state: bool = False):
    bnorm = jnp.linalg.norm(b)
    bnorm = jnp.where(bnorm == 0, 1.0, bnorm)
    out = krylov_loop(static_apply(a), static_apply(m), kdot, b, x0, tol,
                      maxiter, params, bnorm, init_tag=init_tag,
                      guards=guards, flight=flight, resume=resume,
                      stop_at=stop_at)
    res, ckpt = _loop_result(out, bnorm, tol)
    if return_state:
        return res, ckpt, out
    return (res, ckpt) if return_ckpt else res


def _as_arg(f):
    """An operator or preconditioner as an argument of a jitted loop:
    packed operands and preconditioner objects are pytrees and pass as
    they are; a callable rides as a leafless ``jax.tree_util.Partial``,
    so its identity is part of the compile-cache key (the memoized
    operator closures keep it from one solve to the next)."""
    if (f is None or isinstance(f, (GSECSR, GSESellC))
            or hasattr(f, "apply_at")):
        return f
    return jax.tree_util.Partial(f if callable(f) else f.apply)


def _loop(op, precond, b, x0, tol, maxiter, params, **kw):
    """The single-chip stepped CG (``precond=None``) / PCG loop over any
    operator and preconditioner form.  ``kw``: ``init_tag``, ``guards``,
    ``flight``, ``return_ckpt``, and the chunk hooks ``resume``,
    ``stop_at``, ``return_state`` (DESIGN.md §17)."""
    return _loop_jit(_as_arg(op), _as_arg(precond), b, x0, tol, maxiter,
                     params, **kw)


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


def _tagmap_run(a, precond, b, tol_, params, guards, flight, tm: TagMap):
    """The ``run(x_start, budget, floor)`` closure the per-group recovery
    ladder drives: mask the operand at the floored map, decode at its max
    tag, monitor pinned (DESIGN.md §18).  A preconditioner runs at the
    map's MAX tag (the conservative charge ``iteration_stream_bytes``
    models)."""
    from repro.kernels.ops import masked_for_tagmap

    def run(x_start, budget, floor):
        tme = tm.floored(floor)
        res, ckpt = _loop(masked_for_tagmap(a, tme), precond, b, x_start,
                          tol_, budget, _pin_params(params, tme.max_tag),
                          init_tag=tme.max_tag, guards=guards, flight=flight,
                          return_ckpt=True)
        return _pack_map_flight(res, tme), ckpt

    return run


def _drive(op, b, precond, *, x0, tol, maxiter, params, final_correction,
           wire, guards, recover, init_tag, flight, tags=None) -> CGResult:
    """The one entry behind ``solve_cg``/``solve_pcg`` (``precond=None``
    is CG) and ``solve_cg_sharded``/``solve_pcg_sharded``.

    In order: the ``tags=`` hand-off (``"adaptive"``, an int or uniform
    map overriding ``init_tag``, a non-uniform map's masked-operand run);
    the sharded dispatch for a ``PartitionedGSECSR``; the run under
    tag-escalation recovery; and the final correction, all under the host
    span ``solve.cg``, ``solve.pcg``, ``solve.cg_sharded`` or
    ``solve.pcg_sharded``.
    """
    from repro.distributed.partition import PartitionedGSECSR

    if isinstance(tags, str):
        if tags != "adaptive":
            raise ValueError(
                f"tags= accepts an int tag, a TagMap, or 'adaptive'; "
                f"got {tags!r}")
        from repro.solvers.adaptive import solve_adaptive

        return solve_adaptive(op, b, precond=precond, x0=x0, tol=tol,
                              maxiter=maxiter, params=params)
    t_override, tm = _normalize_tag_axis(tags, op,
                                         int(jnp.asarray(b).shape[0]))
    if t_override is not None:
        init_tag = t_override
    b, x0, orig_shape = _normalize_b_x0(b, x0)
    if x0 is None:
        x0 = jnp.zeros_like(b)
    if params is None:
        params = P.MonitorParams.for_cg()
    kind = "cg" if precond is None else "pcg"
    attrs = dict(n=int(b.shape[0]), tol=float(tol))

    sharded = isinstance(op, PartitionedGSECSR)
    if sharded:
        from repro.kernels.dist_spmv import make_sharded_operator
        from repro.solvers.precond import DiagGSEPrecond

        op3 = make_sharded_operator(op, wire)
        if precond is not None and not isinstance(precond, DiagGSEPrecond):
            # Only a diagonal preconditioner shards with the operator;
            # any other applies to full vectors over the distributed
            # operator, on the single-chip loop.
            op, sharded = op3, False
    else:
        op3 = (make_gse_operator(op) if isinstance(op, (GSECSR, GSESellC))
               else op)

    if sharded:
        from repro.solvers.sharded import _run_sharded

        span = f"solve.{kind}_sharded"
        attrs.update(wire=wire, shards=int(op.n_shards))

        def run(x_start, budget, tag):
            return _run_sharded(op, kind, b, x_start, tol, budget, params,
                                tag, wire, precond=precond, guards=guards,
                                flight=flight, return_ckpt=True)
    elif tm is not None:
        span = f"solve.{kind}"
        attrs.update(init_tag=tm.max_tag)
        run = _tagmap_run(op, precond, b, jnp.asarray(tol, b.dtype), params,
                          guards, flight, tm)
    else:
        span = f"solve.{kind}"
        attrs.update(init_tag=init_tag)
        tol_ = jnp.asarray(tol, b.dtype)

        def run(x_start, budget, tag):
            return _loop(op, precond, b, x_start, tol_, budget, params,
                         init_tag=tag, guards=guards, flight=flight,
                         return_ckpt=True)

    recover = recover and guards is not None
    with OT.span(span, **attrs):
        if tm is not None:
            res = run_with_recovery_map(run, x0, maxiter, tm, recover=recover)
        else:
            res = run_with_recovery(run, x0, maxiter, init_tag=init_tag,
                                    recover=recover)
        if final_correction:
            res = _finish_with_correction(
                res, b, tol, maxiter, lambda v: op3(v, jnp.int32(3)),
                lambda xr, budget: run(xr, budget, 3)[0])
    return _restore_shape(res, orig_shape)


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
    current tag, inside one branch of the step's tag switch, so the
    preconditioner stream follows the same precision schedule without a
    second stored copy.

    ``apply_a`` is a ``GSECSR``/``GSESellC`` or any callable
    ``apply_a(x, tag)``; both run the same loop.  Passing a
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
    return _drive(apply_a, b, precond, x0=x0, tol=tol, maxiter=maxiter,
                  params=params, final_correction=final_correction,
                  wire=wire, guards=guards, recover=recover,
                  init_tag=init_tag, flight=flight, tags=tags)


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
    """CG for SPD systems.  ``apply_a`` is a ``GSECSR``/``GSESellC`` or a
    (possibly multi-precision) operator ``apply_a(x, tag)``;
    fixed-precision baselines ignore ``tag``.  Both run the same loop,
    bit-identically: ``solve_cg(a, ...)`` and
    ``solve_cg(make_gse_operator(a), ...)`` give the same trajectory.
    Passing a ``PartitionedGSECSR`` selects the fully-sharded distributed
    loop (``solvers.sharded``; ``wire`` picks the halo wire format and is
    ignored otherwise).

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
    return _drive(apply_a, b, None, x0=x0, tol=tol, maxiter=maxiter,
                  params=params, final_correction=final_correction,
                  wire=wire, guards=guards, recover=recover,
                  init_tag=init_tag, flight=flight, tags=tags)
