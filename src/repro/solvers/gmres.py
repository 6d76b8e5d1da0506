"""Restarted GMRES with stepped mixed precision (paper Alg. 3, Sec IV).

GMRES(restart) with iterated classical Gram-Schmidt (CGS2 -- vectorizes on
TPU, numerically equivalent to MGS in practice) and Givens-rotation least
squares.  The residual monitor sees ``|g[j+1]|`` every inner iteration --
exactly the quantity the paper monitors -- and steps the SpMV precision tag
in place.  Tag and residual history persist across restarts.
"""
from __future__ import annotations

from functools import partial
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp

from repro.core import precision as P
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
)
from repro.solvers.cg import _record_switch

__all__ = ["GMRESResult", "solve_gmres"]


class GMRESResult(NamedTuple):
    x: jnp.ndarray
    iters: jnp.ndarray        # total inner iterations (matvecs in Arnoldi)
    relres: jnp.ndarray
    tag: jnp.ndarray
    switch_iters: jnp.ndarray  # (2,) inner-iteration of tag->2 / tag->3
    converged: jnp.ndarray
    # Robustness (DESIGN.md §14): health code (robustness.guards.HEALTH_*)
    # and first guard-trip inner iteration (-1: never).
    health: jnp.ndarray = HEALTH_OK
    trip_iter: jnp.ndarray = -1
    # Observability (DESIGN.md §16): raw flight-recorder ring state (None
    # when recording is off); rows are inner iterations with a0 = the
    # Givens magnitude d, a1 = the Arnoldi subdiagonal H[j+1, j].
    flight: object = None
    # Inner iterations the final correction's resumed segment ran (0
    # where the true residual already met ``tol``; None without
    # final_correction).  Included in ``iters``.
    correction_iters: object = None


def _givens(a, b):
    """Rotation (c, s, d) with d = hypot(a, b), overflow/underflow-safe.

    The naive ``sqrt(a*a + b*b)`` overflows to inf for |a| or |b| above
    ~sqrt(max_float) (1e154 in f64, 1e19 in f32 -- guaranteed territory
    for float32 sharded runs) and underflows to 0 below ~sqrt(tiny),
    poisoning c/s and every later rotation.  Scale by max(|a|, |b|) first
    so the squared terms stay in [0, 1]; c and s come from the SCALED
    quotients (never touching the possibly-overflowing product d).
    """
    m = jnp.maximum(jnp.abs(a), jnp.abs(b))
    safe = m > 0
    scale = jnp.where(safe, m, 1.0)
    an = a / scale
    bn = b / scale
    dn = jnp.sqrt(an * an + bn * bn)  # in [1, sqrt(2)]: exact-safe range
    c = jnp.where(safe, an / dn, 1.0)
    s = jnp.where(safe, bn / dn, 0.0)
    return c, s, dn * scale


@partial(jax.jit, static_argnames=("apply_a", "apply_m", "restart", "maxiter",
                                   "params", "init_tag", "return_monitor",
                                   "guards", "flight", "return_ckpt"))
def _solve_gmres(apply_a, b, x0, tol, restart, maxiter,
                 params: P.MonitorParams, init_tag: int = 1, apply_m=None,
                 return_monitor: bool = False,
                 guards: GuardParams | None = None,
                 flight: OF.FlightParams | None = None,
                 return_ckpt: bool = False):
    """``apply_m`` (optional) right-preconditions: Arnoldi runs on
    ``A M^{-1}`` and the Krylov correction is mapped back through
    ``M^{-1}`` at the end of each cycle.  In exact arithmetic right
    preconditioning keeps ``|g[j+1]|`` equal to the residual norm of the
    original system, so the stepped monitor watches the same quantity as
    in the plain solver -- but under low-tag operator/preconditioner
    perturbation it remains a RECURSIVE residual (paper semantics, same
    as unpreconditioned stepped GMRES): use ``final_correction`` to
    certify the TRUE tag-3 residual.  Both applications run at the
    monitor's current tag; a mid-cycle tag step therefore mixes decode
    precisions inside one Krylov cycle (for ``M^{-1}`` exactly as
    Algorithm 3 already accepts for ``A`` -- the in-place switch, no
    FGMRES-style Z storage); the next restart's explicit
    ``r = b - A x`` re-anchors the cycle."""
    n = b.shape[0]
    dtype = b.dtype
    bnorm = jnp.linalg.norm(b)
    bnorm = jnp.where(bnorm == 0, 1.0, bnorm)
    abstol = tol * bnorm

    def cycle(x, it0, mon, switches, gd, ckpt, fs):
        r = b - apply_a(x, mon.tag)
        beta = jnp.linalg.norm(r)
        if guards is not None:
            # The recomputed restart residual is the one TRUE residual per
            # cycle: a previous cycle whose back-substitution went
            # non-finite (huge y through a near-singular triangle) shows
            # up here even though the recursive |g[j+1]| looked fine.
            gd = guard_step(gd, it0, beta / bnorm, guards)
        # Record the explicitly recomputed restart residual: it is the one
        # TRUE residual per cycle, and skipping it hands the switch
        # metrics a gapped window (RSD/nDec/relDec computed as if the
        # restart re-anchor never happened).  Guarded on ``it0 > 0``: the
        # first cycle's beta is the INITIAL residual, which precedes
        # iteration 0 -- recording it would misalign the window with the
        # per-iteration residual stream the paper's monitor watches.
        mon = jax.lax.cond(
            it0 > 0,
            lambda m: P.record(m, beta / bnorm),
            lambda m: m,
            mon,
        )
        v0 = r / jnp.where(beta == 0, 1.0, beta)
        V = jnp.zeros((restart + 1, n), dtype).at[0].set(v0)
        H = jnp.zeros((restart + 1, restart), dtype)
        cs = jnp.zeros((restart,), dtype)
        sn = jnp.zeros((restart,), dtype)
        g = jnp.zeros((restart + 1,), dtype).at[0].set(beta)

        def inner_cond(c):
            j, resid = c[0], c[6]
            ok = (j < restart) & (resid > abstol) & (it0 + j < maxiter)
            if guards is not None:
                ok = ok & (c[9]["health"] == HEALTH_OK)
            return ok

        def inner_body(c):
            j, V, H, cs, sn, g, resid, mon, switches = c[:9]
            if apply_m is None:
                w = apply_a(V[j], mon.tag)
            else:
                w = apply_a(apply_m(V[j], mon.tag), mon.tag)
            # CGS2: two passes of classical Gram-Schmidt vs rows 0..j.
            mask = (jnp.arange(restart + 1) <= j).astype(dtype)
            h = jnp.zeros((restart + 1,), dtype)
            for _ in range(2):
                corr = (V @ w) * mask
                w = w - corr @ V
                h = h + corr
            hj1 = jnp.linalg.norm(w)
            V = V.at[j + 1].set(w / jnp.where(hj1 == 0, 1.0, hj1))
            col = h.at[j + 1].set(hj1)

            # Apply previous rotations 0..j-1 (sequential recurrence).
            def rot(i, col):
                on = (i < j).astype(dtype)
                t1 = cs[i] * col[i] + sn[i] * col[i + 1]
                t2 = -sn[i] * col[i] + cs[i] * col[i + 1]
                col = col.at[i].set(on * t1 + (1 - on) * col[i])
                col = col.at[i + 1].set(on * t2 + (1 - on) * col[i + 1])
                return col

            col = jax.lax.fori_loop(0, restart, rot, col)
            c_new, s_new, d = _givens(col[j], col[j + 1])
            col = col.at[j].set(d).at[j + 1].set(0.0)
            cs = cs.at[j].set(c_new)
            sn = sn.at[j].set(s_new)
            g = g.at[j + 1].set(-s_new * g[j])
            g = g.at[j].set(c_new * g[j])
            resid = jnp.abs(g[j + 1])
            H = H.at[:, j].set(col)

            mon1 = P.record(mon, resid / bnorm)
            mon2 = P.update_tag(mon1, params)
            switches = _record_switch(switches, mon1, mon2, it0 + j)
            out = (j + 1, V, H, cs, sn, g, resid, mon2, switches)
            gd_new = None
            if guards is not None:
                # Unhappy breakdown: the Krylov space closed (hj1 == 0)
                # with the residual still above tolerance.  (hj1 == 0 AND
                # resid <= abstol is the HAPPY breakdown -- converged.)
                gd_new = guard_step(
                    c[9], it0 + j, resid / bnorm, guards,
                    breakdown=(hj1 == 0) & (resid > abstol),
                    finite_aux=(hj1,),
                )
                out = out + (gd_new,)
            if flight is not None:
                # Observation only (DESIGN.md §16): the flight state is the
                # LAST carry element, after the optional guard state.
                out = out + (OF.flight_record(
                    c[-1], it=it0 + j, relres=resid / bnorm, tag=mon.tag,
                    health=gd_new["health"] if gd_new is not None else None,
                    a0=d, a1=hj1,
                ),)
            return out

        carry = (jnp.int32(0), V, H, cs, sn, g, beta, mon, switches)
        if guards is not None:
            carry = carry + (gd,)
        if flight is not None:
            carry = carry + (fs,)
        outc = jax.lax.while_loop(inner_cond, inner_body, carry)
        j, V, H, cs, sn, g, resid, mon, switches = outc[:9]
        if guards is not None:
            gd = outc[9]
        if flight is not None:
            fs = outc[-1]

        # Back substitution on the leading j x j triangle (padded to full
        # size with identity rows so a single static solve works).
        R = H[:restart, :restart]
        eye = jnp.eye(restart, dtype=dtype)
        live = jnp.arange(restart) < j
        Rm = jnp.where(live[:, None] & live[None, :], R, eye)
        diag = jnp.diagonal(Rm)
        Rm = Rm + jnp.diag(jnp.where(diag == 0, 1.0, 0.0).astype(dtype))
        gm = jnp.where(live, g[:restart], 0.0)
        y = jax.scipy.linalg.solve_triangular(Rm, gm, lower=False)
        u = y @ V[:restart]
        if apply_m is not None:  # x = x0 + M^{-1} (V y), right precond
            u = apply_m(u, mon.tag)
        x_new = x + u
        out = (x_new, it0 + j, mon, switches, resid / bnorm)
        if guards is not None:
            fin = jnp.isfinite(jnp.vdot(x_new, x_new))
            ckpt = jnp.where((gd["health"] == HEALTH_OK) & fin, x_new, ckpt)
            out = out + (gd, ckpt)
        if flight is not None:
            out = out + (fs,)
        return out

    def outer_cond(s):
        ok = (s[4] > tol) & (s[1] < maxiter)
        if guards is not None:
            ok = ok & (s[5]["health"] == HEALTH_OK)
        return ok

    def outer_body(s):
        x, it, mon, switches = s[:4]
        gd = s[5] if guards is not None else None
        ckpt = s[6] if guards is not None else None
        fs = s[-1] if flight is not None else None
        return cycle(x, it, mon, switches, gd, ckpt, fs)

    mon0 = P.init(params, dtype=dtype, tag=init_tag)
    r0 = b - apply_a(x0, mon0.tag)
    relres0 = jnp.linalg.norm(r0) / bnorm
    state = (x0, jnp.int32(0), mon0, jnp.full((2,), -1, jnp.int32), relres0)
    if guards is not None:
        state = state + (guard_init(relres0), x0)
    if flight is not None:
        state = state + (OF.flight_init(flight, dtype),)
    outs = jax.lax.while_loop(outer_cond, outer_body, state)
    x, it, mon, switches, relres = outs[:5]
    gd = outs[5] if guards is not None else None
    ckpt = outs[6] if guards is not None else x
    x_fin = jnp.isfinite(jnp.vdot(x, x))
    conv = (relres <= tol) & x_fin
    health, trip = finalize_health(gd, conv, relres, x_finite=x_fin)
    res = GMRESResult(
        x=x,
        iters=it,
        relres=relres,
        tag=mon.tag,
        switch_iters=switches,
        converged=conv,
        health=health,
        trip_iter=trip,
        flight=outs[-1] if flight is not None else None,
    )
    if return_monitor:  # debug/test hook: expose the residual window
        return res, mon
    if return_ckpt:
        return res, ckpt
    return res


def solve_gmres(
    apply_a: Callable,
    b: jnp.ndarray,
    x0: jnp.ndarray | None = None,
    tol: float = 1e-6,
    restart: int = 30,
    maxiter: int = 15000,
    params: P.MonitorParams | None = None,
    final_correction: bool = False,
    precond=None,
    guards: GuardParams | None = DEFAULT_GUARDS,
    recover: bool = True,
    init_tag: int = 1,
    flight: OF.FlightParams | None = None,
) -> GMRESResult:
    """Restarted GMRES; ``apply_a(x, tag)`` and ``final_correction`` as in
    :func:`repro.solvers.cg.solve_cg`.

    ``precond`` (optional) right-preconditions the iteration: a
    preconditioner object from :mod:`repro.solvers.precond` or a callable
    ``apply_m(r, tag)``.  The preconditioner rides the monitor's tag
    schedule exactly like the operator (DESIGN.md §10).

    ``guards``/``recover``/``init_tag``: in-loop guardrails plus
    checkpoint-rollback tag-escalation recovery, as in
    :func:`repro.solvers.cg.solve_cg` (DESIGN.md §14).  GMRES checkpoints
    at restart-cycle granularity (x only changes at cycle ends).

    ``b``/``x0`` may be ``(n,)`` or ``(n, 1)``; the solution comes back in
    ``b``'s layout.
    """
    from repro.solvers.cg import _normalize_b_x0, _restore_shape

    b, x0, orig_shape = _normalize_b_x0(b, x0)
    if x0 is None:
        x0 = jnp.zeros_like(b)
    if params is None:
        params = P.MonitorParams.for_gmres()
    apply_m = None
    if precond is not None:
        apply_m = precond if callable(precond) else precond.apply
    tol_ = jnp.asarray(tol, b.dtype)

    def run(x_start, budget, tag):
        return _solve_gmres(apply_a, b, x_start, tol_, restart, budget,
                            params, init_tag=tag, apply_m=apply_m,
                            guards=guards, flight=flight, return_ckpt=True)

    with OT.span("solve.gmres", n=int(b.shape[0]), tol=float(tol),
                 restart=restart, init_tag=init_tag):
        res = run_with_recovery(run, x0, maxiter, init_tag=init_tag,
                                recover=recover and guards is not None)
        if not final_correction:
            return _restore_shape(res, orig_shape)
        from repro.solvers.cg import _finish_with_correction

        def apply3(v):
            return apply_a(v, jnp.int32(3))

        def resume(xr, budget):
            return run(xr, budget, 3)[0]

        return _restore_shape(
            _finish_with_correction(res, b, tol, maxiter, apply3, resume),
            orig_shape,
        )
