"""Fused stepped-CG iteration over a GSE-SEM CSR operand (DESIGN.md §4).

One CG iteration is a SpMV plus five vector ops (two dots, two axpys, one
xpby).  Run unfused, each op is its own pass over the vectors and the SpMV
re-decodes the GSE-SEM values; on a bandwidth-bound machine those extra
passes (and kernel launches) erase part of the format's byte savings.

``fused_cg_step`` folds the whole iteration around a single decoded-value
pass:

  * the GSE-SEM values are decoded ONCE per iteration, at the precision the
    monitor's current tag selects (``lax.switch`` over three tag-specialized
    branches, so the tag-1/-2 branches never touch the tail segments);
  * ``p . Ap`` is formed in the same sweep that produces ``Ap``;
  * the x/r axpys, the new residual norm ``r'.r'``, and the search-direction
    update ride the same fused jaxpr -- one kernel program per iteration
    instead of six.

The arithmetic is EXACTLY the sequence of the unfused ``solve_cg`` body
(same ops, same order, same ``acc_dtype``), so fused and unfused runs
produce bit-identical iterate trajectories -- asserted by
tests/test_spmv_pipeline.py.

Each stage runs under its device scope (``obs.trace.SCOPES``): the SpMV
under ``spmv``, the dots and axpys under ``krylov/dot`` and
``krylov/update``, the preconditioner under ``precond``.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.obs import trace as OT
from repro.sparse.spmv import spmv_operand

__all__ = ["fused_cg_step", "fused_cg_step_g", "fused_pcg_step",
           "fused_pcg_step_g", "gse_matvec", "kdot"]


def kdot(u, v):
    """``vdot(u, v)`` under the ``krylov/dot`` scope."""
    with OT.scope(OT.KRYLOV, OT.DOT):
        return jnp.vdot(u, v)


def _step_at_tag(a, x, r, p, rs, *, tag: int, acc_dtype, with_denom=False):
    """One fused CG iteration at a fixed precision tag.

    ``a`` is a ``GSECSR`` or a SELL-C-σ packed ``GSESellC`` --
    ``decode_operand`` recovers the same values either way, so
    the layouts share one bit-identical iteration body (DESIGN.md §12).
    Single decoded-value pass: ``val`` is materialized once and feeds both
    the matvec and (via ``ap``) the direction dot; everything downstream of
    the decode fuses into the same program under jit.
    """
    ap = spmv_operand(a, p, tag, acc_dtype)
    denom = kdot(p, ap)                         # same sweep as the matvec
    with OT.scope(OT.KRYLOV, OT.UPDATE):
        alpha = rs / jnp.where(denom == 0, 1.0, denom)
        x2 = x + alpha * p
        r2 = r - alpha * ap
    rs2 = kdot(r2, r2)                          # residual norm, same sweep
    with OT.scope(OT.KRYLOV, OT.UPDATE):
        beta = rs2 / jnp.where(rs == 0, 1.0, rs)
        p2 = r2 + beta * p
    if with_denom:
        return x2, r2, p2, rs2, denom
    return x2, r2, p2, rs2


def fused_cg_step(a, x, r, p, rs, tag, acc_dtype=jnp.float64):
    """Fused CG iteration with traced precision ``tag`` in {1, 2, 3}.

    ``a`` is a ``GSECSR`` or ``GSESellC`` operand.  Returns
    ``(x', r', p', rs')`` where ``rs' = r'.r'`` is the squared
    recursive residual norm (the monitor records ``sqrt(rs')/||b||``).
    """
    return jax.lax.switch(
        jnp.clip(tag - 1, 0, 2),
        [
            partial(_step_at_tag, a, tag=1, acc_dtype=acc_dtype),
            partial(_step_at_tag, a, tag=2, acc_dtype=acc_dtype),
            partial(_step_at_tag, a, tag=3, acc_dtype=acc_dtype),
        ],
        x, r, p, rs,
    )


def fused_cg_step_g(a, x, r, p, rs, tag, acc_dtype=jnp.float64):
    """``fused_cg_step`` that ALSO returns the curvature ``denom = p.Ap``.

    Same branch bodies, same op order -- the extra output is the scalar the
    fused sweep already computed, exposed so the robustness guards
    (DESIGN.md §14) can check the breakdown condition ``p.Ap <= 0``
    without a second operator application (which would break the
    fused/unfused bit-identity contract).
    """
    return jax.lax.switch(
        jnp.clip(tag - 1, 0, 2),
        [
            partial(_step_at_tag, a, tag=1, acc_dtype=acc_dtype,
                    with_denom=True),
            partial(_step_at_tag, a, tag=2, acc_dtype=acc_dtype,
                    with_denom=True),
            partial(_step_at_tag, a, tag=3, acc_dtype=acc_dtype,
                    with_denom=True),
        ],
        x, r, p, rs,
    )


def _pcg_step_at_tag(a, m, x, r, p, rz, *, tag: int, acc_dtype,
                     with_denom=False):
    """One fused preconditioned-CG iteration at a fixed precision tag.

    The operator decode AND the preconditioner apply run at the same
    static ``tag`` inside one branch, so both streams follow the monitor's
    schedule and neither low-tag branch references its tail segments
    (DESIGN.md §10).  ``a`` may be a ``GSECSR`` or ``GSESellC`` (shared
    ``decode_operand``).  The arithmetic is the exact op sequence of the
    unfused ``_solve_pcg`` body -- bit-identical trajectories.
    """
    ap = spmv_operand(a, p, tag, acc_dtype)
    denom = kdot(p, ap)
    with OT.scope(OT.KRYLOV, OT.UPDATE):
        alpha = rz / jnp.where(denom == 0, 1.0, denom)
        x2 = x + alpha * p
        r2 = r - alpha * ap
    with OT.scope(OT.PRECOND):
        z2 = m.apply_at(r2, tag, acc_dtype)    # same tag as the SpMV
    rz2 = kdot(r2, z2)
    rr2 = kdot(r2, r2)                         # monitor sees sqrt(rr)/||b||
    with OT.scope(OT.KRYLOV, OT.UPDATE):
        beta = rz2 / jnp.where(rz == 0, 1.0, rz)
        p2 = z2 + beta * p
    if with_denom:
        return x2, r2, p2, rz2, rr2, denom
    return x2, r2, p2, rz2, rr2


def fused_pcg_step(a, m, x, r, p, rz, tag, acc_dtype=jnp.float64):
    """Fused PCG iteration with traced precision ``tag`` in {1, 2, 3}.

    ``m`` is a preconditioner from ``solvers.precond`` (anything exposing
    ``apply_at(r, tag, acc_dtype)`` with a static tag).  Returns
    ``(x', r', p', rz', rr')`` where ``rz' = r'.z'`` drives the recurrence
    and ``rr' = r'.r'`` feeds the residual monitor.
    """
    return jax.lax.switch(
        jnp.clip(tag - 1, 0, 2),
        [
            partial(_pcg_step_at_tag, a, m, tag=1, acc_dtype=acc_dtype),
            partial(_pcg_step_at_tag, a, m, tag=2, acc_dtype=acc_dtype),
            partial(_pcg_step_at_tag, a, m, tag=3, acc_dtype=acc_dtype),
        ],
        x, r, p, rz,
    )


def fused_pcg_step_g(a, m, x, r, p, rz, tag, acc_dtype=jnp.float64):
    """``fused_pcg_step`` that also returns ``denom = p.Ap`` (the guards'
    breakdown predicate) -- same branch bodies, same op order."""
    return jax.lax.switch(
        jnp.clip(tag - 1, 0, 2),
        [
            partial(_pcg_step_at_tag, a, m, tag=1, acc_dtype=acc_dtype,
                    with_denom=True),
            partial(_pcg_step_at_tag, a, m, tag=2, acc_dtype=acc_dtype,
                    with_denom=True),
            partial(_pcg_step_at_tag, a, m, tag=3, acc_dtype=acc_dtype,
                    with_denom=True),
        ],
        x, r, p, rz,
    )


def gse_matvec(a, x, tag, acc_dtype=jnp.float64):
    """Tag-dispatched ``A @ x`` over a ``GSECSR`` or ``GSESellC`` operand
    (initial residual / checks); ``spmv_gse`` dispatches on the layout."""
    from repro.sparse.spmv import spmv_gse

    return jax.lax.switch(
        jnp.clip(tag - 1, 0, 2),
        [
            lambda v: spmv_gse(a, v, tag=1, acc_dtype=acc_dtype),
            lambda v: spmv_gse(a, v, tag=2, acc_dtype=acc_dtype),
            lambda v: spmv_gse(a, v, tag=3, acc_dtype=acc_dtype),
        ],
        x,
    )
