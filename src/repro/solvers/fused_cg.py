"""The stepped CG/PCG iteration, written once (DESIGN.md §4).

One iteration is a SpMV, for PCG a preconditioner apply, and five or six
vector ops (the dots, two axpys, one xpby).  ``cg_step`` is that
iteration and the only copy of it: the single-chip loop
(``solvers.cg``), the sharded body inside ``shard_map``
(``solvers.sharded``) and batched's per-column step
(``solvers.batched``) all call it, each with its own operator apply and
dot.

One tag switch, around the whole step: ``lax.switch`` over tags 1-3
picks a branch in which the operator, the halo exchange of a sharded
operator and the preconditioner all run at the same STATIC tag, so a
low-tag branch never references the tail segments, and ``p . Ap``, the
axpys, the residual norm and the direction update ride the same branch
as the SpMV.  A callable operator gets its tag inside the branch.

CG is PCG without a preconditioner: ``z = r`` and ``r.z = r.r``, so CG
takes no second dot.  The step always returns ``p . Ap``, the scalar the
robustness guards (DESIGN.md §14) and the flight recorder read.

Each stage runs under its device scope (``obs.trace.SCOPES``): the SpMV
under ``spmv``, the dots and axpys under ``krylov/dot`` and
``krylov/update``, the preconditioner under ``precond``.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.obs import trace as OT
from repro.sparse.csr import GSECSR, GSESellC
from repro.sparse.spmv import spmv_operand

__all__ = ["cg_step", "kdot", "start", "static_apply", "switched"]


def kdot(u, v):
    """``vdot(u, v)`` under the ``krylov/dot`` scope."""
    with OT.scope(OT.KRYLOV, OT.DOT):
        return jnp.vdot(u, v)


def switched(fn, tag, *args):
    """``fn(*args, t)`` at precision ``tag`` in {1, 2, 3}: a traced tag
    takes a ``lax.switch`` over the three static tags; a Python int is
    already static, so ``fn`` runs at it directly."""
    if isinstance(tag, int):
        return fn(*args, tag)
    return jax.lax.switch(
        jnp.clip(tag - 1, 0, 2),
        [lambda *a, t=t: fn(*a, t) for t in (1, 2, 3)],
        *args,
    )


def static_apply(op, acc_dtype=jnp.float64):
    """``apply(v, tag)`` at a static tag of a ``GSECSR``/``GSESellC``
    operand (``spmv_operand``), of a preconditioner exposing
    ``apply_at`` (``solvers.precond``, ``solvers.multigrid``), or of a
    callable ``apply(v, tag)``, which is returned as it is."""
    if isinstance(op, (GSECSR, GSESellC)):
        return lambda v, tag: spmv_operand(op, v, tag, acc_dtype)
    if hasattr(op, "apply_at"):
        return lambda v, tag: op.apply_at(v, tag, acc_dtype)
    return op


def _step_at_tag(matvec, precond, dot, x, r, p, rz, tag: int):
    ap = matvec(p, tag)
    denom = dot(p, ap)
    with OT.scope(OT.KRYLOV, OT.UPDATE):
        alpha = rz / jnp.where(denom == 0, 1.0, denom)
        x2 = x + alpha * p
        r2 = r - alpha * ap
    if precond is None:
        z2 = r2
        rz2 = rr2 = dot(r2, r2)
    else:
        with OT.scope(OT.PRECOND):
            z2 = precond(r2, tag)              # same tag as the SpMV
        rz2 = dot(r2, z2)
        rr2 = dot(r2, r2)                      # monitor sees sqrt(rr)/||b||
    with OT.scope(OT.KRYLOV, OT.UPDATE):
        beta = rz2 / jnp.where(rz == 0, 1.0, rz)
        p2 = z2 + beta * p
    return x2, r2, p2, rz2, rr2, denom


def cg_step(matvec, precond, dot, x, r, p, rz, tag):
    """One stepped CG (``precond=None``) or PCG iteration at ``tag``.

    ``matvec(v, tag)`` and ``precond(r, tag)`` are applied at a static
    tag (``static_apply``); ``dot`` is ``kdot`` on one chip and the
    psum'd dot inside ``shard_map``.  ``rz`` is the recurrence's scalar
    ``r.z`` (``r.r`` for CG).  Returns ``(x', r', p', rz', rr', p.Ap)``,
    ``rr' = r'.r'`` being what the residual monitor reads; for CG
    ``rz'`` IS ``rr'``.
    """
    return switched(partial(_step_at_tag, matvec, precond, dot), tag,
                    x, r, p, rz)


def start(matvec, precond, dot, b, x0, tag, apply0=None):
    """The loop's first residual and direction at a traced ``tag``:
    ``(r0, p0, rz0, rr0)`` with ``p0 = z0 = M^{-1} r0`` (``r0`` for CG)
    and ``rz0 = r0.z0`` (``rr0`` for CG).  ``apply0(v, tag)`` is the
    operator at a traced tag, by default ``matvec`` under ``switched``."""
    ax = (apply0(x0, tag) if apply0 is not None
          else switched(matvec, tag, x0))
    with OT.scope(OT.KRYLOV, OT.UPDATE):
        r0 = b - ax
    if precond is None:
        rr0 = dot(r0, r0)
        return r0, r0, rr0, rr0
    with OT.scope(OT.PRECOND):
        z0 = switched(precond, tag, r0)
    return r0, z0, dot(r0, z0), dot(r0, r0)
