"""Fully-sharded stepped CG/PCG: the whole Krylov loop inside shard_map.

The production posture for the distributed operator (DESIGN.md §13): the
vector state (x, r, p, z) lives row-sharded on the devices for the WHOLE
solve -- per-iteration traffic is the tag-aware halo exchange plus three
scalar ``psum`` reductions (the CG dots), never a full-vector gather.
The residual monitor (``core.precision``) runs replicated from the
psum'd residual norm, so every shard steps the SAME tag at the same
iteration -- one ``MonitorParams`` schedule drives all shards.  The
per-device body is the single-chip loop itself (``cg.krylov_loop`` around
``fused_cg.cg_step``), with the shard's local matvec and psum'd dots.

Contracts (tests/test_distributed.py):

  * 1 shard, ``wire="exact"``: bit-identical to ``solve_cg``/``solve_pcg``
    on the unsharded ``GSECSR`` (same decode, same op order, psum over one
    device is the identity);
  * k shards, ``wire="exact"``: the SpMV blocks are bitwise equal and only
    the dot-product summation ORDER changes (psum of per-shard partials),
    so trajectories track single-device to ~machine precision;
  * ``wire="gse"``: tag-1/2 halo payloads are head(+tail1) segments --
    lossy on boundary entries only; the recursive residual still converges
    (the monitor sees a slightly stronger low-tag perturbation, which is
    exactly the regime the stepped controller is built for).

``solve_cg``/``solve_pcg`` dispatch here when handed a
``PartitionedGSECSR`` (through the shared entry ``cg._drive``); the
batched solvers ride ``make_sharded_operator`` instead.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import gse
from repro.core import precision as Prec
from repro.distributed.partition import PartitionedGSECSR
from repro.kernels.dist_spmv import (
    AXIS,
    _blk,
    block_arrays,
    local_matvec,
    shard_mesh,
    switched_matvec,
)
from repro.obs import flight as OF
from repro.obs import trace as OT
from repro.robustness.guards import DEFAULT_GUARDS, GuardParams
from repro.solvers.cg import CGResult, _drive, _loop_result, krylov_loop

__all__ = ["solve_cg_sharded", "solve_pcg_sharded"]


def _pdot(u, v):
    """Distributed dot: per-shard partial + psum (the ONE place sharded
    trajectories differ from single-device -- summation order), under
    the ``krylov/dot`` scope."""
    with OT.scope(OT.KRYLOV, OT.DOT):
        return jax.lax.psum(jnp.vdot(u, v), AXIS)


def _pad_to(x, n_padded):
    pad = n_padded - x.shape[0]
    return jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1)) if pad else x


def _diag_apply_at(m_parts, ei_bit_m, frac_bits_m):
    """Static-tag diagonal-preconditioner apply on this shard's slice of
    the packed ``M^{-1}`` diagonal -- elementwise, so the sliced decode is
    bitwise the slice of the full-vector decode (``DiagGSEPrecond``)."""
    m_head, m_tail1, m_tail2, m_table = m_parts

    def apply_at(r, tag: int):
        d = gse._decode_jnp(m_table, m_head, m_tail1, m_tail2, ei_bit_m,
                            frac_bits_m, tag, jnp.float64)
        return d * r.astype(jnp.float64)

    return apply_at


def _sharded_loop_fn(part: PartitionedGSECSR, kind: str, wire: str,
                     maxiter: int, params, init_tag: int,
                     precond_meta=None, guards=None, flight=None):
    """Build (and memoize on the partition) the jitted shard_map solver.

    The per-device body is ``cg.krylov_loop`` -- the single-chip loop,
    its step ``fused_cg.cg_step`` -- over the shard's ``local_matvec``
    (local block + halo, at the step's static tag) with the dots
    ``psum``'d.  The guard state (DESIGN.md §14) and the flight ring run
    on the psum'd replicated scalars -- every shard latches the SAME
    health code at the same iteration and writes the same ring -- while
    the last-finite checkpoint stays row-sharded alongside x.
    """
    key = ("_sharded_solve", kind, wire, maxiter, params, init_tag,
           precond_meta, guards, flight)
    fn = part.__dict__.get(key)
    if fn is not None:
        return fn
    mesh = shard_mesh(part)
    rows, ei, k = part.rows_per_shard, part.ei_bit, int(part.table.size)

    def run(arrays, table, m_head, m_tail1, m_tail2, m_table, b, x0, tol,
            bnorm):
        blk = _blk(arrays, table)

        def matvec(v, tag):
            return local_matvec(blk, v, tag=tag, wire=wire, k=k, rows=rows,
                                ei_bit=ei)

        def apply0(v, tag):
            # The start's traced-tag apply sums its rows after the switch
            # (``switched_matvec`` says why).
            return switched_matvec(blk, v, tag, wire=wire, k=k, rows=rows,
                                   ei_bit=ei)

        precond = (None if kind == "cg" else _diag_apply_at(
            (m_head, m_tail1, m_tail2, m_table), *precond_meta))
        out = krylov_loop(matvec, precond, _pdot, b, x0, tol, maxiter,
                          params, bnorm, init_tag=init_tag, guards=guards,
                          flight=flight, apply0=apply0)
        res, ckpt = _loop_result(out, bnorm, tol)
        outs = tuple(res[:8]) + (ckpt,)
        return outs + (res.flight,) if flight is not None else outs

    sharded = P(AXIS)
    out_specs = (sharded, P(), P(), P(), P(), P(), P(), P(), sharded)
    if flight is not None:
        # The flight ring is replicated: every recorded column derives
        # from psum'd scalars or the replicated monitor state.
        out_specs = out_specs + (P(),)
    fn = jax.jit(jax.shard_map(
        run, mesh=mesh,
        in_specs=(sharded, P()) + (sharded,) * 3 + (P(),)
        + (sharded, sharded, P(), P()),
        out_specs=out_specs,
        check_vma=False,
    ))
    part.__dict__[key] = fn
    return fn


def _empty_diag(part):
    z = jnp.zeros((part.n_padded,), jnp.uint16)
    return z, z, jnp.zeros((part.n_padded,), jnp.uint32), part.table


def _run_sharded(part, kind, b, x0, tol, maxiter, params, init_tag, wire,
                 precond=None, guards=None, flight=None, return_ckpt=False):
    n = part.shape[0]
    if precond is None:
        m_head, m_tail1, m_tail2, m_table = _empty_diag(part)
        precond_meta = None
    else:
        pk = precond.packed
        if pk.frac_bits != 52 or pk.tail2.size != pk.head.size:
            # Mirror gse.decode_jnp's guard: an f32-source pack (pack32,
            # no tail2) supports tags 1/2 only -- the single-chip loop
            # raises at trace time, and the sharded tag-3 branch
            # would otherwise decode garbage silently.
            raise ValueError(
                "sharded PCG needs an f64-source packed diagonal "
                "(head+tail1+tail2, tags 1-3); f32-source packs support "
                "tags 1 and 2 only"
            )
        m_head = _pad_to(pk.head, part.n_padded)
        m_tail1 = _pad_to(pk.tail1, part.n_padded)
        m_tail2 = _pad_to(pk.tail2, part.n_padded)
        m_table = pk.table
        precond_meta = (pk.ei_bit, pk.frac_bits)
    fn = _sharded_loop_fn(part, kind, wire, maxiter, params, init_tag,
                          precond_meta, guards, flight)
    bnorm = jnp.linalg.norm(b)           # computed on the FULL vector so
    bnorm = jnp.where(bnorm == 0, 1.0, bnorm)  # it matches single-device
    outs = fn(
        block_arrays(part), part.table,
        m_head, m_tail1, m_tail2, m_table,
        _pad_to(b, part.n_padded), _pad_to(x0, part.n_padded),
        jnp.asarray(tol, b.dtype), bnorm,
    )
    x, it, rel, tag, sw, conv, health, trip, ckpt = outs[:9]
    fl = outs[9] if flight is not None else None
    res = CGResult(x=x[:n], iters=it, relres=rel, tag=tag,
                   switch_iters=sw, converged=conv, health=health,
                   trip_iter=trip, flight=fl)
    return (res, ckpt[:n]) if return_ckpt else res


def solve_cg_sharded(
    part: PartitionedGSECSR,
    b: jnp.ndarray,
    x0: jnp.ndarray | None = None,
    tol: float = 1e-6,
    maxiter: int = 5000,
    params: Prec.MonitorParams | None = None,
    wire: str = "exact",
    final_correction: bool = False,
    guards: GuardParams | None = DEFAULT_GUARDS,
    recover: bool = True,
    init_tag: int = 1,
    flight: OF.FlightParams | None = None,
) -> CGResult:
    """Distributed stepped CG over a row-sharded operator (DESIGN.md §13).

    The whole loop runs inside one ``shard_map``: vectors stay sharded,
    each iteration moves only the tag-aware halo payload plus three psum
    scalars.  ``wire`` selects the halo wire format (``"exact"``: f64 at
    every tag -- the parity-contract mode; ``"gse"``: tag-1/2 halos ship
    head(+tail1) segments, shrinking wire bytes with the SAME monitor
    schedule that shrinks HBM bytes).

    ``guards``/``recover``/``init_tag`` mirror :func:`repro.solvers.cg.
    solve_cg` (DESIGN.md §14): the guard runs on the psum'd replicated
    scalars inside the shard_map, the checkpoint stays row-sharded, and
    escalation restarts the whole sharded loop from the gathered
    checkpoint at the promoted tag.
    """
    return _drive(part, b, None, x0=x0, tol=tol, maxiter=maxiter,
                  params=params, final_correction=final_correction,
                  wire=wire, guards=guards, recover=recover,
                  init_tag=init_tag, flight=flight)


def solve_pcg_sharded(
    part: PartitionedGSECSR,
    b: jnp.ndarray,
    precond,
    x0: jnp.ndarray | None = None,
    tol: float = 1e-6,
    maxiter: int = 5000,
    params: Prec.MonitorParams | None = None,
    wire: str = "exact",
    final_correction: bool = False,
    guards: GuardParams | None = DEFAULT_GUARDS,
    recover: bool = True,
    init_tag: int = 1,
    flight: OF.FlightParams | None = None,
) -> CGResult:
    """Distributed stepped PCG.  Diagonal GSE preconditioners (Jacobi /
    SPAI-0) shard with the operator -- each device decodes its slice of
    the packed ``M^{-1}`` diagonal at the step's tag, inside the same
    branch as the operator decode.  Other preconditioners run the
    single-chip loop over ``make_sharded_operator`` (full-vector apply).
    ``guards``/``recover``/``init_tag``: see :func:`solve_cg_sharded`.
    """
    return _drive(part, b, precond, x0=x0, tol=tol, maxiter=maxiter,
                  params=params, final_correction=final_correction,
                  wire=wire, guards=guards, recover=recover,
                  init_tag=init_tag, flight=flight)
