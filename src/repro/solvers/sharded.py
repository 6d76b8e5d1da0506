"""Fully-sharded stepped CG/PCG: the whole Krylov loop inside shard_map.

The production posture for the distributed operator (DESIGN.md §13): the
vector state (x, r, p, z) lives row-sharded on the devices for the WHOLE
solve -- per-iteration traffic is the tag-aware halo exchange plus three
scalar ``psum`` reductions (the CG dots), never a full-vector gather.
The residual monitor (``core.precision``) runs replicated from the
psum'd residual norm, so every shard steps the SAME tag at the same
iteration -- one ``MonitorParams`` schedule drives all shards, exactly as
it drives the single-device fused path.

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

``solve_cg``/``solve_pcg``/``solve_cg_batched``/``solve_pcg_batched``
dispatch here when handed a ``PartitionedGSECSR``.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import gse
from repro.core import precision as Prec
from repro.distributed.partition import PartitionedGSECSR
from repro.kernels.dist_spmv import (
    AXIS,
    _blk,
    local_matvec,
    make_sharded_operator,
    shard_mesh,
    switched_matvec,
)
from repro.obs import flight as OF
from repro.obs import trace as OT
from repro.robustness.guards import (
    DEFAULT_GUARDS,
    GuardParams,
    finalize_health,
    run_with_recovery,
)
from repro.solvers.cg import (
    CGResult,
    _finish_with_correction,
    _guarded_body,
    _guarded_cond,
    _guarded_init,
    _normalize_b_x0,
    _record_switch,
    _residual,
    _restore_shape,
)

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


def _matvec_dispatch(blk, wire, k, rows, ei):
    """Traced-tag distributed matvec for use inside the sharded loop --
    same ``lax.switch`` discipline as ``fused_cg_step``, with the halo
    exchange and decode both inside each static-tag branch
    (``switched_matvec``)."""
    def matvec(v, tag):
        return switched_matvec(blk, v, tag, wire=wire, k=k, rows=rows,
                               ei_bit=ei)

    return matvec


def _diag_apply_dispatch(m_parts, ei_bit_m, frac_bits_m):
    """Traced-tag diagonal-preconditioner apply on this shard's slice of
    the packed ``M^{-1}`` diagonal -- elementwise, so the sliced decode is
    bitwise the slice of the full-vector decode (``DiagGSEPrecond``)."""
    m_head, m_tail1, m_tail2, m_table = m_parts

    def apply_at(r, tag: int, acc_dtype=jnp.float64):
        d = gse._decode_jnp(m_table, m_head, m_tail1, m_tail2, ei_bit_m,
                            frac_bits_m, tag, acc_dtype)
        return d * r.astype(acc_dtype)

    def apply(r, tag):
        return jax.lax.switch(
            jnp.clip(tag - 1, 0, 2),
            [partial(apply_at, tag=t) for t in (1, 2, 3)],
            r,
        )

    return apply, apply_at


def _sharded_loop_fn(part: PartitionedGSECSR, kind: str, wire: str,
                     maxiter: int, params, init_tag: int,
                     precond_meta=None, guards=None, flight=None):
    """Build (and memoize on the partition) the jitted shard_map solver.

    The per-device body mirrors ``_solve_cg_fused``/``_solve_pcg_fused``
    op for op; only the dots go through ``psum`` and the operator is the
    shard's local block + halo.  The guard state (DESIGN.md §14) runs on
    the psum'd replicated scalars -- every shard latches the SAME health
    code at the same iteration -- while the last-finite checkpoint stays
    row-sharded alongside x.
    """
    key = ("_sharded_solve", kind, wire, maxiter, params, init_tag,
           precond_meta, guards, flight)
    fn = part.__dict__.get(key)
    if fn is not None:
        return fn
    mesh = shard_mesh(part)
    rows, ei, k = part.rows_per_shard, part.ei_bit, int(part.table.size)

    def run(colpak, head, tail1, tail2, row_ids, bnd_idx, halo_idx, table,
            m_head, m_tail1, m_tail2, m_table, b, x0, tol, bnorm):
        blk = _blk(colpak, head, tail1, tail2, row_ids, bnd_idx, halo_idx,
                   table)
        matvec = _matvec_dispatch(blk, wire, k, rows, ei)
        mon = Prec.init(params, dtype=b.dtype, tag=init_tag)

        def relres(rs):
            return jnp.sqrt(jnp.abs(rs)) / bnorm

        if kind == "cg":
            r0 = _residual(b, matvec(x0, mon.tag))
            state = dict(x=x0, r=r0, p=r0, rs=_pdot(r0, r0),
                         it=jnp.int32(0), mon=mon,
                         switches=jnp.full((2,), -1, jnp.int32))
            state = _guarded_init(state, relres(state["rs"]), guards)
            if flight is not None:
                state["fl"] = OF.flight_init(flight, b.dtype)

            def body(s):
                # EXACTLY fused_cg_step's op order, dots psum'd.
                tag = s["mon"].tag
                ap = matvec(s["p"], tag)
                denom = _pdot(s["p"], ap)
                with OT.scope(OT.KRYLOV, OT.UPDATE):
                    alpha = s["rs"] / jnp.where(denom == 0, 1.0, denom)
                    x = s["x"] + alpha * s["p"]
                    r = s["r"] - alpha * ap
                rs2 = _pdot(r, r)
                with OT.scope(OT.MONITOR):
                    mon1 = Prec.record(s["mon"], relres(rs2))
                    mon2 = Prec.update_tag(mon1, params)
                    sw = _record_switch(s["switches"], mon1, mon2, s["it"])
                with OT.scope(OT.KRYLOV, OT.UPDATE):
                    beta = rs2 / jnp.where(s["rs"] == 0, 1.0, s["rs"])
                    p = r + beta * s["p"]
                out = dict(x=x, r=r, p=p, rs=rs2, it=s["it"] + 1,
                           mon=mon2, switches=sw)
                with OT.scope(OT.MONITOR):
                    out = _guarded_body(s, out, relres(rs2), guards,
                                        denom=denom)
                    if flight is not None:
                        # The recorded scalars are all psum'd/replicated,
                        # so every shard writes the SAME ring (out_spec
                        # P()).
                        g = out.get("g")
                        out["fl"] = OF.flight_record(
                            s["fl"], it=s["it"], relres=relres(rs2), tag=tag,
                            health=g["health"] if g is not None else None,
                            a0=alpha, a1=beta, a2=denom)
                return out

            def cond(s):
                return _guarded_cond(
                    s, (relres(s["rs"]) > tol) & (s["it"] < maxiter), guards
                )

            out = jax.lax.while_loop(cond, body, state)
            final_rel = relres(out["rs"])
        else:  # pcg
            m_apply, m_apply_at = _diag_apply_dispatch(
                (m_head, m_tail1, m_tail2, m_table), *precond_meta
            )
            r0 = _residual(b, matvec(x0, mon.tag))
            with OT.scope(OT.PRECOND):
                z0 = m_apply(r0, mon.tag)
            state = dict(x=x0, r=r0, p=z0, rz=_pdot(r0, z0),
                         rr=_pdot(r0, r0), it=jnp.int32(0), mon=mon,
                         switches=jnp.full((2,), -1, jnp.int32))
            state = _guarded_init(state, relres(state["rr"]), guards)
            if flight is not None:
                state["fl"] = OF.flight_init(flight, b.dtype)

            def step_at(s, tag: int):
                # EXACTLY _pcg_step_at_tag's op order, dots psum'd; the
                # operator decode, halo exchange and preconditioner apply
                # all ride the same static-tag branch.
                ap = local_matvec(blk, s["p"], tag=tag, wire=wire, k=k,
                                  rows=rows, ei_bit=ei)
                denom = _pdot(s["p"], ap)
                with OT.scope(OT.KRYLOV, OT.UPDATE):
                    alpha = s["rz"] / jnp.where(denom == 0, 1.0, denom)
                    x = s["x"] + alpha * s["p"]
                    r = s["r"] - alpha * ap
                with OT.scope(OT.PRECOND):
                    z = m_apply_at(r, tag)
                rz2 = _pdot(r, z)
                rr2 = _pdot(r, r)
                with OT.scope(OT.KRYLOV, OT.UPDATE):
                    beta = rz2 / jnp.where(s["rz"] == 0, 1.0, s["rz"])
                    p = z + beta * s["p"]
                stepped = dict(x=x, r=r, p=p, rz=rz2, rr=rr2)
                if guards is not None or flight is not None:
                    stepped["denom"] = denom
                return stepped

            def body(s):
                krylov = {k_: s[k_] for k_ in ("x", "r", "p", "rz", "rr")}
                stepped = jax.lax.switch(
                    jnp.clip(s["mon"].tag - 1, 0, 2),
                    [partial(step_at, tag=t) for t in (1, 2, 3)],
                    krylov,
                )
                denom = stepped.pop("denom", None)
                with OT.scope(OT.MONITOR):
                    mon1 = Prec.record(s["mon"], relres(stepped["rr"]))
                    mon2 = Prec.update_tag(mon1, params)
                    sw = _record_switch(s["switches"], mon1, mon2, s["it"])
                    rz2 = stepped["rz"]
                    stepped.update(it=s["it"] + 1, mon=mon2, switches=sw)
                    out = _guarded_body(s, stepped, relres(stepped["rr"]),
                                        guards, denom=denom,
                                        breakdown=rz2 < 0, finite_aux=(rz2,))
                    if flight is not None:
                        # Observation-only recompute (bit-identity
                        # contract).
                        alpha = s["rz"] / jnp.where(denom == 0, 1.0, denom)
                        beta = rz2 / jnp.where(s["rz"] == 0, 1.0, s["rz"])
                        g = out.get("g")
                        out["fl"] = OF.flight_record(
                            s["fl"], it=s["it"],
                            relres=relres(stepped["rr"]), tag=s["mon"].tag,
                            health=g["health"] if g is not None else None,
                            a0=alpha, a1=beta, a2=denom)
                return out

            def cond(s):
                return _guarded_cond(
                    s, (relres(s["rr"]) > tol) & (s["it"] < maxiter), guards
                )

            out = jax.lax.while_loop(cond, body, state)
            final_rel = relres(out["rr"])

        conv = final_rel <= tol
        g = out.get("g") if guards is not None else None
        health, trip = finalize_health(g, conv, final_rel)
        ckpt = out["ckpt"] if guards is not None else out["x"]
        outs = (out["x"], out["it"], final_rel, out["mon"].tag,
                out["switches"], conv, health, trip, ckpt)
        if flight is not None:
            outs = outs + (out["fl"],)
        return outs

    sharded = P(AXIS)
    out_specs = (sharded, P(), P(), P(), P(), P(), P(), P(), sharded)
    if flight is not None:
        # The flight ring is replicated: every recorded column derives
        # from psum'd scalars or the replicated monitor state.
        out_specs = out_specs + (P(),)
    fn = jax.jit(jax.shard_map(
        run, mesh=mesh,
        in_specs=(sharded,) * 7 + (P(),) + (sharded,) * 3 + (P(),)
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
            # no tail2) supports tags 1/2 only -- the single-device fused
            # path raises at trace time, and the sharded tag-3 branch
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
        part.colpak, part.head, part.tail1, part.tail2, part.row_ids,
        part.bnd_idx, part.halo_idx, part.table,
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
    b, x0, orig_shape = _normalize_b_x0(b, x0)
    if x0 is None:
        x0 = jnp.zeros_like(b)
    if params is None:
        params = Prec.MonitorParams.for_cg()

    def run(x_start, budget, tag):
        return _run_sharded(part, "cg", b, x_start, tol, budget, params,
                            tag, wire, guards=guards, flight=flight,
                            return_ckpt=True)

    with OT.span("solve.cg_sharded", n=int(b.shape[0]), tol=float(tol),
                 wire=wire, shards=int(part.n_shards)):
        res = run_with_recovery(run, x0, maxiter, init_tag=init_tag,
                                recover=recover and guards is not None)
        if not final_correction:
            return _restore_shape(res, orig_shape)
        op = make_sharded_operator(part, wire)

        def apply3(v):
            return op(v, jnp.int32(3))

        def resume(xr, budget):
            return run(xr, budget, 3)[0]

        return _restore_shape(
            _finish_with_correction(res, b, tol, maxiter, apply3, resume),
            orig_shape,
        )


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
    the packed ``M^{-1}`` diagonal at the monitor's tag, inside the same
    branch as the operator decode (the sharded twin of
    ``fused_pcg_step``).  Non-diagonal preconditioners fall back to the
    generic path over ``make_sharded_operator`` (full-vector apply).
    ``guards``/``recover``/``init_tag``: see :func:`solve_cg_sharded`.
    """
    from repro.solvers.precond import DiagGSEPrecond

    b, x0, orig_shape = _normalize_b_x0(b, x0)
    if x0 is None:
        x0 = jnp.zeros_like(b)
    if params is None:
        params = Prec.MonitorParams.for_cg()
    if not isinstance(precond, DiagGSEPrecond):
        from repro.solvers.cg import solve_pcg

        op = make_sharded_operator(part, wire)
        return solve_pcg(op, b.reshape(orig_shape), precond, x0=x0, tol=tol,
                         maxiter=maxiter, params=params,
                         final_correction=final_correction, guards=guards,
                         recover=recover, init_tag=init_tag, flight=flight)

    def run(x_start, budget, tag):
        return _run_sharded(part, "pcg", b, x_start, tol, budget, params,
                            tag, wire, precond=precond, guards=guards,
                            flight=flight, return_ckpt=True)

    with OT.span("solve.pcg_sharded", n=int(b.shape[0]), tol=float(tol),
                 wire=wire, shards=int(part.n_shards)):
        res = run_with_recovery(run, x0, maxiter, init_tag=init_tag,
                                recover=recover and guards is not None)
        if not final_correction:
            return _restore_shape(res, orig_shape)
        op = make_sharded_operator(part, wire)

        def apply3(v):
            return op(v, jnp.int32(3))

        def resume(xr, budget):
            return run(xr, budget, 3)[0]

        return _restore_shape(
            _finish_with_correction(res, b, tol, maxiter, apply3, resume),
            orig_shape,
        )
