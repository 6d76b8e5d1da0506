"""Adaptive per-group precision driver (PR 10, DESIGN.md §18).

The stepped monitor (paper Alg. 3) promotes the WHOLE operator when
convergence stalls; this driver plans and maintains a per-group map so
only the groups that actually limit the attainable residual stream
extra tail segments.  On the congruence-rescaled generators the
convergence RATE is tag-independent -- the tags separate on the TRUE
residual floor ``||(A~_t - A) x*|| / ||b||``, whose per-group
contributions the planner bounds column-wise as
``sum_j (||E_t[:, j]|| |x*_j|)^2`` (a cancellation-free upper bound, so
a map planned under budget is SAFE even when signed cancellation makes
the realized floor lower).  The default schedule is explore-then-plan:

1. **Explore.**  Run plain CG/PCG at uniform tag 1 -- the cheapest
   stream there is, and (because the column model ignores cancellation)
   also the schedule whose realized floor no partial promotion is
   guaranteed to beat.  Every ``chunk`` iterations the host measures
   the TRUE tag-3 residual (billed), which doubles as the convergence
   test: the solve stops the moment the real residual fits ``tol``,
   recursive lag notwithstanding.
2. **Plan.**  The first time the recursive residual crosses
   ``beta * tol`` the iterate's magnitudes ARE a solution profile
   resolved to about its own error scale: trim below ``rel * rms``,
   feed ``core.precision.decode_error_scores``, and let
   ``plan_tagmap`` greedily promote the largest-contribution groups
   until the modeled floor fits ``theta * tol * ||b||``.  Restart from
   the current ``x`` at the planned map -- restart, not in-place
   switch: a per-group operand change invalidates the Krylov
   recurrence far harder than the paper's scalar tag step, and an
   in-place per-group switch can diverge outright.
3. **Finish + verify.**  Run the planned map to the true-residual stop.
   Every segment's recursive target is the quadrature complement
   ``tol * sqrt(1 - theta^2)`` of the planned floor budget -- deep
   enough that recurrence + floor still lands the true residual inside
   ``tol``, and no deeper, because grinding the recurrence below what
   the floor admits burns real iterations.  If the recurrence exhausts
   while the true residual still misses -- the model underpredicted --
   a reactive replan from the now-sharper iterate promotes the worst
   remaining contributors and restarts; with the column upper bound
   this terminates after at most a couple of short tail segments.

Byte accounting is blended and complete: every chunk bills the map it
ran under (``GSECSR.bytes_touched(tm)`` per iteration), each restart
bills its fresh initial SpMV, the optional probe bills its tag-1
iterations, and each true-residual check bills one tag-3 pass -- the
figure the ``BENCH_adaptive.json`` gate compares against the best
uniform schedule.
"""
from __future__ import annotations

from typing import NamedTuple

import jax.numpy as jnp
import numpy as np

from repro.core import precision as P
from repro.core.tagmap import GROUP_SIZE, TagMap, normalize_tags
from repro.obs import trace as OT
from repro.sparse.csr import GSECSR

__all__ = ["AdaptiveResult", "Promotion", "solve_adaptive"]


class Promotion(NamedTuple):
    """One promotion event in an adaptive solve (telemetry)."""

    it: int          # global iteration the promotion took effect at
    n_promoted: int  # groups whose tag stepped up
    min_tag: int     # new map's min tag
    max_tag: int     # new map's max tag
    crc32: int       # new map's cache-key token


class AdaptiveResult(NamedTuple):
    x: jnp.ndarray
    iters: int
    relres: float        # final recursive relative residual
    true_relres: float   # final TRUE tag-3 residual vs the UNMASKED operand
    converged: bool      # true_relres <= tol
    tagmap: TagMap       # final per-group map
    promotions: tuple    # Promotion events, in order (it=0: an upfront plan)
    spmv_bytes: int      # blended matrix-stream bytes, whole solve
    chunks: int          # host chunks executed
    probe_iters: int = 0  # tag-1 probe iterations billed into spmv_bytes

    @property
    def tag(self) -> int:
        """Max active tag -- rough ``CGResult.tag`` compatibility."""
        return self.tagmap.max_tag


def _init_map(tags0, m: int, group_size: int) -> TagMap:
    """Seed map from the caller's ``tags0`` (int floor or map)."""
    norm = normalize_tags(tags0, m)
    if isinstance(norm, int):
        return TagMap.for_rows(m, norm, group_size)
    return norm


def _inv_diag(a: GSECSR) -> np.ndarray:
    """Inverse absolute diagonal read host-side from the packed tag-3
    decode (no CSR needed -- ``a`` is all the driver gets)."""
    from repro.kernels import ref

    a = a.in_csr_order()
    rows = np.asarray(a.row_ids, np.int64)
    cols = (np.asarray(a.colpak, np.uint32)
            & np.uint32((1 << (32 - a.ei_bit)) - 1)).astype(np.int64)
    v3 = np.asarray(ref.decode_csr_ref(a.colpak, a.head, a.tail1, a.tail2,
                                       a.table, a.ei_bit, 3), np.float64)
    diag = np.zeros(int(a.shape[0]), np.float64)
    dmask = rows == cols
    diag[rows[dmask]] = np.abs(v3[dmask])
    return np.where(diag > 0,
                    1.0 / np.maximum(diag, np.finfo(np.float64).tiny), 1.0)


def _probe_jacobi(a: GSECSR):
    """Diagonal preconditioner for the optional tag-1 planning probe."""
    inv_j = jnp.asarray(_inv_diag(a))

    def apply_m(r, tag):
        return r * inv_j.astype(r.dtype)

    return apply_m


def _trim(xh: np.ndarray, rel: float) -> np.ndarray:
    """Zero the components of a solution-profile estimate that sit below
    its own error scale.  A CG iterate with true relative residual
    ``rel`` has error ``A^{-1} r`` spread across all components at the
    ``~rel * rms(x)`` scale, so components under ``rel * rms`` are
    indistinguishable from zero -- leaving that junk in inflates the
    floor scores of groups ``x*`` never touches, diluting exactly the
    concentration the planner exploits.  Conservative under-promotion
    instead; the reactive replan repairs it from a better iterate."""
    if not np.isfinite(rel) or xh.size == 0:
        return xh
    rms = float(np.linalg.norm(xh)) / np.sqrt(xh.size)
    return np.where(xh > min(rel, 1.0) * rms, xh, 0.0)


def _abs_neumann_profile(a: GSECSR, b: np.ndarray, hops: int = 1) -> np.ndarray:
    """Solution-magnitude seed profile: truncated absolute-value Neumann
    series ``sum_k (D^{-1}|offdiag|)^k D^{-1}|b|``, host-side from the
    packed tag-3 decode.  Zero solve cost; the zeroth term is exact for
    a diagonal operator, and each hop spreads mass along the actual
    coupling pattern (hub rows, point-load neighborhoods) -- unlike a
    signed Jacobi sweep it cannot oscillate or cancel, and truncation
    keeps it finite even where Jacobi iteration diverges.  Reliable on
    diagonally-structured operators (the skewed/hub generators); on
    globally coupled ill-conditioned spectra ``A^{-1}`` is non-local
    and the explore phase's live iterate is the only sound profile."""
    from repro.kernels import ref

    a = a.in_csr_order()
    rows = np.asarray(a.row_ids, np.int64)
    cols = (np.asarray(a.colpak, np.uint32)
            & np.uint32((1 << (32 - a.ei_bit)) - 1)).astype(np.int64)
    v3 = np.abs(np.asarray(ref.decode_csr_ref(a.colpak, a.head, a.tail1,
                                              a.tail2, a.table, a.ei_bit, 3),
                           np.float64))
    m = int(a.shape[0])
    d = np.zeros(m, np.float64)
    dmask = rows == cols
    d[rows[dmask]] = v3[dmask]
    d = np.where(d > 0, d, 1.0)
    x = np.abs(np.asarray(b, np.float64)).reshape(-1) / d
    acc = x.copy()
    off = np.where(dmask, 0.0, v3)
    for _ in range(hops):
        y = np.zeros(m, np.float64)
        np.add.at(y, rows, off * x[cols])
        x = y / d
        acc += x
    return acc


def solve_adaptive(
    a: GSECSR,
    b: jnp.ndarray,
    precond=None,
    x0: jnp.ndarray | None = None,
    tol: float = 1e-6,
    maxiter: int = 5000,
    params: P.MonitorParams | None = None,
    chunk: int | None = None,
    promote_frac: float = 0.1,
    tags0=None,
    group_size: int = GROUP_SIZE,
    profile: str = "explore",
    probe_iters: int = 0,
    theta: float = 0.25,
    beta: float = 2.0,
) -> AdaptiveResult:
    """Data-driven per-group precision CG/PCG (``tags="adaptive"``).

    ``a`` must be a packed ``GSECSR`` (the floor model reads the flat
    packed segments; pass the CSR pack even if you normally solve
    through a SELL view -- the masked operand rides the same
    iteration).  ``precond`` selects PCG for the MAIN solve: a
    ``solvers.precond`` object or any callable
    ``apply_m(r, tag)``; the optional planning probe always uses its
    own host-built Jacobi regardless.

    ``profile`` picks where the planner's solution-magnitude estimate
    comes from:

    - ``"explore"`` (default): no upfront plan -- run uniform tag 1 and
      plan ONCE from the live iterate when its recursive residual first
      crosses ``beta * tol`` (i.e. near recursive exhaustion, where the
      iterate is sharp and the restarted tail is short; an EARLY
      restart re-pays the Krylov plateau on clustered spectra).
    - ``"neumann"``: plan upfront from the free one-hop absolute
      Neumann profile (good on diagonally-dominant / hub structure).
    - ``"probe"``: plan upfront from a billed Jacobi-preconditioned
      tag-1 probe of ``probe_iters`` iterations.

    ``theta`` is the planner's headroom -- the planned map's modeled
    floor must fit in ``theta * tol * ||b||``.  ``tags0`` (a map or
    int) BYPASSES profiling and seeds the solve directly -- the escape
    hatch for callers that planned externally.  ``chunk`` is the host
    true-residual cadence in iterations; ``promote_frac`` the fraction
    of groups promoted when a reactive replan finds its own model
    already under budget.  Whatever the profile, a solve whose
    recurrence exhausts while the true residual misses ``tol`` replans
    reactively from the current iterate and restarts.
    """
    from repro.kernels.ops import masked_for_tagmap
    from repro.solvers.cg import _loop, _normalize_b_x0, _pin_params
    from repro.solvers.operators import make_gse_operator

    if not isinstance(a, GSECSR):
        raise TypeError(
            "solve_adaptive needs a packed GSECSR operand (the floor "
            f"model reads its flat segments); got {type(a).__name__}")
    if profile not in ("explore", "neumann", "probe"):
        raise ValueError(f"unknown profile {profile!r}")
    b, x0, orig_shape = _normalize_b_x0(b, x0)
    x = jnp.zeros_like(b) if x0 is None else x0
    if params is None:
        params = P.MonitorParams.for_cg()
    if chunk is None:
        # The per-chunk TRUE-residual check costs one tag-3 pass
        # (~2 iterations' worth of the cheapest stream), so a cadence of
        # ~100 iterations keeps the overhead under ~2% while stopping
        # the solve the moment the real residual fits.
        chunk = max(1, min(params.m, 100, maxiter))
    m = int(a.shape[0])
    # Segment recurrence target: the quadrature complement of the
    # planned floor budget, sqrt(tol^2 - (theta*tol)^2).  A planned map
    # carries a modeled floor <= theta * tol * ||b||, so stopping the
    # recurrence there still lands the TRUE residual inside tol; any
    # deeper recursive target burns real iterations grinding below what
    # the floor admits.  The explore segment uses the same target: if
    # the uniform tag-1 floor is tiny the boundary true-check accepts
    # right there, and otherwise the replan only needs the iterate as a
    # PROFILE, whose trim plateaus in quality well above this depth.
    seg_tol = tol * float(np.sqrt(max(1.0 - theta * theta, 0.25)))
    bnorm = float(jnp.linalg.norm(b))
    bnorm = 1.0 if bnorm == 0 else bnorm
    promotions: list[Promotion] = []
    bytes_ = 0
    probe_done = 0

    with OT.span("solve.adaptive", n=m, tol=float(tol), chunk=int(chunk)):
        planned = True  # an upfront plan / explicit seed disables beta-replan
        if tags0 is not None:
            tm = _init_map(tags0, m, group_size)
        elif profile == "neumann":
            xh = _abs_neumann_profile(a, np.asarray(b))
            tm = P.plan_tagmap(P.decode_error_scores(a, xh, group_size),
                               theta * tol * bnorm, group_size=group_size)
            promotions.append(Promotion(
                0, int((tm.tags > 1).sum()), tm.min_tag, tm.max_tag,
                tm.crc32))
        elif profile == "probe":
            pr = _loop(a, _probe_jacobi(a), b, x, jnp.asarray(0.0, b.dtype),
                       max(int(probe_iters), 1), _pin_params(params, 1),
                       init_tag=1, guards=None, flight=None)
            probe_done = int(pr.iters)
            bytes_ += (probe_done + 1) * a.bytes_touched(1)
            xh = np.abs(np.asarray(pr.x))
            if not np.isfinite(xh).all() or xh.max() == 0:
                xh = np.abs(np.asarray(b))
            else:
                xh = _trim(xh, float(pr.relres))
            tm = P.plan_tagmap(P.decode_error_scores(a, xh, group_size),
                               theta * tol * bnorm, group_size=group_size)
            promotions.append(Promotion(
                0, int((tm.tags > 1).sum()), tm.min_tag, tm.max_tag,
                tm.crc32))
        else:
            tm = TagMap.for_rows(m, 1, group_size)
            planned = False

        def run_chunk(a_eff, x_start, state, stop, pinned, itag, st):
            return _loop(a_eff, precond, b, x_start, st, maxiter, pinned,
                         init_tag=itag, guards=None, flight=None,
                         resume=state, stop_at=stop, return_state=True)

        def true_relres(xv) -> float:
            ax = make_gse_operator(a)(xv, jnp.int32(3))
            return float(jnp.linalg.norm(b - ax) / bnorm)

        def replan(tm, xv, rel, glob, force):
            """(Re)plan from the live iterate: its magnitudes ARE the
            solution profile any seed could only approximate, resolved
            to about its own true-residual scale.  ``force`` (the
            recurrence-exhausted path) escalates the worst still-open
            contributors even when the model thinks the map already
            fits the budget -- the model underpredicted, so escalation
            must make progress unconditionally."""
            sc = P.decode_error_scores(
                a, _trim(np.abs(np.asarray(xv)), rel), group_size)
            tm2 = P.plan_tagmap(sc, theta * tol * bnorm, tags0=tm,
                                group_size=group_size)
            if force and tm2 == tm:
                tm2 = P.promote_groups(
                    tm, P.map_floor_contrib(sc, tm.tags), frac=promote_frac)
            if tm2 != tm:
                promotions.append(Promotion(
                    glob, int((tm2.tags != tm.tags).sum()),
                    tm2.min_tag, tm2.max_tag, tm2.crc32))
            return tm2

        # ``res.iters`` counts from the start of the current SEGMENT (a
        # restart re-enters the jitted init); ``seg_off`` accumulates the
        # prior segments so every reported/billed iteration is global.
        # Every chunk boundary measures the TRUE tag-3 residual (billed):
        # it is simultaneously the convergence test (stop the moment the
        # real residual fits, even while the recursive one lags), the
        # explore-phase plan trigger, and the final verify.  There is NO
        # rate-based stall heuristic -- on slow spectra the true and
        # recursive residuals plateau TOGETHER mid-run (measured: 3% per
        # 100 iterations with true/rec ratio 1.00), so any plateau
        # detector either false-fires there or is subsumed by the
        # recurrence-exhausted condition below.
        state = None
        seg_off = 0
        seg_it = 0
        chunks = 0
        exhausted = False
        demoted = False
        res = None
        tr = np.inf

        while True:
            a_eff = masked_for_tagmap(a, tm)
            pinned = _pin_params(params, tm.max_tag)
            if state is None:
                bytes_ += a.bytes_touched(tm)  # fresh initial residual SpMV
            stop = min(seg_it + chunk, max(maxiter - seg_off, 1))
            res, _, state = run_chunk(a_eff, x, state, jnp.int32(stop),
                                      pinned, tm.max_tag,
                                      jnp.asarray(seg_tol, b.dtype))
            chunks += 1
            new_seg_it = int(res.iters)
            bytes_ += (new_seg_it - seg_it) * a.bytes_touched(tm)
            glob = seg_off + new_seg_it
            relres = float(res.relres)
            tr = true_relres(res.x)
            bytes_ += a.bytes_touched(3)

            if tr <= tol or glob >= maxiter:
                break

            rec_done = np.isfinite(relres) and relres <= seg_tol
            plan_now = (not planned and np.isfinite(relres)
                        and relres <= beta * tol)

            if (planned and not demoted and not rec_done
                    and np.isfinite(relres) and tr > 3.0 * tol):
                # Demote pass (at most one adoption per solve): an
                # upfront plan came from an approximate profile and may
                # over-promote; once the live iterate has sharpened --
                # but while there is still enough distance to tol to
                # amortize a restart -- re-plan from scratch and adopt
                # a strictly cheaper map if the model finds one.
                tmf = P.plan_tagmap(
                    P.decode_error_scores(
                        a, _trim(np.abs(np.asarray(res.x)), tr), group_size),
                    theta * tol * bnorm, group_size=group_size)
                if (tmf != tm
                        and a.bytes_touched(tmf) < 0.93 * a.bytes_touched(tm)):
                    demoted = True
                    promotions.append(Promotion(
                        glob, int((tmf.tags != tm.tags).sum()),
                        tmf.min_tag, tmf.max_tag, tmf.crc32))
                    tm = tmf
                    x = res.x
                    state = None
                    seg_off = glob
                    seg_it = 0
                    continue

            if rec_done or plan_now or not np.isfinite(relres):
                tm2 = replan(tm, res.x, tr, glob, force=rec_done)
                planned = True
                if tm2 == tm:
                    if rec_done:
                        if exhausted:
                            break  # fully promoted and restarted once
                        exhausted = tm.min_tag == 3
                    else:
                        # Explore-phase plan kept the uniform map: no
                        # operand change, keep the recurrence running.
                        seg_it = new_seg_it
                        continue
                tm = tm2
                x = res.x
                state = None
                seg_off = glob
                seg_it = 0
                continue

            seg_it = new_seg_it

    res_x = res.x.reshape(orig_shape) if res.x.shape != orig_shape else res.x
    return AdaptiveResult(
        x=res_x,
        iters=seg_off + int(res.iters),
        relres=float(res.relres),
        true_relres=float(tr) if np.isfinite(tr) else true_relres(res.x),
        converged=bool(np.isfinite(tr) and tr <= tol),
        tagmap=tm,
        promotions=tuple(promotions),
        spmv_bytes=int(bytes_),
        chunks=chunks,
        probe_iters=probe_done,
    )
