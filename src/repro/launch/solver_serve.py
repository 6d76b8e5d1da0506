"""Request-batching solve service over registered GSE-SEM operators.

The ROADMAP's serving-shaped front-end for the linear-solver path
(DESIGN.md §11): heavy traffic means MANY simultaneous solve requests
against a few shared operators.  The service packs each registered
matrix (and optional preconditioner) ONCE, buckets incoming requests by
(operator, tolerance), pads each bucket to a fixed batch-slot width, and
runs the batched stepped solver -- one streaming pass over the packed
matrix segments feeds every request in a slot, so the dominant matrix
traffic is charged once per iteration however many requests ride along
(``csr.iteration_stream_bytes(..., nrhs=...)``).

Per-request reporting: iterations, final relative residual, the
per-column tag-switch schedule, and the request's modeled byte share of
its batch (matrix bytes split evenly across the iterations' active
columns, vector bytes owned per column).  Padding columns are all-zero
right-hand sides: ``||b|| = 0`` makes them converge at iteration 0, so
they never stream vector bytes and never perturb real requests (the
batched solver's columns are independent by construction).

Usage (demo):
  PYTHONPATH=src python -m repro.launch.solver_serve --requests 6 --slots 4
"""
from __future__ import annotations

import dataclasses
import itertools
import time
from typing import Dict, List, Optional

import jax.numpy as jnp
import numpy as np

from repro.core import precision as P
from repro.core.tagmap import TagMap, normalize_tags
from repro.obs import metrics as OM
from repro.obs import trace as OT
from repro.robustness.guards import (
    DEFAULT_GUARDS,
    GuardParams,
    HEALTH_NONFINITE,
    HEALTH_OK,
    health_name,
)
from repro.sparse.csr import CSR, GSESellC, iteration_stream_bytes, pack_csr
from repro.solvers.batched import (
    column_tags_at,
    solve_cg_batched,
    solve_pcg_batched,
)
from repro.solvers.cg import solve_cg, solve_pcg
from repro.solvers.precond import make_jacobi, make_spai0

__all__ = ["SolveRequest", "SolveReport", "SolverService"]

_PRECOND_FACTORY = {"jacobi": make_jacobi, "spai0": make_spai0}

# Distinguishes the metric series of multiple SolverService instances in
# one process (tests build them freely); the id is a label value, so all
# instances share ONE registered family per metric name.
_SERVICE_IDS = itertools.count()


def _normalize_service_tags(tags, m: int, sharded: bool = False,
                            sell: bool = False):
    """Validate/normalize a service-level ``tags=`` precision axis.

    ``None`` -> the handle/monitor default.  An int or a uniform
    :class:`~repro.core.tagmap.TagMap` normalizes to the int tag (the
    legacy fast path); a NON-uniform map stays a map (single-device
    handles only -- the sharded decode has no per-group pack yet, same
    restriction as the solvers' ``tags=``).  ``"adaptive"`` selects the
    data-driven driver, which reads the flat ``GSECSR`` pack -- so it
    needs a single-device CSR handle.
    """
    if tags is None:
        return None
    if isinstance(tags, str):
        if tags != "adaptive":
            raise ValueError(
                f"tags= accepts an int tag, a TagMap, or 'adaptive'; "
                f"got {tags!r}")
        if sharded or sell:
            raise ValueError(
                "tags='adaptive' needs a single-device CSR handle "
                "(solve_adaptive reads the flat GSECSR pack)")
        return "adaptive"
    norm = normalize_tags(tags, m)
    if isinstance(norm, TagMap) and sharded:
        raise ValueError(
            "per-group tag maps are single-device; the sharded serve "
            "path takes int tags only")
    return norm


def _tags_token(tags):
    """Hashable bucket token for an effective tags axis (maps bucket by
    content CRC, so two equal maps share a batched slot)."""
    if isinstance(tags, TagMap):
        return ("map", tags.crc32)
    return tags


@dataclasses.dataclass
class SolveRequest:
    id: int
    handle: str
    b: jnp.ndarray
    tol: float
    x0: Optional[jnp.ndarray] = None
    deadline_s: Optional[float] = None  # wall-clock budget from submit()
    t_submit: float = 0.0               # time.monotonic() at intake
    tags: object = None                 # per-request precision axis override


@dataclasses.dataclass
class SolveReport:
    id: int
    handle: str
    iters: int
    relres: float
    converged: bool
    tag: int
    switch_iters: np.ndarray  # (2,)
    est_bytes: int            # modeled byte share of the batch
    batch_size: int           # real requests in the slot it ran in
    # Degradation reporting (DESIGN.md §14): structured health string
    # (robustness.guards.HEALTH_NAMES, or "error" when the slot's solve
    # itself raised), the first guard-trip iteration within the batched
    # run (-1: never), how many bounded tag-3 retries this request
    # consumed, and whether its deadline lapsed before recovery finished.
    health: str = "ok"
    trip_iter: int = -1
    retries: int = 0
    deadline_exceeded: bool = False


@dataclasses.dataclass
class _Operator:
    name: str
    csr: CSR
    gse: "object"     # GSECSR or GSESellC, packed once at registration
    precond: object   # precond object or None
    part: object = None   # PartitionedGSECSR when registered sharded
    wire: str = "exact"   # halo wire format for the sharded path
    plan: object = None   # tuned/explicit KernelPlan attached at register
    tags: object = None   # handle-default precision axis (PR 10):
    #                       None | int | TagMap | "adaptive"

    @property
    def solve_op(self):
        """The operand handed to the batched solvers: the partition when
        sharded (distributed operator path), else the packed matrix."""
        return self.part if self.part is not None else self.gse


class SolverService:
    """Minimal request-batching front-end for the batched stepped solvers.

    ``slots`` is the batch width every bucket is padded to (the serving
    analogue of a fixed decode batch): requests against the same
    (operator, tol) bucket share one batched solve.  ``flush()`` drains
    all pending requests and returns per-request ``SolveReport``s.
    """

    def __init__(self, slots: int = 4,
                 params: P.MonitorParams | None = None,
                 maxiter: int = 5000,
                 guards: GuardParams | None = DEFAULT_GUARDS,
                 max_retries: int = 1):
        if slots < 1:
            raise ValueError(f"slots must be >= 1, got {slots}")
        if max_retries < 0:
            raise ValueError(f"max_retries must be >= 0, got {max_retries}")
        self.slots = slots
        self.params = params or P.MonitorParams.for_cg()
        self.maxiter = maxiter
        self.guards = guards
        self.max_retries = max_retries
        self._ops: Dict[str, _Operator] = {}
        self._pending: List[SolveRequest] = []
        self._ids = itertools.count()
        self._solutions: Dict[int, jnp.ndarray] = {}
        # Registry-backed telemetry (DESIGN.md §16).  ``stats`` keeps the
        # historical dict shape; the gauge tracks the live queue depth and
        # the histograms feed the p50/p95/p99 flush-latency and
        # bytes-per-request numbers ``run.py --obs`` reports.
        self.service_id = str(next(_SERVICE_IDS))
        const = {"service": self.service_id}
        self.stats = OM.stats_view(
            "repro_serve_events_total",
            ("batches", "requests", "padded_cols", "modeled_bytes",
             "retries", "errors", "deadline_exceeded"),
            help="SolverService lifetime event counts by kind.",
            const=const,
        )
        self.queue_depth = OM.REGISTRY.gauge(
            "repro_serve_queue_depth",
            "Requests waiting for the next flush.",
            labelnames=("service",),
        ).labels(**const)
        self.flush_latency = OM.REGISTRY.histogram(
            "repro_serve_flush_latency_seconds",
            "Wall-clock seconds per SolverService.flush call.",
            labelnames=("service",),
        ).labels(**const)
        self.request_bytes = OM.REGISTRY.histogram(
            "repro_serve_request_bytes",
            "Modeled streamed bytes charged to each served request.",
            labelnames=("service",),
            buckets=OM.DEFAULT_BYTE_BUCKETS,
        ).labels(**const)

    # -- registration ------------------------------------------------------

    def register(self, name: str, a: CSR, k: int = 8,
                 precond: str | object | None = None,
                 layout: str = "csr", sharded: bool = False,
                 shards: int | None = None, wire: str = "exact",
                 plan=None, tune: bool = False, tags=None) -> str:
        """Pack ``a`` (and optionally a preconditioner) once; returns the
        handle requests are submitted against.  ``precond`` is ``None``,
        ``"jacobi"``/``"spai0"``, or a ready :mod:`repro.solvers.precond`
        object (Carson-Khan-style setup reuse: one packed preconditioner
        serves every request against the handle).

        ``layout="sell"`` additionally packs the operator into the
        SELL-C-σ sliced layout (``kernels.ops.sell_pack_gsecsr``, cached
        on the packed instance -- DESIGN.md §12): trajectories are
        bit-identical to the ``"csr"`` default, but byte reports charge
        the layout's ACTUAL padded slots instead of nnz only.

        ``sharded=True`` row-shards the packed operator across ``shards``
        devices (default: all visible) and serves every request against
        the handle through the distributed solver path (DESIGN.md §13);
        ``wire`` picks the halo wire format (``"exact"`` f64 halos,
        ``"gse"`` tag-aware compressed halos) and the byte reports add the
        halo wire traffic per iteration.

        ``plan``/``tune`` attach a kernel launch plan to the handle
        (DESIGN.md §15): an explicit :class:`repro.perf.plan.KernelPlan`
        is used as-is; ``tune=True`` resolves one through the persisted
        autotuner (``perf.autotune.get_or_tune`` -- a sweep on the first
        registration of a matrix class, a pure cache hit afterwards).
        The SELL pack then uses the plan's C/σ/lane/bucket parameters;
        solve trajectories stay bit-identical (the stepped solvers decode
        through the packed store, not the launch blocks).

        ``tags`` sets the handle's DEFAULT precision axis (PR 10,
        DESIGN.md §18), overridable per request at ``submit``: an int or
        uniform :class:`~repro.core.tagmap.TagMap` pins the start tag, a
        non-uniform map runs the masked per-group schedule, and
        ``"adaptive"`` serves every request against the handle through
        the data-driven per-group driver
        (:func:`repro.solvers.adaptive.solve_adaptive`)."""
        if name in self._ops:
            raise ValueError(f"handle {name!r} already registered")
        if layout not in ("csr", "sell"):
            raise ValueError(
                f"unknown layout {layout!r}; expected 'csr' or 'sell'"
            )
        if sharded and layout == "sell":
            raise ValueError(
                "sharded=True serves through the row-sharded CSR decode; "
                "the SELL layout is single-device (pick one)"
            )
        if wire not in ("exact", "gse"):
            raise ValueError(
                f"unknown wire mode {wire!r}; expected 'exact' or 'gse'"
            )
        tags = _normalize_service_tags(tags, int(a.shape[0]),
                                       sharded=sharded,
                                       sell=layout == "sell")
        if isinstance(precond, str):
            try:
                precond = _PRECOND_FACTORY[precond](a, k=k)
            except KeyError:
                raise ValueError(
                    f"unknown preconditioner {precond!r}; expected one of "
                    f"{sorted(_PRECOND_FACTORY)}"
                ) from None
        gse = pack_csr(a, k=k)
        if tune and plan is None:
            from repro.perf import autotune

            plan, _, _ = autotune.get_or_tune(
                gse, tag=1, layout="sell" if layout == "sell" else "ell")
        part = None
        if sharded:
            import jax

            from repro.distributed.partition import partition_gsecsr

            part = partition_gsecsr(gse, shards or jax.device_count())
        if layout == "sell":
            from repro.kernels.ops import sell_pack_gsecsr

            gse = sell_pack_gsecsr(gse, plan=plan)
        self._ops[name] = _Operator(
            name=name, csr=a, gse=gse, precond=precond, part=part,
            wire=wire, plan=plan, tags=tags
        )
        return name

    # -- request intake ----------------------------------------------------

    def submit(self, handle: str, b, tol: float = 1e-8, x0=None,
               deadline_s: float | None = None, tags=None) -> int:
        """Queue one solve request; returns its request id.

        ``tags`` overrides the handle's default precision axis for this
        request only (same values as ``register``; requests bucket by
        their EFFECTIVE axis, so mixed-tags traffic against one handle
        never shares a batched slot across axes).

        Intake validation (DESIGN.md §14): ``b`` must match the handle's
        dimension, be a floating dtype, and be entirely finite -- a NaN/Inf
        right-hand side can never produce a meaningful solution, so it is
        rejected HERE with ``ValueError`` instead of burning a batch slot
        and coming back flagged ``nonfinite``.  ``deadline_s`` is a
        wall-clock budget measured from submission; a lapsed deadline
        suppresses tag-3 retry recovery for this request (the degraded
        report still carries whatever the batched pass produced)."""
        op = self._ops.get(handle)
        if op is None:
            raise KeyError(f"unknown handle {handle!r}")
        b = jnp.asarray(b)
        if b.ndim == 2 and b.shape[1] == 1:
            b = b[:, 0]
        if b.ndim != 1 or b.shape[0] != op.csr.shape[0]:
            raise ValueError(
                f"b must be ({op.csr.shape[0]},) or ({op.csr.shape[0]}, 1) "
                f"for handle {handle!r}; got {tuple(b.shape)}"
            )
        if not jnp.issubdtype(b.dtype, jnp.floating):
            raise ValueError(
                f"b must have a floating dtype for handle {handle!r}; "
                f"got {b.dtype}"
            )
        if not bool(jnp.isfinite(b).all()):
            raise ValueError(
                f"b contains non-finite entries (handle {handle!r}); "
                "rejected at intake"
            )
        if x0 is not None:
            x0 = jnp.asarray(x0)
            if x0.ndim == 2 and x0.shape[1] == 1:
                x0 = x0[:, 0]  # same (n, 1) normalization as b
            if x0.shape != b.shape:
                raise ValueError(
                    f"x0 shape {tuple(x0.shape)} != b shape {tuple(b.shape)}"
                )
            if not bool(jnp.isfinite(x0).all()):
                raise ValueError(
                    f"x0 contains non-finite entries (handle {handle!r}); "
                    "rejected at intake"
                )
        if deadline_s is not None and deadline_s <= 0:
            raise ValueError(f"deadline_s must be > 0, got {deadline_s}")
        tags = _normalize_service_tags(
            tags, int(op.csr.shape[0]), sharded=op.part is not None,
            sell=isinstance(op.gse, GSESellC))
        rid = next(self._ids)
        self._pending.append(SolveRequest(rid, handle, b, float(tol), x0,
                                          deadline_s=deadline_s,
                                          t_submit=time.monotonic(),
                                          tags=tags))
        self.queue_depth.set(len(self._pending))
        return rid

    # -- batch execution ---------------------------------------------------

    def flush(self) -> Dict[int, SolveReport]:
        """Drain pending requests: bucket by (handle, tol), pad to the slot
        width, run the batched stepped solver, report per request.

        Solutions are retained only until the NEXT flush (claim them with
        :meth:`solution`), so a long-running service that only reads the
        reports does not accumulate solved vectors without bound.

        Degradation contract (DESIGN.md §14): ``flush`` never raises out
        of a slot -- a slot whose solve itself throws degrades to error
        reports (``health="error"``, not converged, no solution) for its
        requests, and every returned solution is either finite or flagged
        by a non-ok health."""
        t0 = time.perf_counter()
        self._solutions.clear()
        buckets: Dict[tuple, tuple] = {}
        for req in self._pending:
            # The EFFECTIVE precision axis (request override, else the
            # handle default) is part of the bucket: one batched slot,
            # one axis.
            eff = req.tags if req.tags is not None \
                else self._ops[req.handle].tags
            key = (req.handle, req.tol, _tags_token(eff))
            buckets.setdefault(key, (eff, []))[1].append(req)
        drained = len(self._pending)
        self._pending = []
        self.queue_depth.set(0)

        reports: Dict[int, SolveReport] = {}
        with OT.span("serve.flush", service=self.service_id,
                     requests=drained) as attrs:
            for (handle, tol, _tok), (eff, reqs) in buckets.items():
                op = self._ops[handle]
                for i in range(0, len(reqs), self.slots):
                    chunk = reqs[i:i + self.slots]
                    try:
                        reports.update(
                            self._run_slot(op, tol, chunk, tags=eff))
                    except Exception:  # degraded, never propagated
                        self.stats["errors"] += 1
                        for req in chunk:
                            self._solutions.pop(req.id, None)
                            reports[req.id] = SolveReport(
                                id=req.id, handle=op.name, iters=0,
                                relres=float("inf"), converged=False, tag=0,
                                switch_iters=np.full(2, -1, np.int64),
                                est_bytes=0, batch_size=len(chunk),
                                health="error",
                            )
            attrs["bytes"] = sum(r.est_bytes for r in reports.values())
        for rep in reports.values():
            self.request_bytes.observe(rep.est_bytes)
        self.flush_latency.observe(time.perf_counter() - t0)
        return reports

    def _run_slot(self, op: _Operator, tol: float,
                  reqs: List[SolveRequest],
                  tags=None) -> Dict[int, SolveReport]:
        if tags == "adaptive":
            return self._run_adaptive(op, tol, reqs)
        n = op.csr.shape[0]
        nrhs = self.slots
        pad = nrhs - len(reqs)
        zero = jnp.zeros((n,), reqs[0].b.dtype)
        cols = [r.b for r in reqs] + [zero] * pad
        b = jnp.stack(cols, axis=1)
        x0 = None
        if any(r.x0 is not None for r in reqs):
            x0 = jnp.stack(
                [r.x0 if r.x0 is not None else zero for r in reqs]
                + [zero] * pad,
                axis=1,
            )
        if op.precond is not None:
            res = solve_pcg_batched(op.solve_op, b, op.precond, x0=x0,
                                    tol=tol, maxiter=self.maxiter,
                                    params=self.params, wire=op.wire,
                                    guards=self.guards, tags=tags)
        else:
            res = solve_cg_batched(op.solve_op, b, x0=x0, tol=tol,
                                   maxiter=self.maxiter, params=self.params,
                                   wire=op.wire, guards=self.guards,
                                   tags=tags)

        iters = np.asarray(res.iters)
        sw = np.asarray(res.switch_iters)
        nreal = len(reqs)
        health = np.broadcast_to(
            np.asarray(getattr(res, "health", 0)), iters.shape
        ).astype(np.int64)
        trip = np.broadcast_to(
            np.asarray(getattr(res, "trip_iter", -1)), iters.shape
        ).astype(np.int64)
        shares, total_bytes = self._byte_shares(op, iters, sw, tags=tags)
        self.stats["batches"] += 1
        self.stats["requests"] += nreal
        self.stats["padded_cols"] += pad
        self.stats["modeled_bytes"] += total_bytes

        out = {}
        for j, req in enumerate(reqs):
            x = res.x[:, j]
            it_j = int(iters[j])
            relres_j = float(res.relres[j])
            conv_j = bool(res.converged[j])
            tag_j = int(res.tag[j])
            sw_j = sw[j]
            bytes_j = int(shares[j])
            h_j = int(health[j])
            trip_j = int(trip[j])
            retries = 0
            deadline_hit = False
            x_finite = bool(jnp.isfinite(jnp.vdot(x, x)))
            # Degraded column: bounded single-RHS retries at tag 3 (the
            # exact path -- the strongest rung the escalation ladder has).
            # A lapsed deadline suppresses retries; the degraded report
            # still ships whatever the batched pass produced, flagged.
            while (not conv_j or not x_finite) and retries < self.max_retries:
                if req.deadline_s is not None and \
                        time.monotonic() - req.t_submit > req.deadline_s:
                    deadline_hit = True
                    self.stats["deadline_exceeded"] += 1
                    break
                retries += 1
                self.stats["retries"] += 1
                warm = x if x_finite else req.x0
                if op.precond is not None:
                    r2 = solve_pcg(op.solve_op, req.b, op.precond, x0=warm,
                                   tol=tol, maxiter=self.maxiter,
                                   params=self.params, wire=op.wire,
                                   guards=self.guards, init_tag=3)
                else:
                    r2 = solve_cg(op.solve_op, req.b, x0=warm, tol=tol,
                                  maxiter=self.maxiter, params=self.params,
                                  wire=op.wire, guards=self.guards,
                                  init_tag=3)
                rx_finite = bool(jnp.isfinite(jnp.vdot(r2.x, r2.x)))
                r2_trip = int(getattr(r2, "trip_iter", -1))
                if trip_j < 0 and r2_trip >= 0:
                    trip_j = it_j + r2_trip
                it_j += int(r2.iters)
                relres_j = float(r2.relres)
                conv_j = bool(r2.converged)
                tag_j = int(r2.tag)
                h_j = int(getattr(r2, "health", HEALTH_OK))
                if rx_finite:
                    x = r2.x
                x_finite = x_finite or rx_finite
                sh2, tot2 = self._byte_shares(
                    op, np.asarray([int(r2.iters)]),
                    np.asarray(r2.switch_iters).reshape(1, -1),
                )
                bytes_j += int(sh2[0])
                self.stats["modeled_bytes"] += tot2
            # Belt and braces: a non-finite solution NEVER leaves the
            # service unflagged, whatever the solver reported.
            if not x_finite and h_j == HEALTH_OK:
                h_j = HEALTH_NONFINITE
                conv_j = False
            self._solutions[req.id] = x
            out[req.id] = SolveReport(
                id=req.id,
                handle=op.name,
                iters=it_j,
                relres=relres_j,
                converged=conv_j,
                tag=tag_j,
                switch_iters=sw_j,
                est_bytes=bytes_j,
                batch_size=nreal,
                health=health_name(h_j),
                trip_iter=trip_j,
                retries=retries,
                deadline_exceeded=deadline_hit,
            )
        return out

    def _run_adaptive(self, op: _Operator, tol: float,
                      reqs: List[SolveRequest]) -> Dict[int, SolveReport]:
        """``tags="adaptive"`` dispatch: the data-driven per-group driver
        is a host loop over single-RHS segments (DESIGN.md §18), so each
        request runs its own solve -- no slot sharing, and ``est_bytes``
        is the driver's OWN blended account (masked matrix stream plus
        the billed true-residual checks, ``AdaptiveResult.spmv_bytes``)
        instead of the column-share model.  ``relres`` reports the TRUE
        tag-3 residual -- the number the adaptive stop is gated on.
        Degraded requests get the same bounded tag-3 retry as the
        batched path."""
        from repro.solvers.adaptive import solve_adaptive

        clock = getattr(self, "clock", time.monotonic)
        out = {}
        self.stats["batches"] += 1
        self.stats["requests"] += len(reqs)
        for req in reqs:
            res = solve_adaptive(op.gse, req.b, precond=op.precond,
                                 x0=req.x0, tol=tol, maxiter=self.maxiter,
                                 params=self.params)
            x = res.x
            it_j = int(res.iters)
            relres_j = float(res.true_relres)
            conv_j = bool(res.converged)
            tag_j = int(res.tagmap.max_tag)
            bytes_j = int(res.spmv_bytes)
            h_j = HEALTH_OK
            retries = 0
            deadline_hit = False
            x_finite = bool(jnp.isfinite(jnp.vdot(x, x)))
            self.stats["modeled_bytes"] += bytes_j
            while (not conv_j or not x_finite) and retries < self.max_retries:
                if req.deadline_s is not None and \
                        clock() - req.t_submit > req.deadline_s:
                    deadline_hit = True
                    self.stats["deadline_exceeded"] += 1
                    break
                retries += 1
                self.stats["retries"] += 1
                warm = x if x_finite else req.x0
                if op.precond is not None:
                    r2 = solve_pcg(op.gse, req.b, op.precond, x0=warm,
                                   tol=tol, maxiter=self.maxiter,
                                   params=self.params, guards=self.guards,
                                   init_tag=3)
                else:
                    r2 = solve_cg(op.gse, req.b, x0=warm, tol=tol,
                                  maxiter=self.maxiter, params=self.params,
                                  guards=self.guards, init_tag=3)
                rx_finite = bool(jnp.isfinite(jnp.vdot(r2.x, r2.x)))
                it_j += int(r2.iters)
                relres_j = float(r2.relres)
                conv_j = bool(r2.converged)
                tag_j = int(r2.tag)
                h_j = int(getattr(r2, "health", HEALTH_OK))
                if rx_finite:
                    x = r2.x
                x_finite = x_finite or rx_finite
                sh2, tot2 = self._byte_shares(
                    op, np.asarray([int(r2.iters)]),
                    np.asarray(r2.switch_iters).reshape(1, -1),
                )
                bytes_j += int(sh2[0])
                self.stats["modeled_bytes"] += tot2
            if not x_finite and h_j == HEALTH_OK:
                h_j = HEALTH_NONFINITE
                conv_j = False
            self._solutions[req.id] = x
            out[req.id] = SolveReport(
                id=req.id,
                handle=op.name,
                iters=it_j,
                relres=relres_j,
                converged=conv_j,
                tag=tag_j,
                switch_iters=np.full(2, -1, np.int64),
                est_bytes=bytes_j,
                batch_size=len(reqs),
                health=health_name(h_j),
                trip_iter=-1,
                retries=retries,
                deadline_exceeded=deadline_hit,
            )
        return out

    def solution(self, request_id: int) -> jnp.ndarray:
        """The solved ``x`` for a flushed request (pop to free memory)."""
        try:
            return self._solutions.pop(request_id)
        except KeyError:
            raise KeyError(
                f"no flushed solution for request {request_id!r}"
            ) from None

    def _byte_shares(self, op: _Operator, iters, sw, tags=None):
        """One walk of the per-iteration byte model: returns the per-column
        shares AND their sum, which is exactly ``batched_run_bytes`` (each
        iteration adds ``iteration_stream_bytes(..., nrhs=n_active)``
        split evenly among the columns sharing the streaming pass).

        ``tags`` is the slot's effective precision axis: a non-uniform
        :class:`TagMap` charges every live iteration the BLENDED
        per-group stream (the map is pinned -- no switch schedule); an
        int floors the monitor's switch-schedule tag (the batch started
        there, not at tag 1)."""
        nrhs = iters.shape[0]
        shares = np.zeros(nrhs, np.float64)
        tm = tags if isinstance(tags, TagMap) else None
        floor = int(tags) if isinstance(tags, (int, np.integer)) else 1
        for it in range(int(iters.max(initial=0))):
            col_tags = column_tags_at(iters, sw, it)
            live = np.nonzero(col_tags > 0)[0]
            if live.size == 0:
                continue
            if tm is not None:
                tot = iteration_stream_bytes(op.gse, tm, op.precond,
                                             nrhs=live.size)
                shares[live] += tot / live.size
                continue
            tag = max(int(col_tags.max()), floor)
            if op.part is not None:
                # Sharded handle: the canonical distributed account --
                # single-device matrix stream redistributed + per-column
                # halo wire traffic + per-extra-column vector streams.
                tot = op.part.iteration_stream_bytes(tag, op.wire,
                                                     nrhs=live.size)
                if op.precond is not None:
                    tot += op.precond.bytes_touched(tag)
            else:
                tot = iteration_stream_bytes(op.gse, tag, op.precond,
                                             nrhs=live.size)
            # The iteration's batch total divides evenly among the
            # columns sharing the streaming pass.
            shares[live] += tot / live.size
        return np.rint(shares).astype(np.int64), int(round(shares.sum()))


def main():
    import argparse
    import time

    from repro.sparse import generators as G
    from repro.sparse.spmv import spmv

    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--n", type=int, default=24, help="Poisson grid side")
    ap.add_argument("--precond", default="none",
                    choices=["none", "jacobi", "spai0"])
    ap.add_argument("--layout", default="csr", choices=["csr", "sell"],
                    help="operator pack: 'sell' rides the SELL-C-sigma "
                         "sliced layout (padding-honest byte reports)")
    ap.add_argument("--shards", type=int, default=0,
                    help="> 0: row-shard the operator and serve through "
                         "the distributed path (needs that many devices; "
                         "XLA_FLAGS=--xla_force_host_platform_device_"
                         "count=N on CPU)")
    ap.add_argument("--wire", default="exact", choices=["exact", "gse"],
                    help="halo wire format for --shards (DESIGN.md "
                         "section 13)")
    ap.add_argument("--tol", type=float, default=1e-8)
    args = ap.parse_args()

    a = G.poisson2d(args.n)
    params = P.MonitorParams(t=40, l=60, m=30, rsd_limit=0.5,
                             reldec_limit=0.45)
    svc = SolverService(slots=args.slots, params=params, maxiter=20000)
    svc.register("poisson", a, k=8,
                 precond=None if args.precond == "none" else args.precond,
                 layout=args.layout, sharded=args.shards > 0,
                 shards=args.shards or None, wire=args.wire)

    rng = np.random.default_rng(0)
    ids = []
    for _ in range(args.requests):
        b = spmv(a, jnp.asarray(rng.normal(size=a.shape[1])))
        ids.append(svc.submit("poisson", b, tol=args.tol))

    t0 = time.time()
    reports = svc.flush()
    dt = time.time() - t0
    for rid in ids:
        r = reports[rid]
        print(
            f"req {r.id}: iters={r.iters} relres={r.relres:.2e} "
            f"converged={r.converged} tag={r.tag} "
            f"switches={r.switch_iters.tolist()} "
            f"est_bytes={r.est_bytes} batch={r.batch_size}/{args.slots} "
            f"health={r.health}"
        )
    s = svc.stats
    print(
        f"served {s['requests']} requests in {s['batches']} batches "
        f"({s['padded_cols']} padded cols, "
        f"{s['modeled_bytes'] / 1e6:.2f} MB modeled matrix+vector stream) "
        f"in {dt:.2f}s"
    )


if __name__ == "__main__":
    import jax

    from repro import compile_cache

    jax.config.update("jax_enable_x64", True)
    compile_cache.enable()
    main()
