"""Quickstart: the GSE-SEM format in 60 seconds.

  PYTHONPATH=src python examples/quickstart.py
"""
import jax

jax.config.update("jax_enable_x64", True)

from repro import compile_cache  # noqa: E402

compile_cache.enable()

import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.core import gse  # noqa: E402
from repro.sparse import generators as G  # noqa: E402
from repro.sparse.csr import iteration_stream_bytes, pack_csr  # noqa: E402
from repro.solvers import (  # noqa: E402
    make_gse_operator,
    make_jacobi,
    solve_cg,
    solve_ir,
    solve_pcg,
)
from repro.core.precision import MonitorParams  # noqa: E402


def main():
    # --- 1. pack a float vector against 8 shared exponents ---------------
    rng = np.random.default_rng(0)
    vals = rng.normal(size=4096) * np.exp2(rng.integers(-2, 3, 4096))
    packed = gse.pack(vals, k=8)
    print("shared exponents (unbiased):",
          (np.asarray(packed.table) - 1023).tolist())
    for tag, name in ((1, "head        16b"), (2, "head+tail1  32b"),
                      (3, "head+t1+t2  64b")):
        dec = gse.decode(packed, tag)
        rel = np.abs(dec - vals) / np.abs(vals)
        print(f"  tag {tag} ({name}): max rel err {rel.max():.3e}")

    # --- 2. one stored sparse matrix, three SpMV precisions --------------
    a = G.random_spd(2000, seed=1)
    g = pack_csr(a, k=8)
    print(f"\nCSR packed: {a.nnz} nnz")
    # Per-call byte accounting: what a tag-t SpMV actually streams from
    # HBM (values + packed colidx + rowptr/table).  The tag-specialized
    # kernels provably touch nothing else (DESIGN.md §2.4).
    print("  modeled SpMV bytes/nnz: "
          + " ".join(f"tag{t}={g.bytes_per_nnz(t)}" for t in (1, 2, 3))
          + f"  (fp64 CSR={a.bytes_per_nnz(jnp.float64)})")
    print("  modeled SpMV MB/call:   "
          + " ".join(f"tag{t}={g.bytes_touched(t)/1e6:.2f}"
                     for t in (1, 2, 3)))

    # --- 3. stepped mixed-precision CG (the paper's algorithm) -----------
    # Each iteration is one step at the monitor's tag: one decoded-value
    # pass with the dots/axpys folded around the SpMV (DESIGN.md §4).  The
    # GSECSR rides the loop as a packed operand.
    x_true = rng.normal(size=a.shape[1])
    from repro.sparse.spmv import spmv

    b = spmv(a, jnp.asarray(x_true))
    res = solve_cg(
        g, b, tol=1e-8, maxiter=3000,
        params=MonitorParams(t=40, l=60, m=30),
    )
    print(f"\nstepped CG: converged={bool(res.converged)} "
          f"iters={int(res.iters)} final tag={int(res.tag)} "
          f"relres={float(res.relres):.2e} "
          f"switches at {res.switch_iters.tolist()}")
    err = np.abs(np.asarray(res.x) - x_true).max()
    print(f"solution max abs error vs truth: {err:.2e}")

    # The same operator as a callable runs the same loop, bit-identically:
    res2 = solve_cg(
        make_gse_operator(g), b, tol=1e-8, maxiter=3000,
        params=MonitorParams(t=40, l=60, m=30),
    )
    agrees = (int(res2.iters) == int(res.iters)
              and float(res2.relres) == float(res.relres))
    print(f"callable operator agrees: {agrees} (iters={int(res2.iters)}, "
          f"relres={float(res2.relres):.2e})")

    # --- 4. preconditioned stepped CG on an ill-conditioned system ------
    # The GSE-packed Jacobi preconditioner is packed ONCE and applied at
    # the monitor's current tag -- same one-copy/three-precision storage
    # as the operator, so a tag-1 apply streams 2 bytes per stored entry
    # (DESIGN.md §10).
    ill = G.ill_conditioned_spd(32, decades=8.0, seed=0)
    gi = pack_csr(ill, k=8)
    mi = make_jacobi(ill, k=8)
    bi = spmv(ill, jnp.asarray(rng.normal(size=ill.shape[1])))
    fast = MonitorParams(t=30, l=30, m=15, rsd_limit=0.5, reldec_limit=0.45)
    res_cg = solve_cg(gi, bi, tol=1e-10, maxiter=30000, params=fast)
    res_pcg = solve_pcg(gi, bi, mi, tol=1e-10, maxiter=30000, params=fast)
    print(f"\nill-conditioned SPD (cond >= 1e6):")
    print(f"  stepped CG :          iters={int(res_cg.iters):5d} "
          f"converged={bool(res_cg.converged)}")
    print(f"  stepped PCG (jacobi): iters={int(res_pcg.iters):5d} "
          f"converged={bool(res_pcg.converged)}")
    print("  iteration stream bytes (matrix+precond): "
          + " ".join(f"tag{t}={iteration_stream_bytes(gi, t, mi)}"
                     for t in (1, 2, 3)))

    # --- 5. stepped iterative refinement (Carson-Khan shape) ------------
    # Outer loop: tag-3 residual + full-precision correction.  Inner loop:
    # loose stepped PCG that mostly stays on the cheap tags.
    res_ir = solve_ir(gi, bi, tol=1e-11, max_outer=10, inner="cg",
                      inner_tol=1e-4, inner_maxiter=4000, params=fast,
                      precond=mi)
    print(f"stepped IR: converged={res_ir.converged} "
          f"outer={res_ir.outer_iters} inner={res_ir.inner_iters} "
          f"true relres={res_ir.relres:.2e}")

    # --- 6. batched multi-RHS stepped solve (DESIGN.md section 11) -------
    # Four right-hand sides share ONE packed operand: the matrix segment
    # bytes are charged once per iteration (vector bytes per active
    # column) and each column runs its OWN monitor/tag schedule, bit-
    # identical to four independent solve_cg runs.  Columns deactivate
    # as they converge -- watch the per-column iteration counts differ.
    from repro.solvers import solve_cg_batched, batched_run_bytes

    B = jnp.stack([spmv(a, jnp.asarray(rng.normal(size=a.shape[1])))
                   for _ in range(4)], axis=1)
    res_b = solve_cg_batched(g, B, tol=1e-8, maxiter=3000,
                             params=MonitorParams(t=40, l=60, m=30))
    print(f"\nbatched stepped CG on {B.shape[1]} RHS (one shared operand):")
    for j in range(B.shape[1]):
        print(f"  col {j}: iters={int(res_b.iters[j]):4d} "
              f"tag={int(res_b.tag[j])} "
              f"relres={float(res_b.relres[j]):.2e} "
              f"switches at {res_b.switch_iters[j].tolist()}")
    run_b = batched_run_bytes(g, res_b.iters, res_b.switch_iters)
    naive = sum(
        int(batched_run_bytes(g, res_b.iters[j:j + 1],
                              res_b.switch_iters[j:j + 1]))
        for j in range(B.shape[1])
    )
    print(f"  modeled stream: {run_b / 1e6:.2f} MB batched vs "
          f"{naive / 1e6:.2f} MB as 4 independent runs "
          f"(matrix bytes charged once per iteration)")
    print("  per-iteration bytes: "
          + " ".join(f"nrhs={m}:{iteration_stream_bytes(g, 1, nrhs=m)}"
                     for m in (1, 4)))

    # --- 7. SELL-C-sigma layout: padding-honest bytes on skewed rows -----
    # Uniform ELL pads EVERY row to the longest row's width, so a few
    # dense rows blow up the streamed bytes for the whole matrix.  The
    # sliced layout (DESIGN.md section 12) sorts rows by length in
    # sigma-windows and pads each C-row slice only to its own width;
    # solver trajectories through it are bit-identical to the CSR
    # reference, only the traffic changes.
    from repro.kernels.ops import sell_pack_gsecsr
    from repro.sparse.csr import ell_layout

    sk = G.skewed_spd(512, seed=0)           # power-law rows + dense hubs
    gsk = pack_csr(sk, k=8)
    sell = sell_pack_gsecsr(gsk)             # cached on the instance
    ell = ell_layout(gsk)
    print(f"\nskewed matrix ({sk.nnz} nnz, widths {list(sell.widths)}):")
    print(f"  uniform ELL : padding_ratio={ell.padding_ratio:.3f} "
          f"tag-1 {ell.bytes_touched(1) / sk.nnz:.1f} B/nnz")
    print(f"  SELL-C-sigma: padding_ratio={sell.padding_ratio:.3f} "
          f"tag-1 {sell.bytes_touched(1) / sk.nnz:.1f} B/nnz")
    res_sell = solve_cg(sell, spmv(sk, jnp.ones((sk.shape[1],))),
                        tol=1e-8, maxiter=2000, params=fast)
    print(f"  solve_cg over the SELL pack: iters={int(res_sell.iters)} "
          f"relres={float(res_sell.relres):.2e} (bit-identical to CSR)")

    # --- 8. row-sharded distributed solve + tag-aware halo wire ----------
    # The same packed operator split across devices (DESIGN.md section
    # 13): each shard streams its row block through the same
    # tag-specialized decode, and only boundary x-entries cross the
    # interconnect -- at tag 1 as 2-byte GSE heads, at tag 2 head+tail1,
    # at tag 3 exact float64.  Needs > 1 device; on CPU run with
    # XLA_FLAGS=--xla_force_host_platform_device_count=8 (the import
    # above already happened, so we only demo when devices exist).
    from repro.distributed.partition import partition_gsecsr

    shards = min(4, jax.device_count())
    ap = G.poisson2d(24)
    gp = pack_csr(ap, k=8)
    bp = spmv(ap, jnp.ones((ap.shape[1],)))
    part = partition_gsecsr(gp, shards)
    print(f"\ndistributed ({shards} shard(s), poisson 24^2):")
    print("  per-shard matrix bytes (tag 1):",
          list(part.shard_stream_bytes(1)),
          "+ shared", part.shared_stream_bytes(),
          "= single-device", iteration_stream_bytes(gp, 1))
    print("  halo wire bytes/SpMV: "
          + " ".join(f"tag{t}={part.halo_wire_bytes(t, 'gse')}"
                     for t in (1, 2, 3))
          + "  (exact wire: "
          + str(part.halo_wire_bytes(1, "exact")) + " at every tag)")
    # solve_cg dispatches on the partition: the whole loop runs sharded
    # under shard_map (psum dots, halo exchange per iteration).
    res_d = solve_cg(part, bp, tol=1e-8, maxiter=2000, params=fast)
    print(f"  sharded solve_cg: iters={int(res_d.iters)} "
          f"relres={float(res_d.relres):.2e} "
          f"(exact wire: trajectory matches single-device)")

    # --- 9. guardrails, fault injection, tag-escalation recovery --------
    # (DESIGN.md section 14) Every solve now carries a structured
    # ``health`` status, and in-loop guards watch for breakdown
    # (p.Ap <= 0), divergence, non-finite residuals, and stalls.  Inject
    # a deterministic fault that makes the operator indefinite at tag 1
    # ONLY: the guard trips on the first iteration, rolls back to the
    # last finite checkpoint, promotes the tag (byte-accounted in
    # switch_iters), and finishes the solve on the healthy rungs -- the
    # paper's one-copy/three-precision storage is what makes this
    # escalation free of any repacking.
    from repro.robustness.faults import make_tag_fault_operator
    from repro.robustness.guards import health_name

    bad = make_tag_fault_operator(gp, mode="indefinite", fail_tag=1)
    res_f = solve_cg(bad, bp, tol=1e-8, maxiter=2000, params=fast)
    print("\nfault injection + recovery (indefinite at tag 1):")
    print(f"  tripped at iter {int(res_f.trip_iter)}, escalated: "
          f"switches={np.asarray(res_f.switch_iters).tolist()} -> "
          f"final tag {int(res_f.tag)}")
    print(f"  recovered: converged={bool(res_f.converged)} "
          f"relres={float(res_f.relres):.2e} "
          f"health={health_name(int(res_f.health))}")
    # The same guards ride every loop for free -- the clean solve above
    # reports health too:
    print(f"  clean sharded solve health: "
          f"{health_name(int(res_d.health))} "
          f"(trip_iter={int(res_d.trip_iter)})")

    # --- 10. launch-plan autotuner + roofline ledger ---------------------
    # (DESIGN.md section 15) Every Pallas kernel launch resolves its
    # blocks through one dispatcher: explicit > tuned cache > the
    # historical (8, 128) default -- with an empty cache nothing changes,
    # bit for bit.  ``autotune.get_or_tune`` sweeps the launch axes
    # (BM/BL, SELL C/sigma, width buckets) for this operator's shape
    # class ONCE and persists the winner (checksum-verified JSON, like
    # the pack cache); ``planned_spmv`` then dispatches through it.  The
    # ledger prices what each call SHOULD stream, and the roofline probe
    # turns wall time into fraction-of-attainable -- the unit the CI
    # perf gates use instead of microseconds.  Run the full sweep with:
    #   PYTHONPATH=src python benchmarks/run.py --tune
    from repro.kernels.ops import planned_spmv
    from repro.perf import autotune, roofline
    from repro.perf.ledger import achieved, spmv_ledger
    from repro.perf.timing import best_seconds

    plan, report, hit = autotune.get_or_tune(gsk, tag=1, layout="sell")
    print(f"\nautotuned launch plan for the skewed operator "
          f"(cache hit: {hit}):")
    print(f"  default plan: {report['default_us']:8.1f} us/SpMV")
    print(f"  tuned plan  : {report['us']:8.1f} us/SpMV  "
          f"{plan.to_dict()}")
    xs = jnp.ones((gsk.shape[1],), jnp.float32)
    sec = best_seconds(planned_spmv, gsk, xs, tag=1, layout="sell",
                      iters=5, warmup=2)
    roof = roofline.host_roofline(quick=True)   # persisted probe
    led = spmv_ledger(gsk, tag=1,
                      layout=sell_pack_gsecsr(gsk, plan=plan))
    rates = achieved(led, sec, roof)
    print(f"  re-measured through the tuned dispatcher: "
          f"{rates['us']:.1f} us, {rates['achieved_gbps']:.2f} GB/s "
          f"physical ({rates['effective_gbps']:.2f} effective), "
          f"roofline fraction {rates['roofline_fraction']:.3f}")

    # --- 11. flight recorder, span tracing, metrics ----------------------
    # (DESIGN.md section 16) Pass ``flight=FlightParams(...)`` to any
    # solver and a device-side ring buffer records one row per iteration
    # -- iteration, relres, the tag the iteration RAN at, guard health,
    # alpha/beta/curvature -- with ZERO host syncs in-loop and a
    # bit-identical trajectory (the recorder only observes values the
    # iteration already computed).  Spans capture the host-side timeline
    # around pack/tune/solve/serve, and the metrics registry exposes
    # every counter the caches and the solve service keep.
    from repro.obs import FlightParams, FlightLog, capture
    from repro.obs import metrics as om

    with capture("/tmp/quickstart_trace.jsonl") as tracer:
        res_fl = solve_cg(gi, bi, tol=1e-10, maxiter=30000, params=fast,
                          flight=FlightParams(capacity=64))
    flog = FlightLog.from_state(res_fl.flight)
    print("\nflight recording of the ill-conditioned stepped CG "
          f"(last {len(flog)} of {flog.recorded} iterations):")
    print(flog.pretty(max_rows=6))
    print(f"  summary: {flog.summary()['switch_iters']} switches, "
          f"first unhealthy iter {flog.first_unhealthy()}")
    print(f"  span capture: {len(tracer.events)} events -> "
          "/tmp/quickstart_trace.jsonl")
    # The registry already holds the pack-cache counters from every
    # solve above; Prometheus exposition is one call:
    line = [ln for ln in om.REGISTRY.to_prometheus().splitlines()
            if ln.startswith("repro_pack_cache_events_total")][:2]
    print("  metrics excerpt: " + "; ".join(line))

    # --- 12. resilient async serving: chunks, deadlines, breakers --------
    # (DESIGN.md section 17) The async service runs every solve in
    # bounded CHUNKS of iterations -- bit-identical to the unchunked
    # solve -- so at each chunk boundary it can join new requests into a
    # running batch, enforce deadlines mid-solve (an expired request
    # returns its last checkpoint FLAGGED, never silently dropped), and
    # shed typed responses under overload instead of queueing unboundedly.
    from repro.serve import AsyncSolveService, BreakerParams, Shed

    svc = AsyncSolveService(slots=4, params=fast, chunk_iters=32,
                            queue_limit=4,
                            breaker=BreakerParams(fail_threshold=2))
    svc.register("spd", a)
    svc.register("ill", ill)
    ids = [svc.submit("spd", b, tol=1e-10) for _ in range(3)]
    # More than the queue admits: the overflow submissions come back as
    # typed sheds carrying a reason (and retry_after_s for breaker sheds).
    extra = [svc.submit("spd", b, tol=1e-10) for _ in range(4)]
    sheds = [r for r in extra if isinstance(r, Shed)]
    reports = svc.run_until_idle()
    print("\nasync serve: "
          f"{sum(reports[i.id].converged for i in ids)}/{len(ids)} "
          f"converged, {len(sheds)} shed "
          f"({sheds[0].reason if sheds else '-'}), max batch "
          f"{max(r.batch_size for r in reports.values())}")
    # A request with a deadline comes back at the next chunk boundary
    # after expiry -- flagged, with the freshest finite iterate:
    rid = svc.submit("ill", bi, tol=1e-14, deadline_s=1e-3)
    rep = svc.run_until_idle()[rid.id]
    print(f"  deadline demo: health={rep.health} "
          f"deadline_exceeded={rep.deadline_exceeded} after {rep.iters} "
          "iterations (solution = last checkpoint)")
    # Repeat right-hand sides warm-start from the LRU keyed on
    # (handle, crc32(b)); breaker trips/sheds land in the registry:
    print("  warm LRU: " + ", ".join(
        f"{k}={int(svc.warm[k])}" for k in ("hit", "miss", "store")))
    # The chaos traffic replay (pack/wire/operand faults, stalls,
    # bursts; 100% detection and zero unflagged non-finites) runs with:
    #   PYTHONPATH=src python benchmarks/run.py --quick --serve

    # --- 13. adaptive per-group precision: the tag axis as a MAP ---------
    # (DESIGN.md section 18) Everything so far moved ONE scalar tag for
    # the whole operator.  The tags= axis generalizes it to a per-group
    # TagMap: each block of 8 rows carries its own tag, entries decode at
    # max(row tag, col tag) -- the masked operand stays exactly symmetric
    # -- and bytes blend per entry.  tags="adaptive" plans the map from
    # the data: run cheap, measure which groups' decode floor blocks the
    # TRUE residual, promote exactly those, restart from the iterate.
    import dataclasses

    from repro.solvers.adaptive import solve_adaptive
    from repro.sparse.spmv import spmv_gse

    adl = G.ill_conditioned_spd(16, decades=8.0, seed=0)
    ga = pack_csr(adl, k=8)
    ma = int(ga.shape[0])
    ba = np.zeros(ma)
    ba[np.random.default_rng(7).choice(ma, 4, replace=False)] = 1.0
    ba = jnp.asarray(ba)
    tol = 2e-3
    bn = float(jnp.linalg.norm(ba))
    print("\nadaptive per-group precision (ill-conditioned SPD, "
          f"n={ma}, tol={tol:g}):")
    best_uniform = None
    for t in (1, 2, 3):
        # max_tag=t pins the monitor: a pure uniform tag-t schedule.
        r = solve_cg(ga, ba, tol=tol, maxiter=4000,
                     params=dataclasses.replace(fast, max_tag=t), tags=t)
        true = float(jnp.linalg.norm(
            ba - spmv_gse(ga, r.x, tag=3))) / bn
        # (iters+1) streams at tag t + one tag-3 pass for the true check.
        by = (int(r.iters) + 1) * ga.bytes_touched(t) + ga.bytes_touched(3)
        ok = true <= tol
        if ok and (best_uniform is None or by < best_uniform):
            best_uniform = by
        print(f"  uniform tag {t}: iters={int(r.iters):4d} "
              f"true relres={true:.2e} bytes={by / 1e6:7.2f} MB"
              + ("" if ok else "  (misses tol: tag-1 decode floor)"))
    res_ad = solve_adaptive(ga, ba, tol=tol, maxiter=4000)
    counts = {t: c for t, c in res_ad.tagmap.tag_counts().items() if c}
    print(f"  adaptive map : iters={res_ad.iters:4d} "
          f"true relres={res_ad.true_relres:.2e} "
          f"bytes={res_ad.spmv_bytes / 1e6:7.2f} MB  groups={counts}")
    print(f"  -> beats best uniform schedule by "
          f"{100 * (1 - res_ad.spmv_bytes / best_uniform):.1f}% of bytes "
          "at equal-or-better residual")
    # The same axis rides every entry point: solve_cg(..., tags=TagMap)
    # masks per group; the serve layer takes register/submit
    # tags="adaptive"; uniform maps are bit-identical to the int tag.
    # The gated comparison (incl. a skewed generator where the upfront
    # Neumann profile plans the map) runs with:
    #   PYTHONPATH=src python benchmarks/run.py --adaptive


if __name__ == "__main__":
    main()
