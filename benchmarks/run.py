"""Benchmark harness: one module per paper table/figure (deliverable d).

Prints ``name,us_per_call,derived`` CSV rows.

  fig1   -- Fig. 1   value/exponent/mantissa entropy, top-k coverage
  fig45  -- Figs 4/5 shared-exponent count k sweep (speed + error)
  fig6   -- Fig. 6   SpMV format comparison (GSE-SEM vs FP16/BF16/FP64)
  tab34  -- Tables III/IV  CG/GMRES convergence per format
  fig89  -- Figs 8/9 solver wall time + GSE-SEM* projection (Eq. 7)
  lm     -- beyond-paper: GSE-SEM LM weight serving ladder
  roofline -- dry-run roofline table (deliverable g)

``--quick`` runs a trimmed fig6 SpMV sweep and writes ``BENCH_spmv.json``
(format/tag x time x modeled GB/s from the ``bytes_touched`` accounting)
at the repo root -- the perf-trajectory artifact CI regresses against.

``--precond {none,jacobi,spai0}`` adds stepped preconditioned rows to
fig89 (GSE-packed preconditioner riding the operator's tag schedule;
preconditioner bytes charged at the per-iteration tag actually run).

``--nrhs N`` (N > 1) adds batched multi-RHS stepped-CG rows to fig89
(matrix bytes charged once per iteration, vector bytes per active
column); with ``--quick`` it instead runs a trimmed batched solve and
writes ``BENCH_batch.json`` -- per-request iterations/residual plus the
bytes/iteration ratio vs nrhs=1 the acceptance bar bounds (< 2x at
nrhs=4 on the stream-dominated smoke matrix).

``--shards N`` (N > 1) adds row-sharded distributed stepped-CG rows to
fig89 (per-shard matrix streams + tag-aware halo wire bytes, DESIGN.md
section 13); with ``--quick`` it instead runs the distributed smoke and
writes ``BENCH_dist.json``, gating exact-wire parity with ``solve_cg``,
the per-shard byte-sum identity, and the tag-1 < 50% tag-3 halo wire
ladder.  Forces ``N`` host CPU devices when XLA_FLAGS is unset.

``--tune`` runs the autotune + roofline sweep (benchmarks/tune_bench.py,
DESIGN.md section 15) and writes ``BENCH_roofline.json``: per-kernel
{flops, bytes, achieved_gbps, roofline_fraction} for default and tuned
launch plans, the gse_h-vs-fp64 parity case, and a persisted-cache
replay pass.  Gates on roofline FRACTION (tuned >= untuned), wall-clock
parity below the decode crossover, and zero re-sweeps on replay -- never
on absolute microseconds.  Composes with ``--quick``.

``--robust`` runs the fault-injection / recovery / guard-overhead sweep
(benchmarks/robust_bench.py, DESIGN.md section 14) and writes
``BENCH_robust.json``, gating 100% detection of injected pack/cache/wire
corruption and 100% recovery of the low-tag operator faults.  Forces two
host CPU devices (for the wire-checksum harness) when XLA_FLAGS is
unset.  Composes with ``--quick`` for the trimmed CI smoke.

``--serve`` runs the chaos traffic-replay harness for the async solve
service (benchmarks/serve_bench.py, DESIGN.md section 17) and writes
``BENCH_serve.json``: p50/p95/p99 end-to-end latency, shed counts, and a
per-family chaos ledger (pack + pack-cache corruption, wire faults,
operand faults, slow-shard stalls, queue bursts).  Gates 100% chaos
detection, zero UNFLAGGED non-finite solutions, typed shedding under
overload with a bounded shed rate, and a loose absolute p99 bound (the
injected stall skew dominates, so the gate is not wall-clock noise).
Forces two host CPU devices (for the sharded wire-fault case) when
XLA_FLAGS is unset.  Composes with ``--quick`` for the trimmed CI smoke.

``--obs`` runs the observability sweep (benchmarks/obs_bench.py,
DESIGN.md section 16) and writes ``BENCH_obs.json`` plus a span capture
``TRACE_obs.jsonl``, gating recorder-on/off bit identity across every
solver family, flight-vs-monitor telemetry consistency, the <= 1.10
flight+span overhead ratio, and trace schema validity.  The serve-replay
section reports p50/p95/p99 flush latency and bytes/request straight
from the metrics registry.  Forces two host CPU devices (for the sharded
identity case) when XLA_FLAGS is unset.  Composes with ``--quick``.

``--adaptive`` runs the per-group precision sweep
(benchmarks/adaptive_bench.py, DESIGN.md section 18) and writes
``BENCH_adaptive.json``: on the ill-conditioned and skewed generators,
uniform pinned tag-{1,2,3} CG baselines vs the data-driven TagMap
schedule from ``solve_adaptive``.  Gates the adaptive run to an
equal-or-better TRUE (tag-3) residual with STRICTLY fewer streamed
bytes than the best uniform schedule that meets tolerance.
"""
from __future__ import annotations

import argparse
import json
import os
import pathlib
import sys
import traceback

_REPO_ROOT = pathlib.Path(__file__).resolve().parent.parent
if str(_REPO_ROOT) not in sys.path:  # allow `python benchmarks/run.py`
    sys.path.insert(0, str(_REPO_ROOT))


def _write_payload(payload: dict, path: pathlib.Path) -> None:
    """Stamp the provenance header (DESIGN.md §16) and write the artifact.

    Every BENCH_*.json carries WHAT produced it -- git sha, jax/jaxlib
    versions, device kind, host roofline, UTC timestamp -- so a regression
    diff can tell a code change from an environment change.  Written
    BEFORE any gate raises so a failing run still uploads diagnostics.
    """
    from benchmarks import common

    payload["provenance"] = common.provenance()
    path.write_text(json.dumps(payload, indent=2, sort_keys=True) + "\n")
    print(f"wrote {path}", file=sys.stderr)


def run_quick(out_path: pathlib.Path | None = None) -> dict:
    """CI smoke mode: trimmed SpMV format sweep -> BENCH_spmv.json.

    The ``skewed_layouts`` entry compares uniform-ELL vs SELL-C-σ padding
    on the skewed benchmark matrix and is gated (DESIGN.md §12): the SELL
    layout must waste < 50% of uniform ELL's padded-slot fraction, stream
    < 50% of its modeled tag-1 bytes, and keep tag-1 effective bytes
    within 10% of the kernel's 10 B per slot (6 B/nnz of segments + 4 B of
    gathered x; ``precision_table.KERNEL_SLOT_BYTES``).  The JSON is written
    BEFORE the gate raises so a failing run still uploads diagnostics.
    """
    from benchmarks import fig6_spmv_formats

    results = fig6_spmv_formats.run(quick=True)
    payload = {
        "bench": "spmv_formats_quick",
        "schema": "matrix -> format -> {us, err, gflops, bytes_per_nnz, "
                  "bytes_touched, model_gbps}; skewed_layouts -> "
                  "{ell, sell} -> {slots, padding_ratio, bytes_touched_tagT,"
                  " bytes_per_nnz_tag1}",
        "results": results,
    }
    _write_payload(payload, out_path or (_REPO_ROOT / "BENCH_spmv.json"))

    lay = results["skewed_layouts"]["layouts"]
    sell, ell = lay["sell"], lay["ell"]
    if not sell["padding_ratio"] < 0.5 * ell["padding_ratio"]:
        raise SystemExit(
            f"skewed smoke: SELL padding_ratio {sell['padding_ratio']:.4f} "
            f"not < 0.5x uniform-ELL's {ell['padding_ratio']:.4f}"
        )
    if not sell["bytes_touched_tag1"] < 0.5 * ell["bytes_touched_tag1"]:
        raise SystemExit(
            f"skewed smoke: SELL tag-1 bytes {sell['bytes_touched_tag1']} "
            f"not < 50% of uniform-ELL's {ell['bytes_touched_tag1']}"
        )
    from repro.core.precision_table import KERNEL_SLOT_BYTES

    per_slot = KERNEL_SLOT_BYTES[1]
    if abs(sell["bytes_per_nnz_tag1"] - per_slot) / per_slot > 0.10:
        raise SystemExit(
            f"skewed smoke: SELL tag-1 effective {sell['bytes_per_nnz_tag1']:.3f} "
            f"B/nnz strayed > 10% from the kernel's {per_slot} B per slot"
        )
    return payload


def run_quick_batch(nrhs: int, out_path: pathlib.Path | None = None) -> dict:
    """CI batched smoke: one multi-RHS stepped CG -> BENCH_batch.json.

    Runs ``solve_cg_batched`` over ``nrhs`` right-hand sides sharing one
    packed random-SPD operand (nnz/row high enough that the matrix
    segments dominate the stream) and records the byte-model headline:
    bytes/iteration at ``nrhs`` vs the unchanged nrhs=1 figure.
    """
    from benchmarks import fig89_solver_time
    from repro.core.precision import MonitorParams
    from repro.sparse import generators as G
    from repro.sparse.csr import pack_csr

    a = G.random_spd(600, seed=5)
    g = pack_csr(a, k=8)
    params = MonitorParams(t=40, l=60, m=30, rsd_limit=0.5, reldec_limit=0.45)
    case = fig89_solver_time.batched_case(a, g, nrhs, params=params,
                                          maxiter=1500, seed=5)
    payload = {
        "bench": "batched_multirhs_quick",
        "schema": "batched stepped CG over random_spd_600: per-column "
                  "iters/relres/switches + bytes/iteration vs nrhs=1",
        "matrix": "random_spd_600",
        "results": case,
    }
    _write_payload(payload, out_path or (_REPO_ROOT / "BENCH_batch.json"))
    if not all(case["converged"]):
        raise SystemExit("batched smoke: not all columns converged")
    if nrhs >= 2 and case["per_iter_ratio"] >= 2.0:
        raise SystemExit(
            f"batched smoke: bytes/iteration ratio {case['per_iter_ratio']:.2f} "
            f"at nrhs={nrhs} not < 2x the nrhs=1 figure"
        )
    return payload


def run_quick_dist(shards: int, out_path: pathlib.Path | None = None) -> dict:
    """CI distributed smoke: row-sharded stepped CG -> BENCH_dist.json.

    Runs ``fig89.dist_case`` (Poisson 24^2 over ``shards`` forced host
    devices) and gates the distributed contracts (DESIGN.md §13):

      * convergence (exact AND gse wire) with the exact-wire trajectory
        within 1e-10 of single-device ``solve_cg``;
      * the byte-model identity -- per-shard matrix streams + shared
        terms sum EXACTLY to the single-device ``iteration_stream_bytes``;
      * the halo wire ladder -- tag-1 wire bytes < 50% of tag-3's.

    The JSON is written BEFORE the gates raise so a failing run still
    uploads diagnostics.
    """
    from benchmarks import fig89_solver_time
    from repro.core.precision import MonitorParams
    from repro.sparse import generators as G
    from repro.sparse.csr import pack_csr

    a = G.poisson2d(24)
    g = pack_csr(a, k=8)
    params = MonitorParams(t=40, l=60, m=30, rsd_limit=0.5, reldec_limit=0.45)
    case = fig89_solver_time.dist_case(a, g, shards, wire="gse",
                                       params=params, tol=1e-8,
                                       maxiter=2000, seed=7)
    payload = {
        "bench": "distributed_sharded_quick",
        "schema": "row-sharded stepped CG over poisson2d_24: exact-wire "
                  "parity vs solve_cg, per-shard byte model + halo wire "
                  "ladder (DESIGN.md section 13)",
        "matrix": "poisson2d_24",
        "results": case,
    }
    _write_payload(payload, out_path or (_REPO_ROOT / "BENCH_dist.json"))
    if not case["converged"]:
        raise SystemExit("dist smoke: gse-wire sharded run did not converge")
    if case["exact_iters"] != case["ref_iters"]:
        raise SystemExit(
            f"dist smoke: exact-wire iters {case['exact_iters']} != "
            f"single-device {case['ref_iters']}"
        )
    if case["exact_x_maxdiff"] > 1e-10:
        raise SystemExit(
            f"dist smoke: exact-wire trajectory strayed "
            f"{case['exact_x_maxdiff']:.2e} > 1e-10 from single-device"
        )
    if not case["byte_sum_identity"]:
        raise SystemExit(
            "dist smoke: per-shard bytes + shared terms != single-device "
            "iteration_stream_bytes"
        )
    w = case["halo_wire_bytes"]
    if not w[1] < 0.5 * w[3]:
        raise SystemExit(
            f"dist smoke: tag-1 halo wire bytes {w[1]} not < 50% of "
            f"tag-3's {w[3]}"
        )
    return payload


def run_robust(quick: bool, out_path: pathlib.Path | None = None) -> dict:
    """Robustness sweep: fault detection + recovery -> BENCH_robust.json.

    Gates (DESIGN.md §14): every seeded pack/cache/wire corruption must be
    DETECTED (rate == 1.0) and every low-tag operator fault must RECOVER
    through tag escalation to a converged finite solution (rate == 1.0).
    The clean-path guard-overhead ratio rides along in the JSON (the
    acceptance bar is <= 1.10 on quiet hardware) but is not hard-gated --
    shared CI runners make wall-clock ratios too noisy to fail a build on.
    The JSON is written BEFORE the gates raise so a failing run still
    uploads diagnostics.
    """
    from benchmarks import robust_bench

    results = robust_bench.run(quick=quick)
    payload = {
        "bench": "robustness_fault_injection",
        "schema": "detection -> {cases, rate, wire_skipped}; recovery -> "
                  "{cases, rate}; overhead -> {guards_on_s, guards_off_s, "
                  "ratio} (DESIGN.md section 14)",
        "results": results,
    }
    _write_payload(payload, out_path or (_REPO_ROOT / "BENCH_robust.json"))

    det = results["detection"]
    if det["wire_skipped"]:
        raise SystemExit(
            "robust sweep: wire-checksum cases skipped (need >= 2 devices; "
            "run.py forces them when XLA_FLAGS is unset)"
        )
    if det["rate"] != 1.0:
        missed = [k for k, v in det["cases"].items() if not v]
        raise SystemExit(
            f"robust sweep: detection rate {det['rate']:.3f} != 1.0; "
            f"missed {missed}"
        )
    rec = results["recovery"]
    if rec["rate"] != 1.0:
        missed = [k for k, v in rec["cases"].items() if not v["recovered"]]
        raise SystemExit(
            f"robust sweep: recovery rate {rec['rate']:.3f} != 1.0; "
            f"failed {missed}"
        )
    if results["overhead"]["ratio"] > 1.10:
        print(
            f"WARNING: clean-path guard overhead ratio "
            f"{results['overhead']['ratio']:.3f} > 1.10 "
            "(not gated: wall-clock noise)", file=sys.stderr,
        )
    return payload


def run_tune(quick: bool, out_path: pathlib.Path | None = None) -> dict:
    """Autotune + roofline sweep -> BENCH_roofline.json (DESIGN.md §15).

    Gates on ROOFLINE FRACTION and counter discipline, not absolute
    microseconds (heterogeneous CI hosts move the roof and the
    measurement together):

      * every tuned plan is no slower than the default on the sweep's own
        measurements, and its roofline fraction at the shared byte model
        is no lower than the untuned one;
      * the gse_h-vs-fp64 smoke case holds wall-clock parity (>= 0.90)
        under min timing -- the case sits below the measured
        decode-overhead crossover (``autotune.DECODE_BOUND_NNZ``), where
        byte savings cannot show up in wall time; above the crossover the
        gate tightens to effective-GB/s dominance;
      * the replay pass re-resolves every plan from the PERSISTED cache:
        all hits, zero re-sweeps.

    The JSON is written BEFORE the gates raise so a failing run still
    uploads diagnostics.
    """
    from benchmarks import tune_bench

    results = tune_bench.run(quick=quick)
    payload = {
        "bench": "autotune_roofline",
        "schema": "host -> {stream_gbps, peak_gflops}; kernels -> per "
                  "(tag, layout, nrhs) {untuned, tuned} x {flops, bytes, "
                  "us, achieved_gbps, effective_gbps, roofline_fraction}; "
                  "formats -> gse_h vs fp64 parity; replay -> cache-hit "
                  "counters (DESIGN.md section 15)",
        "results": results,
    }
    _write_payload(payload, out_path or (_REPO_ROOT / "BENCH_roofline.json"))

    for row in results["kernels"]:
        if row["speedup"] < 1.0 - 1e-9:
            raise SystemExit(
                f"tune sweep: tuned plan slower than default on {row['key']}"
                f" (speedup {row['speedup']:.3f})"
            )
        if (row["tuned"]["model_roofline_fraction"]
                < row["untuned"]["roofline_fraction"] - 1e-9):
            raise SystemExit(
                f"tune sweep: tuned roofline fraction "
                f"{row['tuned']['model_roofline_fraction']:.4f} below "
                f"untuned {row['untuned']['roofline_fraction']:.4f} on "
                f"{row['key']}"
            )
    fmt = results["formats"]
    if fmt["decode_bound"]:
        if fmt["parity"] < 0.90:
            raise SystemExit(
                f"tune sweep: gse_h wall-clock parity {fmt['parity']:.3f} "
                "< 0.90 vs fp64 on the decode-bound smoke case"
            )
    elif fmt["gse_h"]["effective_gbps"] < fmt["fp64"]["achieved_gbps"]:
        raise SystemExit(
            f"tune sweep: gse_h effective "
            f"{fmt['gse_h']['effective_gbps']:.2f} GB/s below fp64's "
            f"{fmt['fp64']['achieved_gbps']:.2f} above the crossover"
        )
    rep = results["replay"]
    if rep["hits"] != rep["configs"] or rep["sweeps"] != 0:
        raise SystemExit(
            f"tune sweep: replay hit {rep['hits']}/{rep['configs']} plans "
            f"with {rep['sweeps']} re-sweeps (want all hits, zero sweeps)"
        )
    return payload


def run_serve(quick: bool, out_path: pathlib.Path | None = None) -> dict:
    """Chaos traffic replay -> BENCH_serve.json (DESIGN.md §17).

    Gates:

      * every chaos family is DETECTED/handled (rate == 1.0): pack and
        pack-cache corruption repacked, wire + operand faults flagged
        (breaker opens, then heals), deadline expiries returned as
        flagged checkpoints, queue bursts shed typed responses;
      * ZERO unflagged non-finite solutions -- a NaN that reaches a
        caller must carry health != "ok";
      * overload sheds typed responses (both families occurred) and the
        shed rate stays below 0.9 -- the service degrades, it does not
        collapse;
      * p99 end-to-end latency (by the service's own skewed clock) under
        a loose 60 s absolute bound: the deterministic stall injection
        dominates it, so the gate catches pathological re-queue loops,
        not CI jitter.

    The JSON is written BEFORE the gates raise so a failing run still
    uploads diagnostics.
    """
    from benchmarks import serve_bench

    results = serve_bench.run(quick=quick)
    payload = {
        "bench": "serve_chaos_replay",
        "schema": "traffic -> {submitted, completed, sheds, shed_rate, "
                  "warm, max_batch}; latency_s -> {p50, p95, p99}; chaos "
                  "-> {cases, rate, wire_skipped}; unflagged_nonfinite "
                  "(DESIGN.md section 17)",
        "results": results,
    }
    _write_payload(payload, out_path or (_REPO_ROOT / "BENCH_serve.json"))

    chaos = results["chaos"]
    if chaos["wire_skipped"]:
        raise SystemExit(
            "serve replay: wire-fault case skipped (need >= 2 devices; "
            "run.py forces them when XLA_FLAGS is unset)"
        )
    if chaos["rate"] != 1.0:
        missed = [k for k, v in chaos["cases"].items() if not v]
        raise SystemExit(
            f"serve replay: chaos detection rate {chaos['rate']:.3f} != "
            f"1.0; missed {missed}"
        )
    if results["unflagged_nonfinite"] != 0:
        raise SystemExit(
            f"serve replay: {results['unflagged_nonfinite']} non-finite "
            "solution(s) returned without a health flag"
        )
    traffic = results["traffic"]
    if traffic["sheds"]["queue_full"] < 1 \
            or traffic["sheds"]["breaker_open"] < 1:
        raise SystemExit(
            f"serve replay: expected both shed families under the chaos "
            f"trace, got {traffic['sheds']}"
        )
    if traffic["shed_rate"] >= 0.9:
        raise SystemExit(
            f"serve replay: shed rate {traffic['shed_rate']:.2f} >= 0.9 "
            "(the service collapsed instead of degrading)"
        )
    if results["latency_s"]["p99"] > 60.0:
        raise SystemExit(
            f"serve replay: p99 latency {results['latency_s']['p99']:.1f}"
            " s over the 60 s bound (requests re-queued pathologically?)"
        )
    return payload


def run_obs(quick: bool, out_path: pathlib.Path | None = None,
            trace_path: pathlib.Path | None = None) -> dict:
    """Observability sweep -> BENCH_obs.json + TRACE_obs.jsonl (§16).

    Runs ``benchmarks/obs_bench.py`` under a span capture and gates:

      * every recorder-on solve is BIT-IDENTICAL to recorder-off (and its
        telemetry consistent with the solver's own monitor/guard report)
        across CG fused/guarded, PCG, GMRES, batched, and sharded;
      * the clean-path overhead ratio with flight + spans active is
        <= 1.10 (the observability twin of the guard-overhead bar);
      * the captured trace JSONL round-trips through the schema
        validator (``repro.obs.trace.validate_jsonl``).

    The JSON and trace are written BEFORE the gates raise so a failing
    run still uploads diagnostics.
    """
    from benchmarks import obs_bench
    from repro.obs import trace as OT

    tpath = trace_path or (_REPO_ROOT / "TRACE_obs.jsonl")
    with OT.capture(str(tpath)):
        results = obs_bench.run(quick=quick)
    print(f"wrote {tpath}", file=sys.stderr)
    payload = {
        "bench": "observability",
        "schema": "bit_identity -> case -> {identical, consistent, rows, "
                  "switch_iters}; overhead -> {obs_on_s, obs_off_s, ratio}"
                  "; serve -> {flush_latency_s, request_bytes, stats}; "
                  "metrics -> registry exposition (DESIGN.md section 16)",
        "results": results,
    }
    _write_payload(payload, out_path or (_REPO_ROOT / "BENCH_obs.json"))

    n_events = OT.validate_jsonl(str(tpath))
    if n_events < 1:
        raise SystemExit("obs sweep: trace capture recorded no spans")
    for name, case in results["bit_identity"].items():
        if "skipped" in case:
            raise SystemExit(
                f"obs sweep: {name} identity case skipped ({case['skipped']}"
                "; run.py forces 2 host devices when XLA_FLAGS is unset)"
            )
        if not case["identical"]:
            raise SystemExit(
                f"obs sweep: recorder-on solve NOT bit-identical on {name}"
            )
        if not case["consistent"]:
            raise SystemExit(
                f"obs sweep: flight telemetry inconsistent with the "
                f"solver's own report on {name}"
            )
    if results["overhead"]["ratio"] > 1.10:
        raise SystemExit(
            f"obs sweep: flight+span overhead ratio "
            f"{results['overhead']['ratio']:.3f} > 1.10"
        )
    lat = results["serve"]["flush_latency_s"]
    if not lat["count"] or lat["p99"] is None:
        raise SystemExit("obs sweep: serve replay recorded no flush latency")
    return payload


def run_adaptive(quick: bool, out_path: pathlib.Path | None = None) -> dict:
    """Adaptive per-group precision sweep -> BENCH_adaptive.json (§18).

    Runs ``benchmarks/adaptive_bench.py``: on the ill-conditioned and
    skewed generators, the data-driven per-group tag map must reach an
    equal-or-better TRUE (tag-3) residual with STRICTLY fewer total
    streamed bytes than the best uniform-tag schedule that meets the
    same tolerance.  Uniform baselines pin the monitor (``max_tag=t`` +
    ``tags=t``) and are charged ``(iters+1) * bytes_touched(t)`` plus
    one tag-3 true-residual pass; the adaptive run bills its own
    ``spmv_bytes`` counter (blended segments + billed true checks).
    The JSON is written BEFORE the gates raise so a failing run still
    uploads diagnostics.
    """
    from benchmarks import adaptive_bench

    results = adaptive_bench.run(quick=quick)
    payload = {
        "bench": "adaptive_tagmap",
        "schema": "case -> {uniform: [{tag, iters, true_relres, bytes, "
                  "meets_tol}], adaptive: {profile, iters, true_relres, "
                  "bytes, tag_counts, promotions, chunks}, "
                  "best_uniform_bytes, savings_frac} (DESIGN.md "
                  "section 18)",
        "results": results,
    }
    _write_payload(payload, out_path or (_REPO_ROOT / "BENCH_adaptive.json"))

    for name, case in results.items():
        ad = case["adaptive"]
        if not ad["converged"]:
            raise SystemExit(
                f"adaptive sweep: {name} adaptive solve did not converge "
                f"(true relres {ad['true_relres']:.3e})"
            )
        if ad["true_relres"] > case["tol"]:
            raise SystemExit(
                f"adaptive sweep: {name} adaptive TRUE residual "
                f"{ad['true_relres']:.3e} misses tol {case['tol']:g}"
            )
        best = case["best_uniform_bytes"]
        if best is None:
            raise SystemExit(
                f"adaptive sweep: {name} has no qualifying uniform "
                "baseline (every pinned tag missed tolerance)"
            )
        if not ad["bytes"] < best:
            raise SystemExit(
                f"adaptive sweep: {name} adaptive bytes {ad['bytes']} not "
                f"strictly < best uniform {best}"
            )
        print(
            f"adaptive sweep: {name} saves "
            f"{100 * case['savings_frac']:.1f}% bytes vs best uniform "
            f"(map {ad['tag_counts']})", file=sys.stderr,
        )
    return payload


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated subset: fig1,fig45,fig6,tab34,"
                         "fig89,lm,roofline")
    ap.add_argument("--quick", action="store_true",
                    help="smoke mode: trimmed SpMV sweep, emit "
                         "BENCH_spmv.json and exit")
    ap.add_argument("--precond", default="none",
                    choices=["none", "jacobi", "spai0"],
                    help="add stepped preconditioned solver rows to fig89 "
                         "(GSE-packed preconditioner riding the tag "
                         "schedule; includes the ill-conditioned CG case)")
    ap.add_argument("--nrhs", type=int, default=1,
                    help="batch width for the multi-RHS rows: > 1 adds "
                         "batched stepped-CG rows to fig89, or (with "
                         "--quick) runs the batched smoke and writes "
                         "BENCH_batch.json")
    ap.add_argument("--layout", default="nnz", choices=["nnz", "sell"],
                    help="fig89 byte model: 'sell' charges the GSE rows "
                         "the SELL-C-sigma layout's actual padded slots "
                         "instead of nnz only (DESIGN.md section 12)")
    ap.add_argument("--shards", type=int, default=1,
                    help="shard count for the distributed rows: > 1 adds "
                         "row-sharded stepped-CG rows to fig89, or (with "
                         "--quick) runs the distributed smoke and writes "
                         "BENCH_dist.json (forces that many host CPU "
                         "devices if XLA_FLAGS is unset)")
    ap.add_argument("--tune", action="store_true",
                    help="autotune + roofline sweep -> BENCH_roofline.json"
                         ", gating roofline fraction (tuned >= untuned), "
                         "gse_h/fp64 parity, and zero-re-sweep cache "
                         "replay (DESIGN.md section 15); composes with "
                         "--quick for the CI smoke")
    ap.add_argument("--robust", action="store_true",
                    help="fault-injection / recovery / guard-overhead "
                         "sweep -> BENCH_robust.json, gating 100% "
                         "detection and recovery (DESIGN.md section 14; "
                         "forces 2 host CPU devices if XLA_FLAGS is unset)")
    ap.add_argument("--serve", action="store_true",
                    help="chaos traffic replay against the async solve "
                         "service -> BENCH_serve.json, gating 100% chaos "
                         "detection, zero unflagged non-finite solutions, "
                         "typed shedding, and a loose absolute p99 bound "
                         "(DESIGN.md section 17; forces 2 host CPU "
                         "devices if XLA_FLAGS is unset)")
    ap.add_argument("--obs", action="store_true",
                    help="observability sweep -> BENCH_obs.json + "
                         "TRACE_obs.jsonl, gating recorder-on/off bit "
                         "identity, the <= 1.10 flight+span overhead "
                         "ratio, and trace schema validity (DESIGN.md "
                         "section 16; forces 2 host CPU devices if "
                         "XLA_FLAGS is unset)")
    ap.add_argument("--adaptive", action="store_true",
                    help="adaptive per-group precision sweep -> "
                         "BENCH_adaptive.json, gating the data-driven "
                         "tag map to equal-or-better TRUE residual with "
                         "strictly fewer streamed bytes than the best "
                         "uniform-tag schedule on the ill-conditioned "
                         "and skewed generators (DESIGN.md section 18)")
    args = ap.parse_args()
    if args.quick and args.only:
        ap.error("--quick and --only are mutually exclusive")
    if args.nrhs < 1:
        ap.error("--nrhs must be >= 1")
    if args.shards < 1:
        ap.error("--shards must be >= 1")
    if args.quick and args.shards > 1 and args.nrhs > 1:
        ap.error("--quick runs ONE smoke: pass --shards or --nrhs, not "
                 "both (the CI jobs run them separately)")
    if args.robust and (args.shards > 1 or args.nrhs > 1 or args.only):
        ap.error("--robust is its own sweep: drop --shards/--nrhs/--only")
    if args.tune and (args.robust or args.shards > 1 or args.nrhs > 1
                      or args.only):
        ap.error("--tune is its own sweep: drop "
                 "--robust/--shards/--nrhs/--only")
    if args.obs and (args.robust or args.tune or args.shards > 1
                     or args.nrhs > 1 or args.only):
        ap.error("--obs is its own sweep: drop "
                 "--robust/--tune/--shards/--nrhs/--only")
    if args.serve and (args.robust or args.tune or args.obs
                       or args.shards > 1 or args.nrhs > 1 or args.only):
        ap.error("--serve is its own sweep: drop "
                 "--robust/--tune/--obs/--shards/--nrhs/--only")
    if args.adaptive and (args.robust or args.tune or args.obs
                          or args.serve or args.shards > 1
                          or args.nrhs > 1 or args.only):
        ap.error("--adaptive is its own sweep: drop "
                 "--robust/--tune/--obs/--serve/--shards/--nrhs/--only")
    force_devices = args.shards if args.shards > 1 else (
        2 if args.robust or args.obs or args.serve else 0)
    if force_devices and "xla_force_host_platform_device_count" not in (
            os.environ.get("XLA_FLAGS", "")):
        # Must land before jax initializes (all jax imports are lazy,
        # below): the distributed rows / wire-checksum harness need the
        # forced host devices.
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={force_devices}"
        ).strip()

    print("name,us_per_call,derived")
    if args.adaptive:
        run_adaptive(quick=args.quick)
        return
    if args.serve:
        run_serve(quick=args.quick)
        return
    if args.obs:
        run_obs(quick=args.quick)
        return
    if args.robust:
        run_robust(quick=args.quick)
        return
    if args.tune:
        run_tune(quick=args.quick)
        return
    if args.quick:
        if args.shards > 1:  # distributed smoke only; the SpMV sweep and
            run_quick_dist(args.shards)  # batched smoke are other jobs
        elif args.nrhs > 1:
            run_quick_batch(args.nrhs)
        else:
            run_quick()
        return
    want = set(args.only.split(",")) if args.only else None

    from benchmarks import (fig1_entropy, fig45_k_sweep, fig6_spmv_formats,
                            fig89_solver_time, lm_gse_serving, roofline,
                            tab34_solver_convergence)

    from functools import partial

    suites = {
        "fig1": fig1_entropy.run,
        "fig45": fig45_k_sweep.run,
        "fig6": fig6_spmv_formats.run,
        "tab34": tab34_solver_convergence.run,
        "fig89": partial(fig89_solver_time.run, precond=args.precond,
                         nrhs=args.nrhs, layout=args.layout,
                         shards=args.shards),
        "lm": lm_gse_serving.run,
        "roofline": roofline.run,
    }
    failed = []
    for name, fn in suites.items():
        if want and name not in want:
            continue
        try:
            fn()
        except Exception:
            failed.append(name)
            traceback.print_exc()
    if failed:
        print(f"FAILED suites: {failed}", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
