"""Robustness bench: fault detection, escalation recovery, guard overhead.

Three headline numbers for the guardrail stack (DESIGN.md §14):

  * ``detection_rate``  -- fraction of seeded silent-corruption injections
    caught by the integrity machinery: CRC32 segment checksums on packed
    GSE operands (``robustness.faults``), checksum-verified entries of the
    ``kernels/ops._cached_pack`` LRU, and the position-weighted u32 wire
    checksums riding alongside halo payloads (``distributed.wire``).
  * ``recovery_rate``   -- fraction of deterministic low-tag operator
    faults (indefinite / NaN-producing at tags <= fail_tag) that the
    guard + tag-escalation ladder detects AND solves through to a
    converged, finite solution at a higher rung.
  * ``overhead_ratio``  -- clean-path wall-time ratio of the guarded vs
    unguarded stepped CG loop on the fig89 smoke matrix (the guards
    compile into the same jitted iteration; the acceptance bar is <= 10%).

Wire detection needs >= 2 devices (``run.py --robust`` forces two host
CPU devices when XLA_FLAGS is unset); with one device that family is
skipped and reported as such.
"""
from __future__ import annotations

import numpy as np

from benchmarks.common import emit, timed

import jax  # noqa: E402  (common enables x64 first)
import jax.numpy as jnp

_PARAMS = None  # built lazily: MonitorParams import must follow x64 setup


def _params():
    global _PARAMS
    if _PARAMS is None:
        from repro.core.precision import MonitorParams
        _PARAMS = MonitorParams(t=40, l=60, m=30, rsd_limit=0.5,
                                reldec_limit=0.45)
    return _PARAMS


def _operand(n=16, k=8):
    from repro.sparse import generators as G
    from repro.sparse.csr import pack_csr

    csr = G.poisson2d(n)
    return csr, pack_csr(csr, k=k)


def detection_pack(seeds=(0, 1, 2)) -> dict:
    """Seeded bit-flips in every packed GSE segment vs the CRC32 refs."""
    from repro.robustness.faults import (GSECSR_SEGMENTS, corrupt_gsecsr,
                                         gsecsr_checksums, verify_gsecsr)

    _, g = _operand()
    ref = gsecsr_checksums(g)
    cases = {}
    for target in GSECSR_SEGMENTS:
        for seed in seeds:
            bad = corrupt_gsecsr(g, target, seed)
            cases[f"pack/{target}/s{seed}"] = target in verify_gsecsr(bad, ref)
    return cases


def detection_pack_cache(seeds=(0, 1, 2)) -> dict:
    """Corrupt a memoized ``_cached_pack`` entry (keeping its stored
    checksum); the next hit must count a ``corrupt`` detect-and-repack."""
    from repro.kernels.ops import PACK_STATS, sell_pack_gsecsr
    from repro.robustness.faults import corrupt_pack_cache

    _, g = _operand()
    sell_pack_gsecsr(g)  # populate the entry under test
    cases = {}
    for seed in seeds:
        assert corrupt_pack_cache(g, seed=seed)
        before = PACK_STATS["corrupt"]
        sell_pack_gsecsr(g)  # hit: verify -> detect -> repack
        cases[f"cache/sell/s{seed}"] = PACK_STATS["corrupt"] == before + 1
    return cases


def detection_wire(seeds=(0, 1, 2)) -> dict | None:
    """Wire-checksum detection of in-flight halo corruption.

    Runs ``halo_all_gather(..., check=True)`` inside a 2-shard shard_map
    with a seeded fault hook corrupting one payload segment; the
    receiver-side checksum compare must go False.  Returns None (skipped)
    with fewer than 2 devices.
    """
    if jax.device_count() < 2:
        return None
    from jax.sharding import Mesh
    from jax.sharding import PartitionSpec as P

    from repro.distributed.wire import halo_all_gather, set_wire_fault
    from repro.robustness.faults import make_wire_fault

    mesh = Mesh(np.array(jax.devices()[:2]), ("sh",))
    full = jnp.asarray(np.random.default_rng(3).normal(size=64))

    def ok_under(hook, tag, wire) -> bool:
        fn = jax.shard_map(
            lambda bnd: halo_all_gather(bnd, "sh", tag=tag, wire=wire,
                                        check=True)[1],
            mesh=mesh, in_specs=P("sh"), out_specs=P(), check_vma=False,
        )
        set_wire_fault(hook)
        try:
            return bool(fn(full))
        finally:
            set_wire_fault(None)

    combos = [("gse", 1, "head"), ("gse", 1, "table"),
              ("gse", 2, "head"), ("gse", 2, "tail1"), ("gse", 2, "table"),
              ("exact", 3, "raw"), ("gse", 3, "raw")]
    cases = {}
    for wire, tag, target in combos:
        # clean-path sanity: the checksum must PASS without a fault
        cases[f"wire/{wire}-t{tag}/clean"] = ok_under(None, tag, wire)
        for seed in seeds:
            hook = make_wire_fault(target, seed)
            cases[f"wire/{wire}-t{tag}/{target}/s{seed}"] = \
                not ok_under(hook, tag, wire)
    return cases


def recovery_cases(tol=1e-8, maxiter=3000) -> dict:
    """Low-tag operator faults solved through by guard + escalation."""
    from repro.robustness.faults import make_tag_fault_operator
    from repro.robustness.guards import HEALTH_OK
    from repro.solvers.cg import solve_cg, solve_pcg
    from repro.solvers.precond import make_jacobi
    from repro.sparse.spmv import spmv

    csr, g = _operand()
    rng = np.random.default_rng(11)
    b = spmv(csr, jnp.asarray(rng.normal(size=csr.shape[1])))
    jac = make_jacobi(csr)

    def judge(res, fail_tag):
        x_fin = bool(jnp.isfinite(jnp.vdot(res.x, res.x)))
        return {
            "recovered": bool(res.converged) and x_fin
                         and int(res.health) == HEALTH_OK
                         and int(res.tag) > fail_tag,
            "tripped": int(res.trip_iter) >= 0,
            "final_tag": int(res.tag),
            "iters": int(res.iters),
            "relres": float(res.relres),
        }

    cases = {}
    for mode in ("indefinite", "nan"):
        for fail_tag in (1, 2):
            op = make_tag_fault_operator(g, mode, fail_tag=fail_tag)
            res = solve_cg(op, b, tol=tol, maxiter=maxiter, params=_params())
            cases[f"cg/{mode}/fail{fail_tag}"] = judge(res, fail_tag)
    op = make_tag_fault_operator(g, "indefinite", fail_tag=1)
    res = solve_pcg(op, b, jac, tol=tol, maxiter=maxiter, params=_params())
    cases["pcg/indefinite/fail1"] = judge(res, 1)
    return cases


def overhead(n=24, tol=1e-8, maxiter=2000, repeats=3) -> dict:
    """Guards-on vs guards-off wall time of the clean fused stepped CG."""
    from repro.robustness.guards import DEFAULT_GUARDS
    from repro.solvers.cg import solve_cg
    from repro.sparse.spmv import spmv

    csr, g = _operand(n=n)
    rng = np.random.default_rng(7)
    b = spmv(csr, jnp.asarray(rng.normal(size=csr.shape[1])))

    def run_once(guards):
        return solve_cg(g, b, tol=tol, maxiter=maxiter, params=_params(),
                        guards=guards, recover=False)

    out = {}
    for name, guards in (("off", None), ("on", DEFAULT_GUARDS)):
        # Shared best-of-k min timing (benchmarks.common.timed).
        res, best = timed(run_once, guards, iters=repeats, warmup=1)
        out[f"guards_{name}_s"] = best
        out[f"guards_{name}_iters"] = int(res.iters)
    out["ratio"] = out["guards_on_s"] / out["guards_off_s"]
    return out


def _rate(cases: dict) -> float:
    vals = [v["recovered"] if isinstance(v, dict) else v
            for v in cases.values()]
    return float(np.mean([bool(v) for v in vals])) if vals else 0.0


def run(quick: bool = False) -> dict:
    """Full robustness sweep; returns the BENCH_robust.json payload."""
    det = {}
    det.update(detection_pack())
    det.update(detection_pack_cache())
    wire = detection_wire()
    wire_skipped = wire is None
    if wire is not None:
        det.update(wire)
    rec = recovery_cases()
    ovh = overhead(n=16 if quick else 24,
                   maxiter=1500 if quick else 2000)

    results = {
        "detection": {
            "cases": {k: bool(v) for k, v in det.items()},
            "rate": _rate(det),
            "n_cases": len(det),
            "wire_skipped": wire_skipped,
        },
        "recovery": {
            "cases": rec,
            "rate": _rate(rec),
            "n_cases": len(rec),
        },
        "overhead": ovh,
    }
    emit("robust_detection", 0.0,
         f"rate={results['detection']['rate']:.3f}/"
         f"{results['detection']['n_cases']}cases"
         + (" (wire skipped: 1 device)" if wire_skipped else ""))
    emit("robust_recovery", 0.0,
         f"rate={results['recovery']['rate']:.3f}/"
         f"{results['recovery']['n_cases']}cases")
    emit("robust_overhead", ovh["guards_on_s"] * 1e6,
         f"ratio={ovh['ratio']:.3f} vs off={ovh['guards_off_s'] * 1e6:.0f}us")
    return results


if __name__ == "__main__":
    import json

    print(json.dumps(run(quick=True), indent=2, sort_keys=True))
