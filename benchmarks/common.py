"""Shared benchmark utilities: timing + CSV emission.

Imported by every benchmark module -- enables float64 FIRST (the paper's
reference arithmetic; without it everything silently degrades to f32 and
the format-comparison errors drown in accumulation noise).

ALL benchmark timing routes through ``repro.perf.timing`` (PR 7): best-of-k
minimum with ``block_until_ready`` on every output.  The pre-PR-7 median
estimator tracked host noise instead of kernel cost -- it is what made
``gse_h`` look slower than the fp64 baseline in BENCH_spmv.json
(DESIGN.md §15).
"""
from __future__ import annotations

import datetime
import subprocess
from typing import Callable

import jax

jax.config.update("jax_enable_x64", True)

from repro import compile_cache  # noqa: E402  (import after x64 setup)
from repro.obs import metrics as OM  # noqa: E402
from repro.perf import timing  # noqa: E402

compile_cache.enable()


def time_fn(fn: Callable, *args, iters: int = 20, warmup: int = 3) -> float:
    """Best-of-``iters`` wall time (us) of jitted fn (min over runs,
    every output blocked on)."""
    return timing.best_seconds(fn, *args, iters=iters, warmup=warmup) * 1e6


def timed(fn: Callable, *args, iters: int = 2, warmup: int = 1,
          label: str | None = None, **kwargs):
    """(output, best_seconds) of ``fn`` -- the shared helper for solver
    benchmarks that need the result AND the time (fig89, robust_bench).

    With ``label``, the first call (trace + compile) and the steady-state
    best are recorded separately in the metrics registry (DESIGN.md §16):
    ``repro_bench_compile_seconds{case=label}`` gets ``max(first - best,
    0)`` and ``repro_bench_execute_seconds{case=label}`` gets the best --
    so BENCH_obs.json can show how much of a benchmark's wall clock was
    XLA compilation rather than execution.
    """
    if label is None:
        return timing.measure(fn, *args, iters=iters, warmup=warmup,
                              **kwargs)
    out, first, best = timing.measure_split(fn, *args, iters=iters,
                                            warmup=warmup, **kwargs)
    OM.REGISTRY.histogram(
        "repro_bench_compile_seconds",
        "Estimated first-call compile time (first - steady best, >= 0).",
        labelnames=("case",),
    ).labels(case=label).observe(max(first - best, 0.0))
    OM.REGISTRY.histogram(
        "repro_bench_execute_seconds",
        "Steady-state best-of-k execution time.",
        labelnames=("case",),
    ).labels(case=label).observe(best)
    return out, best


def emit(name: str, us: float, derived: str = "") -> None:
    print(f"{name},{us:.2f},{derived}", flush=True)


def provenance() -> dict:
    """Provenance header stamped into every BENCH_*.json (DESIGN.md §16).

    Identifies WHAT produced a benchmark artifact: git commit, jax/jaxlib
    versions, the device kind the run saw, the persisted host roofline
    probe (``perf.tunecache.host_entry``), and a UTC timestamp.  Every
    field degrades to None rather than raising -- benchmarks must emit
    even from a tarball checkout with no git.
    """
    try:
        sha = subprocess.run(
            ["git", "rev-parse", "HEAD"], capture_output=True, text=True,
            timeout=10,
        ).stdout.strip() or None
    except Exception:
        sha = None
    try:
        import jaxlib
        jaxlib_version = jaxlib.__version__
    except Exception:
        jaxlib_version = None
    try:
        dev = jax.devices()[0]
        device_kind = dev.device_kind
        device_count = jax.device_count()
    except Exception:
        device_kind = None
        device_count = None
    try:
        from repro.perf import tunecache
        host = tunecache.host_entry()
    except Exception:
        host = None
    return {
        "git_sha": sha,
        "jax_version": jax.__version__,
        "jaxlib_version": jaxlib_version,
        "backend": jax.default_backend(),
        "device_kind": device_kind,
        "device_count": device_count,
        "host_roofline": host,
        "timestamp_utc": datetime.datetime.now(
            datetime.timezone.utc).isoformat(timespec="seconds"),
    }
