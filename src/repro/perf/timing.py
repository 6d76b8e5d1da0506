"""Best-of-k wall timing with ``block_until_ready`` on every output.

The single timing primitive every benchmark routes through (PR 7
satellite: ``fig89_solver_time.py`` and ``robust_bench.py`` used to
hand-roll ``perf_counter`` loops while ``benchmarks/common.time_fn``
reported a median).  Minimum-of-k is the standard noise-robust estimator
for a deterministic computation on a shared host: every source of
variance (scheduler, turbo, page faults) only ever ADDS time, so the min
converges on the true cost while median/mean track the noise floor --
exactly the artifact that made ``gse_h`` look slower than fp64 in the
pre-PR-7 BENCH_spmv.json (DESIGN.md section 15).
"""
from __future__ import annotations

import time

import jax

__all__ = ["measure", "measure_split", "best_seconds"]


def measure(fn, *args, iters: int = 10, warmup: int = 2, **kwargs):
    """Run ``fn(*args, **kwargs)`` ``warmup + iters`` times; return
    ``(last_output, best_seconds)`` with every output blocked on."""
    if iters < 1:
        raise ValueError(f"iters must be >= 1, got {iters}")
    out = None
    for _ in range(max(warmup, 0)):
        out = jax.block_until_ready(fn(*args, **kwargs))
    best = float("inf")
    for _ in range(iters):
        t0 = time.perf_counter()
        out = jax.block_until_ready(fn(*args, **kwargs))
        best = min(best, time.perf_counter() - t0)
    return out, best


def measure_split(fn, *args, iters: int = 10, warmup: int = 2, **kwargs):
    """Like :func:`measure`, but also times the very first call separately.

    Returns ``(last_output, first_seconds, best_seconds)``.  The first call
    of a jitted ``fn`` pays trace + compile; steady-state calls replay the
    executable.  ``first - best`` is therefore a cheap compile-time
    estimate with no profiler dependency (clamp at 0: on a cache hit the
    first call can land inside run-to-run noise).  Observability callers
    (``benchmarks.common.timed``) record both sides as registry metrics
    (DESIGN.md §16).
    """
    if iters < 1:
        raise ValueError(f"iters must be >= 1, got {iters}")
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args, **kwargs))
    first = time.perf_counter() - t0
    for _ in range(max(warmup - 1, 0)):
        out = jax.block_until_ready(fn(*args, **kwargs))
    best = float("inf")
    for _ in range(iters):
        t0 = time.perf_counter()
        out = jax.block_until_ready(fn(*args, **kwargs))
        best = min(best, time.perf_counter() - t0)
    return out, first, best


def best_seconds(fn, *args, iters: int = 10, warmup: int = 2,
                 **kwargs) -> float:
    """Best-of-k seconds only (drops the output)."""
    return measure(fn, *args, iters=iters, warmup=warmup, **kwargs)[1]
