#!/usr/bin/env python3
"""Smoke run of the main path on a TPU: stepped GSE CG solves and the solve
service on poisson3d(64), and the compiled Pallas SpMV/SpMM kernels on
poisson3d(128).

    python chip_smoke.py              # one chip, every phase
    python chip_smoke.py --chips 4    # only the 4-shard sharded solves

One process runs every phase; it starts no children.  It exits non-zero,
and prints no result line, when JAX finds no TPU or when any phase fails.
Every answer is checked against a plain float64 numpy reference over the
CSR arrays, independent of the code under test.  The last line printed
on success is ``{"ok": true, "device": {...}}``.

The solves run at 64^3 (262,144 unknowns) and not at 128^3: their SpMV is
the emulated-float64 jnp decode + gather + row reduction, which took
about 1.6 s per call at 128^3 on a v5e with a ``segment_sum`` reduction,
so one 128^3 solve would have taken about 12 minutes.  The kernels stream float32 and run at 128^3.

Times printed here are set-up and smoke numbers; they are not benchmark
measurements.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

import jax  # noqa: E402

jax.config.update("jax_enable_x64", True)

import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro import compile_cache  # noqa: E402

SOLVE_GRID = 64     # poisson3d grid of the solves, service, sharded CG
KERNEL_GRID = 128   # poisson3d grid of the kernel checks: 2,097,152 rows
TOL = 1e-8          # solver tolerance and the true-residual bound
KERNEL_RTOL = 1e-5  # norm-wise kernel vs jnp-path relative error bound
NRHS = 4

# XLA compile (or persistent-cache load) time; traces of nested jits
# overlap, so tracing stays in the "rest" of a block's wall time.
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_compile = {"s": 0.0, "cache_hits": 0}


def _on_duration(name, secs, **_):
    if name == _COMPILE_EVENT:
        _compile["s"] += secs


def _on_event(name, **_):
    if name == "/jax/compilation_cache/cache_hits":
        _compile["cache_hits"] += 1


class Timed:
    """Wall seconds of a block, split into XLA compile seconds (from
    JAX's monitoring events; a persistent-cache hit counts its load) and
    the rest, which includes tracing and the host work."""

    def __enter__(self):
        self._c0 = _compile["s"]
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self.wall = time.perf_counter() - self._t0
        self.compile_s = _compile["s"] - self._c0
        self.run_s = self.wall - self.compile_s

    def __str__(self):
        return f"compile_s={self.compile_s:.2f} run_s={self.run_s:.2f}"


class HostRef:
    """Plain float64 numpy over the CSR arrays: the reference every
    device answer is checked against."""

    def __init__(self, a):
        self.row_ids = np.asarray(a.row_ids, np.int64)
        self.col = np.asarray(a.col, np.int64)
        self.val = np.asarray(a.val, np.float64)
        self.n = int(a.shape[0])

    def matvec(self, x):
        return np.bincount(self.row_ids, weights=self.val * x[self.col],
                           minlength=self.n)

    def relres(self, x, b):
        x = np.asarray(x, np.float64)
        return float(np.linalg.norm(b - self.matvec(x)) / np.linalg.norm(b))


class Smoke:
    def __init__(self, solve_grid: int, kernel_grid: int, seed: int,
                 interpret: bool):
        self.solve_grid = solve_grid
        self.kernel_grid = kernel_grid
        self.seed = seed
        self.interpret = interpret
        self.failures = []

    def check(self, ok: bool, what: str):
        if not ok:
            self.failures.append(what)
            print(f"FAIL {what}", flush=True)

    def phase(self, name, fn):
        print(f"== {name}", flush=True)
        try:
            fn()
        except Exception:
            traceback.print_exc()
            sys.stdout.flush()
            self.check(False, f"{name} raised")
        gc.collect()

    # -- operator ---------------------------------------------------------

    def operator(self, grid):
        """poisson3d(grid), its host reference and its GSE-SEM CSR pack."""
        from repro.sparse import generators as G
        from repro.sparse.csr import pack_csr

        with Timed() as t:
            a = G.poisson3d(grid)
            ref = HostRef(a)
            g = pack_csr(a, k=8)
        print(f"operator poisson3d({grid}): n={a.shape[0]} nnz={a.nnz} "
              f"ei_bit={g.ei_bit} build_s={t.wall:.2f}", flush=True)
        return a, ref, g

    def build(self):
        from repro.sparse.spmv import decode_gsecsr

        self.rng = np.random.default_rng(self.seed)
        self.a, self.ref, self.g = self.operator(self.solve_grid)
        self.b = self.ref.matvec(self.rng.standard_normal(self.a.shape[0]))
        self.b_dev = jnp.asarray(self.b)
        val, _ = decode_gsecsr(self.g.in_csr_order(), 3)
        val = np.asarray(val)
        diff = float(np.max(np.abs(val - self.ref.val)))
        print(f"tag-3 device decode vs host float64 values: "
              f"max_abs_diff={diff:.3e}", flush=True)
        self.check(diff == 0.0, "tag-3 decode is not exact")

    # -- stepped solves ---------------------------------------------------

    def solve(self, name, op, **kw):
        from repro.solvers import solve_cg

        with Timed() as t:
            res = solve_cg(op, self.b_dev, tol=TOL, final_correction=True,
                           **kw)
            jax.block_until_ready(res.x)
        rel = self.ref.relres(res.x, self.b)
        print(f"solve[{name}] iters={int(res.iters)} "
              f"switch_iters={np.asarray(res.switch_iters).tolist()} "
              f"final_tag={int(res.tag)} converged={bool(res.converged)} "
              f"recursive_relres={float(res.relres):.3e} "
              f"host_true_relres={rel:.3e} {t}", flush=True)
        self.check(bool(res.converged) and rel <= TOL,
                   f"solve[{name}] true relres {rel:.3e} > {TOL}")

    def csr_solve(self):
        self.solve("csr", self.g)

    def sell_solve(self):
        from repro.sparse.csr import pack_sell

        self.solve("sell", pack_sell(self.g))

    # -- kernels ------------------------------------------------------------

    def _kernel_check(self, name, tag, fn, want_fn):
        with Timed() as t:
            y = jax.block_until_ready(fn())
        want = np.asarray(want_fn(), np.float64)
        err = float(np.linalg.norm(np.asarray(y, np.float64) - want)
                    / np.linalg.norm(want))
        print(f"kernel[{name} tag{tag}] shape={tuple(y.shape)} "
              f"rel_err={err:.3e} {t}", flush=True)
        self.check(bool(np.isfinite(err)) and err <= KERNEL_RTOL,
                   f"kernel[{name} tag{tag}] rel_err {err:.3e}")

    def kernels(self):
        """The compiled kernels against the jnp path at the same tag, on
        poisson3d(kernel_grid): SELL first, then uniform ELL, each pack
        dropped before the next so one 128-wide pack is live at a time."""
        from repro.kernels import ops
        from repro.sparse.csr import pack_sell
        from repro.sparse.spmv import spmm_gse, spmv_gse

        _, _, g = self.operator(self.kernel_grid)
        interp = self.interpret
        x = jnp.asarray(self.rng.standard_normal(g.shape[1]), jnp.float32)
        xs = jnp.asarray(self.rng.standard_normal((g.shape[1], NRHS)),
                         jnp.float32)
        sell = pack_sell(g)
        print(f"sell pack: widths={sell.widths} slots={sell.slots}",
              flush=True)
        for tag in (1, 2, 3):
            self._kernel_check(
                "spmv_sell", tag,
                lambda: ops.gse_spmv_sell(sell, x, tag=tag, interpret=interp),
                lambda: spmv_gse(g, x, tag=tag))
        del sell
        ell = ops.ell_pack_gsecsr(g)
        for tag in (1, 2, 3):
            self._kernel_check(
                "spmv_ell", tag,
                lambda: ops.gse_spmv_ell(ell, g.table, x, g.ei_bit, tag=tag,
                                         interpret=interp),
                lambda: spmv_gse(g, x, tag=tag))
        for tag in (1, 2, 3):
            self._kernel_check(
                f"spmm_ell nrhs{NRHS}", tag,
                lambda: ops.gse_spmm_ell(ell, g.table, xs, g.ei_bit,
                                         tag=tag, interpret=interp),
                lambda: spmm_gse(g, xs, tag=tag))

    # -- service ------------------------------------------------------------

    def service(self):
        from repro.serve.service import Accepted, AsyncSolveService

        svc = AsyncSolveService(slots=NRHS)
        with Timed() as t:
            h = svc.register("poisson3d", self.a, k=8)
        print(f"service register {t}", flush=True)
        rhs = [self.ref.matvec(self.rng.standard_normal(self.a.shape[0]))
               for _ in range(NRHS)]
        with Timed() as t:
            acks = [svc.submit(h, jnp.asarray(b), tol=TOL) for b in rhs]
            reports = svc.run_until_idle()
        self.check(all(isinstance(k, Accepted) for k in acks),
                   f"service shed a request: {acks}")
        for ack, b in zip(acks, rhs):
            rep = reports[ack.id]
            rel = self.ref.relres(svc.solution(ack.id), b)
            print(f"service[req {ack.id}] iters={rep.iters} "
                  f"converged={rep.converged} tag={rep.tag} "
                  f"health={rep.health} batch={rep.batch_size} "
                  f"host_true_relres={rel:.3e}", flush=True)
            self.check(rep.converged and rel <= TOL,
                       f"service req {ack.id} true relres {rel:.3e}")
        print(f"service drained {len(reports)} requests {t}", flush=True)

    # -- four chips ---------------------------------------------------------

    def sharded(self):
        from repro.distributed.partition import partition_gsecsr

        with Timed() as t:
            part = partition_gsecsr(self.g, 4)
        devs = {d for leaf in (part.colpak, part.head, part.tail1,
                               part.tail2, part.row_ids)
                for d in leaf.devices()}
        print(f"partition: shards={part.n_shards} "
              f"rows_per_shard={part.rows_per_shard} "
              f"devices={sorted(d.id for d in devs)} "
              f"halo_entries={part.halo_entries} build_s={t.wall:.2f}",
              flush=True)
        self.check(len(devs) == 4,
                   f"stacked operands span {len(devs)} devices, not 4")
        for wire in ("exact", "gse"):
            self.solve(f"sharded4 wire={wire}", part, wire=wire)


def device_check(chips: int):
    import jaxlib

    devs = jax.devices()
    d0 = devs[0]
    print(f"jax {jax.__version__} jaxlib {jaxlib.__version__}", flush=True)
    print(f"devices: {devs}", flush=True)
    print(f"platform={d0.platform} device_kind={d0.device_kind} "
          f"count={len(devs)}", flush=True)
    if d0.platform != "tpu":
        print(f"no TPU found (platform {d0.platform!r}); this smoke run "
              "needs the chip", file=sys.stderr, flush=True)
        sys.exit(2)
    if len(devs) < chips:
        print(f"--chips {chips} needs {chips} TPU devices, found "
              f"{len(devs)}", file=sys.stderr, flush=True)
        sys.exit(2)
    return d0, len(devs)


def run(smoke: Smoke, chips: int) -> None:
    smoke.phase("operator", smoke.build)
    if smoke.failures:
        return
    if chips == 4:
        smoke.phase("sharded solves", smoke.sharded)
        return
    smoke.phase("stepped CG on GSECSR", smoke.csr_solve)
    smoke.phase("stepped CG on GSESellC", smoke.sell_solve)
    smoke.phase("solve service", smoke.service)
    smoke.phase("kernels", smoke.kernels)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the 4-shard sharded solves")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    d0, count = device_check(args.chips)
    cache_dir = compile_cache.enable()
    print(f"compile cache: {cache_dir}", flush=True)
    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    jax.monitoring.register_event_listener(_on_event)

    smoke = Smoke(SOLVE_GRID, KERNEL_GRID, seed=args.seed, interpret=False)
    t0 = time.perf_counter()
    run(smoke, args.chips)
    print(f"total_s={time.perf_counter() - t0:.2f} "
          f"compile_s={_compile['s']:.2f} "
          f"compile_cache_hits={_compile['cache_hits']}", flush=True)
    if smoke.failures:
        print(f"{len(smoke.failures)} check(s) failed: {smoke.failures}",
              file=sys.stderr, flush=True)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": d0.platform, "kind": d0.device_kind, "count": count}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
