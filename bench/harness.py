"""The benchmark harness: one cell of ``BENCHMARK.json``, run end to end.

Everything a cell needs is found by name:

- the cell's configuration file, named in ``BENCHMARK.json``'s ``configs``
  (``bench/configs/<config>.json``): the operator recipe, the solver entry
  and its arguments, the stated accuracy;
- its traffic mix, ``bench/traffic/<traffic>.json``: the parameters that
  the one generator of right-hand sides (``bench/rhs.py``) reads;
- the least-bytes count of its solver kind, ``bench/work/<kind>.py``;
- one reader per per-layer metric, ``bench/metrics/<metric>.py``, each
  with ``read(rec)`` returning a number or ``None``;
- the chip's published peaks, ``bench/peaks.json``, by ``device_kind``.

A run builds the operator on the host, packs and places it through the
program's public entries, warms every program the window runs, and then
solves one right-hand side after another for ``seconds``.  Once the
window has closed it checks every answer against the benchmark's own
float64 reference (``bench/operators.py``).
"""
from __future__ import annotations

import importlib.util
import json
import sys
import tempfile
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[1]
BENCH = Path(__file__).resolve().parent

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_DIR = ROOT / ".jax_cache"
CHECK_SAMPLE = 64      # answers compared at most; a seeded sample past it
NO_STOP_TOL = 1e300    # a tolerance every start meets: zero iterations


class NoChip(RuntimeError):
    """JAX finds no accelerator, or fewer chips than the cell asks for."""


def _json(path: Path):
    with open(path) as fh:
        return json.load(fh)


def load_module(path: Path):
    """Import the Python file at ``path`` (a metric reader or a byte
    count), once per path."""
    key = f"bench_file_{abs(hash(str(path)))}"
    if key not in sys.modules:
        spec = importlib.util.spec_from_file_location(key, path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        sys.modules[key] = mod
    return sys.modules[key]


class Cell:
    """One entry of ``BENCHMARK.json``'s ``workloads`` with its files."""

    def __init__(self, name: str, root: Path = ROOT):
        bm = _json(root / "BENCHMARK.json")
        cells = {w["name"]: w for w in bm["workloads"]}
        if name not in cells:
            raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                           f"have {sorted(cells)}")
        w = cells[name]
        cfgs = {c["name"]: c for c in bm["configs"]}
        self.name = name
        self.chips = int(w["chips"])
        bench = root / "bench"
        self.config = _json(root / cfgs[w["config"]]["file"])
        self.traffic = _json(bench / "traffic" / f"{w['traffic']}.json")

        def mine(m):
            return "workloads" not in m or name in m["workloads"]

        self.end_to_end = [m for m in bm["end_to_end"] if mine(m)]
        reported = {m["name"] for m in self.end_to_end}
        self.per_layer = [m for m in bm["per_layer"]
                          if mine(m) and m["moves"] in reported]
        self.work = load_module(
            bench / "work" / f"{self.config['solver']['kind']}.py")
        self.readers = {m["name"]: load_module(bench / "metrics" /
                                               f"{m['name']}.py")
                        for m in self.per_layer}


def devices(chips: int, require_tpu: bool = True):
    import jax

    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX found platform {devs[0].platform!r}")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found {len(devs)}")
    return devs[:chips]


def halo_entries(coo, shards: int) -> int:
    """Entries of the input vector that contiguous row blocks read from
    other blocks, summed over the blocks."""
    if shards <= 1:
        return 0
    r = -(-coo.n // shards)
    total = 0
    for s in range(shards):
        lo, hi = s * r, min((s + 1) * r, coo.n)
        sel = (coo.rows >= lo) & (coo.rows < hi)
        c = coo.cols[sel]
        total += int(np.unique(c[(c < lo) | (c >= hi)]).size)
    return total


class Deployment:
    """The operator built, packed and placed, and the solve entry bound to
    it, as the configuration describes them."""

    def __init__(self, cell: Cell):
        from bench import operators
        from repro import solvers
        from repro.sparse import csr as C

        cfg = cell.config
        sv = cfg["solver"]
        self.coo = operators.build(cfg)
        a = C.from_coo(self.coo.rows, self.coo.cols, self.coo.vals,
                       (self.coo.n, self.coo.n))
        g = C.pack_csr(a, k=int(cfg["k"]))
        if cfg.get("layout", "csr") == "sell":
            g = C.pack_sell(g)
        shards = int(sv.get("shards", 1))
        if shards > 1:
            from repro.distributed.partition import partition_gsecsr

            g = partition_gsecsr(g, shards)
        self.operand = g
        self.kwargs = dict(sv["args"])
        if sv.get("precond"):
            make = getattr(solvers, f"make_{sv['precond']}")
            self.kwargs["precond"] = make(a, k=int(cfg["k"]))
        self.entry_name = sv["entry"]
        self.tol = float(cfg["accuracy"]["true_relres"])
        self.shape = {"n": self.coo.n, "nnz": self.coo.nnz,
                      "halo": halo_entries(self.coo, shards),
                      "chips": cell.chips}

    def solve(self, b, x0=None, **over):
        from repro import solvers

        kw = dict(self.kwargs, **over)
        return getattr(solvers, self.entry_name)(self.operand, b, x0=x0, **kw)

    def warm(self, b, x0, resume_after):
        """Compile (or load) every program a window solve runs.

        One solve of ``b`` from ``x0`` stops before its first iteration:
        that runs the solve's loop and its true-residual check.  Then the
        final correction's resume program is warmed for each first-phase
        iteration count in ``resume_after`` (the configuration's
        ``solver.warm.resume_after``), since its budget, ``maxiter`` less
        that count, is a static argument of the program."""
        import jax

        jax.block_until_ready(self.solve(b, x0, tol=NO_STOP_TOL).x)
        if not self.kwargs.get("final_correction"):
            return
        maxiter = int(self.kwargs["maxiter"])
        for n1 in resume_after:
            res = self.solve(b, x0, tol=NO_STOP_TOL,
                             maxiter=max(maxiter - int(n1), 1),
                             init_tag=3, final_correction=False)
            jax.block_until_ready(res.x)


class CompileCounter:
    """Counts backend compilations (persistent-cache loads included)."""

    def __init__(self):
        self.n = 0

    def __call__(self, name, secs, **_):
        if name == COMPILE_EVENT:
            self.n += 1


class CorrectionLog:
    """Records the iterations each solve's final correction ran.

    The solve result does not carry them, so in a traced run the shared
    correction epilogue of ``repro.solvers`` is wrapped to read them off
    its input and output; every module that imported it gets the wrapper.
    The epilogue already waits for both results on the host, so the
    wrapper adds no wait of its own.  Where the program has no such
    epilogue, installing fails: the metrics that need the count would
    otherwise drop out of the result unseen.
    """

    NAME = "_finish_with_correction"

    def __init__(self):
        self.iters = []
        self._patched = []

    def install(self):
        import repro.solvers.cg as cg_mod

        orig = getattr(cg_mod, self.NAME, None)
        if orig is None:
            raise RuntimeError(
                f"repro.solvers.cg.{self.NAME} is gone: the traced run "
                "cannot count the final correction's iterations; read them "
                "from the solve result instead")

        def wrapped(res, *args, **kw):
            out = orig(res, *args, **kw)
            self.iters.append(int(out.iters) - int(res.iters))
            return out

        for mod in list(sys.modules.values()):
            if (getattr(mod, "__name__", "").startswith("repro.")
                    and getattr(mod, self.NAME, None) is orig):
                setattr(mod, self.NAME, wrapped)
                self._patched.append((mod, orig))

    def uninstall(self):
        for mod, orig in self._patched:
            setattr(mod, self.NAME, orig)
        self._patched = []


def _solve_record(res, corr):
    c = getattr(res, "correction_iters", None)
    return {"iters": int(res.iters),
            "switch_iters": [int(v) for v in np.asarray(res.switch_iters)],
            "tag": int(res.tag),
            "correction_iters": int(c) if c is not None else corr}


def _profile(trace_dir):
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(trace_dir, profiler_options=opts)


def _memory_peak(devs) -> int:
    peaks = []
    for d in devs:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks)


def run(cell: Cell, seed: int, seconds: float, trace: bool, t_start: float,
        require_tpu: bool = True, log=sys.stderr) -> dict:
    """One run of ``cell``; returns the result line as a dict.

    ``t_start`` is the host clock when the process began, so that set-up
    counts the imports and the device's start.  ``require_tpu=False`` is
    for the benchmark's own tests on the CPU.
    """
    import jax

    devs = devices(cell.chips, require_tpu)

    counter = CompileCounter()
    jax.monitoring.register_event_duration_secs_listener(counter)
    try:
        return _run(cell, devs, seed, seconds, trace, t_start, counter, log)
    finally:
        jax.monitoring.unregister_event_duration_listener(counter)


def _run(cell, devs, seed, seconds, trace, t_start, counter, log):
    import jax

    from bench import trace_reduce
    from bench.rhs import Stream

    def say(msg):
        print(msg, file=log, flush=True)

    def put(v):
        return None if v is None else jax.device_put(v)

    peak = _peak(devs[0]) if trace else None
    phases = {"start": time.perf_counter() - t_start}
    dep = Deployment(cell)
    phases["operator"] = time.perf_counter() - t_start
    stream = Stream(cell.traffic, dep.coo, cell.config, seed)
    pool = [stream.rhs(i) for i in range(stream.pool_size)]
    pool_dev = [(put(b), put(stream.x0(i))) for i, b in enumerate(pool)]
    phases["traffic"] = time.perf_counter() - t_start
    dep.warm(jax.device_put(stream.warm_rhs()), pool_dev[0][1],
             cell.config["solver"]["warm"]["resume_after"])
    jax.block_until_ready(pool_dev)
    setup_s = time.perf_counter() - t_start
    phases["warm"] = setup_s
    say(f"[{cell.name}] n={dep.shape['n']} nnz={dep.shape['nnz']} "
        f"halo={dep.shape['halo']} setup_s={setup_s:.3f} phases_end_s="
        + " ".join(f"{k}:{v:.3f}" for k, v in phases.items()))

    corr = CorrectionLog()
    tmp = None
    if trace:
        from repro.obs import trace as OT

        if dep.kwargs.get("final_correction"):
            corr.install()
        OT.install(OT.Tracer())
        tmp = tempfile.TemporaryDirectory(prefix="bench_trace_")
        _profile(tmp.name)

    results, corr_of, ends = [], [], []
    c0 = counter.n
    try:
        with jax.profiler.TraceAnnotation(trace_reduce.WINDOW_SPAN):
            t0 = time.perf_counter()
            t1 = t0
            while not results or t1 - t0 < seconds:
                b, x0 = pool_dev[len(results) % len(pool_dev)]
                n_corr = len(corr.iters)
                with jax.profiler.TraceAnnotation("bench.solve"):
                    res = dep.solve(b, x0)
                    jax.block_until_ready(res.x)
                t1 = time.perf_counter()
                ends.append(t1 - t0)
                results.append(res)
                corr_of.append(corr.iters[n_corr]
                               if len(corr.iters) > n_corr else
                               None if dep.kwargs.get("final_correction")
                               else 0)
        window_s = t1 - t0
        window_compiles = counter.n - c0
    finally:
        if trace:
            jax.profiler.stop_trace()
            OT.uninstall()
            corr.uninstall()

    memory_peak = _memory_peak(devs)
    solves = [_solve_record(r, c) for r, c in zip(results, corr_of)]
    xs = [np.asarray(r.x, np.float64) for r in results]
    del results, pool_dev, dep.operand, dep.kwargs

    reduced = None
    if trace:
        files = sorted(Path(tmp.name).rglob("*.xplane.pb"))
        reduced = trace_reduce.reduce_planes(
            trace_reduce.load_planes(files[-1]))
        tmp.cleanup()

    # The reference: every answer (or a seeded sample) against the
    # operator as generated, in float64 numpy on the host.
    idx = list(range(len(xs)))
    if len(idx) > CHECK_SAMPLE:
        idx = sorted(np.random.default_rng([seed, 2]).choice(
            len(xs), CHECK_SAMPLE, replace=False).tolist())
    rel = [dep.coo.true_relres(xs[i], pool[i % len(pool)]) for i in idx]
    bad = [r for r in rel if not (np.isfinite(r) and r <= dep.tol)]
    worst = max(rel) if all(np.isfinite(rel)) else float("inf")

    rec = {"solves": solves, "window_s": window_s,
           "window_compiles": window_compiles, "trace": reduced,
           "shape": dep.shape, "work": cell.work,
           "peak": peak}
    metrics = {}
    if trace:
        for m in cell.per_layer:
            v = cell.readers[m["name"]].read(rec)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    else:
        values = {"solve_s": window_s / len(solves), "setup_s": setup_s}
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}

    d0 = devs[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(devs), "memory_peak_bytes": memory_peak}
    line = {"correct": not bad,
            "attempted": len(solves), "failed": len(bad),
            "metrics": metrics, "device": device}
    if trace:
        device["busy_s"] = (sum(d["busy_s"] for d in reduced["devices"].values())
                            / len(reduced["devices"]))
        device["window_s"] = reduced["window_s"]
        line["breakdown"] = {"device_ops": reduced["device_ops"],
                             "idle_gaps": reduced["idle_gaps"]}
    say(f"[{cell.name}] solve_ends_s={[round(t, 4) for t in ends]}")
    say(f"[{cell.name}] solves={len(solves)} window_s={window_s:.3f} "
        f"iters={[s['iters'] for s in solves]} "
        f"switch_iters={[s['switch_iters'] for s in solves]} "
        f"correction_iters={[s['correction_iters'] for s in solves]} "
        f"window_compiles={window_compiles} memory_peak_bytes={memory_peak}")
    say(f"[{cell.name}] true_relres={[float(f'{r:.6e}') for r in rel]}")
    checks = {"worst_true_relres": {"value": worst, "limit": dep.tol}}
    for k, v in checks.items():
        say(f"check {k} = {v['value']!r} limit {v['limit']!r}")
    line["checks"] = checks
    return line


def _peak(device) -> dict:
    peaks = _json(BENCH / "peaks.json")["devices"]
    if device.device_kind not in peaks:
        raise KeyError(f"no published peaks for device kind "
                       f"{device.device_kind!r} in bench/peaks.json")
    return peaks[device.device_kind]


def configure_jax():
    """float64 on, and JAX's persistent compile cache at the checkout's
    one fixed directory.

    This departs from ``repro.compile_cache.enable`` on purpose: a
    ``JAX_COMPILATION_CACHE_DIR`` from the environment lies outside the
    checkout and may be shared by two checkouts under comparison, so the
    benchmark names its own.  The cache keeps every entry: with eviction
    on (a size limit from the environment), an entry written without its
    access-time file makes every later write fail, and each run would
    compile its programs again."""
    import jax

    jax.config.update("jax_enable_x64", True)
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_compilation_cache_max_size", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


def main(argv=None, t_start=None) -> int:
    import argparse

    t_start = time.perf_counter() if t_start is None else t_start
    ap = argparse.ArgumentParser(description="Run one benchmark cell.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    configure_jax()
    try:
        line = run(Cell(args.workload), args.seed, args.seconds,
                   bool(args.trace), t_start)
    except NoChip as e:
        print(f"refusing to measure: {e}", file=sys.stderr, flush=True)
        return 2
    print(json.dumps(line), flush=True)
    return 0
