"""The reduction by the program's scope and span names, on a synthetic
trace and on one recorded here (its modules' HLO read from the profile
itself); the ``spmv_roofline`` and
``correction_ms`` readers on a recorded run record; the program's
``correction_iters`` against the harness's ``CorrectionLog``."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness
from bench import scope_reduce as S
from bench import trace_reduce as T
from bench.work import cg

READERS = {n: harness.load_module(harness.BENCH / "metrics" / f"{n}.py")
           for n in ("spmv_roofline", "correction_ms")}


class Ev:
    def __init__(self, name, start, dur):
        self.name, self.start_ns, self.duration_ns = name, start, dur


class Line:
    def __init__(self, name, events):
        self.name, self.events = name, events


class Plane:
    def __init__(self, name, lines):
        self.name, self.lines = name, lines


def _op(name, start, dur):
    return Ev(f"%{name} = f64[8] {name}(...)", start, dur)


LOOP = "jit(_solve_cg_fused)/while/body/cond/branch_0_fun"
# Two modules that both hold a ``fusion.8``: the loop's scatter and the
# correction check's.
OP_NAMES = {
    7: {"while.1": "jit(_solve_cg_fused)/while",
        "fusion.8": f"{LOOP}/spmv/scatter/scatter-add",
        "fusion": f"{LOOP}/spmv/gather/mul",
        "fusion.3": f"{LOOP}/krylov/dot/dot_general",
        "fusion.4": "jit(_solve_cg_fused)/while/body/monitor/select_n",
        "all-gather.1": f"{LOOP}/spmv/halo/all_gather"},
    9: {"fusion.8": "jit(cond)/cond/branch_2_fun/jit(_spmv_gse)/spmv/"
                    "scatter/scatter-add"},
}


def _planes():
    host = Plane("/host:CPU", [Line("python", [
        Ev(T.WINDOW_SPAN, 100, 1000),                   # [100, 1100)
        Ev("solve.cg", 100, 900),
        Ev("solve.correction", 700, 300),               # [700, 1000)
        Ev("solve.correction.check", 700, 250),
        Ev("solve.cg", 20, 50),                         # before the window
        Ev("bench.solve", 100, 950),
    ])])
    d0 = Plane("/device:TPU:0", [
        Line(S.MODULES_LINE, [Ev("jit__solve_cg_fused(7)", 100, 500),
                              Ev("jit_cond(9)", 800, 100),
                              Ev("jit_copy(11)", 950, 20)]),
        Line("XLA Ops", [
            _op("while.1", 100, 500),
            _op("fusion.8", 100, 300),
            _op("fusion", 400, 100),
            _op("fusion.3", 500, 50),
            _op("fusion.4", 550, 50),
            _op("fusion.8", 800, 100),                  # in jit_cond
            _op("copy.2", 950, 20),                     # no module HLO
        ])])
    d1 = Plane("/device:TPU:1", [
        Line(S.MODULES_LINE, [Ev("jit__solve_cg_fused(7)", 100, 150)]),
        Line("XLA Ops", [_op("fusion.8", 100, 100),
                         _op("all-gather.1", 200, 40),
                         _op("fusion.3", 300, 10)])])   # outside any module
    return [host, d0, d1]


def test_scope_path():
    assert S.scope_path(f"{LOOP}/spmv/scatter/scatter-add") == "spmv/scatter"
    assert S.scope_path("jit(f)/krylov/update/mul") == "krylov/update"
    assert S.scope_path("jit(f)/precond/mul") == "precond"
    # A child name alone, or a primitive named like a child, is no scope.
    assert S.scope_path("jit(f)/while/body/gather") is None
    assert S.scope_path("jit(f)/decode/mul") is None
    # A stage with an unknown child keeps the stage.
    assert S.scope_path("jit(f)/spmv/jit(_decode_gsecsr)/mul") == "spmv"
    assert S.scope_path("/src/repro/sparse/spmv.py:12") is None


def test_scopes_and_spans():
    r = S.reduce_planes(_planes(), OP_NAMES)
    sc = r["scopes"]
    # Self seconds, summed over the two devices and halved; the loop
    # holds its body, so it keeps no time of its own.  Both modules'
    # ``fusion.8`` land in spmv/scatter.
    assert sc["spmv/scatter"]["seconds"] == pytest.approx(500e-9 / 2)
    assert sc["spmv/scatter"]["ops"] == 3 / 2
    assert sc["spmv/gather"]["seconds"] == pytest.approx(100e-9 / 2)
    assert sc["spmv/halo"]["seconds"] == pytest.approx(40e-9 / 2)
    assert sc["krylov/dot"]["seconds"] == pytest.approx(50e-9 / 2)
    assert sc["monitor"]["seconds"] == pytest.approx(50e-9 / 2)
    # The copy's module left no HLO, and TPU:1's last op ran outside any
    # module: both unscoped, as is the loop (with no self time).
    assert sc["unscoped"]["seconds"] == pytest.approx(30e-9 / 2)
    assert sc["unscoped"]["ops"] == 3 / 2
    assert list(sc)[0] == "spmv/scatter"
    # Every op's self time lands in exactly one scope: the scopes sum to
    # the busy time of the devices.
    busy = T.reduce_planes(_planes())["devices"]
    assert sum(v["seconds"] for v in sc.values()) == pytest.approx(
        sum(d["busy_s"] for d in busy.values()) / 2)
    sp = r["spans"]
    assert set(sp) == {"solve.cg", "solve.correction",
                       "solve.correction.check"}
    assert sp["solve.cg"]["count"] == 1          # the one in the window
    assert sp["solve.cg"]["seconds"] == pytest.approx(900e-9)
    # The busiest device (TPU:0) runs [100, 600), [800, 900), [950, 970).
    assert sp["solve.cg"]["idle_s"] == pytest.approx(280e-9)
    assert sp["solve.correction"]["idle_s"] == pytest.approx(180e-9)
    assert sp["solve.correction.check"]["seconds"] == pytest.approx(250e-9)
    assert sp["solve.correction.check"]["idle_s"] == pytest.approx(150e-9)
    # Without the modules' HLO every op is unscoped.
    assert set(S.reduce_planes(_planes(), {})["scopes"]) == {"unscoped"}


def test_no_device_plane_is_an_error():
    with pytest.raises(ValueError, match="no device plane"):
        S.reduce_planes(_planes()[:1], OP_NAMES)


def _recorded(tmp_path):
    """A CPU profile of one window of two solves, the program's spans in
    it, and the optimized HLO of the modules it ran."""
    from repro.obs import trace as OT
    from repro.solvers import solve_cg
    from repro.sparse import generators as G
    from repro.sparse.csr import pack_csr

    g = pack_csr(G.poisson3d(5), k=8)
    b = jnp.linspace(0.5, 1.5, g.shape[0])
    solve_cg(g, b, tol=1e-8, maxiter=200, final_correction=True)
    jax.profiler.start_trace(str(tmp_path))
    solves = []
    with OT.capture():
        with jax.profiler.TraceAnnotation(T.WINDOW_SPAN):
            for _ in range(2):
                res = solve_cg(g, b, tol=1e-8, maxiter=200,
                               final_correction=True)
                jax.block_until_ready(res.x)
                solves.append(res)
    jax.profiler.stop_trace()
    (path,) = tmp_path.rglob("*.xplane.pb")
    return path, solves, g


def test_op_names_from_a_recorded_profile(tmp_path):
    """The profile holds the optimized HLO of the solve loop, whose
    instructions carry the program's scopes."""
    path, _, _ = _recorded(tmp_path)
    op_names = S.load_op_names(path)
    paths = {S.scope_path(n) for names in op_names.values()
             for n in names.values()}
    assert {"spmv/decode", "spmv/gather", "spmv/scatter", "krylov/dot",
            "krylov/update", "monitor"} <= paths
    assert all(isinstance(pid, int) for pid in op_names)


def _fake_device(planes, op_names):
    """A device plane that runs, through each loop and correction span of
    a recorded profile, a module of the profile and one of its scatter
    instructions for a quarter of the span."""
    pid, instr = next(
        (pid, i) for pid, names in op_names.items()
        for i, n in names.items() if S.scope_path(n) == "spmv/scatter")
    spans = [(s, e) for n, s, e in T._host_spans(planes)
             if n in ("solve.cg", "solve.correction")]
    mods = [Ev(f"jit_f({pid})", s, e - s) for s, e in spans]
    ops = [_op(instr, s, (e - s) / 4) for s, e in spans]
    return Plane("/device:TPU:0", [Line(S.MODULES_LINE, mods),
                                   Line("XLA Ops", ops)])


def test_readers_on_a_recorded_record(tmp_path):
    path, results, g = _recorded(tmp_path)
    planes = T.load_planes(path)
    op_names = S.load_op_names(path)
    planes.append(_fake_device(planes, op_names))
    scoped = S.reduce_planes(planes, op_names)
    assert scoped["spans"]["solve.correction"]["count"] == 2
    assert scoped["spans"]["solve.correction.check"]["count"] == 2
    assert "solve.correction.resume" not in scoped["spans"]
    assert set(scoped["scopes"]) == {"spmv/scatter"}
    solves = [harness._solve_record(r, None) for r in results]
    assert [s["correction_iters"] for s in solves] == [0, 0]
    shape = {"n": g.shape[0], "nnz": int(g.nnz), "halo": 0, "chips": 1}
    rec = {"solves": solves, "window_s": 1.0, "window_compiles": 0,
           "shape": shape, "work": cg, "peak": {"hbm_bytes_per_s": 819e9},
           "trace": dict(T.reduce_planes(planes), **scoped)}
    ms = READERS["correction_ms"].read(rec)
    assert ms == pytest.approx(
        1e3 * scoped["spans"]["solve.correction"]["seconds"] / 2)
    assert ms > 0
    # Two solves of k tag-1 iterations: k + 1 tag-1 SpMVs and one tag-3
    # check each.
    k = solves[0]["iters"]
    spmv = READERS["spmv_roofline"]
    assert spmv.spmv_count(solves, scoped["spans"]) == {
        1: 2 * (k + 1), 2: 0, 3: 2}
    want = (2 * (k + 1) * spmv.spmv_bytes(shape, 1)
            + 2 * spmv.spmv_bytes(shape, 3))
    spmv_s = scoped["scopes"]["spmv/scatter"]["seconds"]
    assert spmv.read(rec) == pytest.approx(100 * want / (spmv_s * 819e9))
    # Without the program's scopes and spans there is nothing to read.
    rec["trace"] = T.reduce_planes(planes)
    assert spmv.read(rec) is None and READERS["correction_ms"].read(rec) is None


def test_spmv_bytes():
    shape = {"n": 110592, "nnz": 760320, "halo": 0, "chips": 1}
    spmv = READERS["spmv_roofline"]
    assert spmv.spmv_bytes(shape, 1) == (760320 * 6 + 110593 * 4
                                         + 16 * 110592)
    assert spmv.spmv_bytes(dict(shape, halo=10), 3) == (
        cg.matrix_bytes(shape, 3) + 16 * 110592 + 80)


def test_correction_iters_match_the_correction_log():
    """On a solve whose correction resumes, the program's count equals
    what the harness's wrapper of the epilogue reads."""
    from repro.core import precision as P
    from repro.solvers import solve_cg
    from repro.sparse import generators as G
    from repro.sparse.csr import pack_csr

    g = pack_csr(G.random_spd(600, seed=5), k=8)
    b = jnp.asarray(np.random.default_rng(5).normal(size=g.shape[0]))
    params = P.MonitorParams(t=30, l=30, m=15, rsd_limit=0.5,
                             reldec_limit=0.45, max_tag=1)
    log = harness.CorrectionLog()
    log.install()
    try:
        res = solve_cg(g, b, tol=1e-8, maxiter=4000, params=params,
                       final_correction=True)
    finally:
        log.uninstall()
    assert log.iters == [int(res.correction_iters)]
    assert log.iters[0] > 0
