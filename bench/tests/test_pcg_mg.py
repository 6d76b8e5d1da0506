"""The MG-PCG cell ``hpcg_40.pcg_mg``: its least-bytes count, its readers
and a run through the harness on the CPU at a grid its four levels halve.

``test_harness.py`` drives every cell at a 12^3 grid, which four levels
cannot halve (12 is not divisible by 8), so this cell runs here at 16^3."""
import numpy as np
import pytest

from bench import harness
from bench.tests.helpers import run_tiny
from bench.work import cg, pcg_mg

CELL = "hpcg_40.pcg_mg"
SHAPE = {"n": 64000, "nnz": 1643032, "halo": 0, "chips": 1}


def test_levels_at_40():
    """HPCG's hierarchy of a 40^3 box: 40/20/10/5."""
    assert [(lv["n"], lv["nnz"]) for lv in pcg_mg.levels(SHAPE)] == [
        (64000, 1643032), (8000, 195112), (1000, 21952), (125, 2197)]
    assert [lv["f2c_nnz"] for lv in pcg_mg.levels(SHAPE)] == [
        59 ** 3, 29 ** 3, 14 ** 3, 0]


@pytest.mark.parametrize("shape", [
    {"n": 64000, "nnz": 438400},          # the 7-point stencil on 40^3
    {"n": 64000 + 1, "nnz": 1643032},     # not a cube
    {"n": 12 ** 3, "nnz": 34 ** 3},       # 12 does not halve three times
])
def test_non_box_shapes_raise(shape):
    with pytest.raises(ValueError):
        pcg_mg.iteration_bytes(dict(shape, halo=0, chips=1), 1)


def test_counts_are_the_programs_levels():
    """Each level's rows and entries are those of the hierarchy the program
    packs, and the residual's entries those of its colour-0 block."""
    from repro.solvers import make_mg
    from repro.sparse.generators import hpcg27

    a = hpcg27(16)
    m = make_mg(a)
    shape = {"n": a.shape[0], "nnz": int(a.nnz), "halo": 0, "chips": 1}
    for lv, level in zip(pcg_mg.levels(shape), m.levels):
        nnz = np.asarray(level.ops.rowptr)[:, -1]
        assert (level.leave is None or level.leave.size == lv["n"])
        assert int(nnz.sum()) == lv["nnz"]
        if lv["f2c_nnz"]:
            assert level.ops.shape[0] * 8 == lv["n"]
            assert nnz[0] == lv["f2c_nnz"]


@pytest.mark.parametrize("tag", [1, 2, 3])
def test_iteration_is_cg_plus_the_vcycle(tag):
    seg = cg.SEGMENT_BYTES[tag] + cg.COLUMN_BYTES
    fine = 4 * 1643032 * seg + 205379 * seg
    assert pcg_mg.vcycle_bytes(SHAPE, tag) > fine
    assert pcg_mg.iteration_bytes(SHAPE, tag) == (
        cg.iteration_bytes(SHAPE, tag) + pcg_mg.vcycle_bytes(SHAPE, tag))


def _reader(name):
    return harness.load_module(harness.BENCH / "metrics" / f"{name}.py")


def _rec(scopes=None):
    solves = [{"iters": 28, "switch_iters": [-1, -1], "tag": 1,
               "correction_iters": 0},
              {"iters": 30, "switch_iters": [-1, -1], "tag": 1,
               "correction_iters": 0}]
    trace = {"window_s": 9.0, "devices": {"/device:TPU:0": {
        "busy_s": 8.5, "collective_s": 0.0}}}
    if scopes is not None:
        trace["scopes"] = scopes
    return {"solves": solves, "window_s": 10.0, "window_compiles": 0,
            "shape": SHAPE, "work": pcg_mg,
            "peak": {"hbm_bytes_per_s": 819e9}, "trace": trace}


def test_readers_on_a_record():
    rec = _rec()
    assert _reader("mg_pcg_iters").read(rec) == 29
    assert _reader("mg_iter_ms").read(rec) == pytest.approx(1e4 / 58)
    want = 58 * pcg_mg.iteration_bytes(SHAPE, 1) / (8.5 * 819e9) * 100
    assert _reader("mg_iter_roofline").read(rec) == pytest.approx(want)


def test_vcycle_share():
    reader = _reader("vcycle_share")
    scopes = {"precond/smooth": {"seconds": 6.0, "ops": 10},
              "precond/residual": {"seconds": 0.5, "ops": 3},
              "precond/transfer": {"seconds": 0.3, "ops": 4},
              "spmv/gather": {"seconds": 1.0, "ops": 2},
              "krylov/dot": {"seconds": 0.2, "ops": 2}}
    assert reader.read(_rec(scopes)) == pytest.approx(100 * 6.8 / 8.0)
    # The benchmark's scope copy names the V-cycle ``precond`` alone.
    assert reader.read(_rec({"precond": {"seconds": 3.0, "ops": 1},
                             "unscoped": {"seconds": 1.0, "ops": 1}})) == 75
    # No scopes merged into the record, or none under the preconditioner.
    assert reader.read(_rec()) is None
    assert reader.read(_rec({"spmv/gather": {"seconds": 1.0, "ops": 1}})) \
        is None


@pytest.mark.parametrize("trace", [0, 1])
def test_cell_runs_at_16(monkeypatch, trace):
    cell = harness.Cell(CELL)
    assert cell.config["grid"] == [40, 40, 40]
    cell.config["grid"] = [16, 16, 16]
    line = run_tiny(monkeypatch, cell, bool(trace))
    assert line["correct"] is True and line["failed"] == 0
    chk = line["checks"]["worst_true_relres"]
    assert chk["value"] <= chk["limit"] == 1e-8
    if trace:
        assert set(line["metrics"]) == {"mg_pcg_iters", "mg_iter_ms",
                                        "mg_iter_roofline"}
        assert 5 <= line["metrics"]["mg_pcg_iters"]["value"] <= 20
    else:
        assert set(line["metrics"]) == {"solve_s", "setup_s"}
    assert np.isfinite([m["value"] for m in line["metrics"].values()]).all()
