"""The per-layer readers on a hand-made run record."""
import pytest

from bench import harness
from bench.work import cg

READERS = {n: harness.load_module(harness.BENCH / "metrics" / f"{n}.py")
           for n in ("iters", "iter_ms", "window_compiles", "iter_roofline",
                     "device_idle_share")}
SHAPE = {"n": 1000, "nnz": 7000, "halo": 0, "chips": 1}


def _rec(solves, devices=None, window_s=2.0):
    devices = devices or {"/device:TPU:0": {"busy_s": 1.5, "collective_s": 0.0}}
    return {"solves": solves, "window_s": window_s, "window_compiles": 3,
            "shape": SHAPE, "work": cg,
            "peak": {"hbm_bytes_per_s": 1e9},
            "trace": {"window_s": 1.8, "devices": devices}}


def _solve(iters, s2=-1, s3=-1, tag=1, corr=0):
    return {"iters": iters, "switch_iters": [s2, s3], "tag": tag,
            "correction_iters": corr}


@pytest.mark.parametrize("solve, split", [
    (_solve(100), {1: 100, 2: 0, 3: 0}),
    (_solve(100, 40, 70, tag=3), {1: 40, 2: 30, 3: 30}),
    (_solve(100, 40, tag=2), {1: 40, 2: 60, 3: 0}),
    (_solve(250, tag=3, corr=110), {1: 140, 2: 0, 3: 110}),
    (_solve(250, 60, 90, tag=3, corr=100), {1: 60, 2: 30, 3: 160}),
    (_solve(100, -1, 50, tag=3), {1: 50, 2: 0, 3: 50}),
])
def test_tag_split(solve, split):
    assert READERS["iter_roofline"].tag_iterations(solve) == split


def test_unknown_correction_split_reads_nothing():
    amb = _solve(250, tag=3, corr=None)
    assert READERS["iter_roofline"].tag_iterations(amb) is None
    assert READERS["iter_roofline"].read(_rec([amb])) is None
    # Without a correction the split needs no count.
    assert READERS["iter_roofline"].tag_iterations(
        _solve(100, tag=1, corr=None)) == {1: 100, 2: 0, 3: 0}


def test_readings():
    solves = [_solve(100), _solve(250, tag=3, corr=110)]
    rec = _rec(solves)
    r = {n: m.read(rec) for n, m in READERS.items()}
    assert r["iters"] == 175
    assert r["iter_ms"] == pytest.approx(2000 / 350)
    assert r["window_compiles"] == 3
    want = (240 * cg.iteration_bytes(SHAPE, 1)
            + 110 * cg.iteration_bytes(SHAPE, 3))
    assert r["iter_roofline"] == pytest.approx(100 * want / (1.5 * 1e9))
    assert r["device_idle_share"] == pytest.approx(100 * (1 - 1.5 / 1.8))


def test_several_chips():
    devs = {f"/device:TPU:{i}": {"busy_s": b, "collective_s": c}
            for i, (b, c) in enumerate([(1.0, 0.2), (1.2, 0.4)])}
    rec = _rec([_solve(100)], devs)
    rec["shape"] = dict(SHAPE, chips=2)
    assert READERS["device_idle_share"].read(rec) == pytest.approx(
        100 * (1 - 1.2 / 1.8))
    share = READERS["iter_roofline"].read(rec)
    assert share == pytest.approx(
        100 * 100 * cg.iteration_bytes(rec["shape"], 1) / 2 / (1.1 * 1e9))


def test_collective_share():
    reader = harness.load_module(harness.BENCH / "metrics" / "collective_share.py")
    devs = {f"/device:TPU:{i}": {"busy_s": 1.5, "collective_s": c}
            for i, c in enumerate([0.2, 0.4])}
    assert reader.read(_rec([_solve(100)], devs)) == pytest.approx(
        100 * 0.3 / 1.8)
    # One chip runs no collective: nothing to read, never 0.
    assert reader.read(_rec([_solve(100)])) is None
