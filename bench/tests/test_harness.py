"""Every cell of BENCHMARK.json, found by name and driven through the
harness at a tiny grid on the CPU; the command's refusal without a chip;
a cell added as files alone."""
import json
import math
import os
import shutil
import subprocess
import sys

import pytest

from bench import harness
from bench.tests.helpers import run_tiny, tiny_cell

BM = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in BM["workloads"]]
KEYS = ["correct", "attempted", "failed", "metrics", "device"]


@pytest.mark.parametrize("name", CELLS)
def test_cell_files_found_by_name(name):
    cell = harness.Cell(name)
    e2e = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert cell.per_layer and set(cell.readers) == {m["name"] for m in cell.per_layer}
    assert callable(cell.work.iteration_bytes)
    assert cell.config["chips"] == cell.chips


def _check_line(line, names, trace):
    keys = list(line)
    assert keys[:5] == KEYS and keys[-1] == "checks"
    assert ("breakdown" in keys) == trace
    assert json.loads(json.dumps(line)) == line
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 1
    assert set(line["metrics"]) == names
    for m in line["metrics"].values():
        assert math.isfinite(m["value"]) and m["unit"]
    dev = line["device"]
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(dev)
    chk = line["checks"]["worst_true_relres"]
    assert chk["value"] <= chk["limit"]


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("name", CELLS)
def test_cell_runs_at_tiny_grid(monkeypatch, name, trace):
    cell = tiny_cell(name)
    line = run_tiny(monkeypatch, cell, bool(trace))
    if trace:
        names = {m["name"] for m in cell.per_layer}
        assert line["device"]["busy_s"] > 0 and line["device"]["window_s"] > 0
        assert len(line["breakdown"]["device_ops"]) <= 10
        assert len(line["breakdown"]["idle_gaps"]) <= 10
    else:
        names = {m["name"] for m in cell.end_to_end}
    _check_line(line, names, bool(trace))


def test_sharded_deployment_runs():
    """The four-chip cell's row-sharded path, on four virtual CPU
    devices."""
    cell = tiny_cell("lap3d_76.cg.4chip")
    assert cell.config["solver"]["shards"] == 4
    with pytest.MonkeyPatch.context() as mp:
        line = run_tiny(mp, cell, True)
    assert line["device"]["count"] == 4 and line["correct"] is True
    assert line["metrics"]["iters"]["value"] > 0
    assert line["metrics"]["collective_share"]["value"] > 0


def test_new_cell_from_files_alone(monkeypatch, tmp_path):
    """A configuration (a 27-point stencil in the SELL layout), a traffic
    mix and a per-layer metric added as new files plus entries in
    BENCHMARK.json run with no edit to a file."""
    for sub in ("configs", "traffic", "metrics", "work"):
        shutil.copytree(harness.BENCH / sub, tmp_path / "bench" / sub)
    bm = json.loads(json.dumps(BM))
    cfg = json.loads((harness.ROOT / "bench/configs/lap3d_48.json").read_text())
    cfg.update(name="box27", grid=[6, 6, 6], layout="sell", stencil={
        "center": 26.0,
        "neighbors": [[i, j, k, -1.0] for i in (-1, 0, 1) for j in (-1, 0, 1)
                      for k in (-1, 0, 1) if (i, j, k) != (0, 0, 0)]})
    (tmp_path / "bench/configs/box27.json").write_text(json.dumps(cfg))
    # hypre's HPCG-style right-hand side b = A 1, the same in every run.
    traffic = json.loads((harness.BENCH / "traffic/single_rhs.json").read_text())
    traffic.update(name="four", x="ones", bases=1, base_seed=None,
                   vary="none", pool=4)
    (tmp_path / "bench/traffic/four.json").write_text(json.dumps(traffic))
    (tmp_path / "bench/metrics/solves.py").write_text(
        "def read(rec):\n    return float(len(rec['solves']))\n")
    bm["configs"].append({"name": "box27", "source": "x", "reduced": [],
                          "file": "bench/configs/box27.json", "why": "x"})
    bm["workloads"].append({"name": "box27.four", "config": "box27",
                            "traffic": "four", "chips": 1, "why": "x"})
    bm["per_layer"].append({"name": "solves", "unit": "solves",
                            "better": "higher", "source": "program_counter",
                            "layer": "x", "moves": "solve_s",
                            "workloads": ["box27.four"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bm))
    cell = harness.Cell("box27.four", tmp_path)
    line = run_tiny(monkeypatch, cell, True, seconds=0.5)
    assert line["metrics"]["solves"]["value"] == line["attempted"] > 1
    assert line["correct"] is True
    assert cell.traffic["x"] == "ones"


def test_warm_covers_the_resume_budgets(monkeypatch):
    """Set-up warms the final correction's resume program once for each
    first-phase count the configuration lists."""
    cell = tiny_cell("lap3d_48.cg")
    cell.config["solver"]["warm"]["resume_after"] = [3, 5]
    seen = []
    real = harness.Deployment.solve

    def solve(self, b, x0=None, **over):
        seen.append(over)
        return real(self, b, x0, **over)

    monkeypatch.setattr(harness.Deployment, "solve", solve)
    run_tiny(monkeypatch, cell, seconds=0.0)
    maxiter = cell.config["solver"]["args"]["maxiter"]
    resumes = [o["maxiter"] for o in seen if o.get("init_tag") == 3]
    assert resumes == [maxiter - 3, maxiter - 5]
    assert seen[0] == {"tol": harness.NO_STOP_TOL}


def test_traced_run_needs_the_correction_hook(monkeypatch):
    """A traced run of a cell with a final correction fails, and does not
    drop its tag split, where the program's epilogue is gone."""
    monkeypatch.setattr(harness.CorrectionLog, "NAME", "_renamed_epilogue")
    with pytest.raises(RuntimeError, match="final correction"):
        run_tiny(monkeypatch, tiny_cell("lap3d_48.cg"), True)


def _command(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELLS[0], "--seed",
         str(2**33 + 1), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_command_refuses_without_a_chip():
    p = _command(harness.ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_command_fails_without_the_program(tmp_path):
    """A directory with only BENCHMARK.json and the benchmark's files."""
    shutil.copy(harness.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(harness.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _command(tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
