"""Drive a cell through the harness on the CPU at a tiny grid."""
import time

from bench import harness, trace_reduce

TINY = {1: 12, 4: 16}


class _Ev:
    def __init__(self, name, start, dur):
        self.name, self.start_ns, self.duration_ns = name, start, dur


class _Line:
    def __init__(self, name, events):
        self.name, self.events = name, events


class _Plane:
    def __init__(self, name, lines):
        self.name, self.lines = name, lines


def with_fake_devices(chips):
    """A ``load_planes`` that adds device planes to a CPU trace: each
    device runs one op through each window solve, and on several chips a
    collective for its first tenth."""
    real = trace_reduce.load_planes

    def load(path):
        planes = real(path)
        solves = [(s, e) for n, s, e in trace_reduce._host_spans(planes)
                  if n == "bench.solve"]
        for i in range(chips):
            evs = [_Ev("fusion.1", s, e - s) for s, e in solves]
            if chips > 1:
                evs += [_Ev("all-gather.2", s, (e - s) / 10) for s, e in solves]
            planes.append(_Plane(f"/device:TPU:{i}", [_Line("XLA Ops", evs)]))
        return planes

    return load


def tiny_cell(name, root=harness.ROOT):
    cell = harness.Cell(name, root)
    g = TINY[cell.chips]
    cell.config["grid"] = [g, g, g]
    return cell


def pcg_cell():
    """``lap3d_48.cg``'s deployment solved by Jacobi-PCG, as a
    configuration with ``solve_pcg`` would state it."""
    cell = tiny_cell("lap3d_48.cg")
    cell.config["solver"].update(kind="pcg_jacobi", entry="solve_pcg",
                                 precond="jacobi")
    cell.work = harness.load_module(harness.BENCH / "work" / "pcg_jacobi.py")
    return cell


ONE_CHIP = {"cg": lambda: tiny_cell("lap3d_48.cg"), "pcg": pcg_cell}


def run_tiny(monkeypatch, cell, trace=False, seed=2**40 + 3, seconds=0.05):
    monkeypatch.setattr(harness, "_peak", lambda d: {"hbm_bytes_per_s": 819e9})
    monkeypatch.setattr(trace_reduce, "load_planes",
                        with_fake_devices(cell.chips))
    return harness.run(cell, seed, seconds, trace, time.perf_counter(),
                       require_tpu=False)
