"""The trace reduction on a synthetic trace and on one recorded here."""
import pytest

from bench import trace_reduce as T


class Ev:
    def __init__(self, name, start, dur):
        self.name, self.start_ns, self.duration_ns = name, start, dur


class Line:
    def __init__(self, name, events):
        self.name, self.events = name, events


class Plane:
    def __init__(self, name, lines):
        self.name, self.lines = name, lines


def _planes():
    host = Plane("/host:CPU", [Line("python", [
        Ev("before", 0, 50),
        Ev(T.WINDOW_SPAN, 100, 1000),         # window [100, 1100)
        Ev("bench.solve", 100, 600),
        Ev("solve.cg", 110, 580),
        Ev("bench.solve", 700, 400),
        Ev("compile", 690, 60),               # covers the gap [700, 750)
    ])])
    d0 = Plane("/device:TPU:0", [
        Line("XLA Modules", [Ev("jit_solve", 100, 1000)]),
        Line("XLA Ops", [
            Ev("%scatter.1 = f32[8] scatter(...)", 50, 100),   # [100, 150)
            Ev("%while.4 = (f32[8]) while(...)", 150, 350),   # holds the next two
            Ev("%fusion.2 = f32[8] fusion(%all-reduce.9)", 150, 250),
            Ev("%all-reduce.3 = f32[] all-reduce(...)", 400, 100),
            Ev("%fusion.2 = f32[8] fusion(%all-reduce.9)", 750, 300),
        ])])
    d1 = Plane("/device:TPU:1", [Line("XLA Ops", [
        Ev("%all-gather.7 = f32[8] all-gather(...)", 100, 200),
        Ev("%fusion.2 = f32[8] fusion(...)", 760, 100),
    ])])
    other = Plane("/device:TPU:0 stats", [Line("XLA Ops", [Ev("x", 0, 5000)])])
    return [host, d0, d1, other]


def test_busy_collectives_ops_and_gaps():
    r = T.reduce_planes(_planes())
    assert r["window_s"] == pytest.approx(1000e-9)
    d0, d1 = r["devices"]["/device:TPU:0"], r["devices"]["/device:TPU:1"]
    assert set(r["devices"]) == {"/device:TPU:0", "/device:TPU:1"}
    assert d0["busy_s"] == pytest.approx(700e-9)      # 400 + 300
    assert d0["collective_s"] == pytest.approx(100e-9)
    assert d1["busy_s"] == pytest.approx(300e-9)
    assert d1["collective_s"] == pytest.approx(200e-9)
    # Self time under the HLO name, averaged over the two devices: the
    # loop holds its body, so it keeps none of its own.
    ops = dict(r["device_ops"])
    assert ops["fusion.2"] == pytest.approx((250 + 300 + 100) * 1e-9 / 2)
    assert ops["scatter.1"] == pytest.approx(50e-9 / 2)
    assert ops["all-reduce.3"] == pytest.approx(100e-9 / 2)
    assert ops["while.4"] == pytest.approx(0.0)
    assert r["device_ops"][0][0] == "fusion.2"
    # No device busy in [500, 750) and [1050, 1100): the longest first,
    # each labelled by the shortest host span covering half of it.
    gaps = r["idle_gaps"]
    assert [g[1] for g in gaps] == pytest.approx([250e-9, 50e-9])
    assert gaps[0][0] == "solve.cg"          # covers 190 of its 250
    assert gaps[1][0] == "bench.solve"


def test_gap_label_prefers_innermost_span():
    planes = _planes()
    planes[0].lines[0].events.append(Ev("resume", 520, 200))
    r = T.reduce_planes(planes)
    assert r["idle_gaps"][0][0] == "resume"


def test_no_window_or_device_is_an_error():
    planes = _planes()
    with pytest.raises(ValueError, match="no device plane"):
        T.reduce_planes(planes[:1])
    planes[0].lines[0].events = [Ev("x", 0, 10)]
    with pytest.raises(ValueError, match="bench.window"):
        T.reduce_planes(planes)


def test_recorded_trace_host_spans(tmp_path):
    """A trace recorded by the profiler here: the window and the solve
    spans are read back from the host plane of the ``.xplane.pb``."""
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: jnp.sin(x) @ x)
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(T.WINDOW_SPAN):
        with jax.profiler.TraceAnnotation("bench.solve"):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    (path,) = tmp_path.rglob("*.xplane.pb")
    planes = T.load_planes(path)
    names = {n for n, _, _ in T._host_spans(planes)}
    assert {T.WINDOW_SPAN, "bench.solve"} <= names
    lo, hi = T._window(T._host_spans(planes))
    assert hi > lo
    # The CPU backend records no device plane with an 'XLA Ops' line.
    with pytest.raises(ValueError, match="no device plane"):
        T.reduce_planes(planes)
