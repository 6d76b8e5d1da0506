"""Reduce a JAX profiler trace to the numbers the per-layer metrics read.

``reduce_planes`` takes the planes of an ``.xplane.pb`` as
``jax.profiler.ProfileData`` gives them (anything with ``.name``,
``.lines``; lines with ``.name``, ``.events``; events with ``.name``,
``.start_ns``, ``.duration_ns``) and returns:

- ``window_s``: the length of the traced window, the host span
  ``bench.window`` that the harness opens around its measured solves;
- per device: ``busy_s``, the union of the intervals in which an operation
  ran on that device inside the window, and ``collective_s``, the device
  time of its collective operations;
- ``device_ops``: device self seconds by op name (an op's time less the
  time of the ops nested in it, as a loop holds its body), summed over
  devices and divided by their number, most first;
- ``idle_gaps``: the longest intervals inside the window in which no
  device ran an operation, each labelled by the innermost host span that
  covers most of it.
"""
from __future__ import annotations

import re

WINDOW_SPAN = "bench.window"
OPS_LINE = "XLA Ops"
_DEVICE_PLANE = re.compile(r"^/device:[A-Z]+:\d+$")
_COLLECTIVE = re.compile(
    r"all-gather|all-reduce|collective-permute|all-to-all|reduce-scatter",
    re.IGNORECASE)


def load_planes(path):
    """The planes of one ``.xplane.pb`` file."""
    from jax.profiler import ProfileData

    return list(ProfileData.from_file(str(path)).planes)


def _union(intervals):
    """Sorted, merged ``(start, end)`` intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _events(line):
    for ev in line.events:
        s = float(ev.start_ns)
        yield ev.name, s, s + float(ev.duration_ns)


def op_name(text: str) -> str:
    """The HLO name of a device op event: ``%fusion.8 = f32[...] ...``
    gives ``fusion.8``."""
    return text.split(" = ", 1)[0].lstrip("%")


def _self_times(ops):
    """``(name, self_ns)`` of nested ``(name, start, end)`` op events that
    are already clipped to the window."""
    out, stack = [], []
    for name, s, e in sorted(ops, key=lambda o: (o[1], -o[2])):
        while stack and stack[-1][2] <= s:
            out.append((stack[-1][0], stack[-1][2] - stack[-1][1]
                        - stack[-1][3]))
            stack.pop()
        if stack:
            stack[-1][3] += e - s
        stack.append([name, s, e, 0.0])
    out.extend((n, e - s - c) for n, s, e, c in stack)
    return out


def _host_spans(planes):
    """Every host event as ``(name, start_ns, end_ns)``."""
    spans = []
    for plane in planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend(_events(line))
    return spans


def _window(host_spans):
    hits = [(s, e) for n, s, e in host_spans if n == WINDOW_SPAN]
    if not hits:
        raise ValueError(f"no host span {WINDOW_SPAN!r} in the trace")
    return min(s for s, _ in hits), max(e for _, e in hits)


def _label(gap, host_spans):
    """The shortest host span that covers at least half of ``gap``."""
    lo, hi = gap
    best = None
    for name, s, e in host_spans:
        if name == WINDOW_SPAN:
            continue
        cover = min(e, hi) - max(s, lo)
        if cover * 2 >= hi - lo and (best is None or e - s < best[0]):
            best = (e - s, name)
    return best[1] if best else "no host span"


def reduce_planes(planes, top: int = 10) -> dict:
    planes = list(planes)
    host = _host_spans(planes)
    lo, hi = _window(host)
    devices, op_ns, merged = {}, {}, []
    for plane in planes:
        if not _DEVICE_PLANE.match(plane.name):
            continue
        lines = [ln for ln in plane.lines if ln.name == OPS_LINE]
        if not lines:
            continue
        ops = [(op_name(n), max(s, lo), min(e, hi))
               for n, s, e in _events(lines[0]) if e > lo and s < hi]
        for name, self_ns in _self_times(ops):
            op_ns[name] = op_ns.get(name, 0.0) + self_ns
        coll = sum(e - s for n, s, e in ops if _COLLECTIVE.search(n))
        busy = _union([(s, e) for _, s, e in ops])
        merged.extend(busy)
        devices[plane.name] = {
            "busy_s": sum(e - s for s, e in busy) * 1e-9,
            "collective_s": coll * 1e-9,
        }
    if not devices:
        raise ValueError("no device plane with an 'XLA Ops' line")
    n_dev = len(devices)
    ops = sorted(((k, v * 1e-9 / n_dev) for k, v in op_ns.items()),
                 key=lambda kv: -kv[1])
    gaps, t = [], lo
    for s, e in _union(merged) + [(hi, hi)]:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    gaps.sort(key=lambda g: g[0] - g[1])
    return {
        "window_s": (hi - lo) * 1e-9,
        "devices": devices,
        "device_ops": [[k, v] for k, v in ops[:top]],
        "idle_gaps": [[_label(g, host), (g[1] - g[0]) * 1e-9]
                      for g in gaps[:top]],
    }
