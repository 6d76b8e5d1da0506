"""Reduce a JAX profiler trace by the program's own stage names.

``bench/trace_reduce.py`` names device ops by XLA's numbering
(``fusion.8``), which changes with any edit to the program.  The solve
path runs each device stage under a ``jax.named_scope`` of one fixed
vocabulary (``repro.obs.trace.SCOPES``), and XLA keeps the scope path in
the ``op_name`` metadata of each instruction of the optimized module.  A
TPU profile's op events carry no such name (their ``tf_op`` stat is cut
short or missing on fusions), but the profile holds every executed
module's optimized HLO (the ``Hlo Proto`` stats of its ``/host:metadata``
plane).  So each op event is mapped through the module that ran it, from
the device's ``XLA Modules`` line, to the ``op_name`` of the instruction
of the same name.

``load_op_names(path)`` reads those modules from an ``.xplane.pb``;
``reduce_planes(planes, op_names)``, with the planes that
``trace_reduce.load_planes`` gives, returns:

- ``scopes``: device self seconds and op-event count by scope path
  (``spmv/scatter``, ``krylov/dot``, ``monitor``, ..., ``unscoped`` for
  the rest), summed over devices and divided by their number, as
  ``trace_reduce`` does for ``device_ops``;
- ``spans``: for the program's host spans named ``solve.*`` inside the
  window, their count, total seconds, and the seconds inside them in
  which the busiest device ran nothing.

The benchmark keeps its own copy of the scope names: a rename in the
program shows here as time moving to ``unscoped``.
"""
from __future__ import annotations

import bisect
import re

from bench import trace_reduce as T

SCOPES = {
    "spmv": ("decode", "gather", "scatter", "halo"),
    "krylov": ("dot", "update"),
    "precond": (),
    "monitor": (),
}
UNSCOPED = "unscoped"
SPAN_PREFIX = "solve."
MODULES_LINE = "XLA Modules"
METADATA_PLANE = "/host:metadata"
HLO_PROTO_STAT = "Hlo Proto"
_PROGRAM_ID = re.compile(r"\((\d+)\)$")


def scope_path(name_stack: str):
    """The known scope path in an op's name stack, or ``None``:
    ``jit(f)/while/body/spmv/scatter/scatter-add`` gives
    ``spmv/scatter``, a stage with no known child its stage alone."""
    parts = name_stack.split("/")
    for i, part in enumerate(parts):
        if part in SCOPES:
            child = parts[i + 1] if i + 1 < len(parts) else None
            return f"{part}/{child}" if child in SCOPES[part] else part
    return None


# -- the optimized HLO of every module the profile ran -------------------
#
# A few protobuf messages are read straight off the wire, by field
# number: XSpace.planes = 1; XPlane.name = 2, .event_metadata = 4 (map
# entry: key 1, value 2), .stat_metadata = 5 (same); XEventMetadata.id =
# 1, .stats = 5; XStatMetadata.id = 1, .name = 2; XStat.metadata_id = 1,
# .bytes_value = 6; HloProto.hlo_module = 1; HloModuleProto.computations
# = 3; HloComputationProto.instructions = 2; HloInstructionProto.name = 1,
# .metadata = 7; OpMetadata.op_name = 2.

def _varint(buf, i):
    out = shift = 0
    while True:
        byte = buf[i]
        i += 1
        out |= (byte & 0x7F) << shift
        shift += 7
        if byte < 0x80:
            return out, i


def _fields(buf):
    """``(field number, value)`` of one message: an int for a varint, a
    memoryview for anything length-delimited or fixed."""
    i, end = 0, len(buf)
    while i < end:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            value, i = buf[i:i + n], i + n
        elif wire in (1, 5):
            n = 8 if wire == 1 else 4
            value, i = buf[i:i + n], i + n
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield key >> 3, value


def _first(buf, number):
    return next((v for f, v in _fields(buf) if f == number), None)


def _map_values(plane, number):
    for f, entry in _fields(plane):
        if f == number:
            value = _first(entry, 2)
            if value is not None:
                yield value


def _instruction_op_names(hlo_proto) -> dict:
    names = {}
    module = _first(hlo_proto, 1)
    for f, comp in _fields(module if module is not None else b""):
        if f != 3:
            continue
        for g, instr in _fields(comp):
            if g != 2:
                continue
            name = meta = None
            for h, v in _fields(instr):
                if h == 1:
                    name = bytes(v).decode()
                elif h == 7:
                    meta = v
            op_name = _first(meta, 2) if meta is not None else None
            if name is not None and op_name is not None:
                names[name] = bytes(op_name).decode()
    return names


def load_op_names(path) -> dict:
    """``{program id: {instruction name: op_name}}`` of every optimized
    module held in the profile at ``path``."""
    with open(path, "rb") as fh:
        space = memoryview(fh.read())
    out = {}
    for f, plane in _fields(space):
        if f != 1 or bytes(_first(plane, 2) or b"") != METADATA_PLANE.encode():
            continue
        stat_ids = {_first(m, 1) for m in _map_values(plane, 5)
                    if bytes(_first(m, 2) or b"") == HLO_PROTO_STAT.encode()}
        for meta in _map_values(plane, 4):
            for g, stat in _fields(meta):
                if g == 5 and _first(stat, 1) in stat_ids:
                    out[_first(meta, 1)] = _instruction_op_names(
                        _first(stat, 6) or b"")
    return out


# -- the reduction ------------------------------------------------------

def _modules(plane, lo, hi):
    """``(starts, [(end, program id)])`` of the module runs on a device."""
    runs = []
    for line in plane.lines:
        if line.name != MODULES_LINE:
            continue
        for ev in line.events:
            s = float(ev.start_ns)
            e = s + float(ev.duration_ns)
            m = _PROGRAM_ID.search(ev.name)
            if m and e > lo and s < hi:
                runs.append((s, e, int(m.group(1))))
    runs.sort()
    return [r[0] for r in runs], [(r[1], r[2]) for r in runs]


def _ops(plane, lo, hi, op_names):
    lines = [ln for ln in plane.lines if ln.name == T.OPS_LINE]
    if not lines:
        return []
    starts, runs = _modules(plane, lo, hi)
    out = []
    for ev in lines[0].events:
        s = float(ev.start_ns)
        e = s + float(ev.duration_ns)
        if e <= lo or s >= hi:
            continue
        k = bisect.bisect_right(starts, s) - 1
        names = op_names.get(runs[k][1], {}) if k >= 0 and s < runs[k][0] \
            else {}
        path = scope_path(names.get(T.op_name(ev.name), ""))
        out.append((path or UNSCOPED, max(s, lo), min(e, hi)))
    return out


def _overlap(intervals, lo, hi):
    return sum(max(0.0, min(e, hi) - max(s, lo)) for s, e in intervals)


def reduce_planes(planes, op_names: dict) -> dict:
    planes = list(planes)
    host = T._host_spans(planes)
    lo, hi = T._window(host)
    secs, counts, busy = {}, {}, []
    for plane in planes:
        if not T._DEVICE_PLANE.match(plane.name):
            continue
        ops = _ops(plane, lo, hi, op_names)
        if not ops:
            continue
        for path, self_ns in T._self_times(ops):
            secs[path] = secs.get(path, 0.0) + self_ns
        for path, _, _ in ops:
            counts[path] = counts.get(path, 0) + 1
        busy.append(T._union([(s, e) for _, s, e in ops]))
    if not busy:
        raise ValueError("no device plane with an 'XLA Ops' line")
    n_dev = len(busy)
    scopes = {p: {"seconds": secs[p] * 1e-9 / n_dev,
                  "ops": counts[p] / n_dev}
              for p in sorted(secs, key=lambda p: -secs[p])}
    busiest = max(busy, key=lambda iv: sum(e - s for s, e in iv))
    spans = {}
    for name, s, e in host:
        if not name.startswith(SPAN_PREFIX) or s < lo or s >= hi:
            continue
        e = min(e, hi)
        rec = spans.setdefault(name, {"count": 0, "seconds": 0.0,
                                      "idle_s": 0.0})
        rec["count"] += 1
        rec["seconds"] += (e - s) * 1e-9
        rec["idle_s"] += (e - s - _overlap(busiest, s, e)) * 1e-9
    return {"scopes": scopes, "spans": spans}
