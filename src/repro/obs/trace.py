"""Span tracer: nested wall-clock spans emitted as schema-versioned JSONL.

Host-side observability companion to the device-side flight recorder.  A
:class:`Tracer` records a tree of named spans (pack, tune-sweep, decode,
solve, halo-exchange, service flush) with free-form attribute dicts — byte
and flop annotations come from the perf ledger at the call sites.  One JSON
object per line; every event carries ``"v": SCHEMA_VERSION`` so downstream
consumers can reject what they don't understand, and
:func:`validate_jsonl` is the schema check CI runs on the emitted file.

When no tracer is installed, :func:`span` is a near-zero-cost no-op, so
instrumented call sites cost nothing on the clean path.  Spans that wrap
code inside a jit trace measure trace/compile-time cost (they run once per
compilation); device-side time is attributed through the device scope
vocabulary below, which the solve path's stages carry as
``jax.named_scope`` names (see DESIGN.md section 16).
"""

from __future__ import annotations

import contextlib
import json
import threading
import time

__all__ = [
    "SCHEMA_VERSION",
    "Tracer",
    "active",
    "annotate",
    "capture",
    "current",
    "event",
    "install",
    "scope",
    "span",
    "uninstall",
    "validate_event",
    "validate_jsonl",
]

SCHEMA_VERSION = 1

# jax.profiler.TraceAnnotation forwards span names into device profiles when
# a profiler session is running; it is a cheap no-op otherwise.  Imported
# lazily so obs.trace itself never forces jax in.
_PROFILER_ANNOTATION = None


def _profiler_annotation():
    global _PROFILER_ANNOTATION
    if _PROFILER_ANNOTATION is None:
        try:
            from jax.profiler import TraceAnnotation
            _PROFILER_ANNOTATION = TraceAnnotation
        except Exception:  # pragma: no cover - profiler unavailable
            _PROFILER_ANNOTATION = False
    return _PROFILER_ANNOTATION or None


class Tracer:
    """Collects span/event records; thread-safe append, per-thread nesting."""

    def __init__(self):
        self._lock = threading.Lock()
        self._local = threading.local()
        self._next_id = 0
        self.events: list[dict] = []

    def _stack(self) -> list:
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        return stack

    def _new_id(self) -> int:
        with self._lock:
            self._next_id += 1
            return self._next_id

    @contextlib.contextmanager
    def span(self, name: str, **attrs):
        """Record a nested span; yields the attrs dict for late annotation."""
        stack = self._stack()
        rec = {
            "v": SCHEMA_VERSION,
            "kind": "span",
            "name": str(name),
            "id": self._new_id(),
            "parent": stack[-1]["id"] if stack else None,
            "depth": len(stack),
            "t0": time.time(),
            "dur_s": 0.0,
            "attrs": dict(attrs),
        }
        stack.append(rec)
        annotation_cls = _profiler_annotation()
        ctx = annotation_cls(rec["name"]) if annotation_cls else None
        start = time.perf_counter()
        try:
            if ctx is not None:
                with ctx:
                    yield rec["attrs"]
            else:
                yield rec["attrs"]
        finally:
            rec["dur_s"] = time.perf_counter() - start
            stack.pop()
            with self._lock:
                self.events.append(rec)

    def event(self, name: str, **attrs):
        """Record an instantaneous (zero-duration) event."""
        stack = self._stack()
        rec = {
            "v": SCHEMA_VERSION,
            "kind": "event",
            "name": str(name),
            "id": self._new_id(),
            "parent": stack[-1]["id"] if stack else None,
            "depth": len(stack),
            "t0": time.time(),
            "dur_s": 0.0,
            "attrs": dict(attrs),
        }
        with self._lock:
            self.events.append(rec)
        return rec

    def annotate(self, **attrs):
        """Merge attrs into the innermost open span (no-op at top level)."""
        stack = self._stack()
        if stack:
            stack[-1]["attrs"].update(attrs)

    def write_jsonl(self, path) -> int:
        """Write one event per line, oldest first; returns the line count."""
        with self._lock:
            events = list(self.events)
        events.sort(key=lambda e: e["id"])
        with open(path, "w") as fh:
            for rec in events:
                fh.write(json.dumps(rec, sort_keys=False) + "\n")
        return len(events)


# -- device scope vocabulary --------------------------------------------
#
# Every device stage of the solve path runs under one of these
# ``jax.named_scope`` paths, so each op XLA emits for it carries the path
# in its ``op_name`` metadata (``.../spmv/scatter/scatter-add``) whatever
# the compiler fuses or numbers.  Scopes are HLO metadata only: they change
# no arithmetic and no compiled program.
SPMV = "spmv"          # one operator application
DECODE = "decode"      #   GSE-SEM segments to acc_dtype values
GATHER = "gather"      #   x[col] and the product with the values
SCATTER = "scatter"    #   the row reduction (slot-major sum or segment_sum)
HALO = "halo"          #   boundary pack and all-gather (sharded only)
KRYLOV = "krylov"      # the Krylov vector work
DOT = "dot"            #   inner products, psum included
UPDATE = "update"      #   axpys, xpby and their scalars
PRECOND = "precond"    # the preconditioner apply
SMOOTH = "smooth"      #   the multigrid smoother's sweeps
RESIDUAL = "residual"  #   the multigrid residual before restriction
TRANSFER = "transfer"  #   restriction, prolongation, the level ordering
MONITOR = "monitor"    # monitor record/step, switch log, guards, flight

SCOPES = {
    SPMV: (DECODE, GATHER, SCATTER, HALO),
    KRYLOV: (DOT, UPDATE),
    PRECOND: (SMOOTH, RESIDUAL, TRANSFER),
    MONITOR: (),
}
_SCOPE_NAMES = frozenset(SCOPES).union(*SCOPES.values())


@contextlib.contextmanager
def scope(*path: str):
    """Enter the nested ``jax.named_scope`` of each name in ``path``.

    Names come from :data:`SCOPES`: a stage's own path
    (``scope(KRYLOV, DOT)``), or a child alone inside a scope its caller
    opened (``scope(DECODE)`` under ``scope(SPMV)``).
    """
    import jax

    with contextlib.ExitStack() as stack:
        for name in path:
            if name not in _SCOPE_NAMES:
                raise ValueError(f"{name!r} is not a device scope name")
            stack.enter_context(jax.named_scope(name))
        yield


# -- module-level installed tracer --------------------------------------

_INSTALLED: Tracer | None = None


def install(tracer: Tracer) -> Tracer:
    global _INSTALLED
    _INSTALLED = tracer
    return tracer


def uninstall() -> None:
    global _INSTALLED
    _INSTALLED = None


def current() -> Tracer | None:
    return _INSTALLED


def active() -> bool:
    return _INSTALLED is not None


_NULL_ATTRS: dict = {}


@contextlib.contextmanager
def span(name: str, **attrs):
    """Span on the installed tracer; near-free no-op when none is active."""
    tracer = _INSTALLED
    if tracer is None:
        yield _NULL_ATTRS
        return
    with tracer.span(name, **attrs) as a:
        yield a


def event(name: str, **attrs):
    tracer = _INSTALLED
    if tracer is not None:
        tracer.event(name, **attrs)


def annotate(**attrs):
    tracer = _INSTALLED
    if tracer is not None:
        tracer.annotate(**attrs)


@contextlib.contextmanager
def capture(path=None):
    """Install a fresh tracer for the block; optionally write JSONL after."""
    tracer = Tracer()
    prev = _INSTALLED
    install(tracer)
    try:
        yield tracer
    finally:
        install(prev) if prev is not None else uninstall()
        if path is not None:
            tracer.write_jsonl(path)


# -- schema validation ---------------------------------------------------

_REQUIRED_FIELDS = {
    "v": int,
    "kind": str,
    "name": str,
    "id": int,
    "depth": int,
    "t0": (int, float),
    "dur_s": (int, float),
    "attrs": dict,
}
_KINDS = ("span", "event")


def validate_event(rec) -> None:
    """Raise ValueError if ``rec`` is not a valid v1 trace event."""
    if not isinstance(rec, dict):
        raise ValueError(f"event must be an object, got {type(rec).__name__}")
    for field, types in _REQUIRED_FIELDS.items():
        if field not in rec:
            raise ValueError(f"missing field {field!r}")
        if not isinstance(rec[field], types):
            raise ValueError(
                f"field {field!r} has type {type(rec[field]).__name__}"
            )
        if field in ("v", "id", "depth") and isinstance(rec[field], bool):
            raise ValueError(f"field {field!r} must be an int, got bool")
    if rec["v"] != SCHEMA_VERSION:
        raise ValueError(f"unsupported schema version {rec['v']}")
    if rec["kind"] not in _KINDS:
        raise ValueError(f"unknown kind {rec['kind']!r}")
    if "parent" not in rec:
        raise ValueError("missing field 'parent'")
    if rec["parent"] is not None and not isinstance(rec["parent"], int):
        raise ValueError("field 'parent' must be int or null")
    if rec["dur_s"] < 0:
        raise ValueError("negative dur_s")
    if rec["depth"] < 0:
        raise ValueError("negative depth")
    for key in rec["attrs"]:
        if not isinstance(key, str):
            raise ValueError("attrs keys must be strings")


def validate_jsonl(path) -> int:
    """Validate every line of a JSONL trace; returns the event count.

    Also checks referential integrity: a span's ``parent`` (when set) must
    be the id of some event in the file.
    """
    count = 0
    ids: set[int] = set()
    parents: list[tuple[int, int]] = []
    with open(path) as fh:
        for lineno, line in enumerate(fh, 1):
            line = line.strip()
            if not line:
                continue
            try:
                rec = json.loads(line)
            except json.JSONDecodeError as exc:
                raise ValueError(f"{path}:{lineno}: bad JSON: {exc}") from exc
            try:
                validate_event(rec)
            except ValueError as exc:
                raise ValueError(f"{path}:{lineno}: {exc}") from exc
            ids.add(rec["id"])
            if rec["parent"] is not None:
                parents.append((lineno, rec["parent"]))
            count += 1
    for lineno, parent in parents:
        if parent not in ids:
            raise ValueError(f"{path}:{lineno}: dangling parent id {parent}")
    return count
