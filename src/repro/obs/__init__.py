"""Observability layer: metrics registry, span tracer, solver flight recorder.

Three parts (DESIGN.md section 16):

- ``obs.metrics``: the single counter/gauge/histogram implementation behind
  ``PACK_STATS``, ``TUNE_STATS`` and ``SolverService`` stats, with
  Prometheus-text and JSON exposition.
- ``obs.trace``: nested wall-clock spans with byte/flop annotations written
  as schema-versioned JSONL, plus the device scope vocabulary
  (``spmv/scatter``, ``krylov/dot``, ...) the solve path's stages carry as
  ``jax.named_scope`` names, so device profiles name each op's stage.
- ``obs.flight``: a fixed-size device-side ring buffer carried through the
  solver ``lax.while_loop`` recording one row per iteration with zero
  host syncs; decoded post-solve into a ``FlightLog``.
"""

from repro.obs import flight, metrics, trace
from repro.obs.flight import FlightLog, FlightParams, flight_init, flight_record
from repro.obs.metrics import REGISTRY, Registry, stats_view
from repro.obs.trace import Tracer, capture, span, validate_jsonl

__all__ = [
    "FlightLog",
    "FlightParams",
    "REGISTRY",
    "Registry",
    "Tracer",
    "capture",
    "flight",
    "flight_init",
    "flight_record",
    "metrics",
    "span",
    "stats_view",
    "trace",
    "validate_jsonl",
]
