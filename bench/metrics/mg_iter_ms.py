"""Milliseconds per MG-PCG iteration, V-cycle included: ``iter_ms.py``'s
reading, the window's host seconds over every iteration its solves ran."""
from bench.metrics.iter_ms import read  # noqa: F401
