"""MG-PCG iterations per solve: ``iters.py``'s reading, ``CGResult.iters``
over the window's solves, in the cells whose solver is CG preconditioned
by the multigrid V-cycle."""
from bench.metrics.iters import read  # noqa: F401
