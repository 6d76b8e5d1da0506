"""Iterative solvers with stepped mixed precision (paper Section III.D).

Beyond-paper subsystem (DESIGN.md §10): GSE-packed preconditioners that
ride the operator's tag schedule (``precond``), preconditioned CG
(``solve_pcg``, the same iteration as CG, ``cg_step``) and right-preconditioned
GMRES (``solve_gmres(..., precond=...)``), plus a stepped
iterative-refinement driver (``solve_ir``).

Batched multi-RHS subsystem (DESIGN.md §11): ``solve_cg_batched`` /
``solve_pcg_batched`` / ``solve_ir_batched`` run per-column precision
schedules over one shared operand (matrix bytes charged once per
iteration, ``batched_run_bytes``); ``launch.solver_serve`` is the
request-batching front-end.

Distributed subsystem (DESIGN.md §13): ``solve_cg_sharded`` /
``solve_pcg_sharded`` run the whole stepped loop row-sharded under
``shard_map`` with a tag-aware GSE halo exchange; ``solve_cg`` /
``solve_pcg`` / the batched solvers dispatch there automatically when
handed a ``distributed.partition.PartitionedGSECSR``.

Robustness subsystem (DESIGN.md §14): every solver result carries a
structured ``health`` status (``health_name`` renders it), the in-loop
guardrails are tuned via ``GuardParams`` (``guards=None`` disables), and
low-tag breakdowns recover by tag escalation on the same packed operand.

Multigrid (DESIGN.md §20): ``make_mg`` is HPCG's V-cycle with a
multicolour symmetric Gauss-Seidel smoother over GSE-packed levels, a
preconditioner for ``solve_pcg`` on box-stencil operators.
"""
from repro.robustness.guards import (
    DEFAULT_GUARDS,
    GuardParams,
    health_name,
)
from repro.solvers.batched import (
    BatchedCGResult,
    BatchedIRResult,
    batched_run_bytes,
    solve_cg_batched,
    solve_ir_batched,
    solve_pcg_batched,
)
from repro.solvers.adaptive import AdaptiveResult, solve_adaptive
from repro.solvers.cg import CGResult, solve_cg, solve_pcg
from repro.solvers.fused_cg import cg_step
from repro.solvers.gmres import GMRESResult, solve_gmres
from repro.solvers.ir import IRResult, solve_ir
from repro.solvers.multigrid import MGPrecond, make_mg
from repro.solvers.operators import (
    make_dense_operator,
    make_fixed_operator,
    make_gse_operator,
    make_precond_operator,
)
from repro.solvers.sharded import solve_cg_sharded, solve_pcg_sharded
from repro.solvers.precond import (
    BlockJacobiGSEPrecond,
    DiagGSEPrecond,
    make_block_jacobi,
    make_jacobi,
    make_spai0,
)

__all__ = [
    "DEFAULT_GUARDS",
    "GuardParams",
    "health_name",
    "AdaptiveResult",
    "solve_adaptive",
    "CGResult",
    "BatchedCGResult",
    "BatchedIRResult",
    "batched_run_bytes",
    "solve_cg",
    "solve_pcg",
    "solve_cg_batched",
    "solve_pcg_batched",
    "solve_ir_batched",
    "solve_cg_sharded",
    "solve_pcg_sharded",
    "cg_step",
    "GMRESResult",
    "solve_gmres",
    "IRResult",
    "solve_ir",
    "make_dense_operator",
    "make_fixed_operator",
    "make_gse_operator",
    "make_precond_operator",
    "BlockJacobiGSEPrecond",
    "DiagGSEPrecond",
    "make_block_jacobi",
    "make_jacobi",
    "make_spai0",
    "MGPrecond",
    "make_mg",
]
