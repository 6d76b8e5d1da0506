"""End-to-end driver (paper-native): stepped mixed-precision GMRES.

Solves an asymmetric convection-diffusion system from one stored GSE-SEM
matrix, starting at 16-bit heads and stepping precision when the residual
stalls -- then compares against FP64 / FP16 / BF16 baselines (Tables
III/IV phenomenology).

  PYTHONPATH=src python examples/solve_stepped_gmres.py
"""
import jax

jax.config.update("jax_enable_x64", True)

from repro import compile_cache  # noqa: E402

compile_cache.enable()

import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.core.precision import MonitorParams  # noqa: E402
from repro.sparse import generators as G  # noqa: E402
from repro.sparse.csr import pack_csr  # noqa: E402
from repro.sparse.spmv import spmv  # noqa: E402
from repro.solvers import (  # noqa: E402
    make_fixed_operator, make_gse_operator, solve_gmres,
)


def main():
    a = G.diag_rescale(G.convection_diffusion_2d(32, beta=5.0), 3.0, 7)
    rng = np.random.default_rng(7)
    x_true = rng.normal(size=a.shape[1])
    b = spmv(a, jnp.asarray(x_true))
    g = pack_csr(a, k=8)
    params = MonitorParams(t=40, l=60, m=30, rsd_limit=0.5,
                           reldec_limit=0.45)

    print(f"system: {a.shape[0]} unknowns, {a.nnz} non-zeros "
          f"(asymmetric, diag-rescaled 6 binades)\n")
    print(f"{'format':10s} {'converged':10s} {'iters':>7s} {'relres':>10s} "
          f"{'final tag':>9s}")
    for label, op in {
        "fp64": make_fixed_operator(a),
        "fp16": make_fixed_operator(a, store_dtype=jnp.float16),
        "bf16": make_fixed_operator(a, store_dtype=jnp.bfloat16),
        "gse-sem": make_gse_operator(g),
    }.items():
        res = solve_gmres(op, b, tol=1e-7, restart=80, maxiter=8000,
                          params=params)
        rr = float(res.relres)
        print(f"{label:10s} {str(bool(res.converged)):10s} "
              f"{int(res.iters):7d} {rr:10.2e} {int(res.tag):9d}"
              + (f"   switches at {res.switch_iters.tolist()}"
                 if label == "gse-sem" else ""))


if __name__ == "__main__":
    main()
