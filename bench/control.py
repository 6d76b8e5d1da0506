#!/usr/bin/env python3
"""The control of a cell's comparison: the benchmark's reference CG in the
program's place, one precision below the configuration's float64.

    python bench/control.py --workload <name> --seeds 1,2,3 [--dtype float32]

For each seed it solves the first right-hand side of that seed's window
pool at the cell's own size, on the default device, and prints the true
relative residual the comparison would read beside the cell's limit.
The last line is a JSON object with every reading.  The benchmark's own
runs never run this.
"""
from __future__ import annotations

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def readings(cell, seeds, dtype):
    from bench import operators
    from bench.rhs import Stream

    coo = operators.build(cell.config)
    sv = cell.config["solver"]
    rows = []
    for seed in seeds:
        b = Stream(cell.traffic, coo, cell.config, seed).rhs(0)
        x, iters = operators.cg_control(
            coo, b, float(sv["args"]["tol"]), int(sv["args"]["maxiter"]),
            dtype, sv["precond"] == "jacobi")
        rows.append({"seed": seed, "iters": iters,
                     "true_relres": coo.true_relres(x, b)})
    return rows


def main(argv=None) -> int:
    import argparse

    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench import harness

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--dtype", default="float32")
    args = ap.parse_args(argv)
    harness.configure_jax()
    import jax
    import jax.numpy as jnp

    cell = harness.Cell(args.workload)
    limit = cell.config["accuracy"]["true_relres"]
    rows = readings(cell, [int(s) for s in args.seeds.split(",")],
                    getattr(jnp, args.dtype))
    for r in rows:
        print(f"control seed={r['seed']} iters={r['iters']} "
              f"true_relres={r['true_relres']!r} limit={limit!r}",
              file=sys.stderr, flush=True)
    d = jax.devices()[0]
    print(json.dumps({"workload": args.workload, "dtype": args.dtype,
                      "device": d.device_kind, "limit": limit,
                      "readings": rows}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
