"""Share of the HBM roofline that the SpMV alone reaches, per chip: the
least bytes of every SpMV the window ran over the device self time under
the program's ``spmv`` scope (``bench/scope_reduce.py``) times the chip's
peak HBM bandwidth (``bench/peaks.json``).

The least bytes of one SpMV at a tag are the matrix's (``bench/work/cg.py``
``matrix_bytes``), 8 B a row for the input and 8 B a row for the output,
and 8 B for each halo entry on shards.  A solve runs one SpMV an
iteration, at the tag ``iter_roofline.tag_iterations`` gives it, one for
the initial residual of each loop run (tag 1 first, tag 3 for a resumed
correction), and the final correction's tag-3 true-residual check.  Loop
runs and checks are counted from the ``solve.correction`` spans.
"""
from bench.work import cg
from bench.metrics.iter_roofline import tag_iterations

F64 = 8


def spmv_bytes(shape: dict, tag: int) -> int:
    return (cg.matrix_bytes(shape, tag) + 2 * shape["n"] * F64
            + shape["halo"] * F64)


def spmv_count(solves, spans) -> dict:
    """``{tag: SpMVs}`` of the window's solves, or ``None`` where a
    solve's split by tag is not known."""
    checks = spans.get("solve.correction", {}).get("count", 0)
    resumes = spans.get("solve.correction.resume", {}).get("count", 0)
    count = {1: len(solves), 2: 0, 3: checks + resumes}
    for solve in solves:
        split = tag_iterations(solve)
        if split is None:
            return None
        for tag, n in split.items():
            count[tag] += n
    return count


def read(rec):
    tr = rec.get("trace")
    if not tr or "scopes" not in tr or not rec["solves"]:
        return None
    spmv_s = sum(v["seconds"] for k, v in tr["scopes"].items()
                 if k.split("/")[0] == "spmv")
    count = spmv_count(rec["solves"], tr.get("spans", {}))
    if spmv_s <= 0 or count is None:
        return None
    total = sum(n * spmv_bytes(rec["shape"], tag) for tag, n in count.items())
    per_chip = total / rec["shape"]["chips"]
    return 100.0 * per_chip / (spmv_s * rec["peak"]["hbm_bytes_per_s"])
