"""Least HBM bytes of one row-sharded stepped-CG iteration, summed over the
chips: the CG iteration's bytes (``cg.py``), with the row pointers counted
once per shard, plus each shard's halo, the float64 entries of the input
vector that it receives from other shards (8 B each)."""
from bench.work import cg


def iteration_bytes(shape: dict, tag: int) -> int:
    extra_rowptr = (shape["chips"] - 1) * cg.ROWPTR_BYTES
    return (cg.iteration_bytes(shape, tag) + extra_rowptr
            + shape["halo"] * cg.F64)
