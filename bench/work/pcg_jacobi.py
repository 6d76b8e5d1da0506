"""Least HBM bytes of one stepped Jacobi-PCG iteration: the CG iteration's
bytes (``cg.py``) plus the stored Jacobi diagonal, read once a row at the
tag's value segments."""
from bench.work import cg


def iteration_bytes(shape: dict, tag: int) -> int:
    return cg.iteration_bytes(shape, tag) + shape["n"] * cg.SEGMENT_BYTES[tag]
