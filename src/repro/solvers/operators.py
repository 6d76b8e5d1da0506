"""Linear-operator factories with precision-tag dispatch (paper Alg. 3).

An *operator* is ``apply(x, tag) -> A @ x`` where ``tag`` is a traced int32
or a static Python int in {1,2,3} (the solver step applies it at a static
tag inside its tag switch).  GSE-SEM operators dispatch via
``fused_cg.switched`` to the three SpMV precisions; fixed-format
baselines ignore the tag.
"""
from __future__ import annotations

from typing import Callable

import jax.numpy as jnp

from repro.solvers.fused_cg import switched
from repro.sparse.csr import CSR
from repro.sparse.spmv import spmv, spmv_gse

__all__ = [
    "make_gse_operator",
    "make_fixed_operator",
    "make_dense_operator",
    "make_precond_operator",
]


def make_gse_operator(a, acc_dtype=jnp.float64) -> Callable:
    """Three-precision operator over one stored copy (the paper's A1/A2/A3).

    ``a`` is a ``GSECSR`` or a SELL-C-σ packed ``GSESellC``; the two are
    bit-identical (DESIGN.md §12).  The closure is memoized on the
    instance: solvers take it as a static jit argument, and a fresh one
    per call would retrace the whole solver.  Its branches call the
    jitted ``spmv_gse``: an eager caller (the final correction's check)
    retraces the switch on every call, and the jitted SpMV keeps that
    retrace cheap to lower."""
    key = ("_tag_operator", jnp.dtype(acc_dtype).name)
    op = a.__dict__.get(key)
    if op is None:
        def op(x, tag):
            return switched(
                lambda v, t: spmv_gse(a, v, tag=t, acc_dtype=acc_dtype), tag,
                x)

        a.__dict__[key] = op
    return op


def make_fixed_operator(a: CSR, store_dtype=jnp.float64, acc_dtype=jnp.float64):
    """FP64/FP32/BF16/FP16 baseline: storage precision fixed, acc high."""

    def apply(x, tag):
        del tag
        return spmv(a, x, store_dtype=store_dtype, acc_dtype=acc_dtype)

    return apply


def make_dense_operator(mat: jnp.ndarray):
    def apply(x, tag):
        del tag
        return mat @ x

    return apply


def make_precond_operator(m, acc_dtype=jnp.float64) -> Callable:
    """``apply_m(r, tag) = M^{-1} r`` over a :mod:`repro.solvers.precond`
    preconditioner -- the preconditioner-side twin of ``make_gse_operator``.
    Delegates to the preconditioner's shared tag dispatch (one stored
    copy, three apply precisions, ``lax.switch`` over the tag-specialized
    decode branches)."""

    def apply(r, tag):
        return m.apply(r, tag, acc_dtype)

    return apply
