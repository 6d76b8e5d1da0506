"""Pallas TPU kernel: blocked-ELL SpMM with fused GSE-SEM decode.

Multi-RHS extension of ``kernels/gse_spmv.py`` (DESIGN.md §11): the paper's
whole case is that SpMV is memory-bound, so the GSE-SEM format wins by
streaming fewer *matrix* bytes per iteration.  With ``nrhs`` right-hand
sides the same packed segments are decoded ONCE per tile and amortized
across all columns of a dense (n, nrhs) operand -- one streaming pass over
the head/tail segments feeds every RHS, multiplying the byte win by the
batch width.

Tag specialization is identical to the SpMV kernel: each tag gets its own
kernel body whose ``pallas_call`` operand list contains ONLY the segments
that tag reads (tail arrays for tags that skip them never enter the jaxpr,
never get a BlockSpec, never get DMA'd):

    tag 1   scales, colpak, head, xg                  (6 + 4*nrhs B/slot)
    tag 2   scales, colpak, head, tail1, xg           (8 + 4*nrhs B/slot)
    tag 3   scales, colpak, head, tail1, tail2, xg    (12 + 4*nrhs B/slot)

Output layout (DESIGN.md §2.3 generalized): each RHS column owns its own
lane-aligned (BM, LANE) accumulator strip inside a (BM, nrhs*LANE) VMEM
tile -- a (BM, BL) product tile is reduced only across its BL/128 sublane
groups per column, so every store fills all 128 lanes.  The reduction
epilogue collapses the LANE partials per (row, column) to the final
(M, nrhs) result.

The dense operand is gathered in XLA before the kernel, as in the SpMV
(``gse_spmv.gather_cols``): ``xg`` is (nrhs, M, L) f32, streamed as
(nrhs, BM, BL) tiles.  So the matrix segments are read once for all
columns while each column adds its own 4 B per padded slot.  Padded
matrix slots carry col=0, head=0 -> mantissa 0 -> contribute 0 to every
column.

Grid: (M/BM, L/BL); the L axis accumulates sequentially into the output
rows, exactly like the SpMV kernel.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.gse_spmv import (
    LANE,
    decode_tile,
    gather_cols,
    segment_operands,
    spmv_operand_names,
)
from repro.perf import plan as launch_plan

__all__ = ["gse_spmm_pallas", "gse_spmm_call", "gse_spmm_sell_call",
           "spmm_operand_names", "LANE"]

# The multi-RHS kernel streams the SAME matrix segment list as the SpMV,
# whatever nrhs is -- one name owns the layout (asserted in tests).
spmm_operand_names = spmv_operand_names


def _accumulate(scales_ref, colpak_ref, head_ref, tail1_ref, tail2_ref,
                xg_ref, out_ref, *, ei_bit: int, tag: int, k: int, nrhs: int):
    """Shared tile math; tail refs are ``None`` for the tags that skip them.

    The decode runs ONCE per (BM, BL) tile (``decode_tile``, shared with
    the SpMV kernel body); the per-column products and lane-group
    reductions reuse the same decoded ``vals`` -- the in-VMEM twin of the
    byte model's "matrix bytes once, vector bytes per column".
    """
    @pl.when(pl.program_id(1) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    vals = decode_tile(scales_ref, colpak_ref, head_ref, tail1_ref,
                       tail2_ref, ei_bit=ei_bit, tag=tag, k=k)

    bm, bl = vals.shape
    for j in range(nrhs):                 # static unroll over RHS columns
        prod = vals * xg_ref[j]           # (BM, BL) -- decoded vals reused
        out_ref[:, j * LANE:(j + 1) * LANE] += jnp.sum(
            prod.reshape(bm, bl // LANE, LANE), axis=1
        )


def _spmm_body_tag1(scales_ref, colpak_ref, head_ref, xg_ref, out_ref, *,
                    ei_bit: int, k: int, nrhs: int):
    _accumulate(scales_ref, colpak_ref, head_ref, None, None, xg_ref, out_ref,
                ei_bit=ei_bit, tag=1, k=k, nrhs=nrhs)


def _spmm_body_tag2(scales_ref, colpak_ref, head_ref, tail1_ref, xg_ref,
                    out_ref, *, ei_bit: int, k: int, nrhs: int):
    _accumulate(scales_ref, colpak_ref, head_ref, tail1_ref, None, xg_ref,
                out_ref, ei_bit=ei_bit, tag=2, k=k, nrhs=nrhs)


def _spmm_body_tag3(scales_ref, colpak_ref, head_ref, tail1_ref, tail2_ref,
                    xg_ref, out_ref, *, ei_bit: int, k: int, nrhs: int):
    _accumulate(scales_ref, colpak_ref, head_ref, tail1_ref, tail2_ref, xg_ref,
                out_ref, ei_bit=ei_bit, tag=3, k=k, nrhs=nrhs)


_BODIES = {1: _spmm_body_tag1, 2: _spmm_body_tag2, 3: _spmm_body_tag3}


def gse_spmm_call(colpak, head, tail1, tail2, x, scales, *, ei_bit: int,
                  tag: int, blocks=None, interpret: bool):
    """Unjitted tag-specialized SpMM (exported for jaxpr inspection).

    colpak/head (+tails the tag reads): (M, L); x: (N, nrhs) dense
    right-hand sides; scales: (1, k).  ``tail1``/``tail2`` may be ``None``
    when ``tag`` does not read them; arrays passed for unread segments are
    ignored (not streamed).  ``blocks=None`` resolves through
    ``perf.plan.resolve`` to the (8, 128) default (DESIGN.md §15).
    ``interpret`` has no default: the CPU/TPU choice belongs to
    ``kernels.ops``.  Returns Y = A @ X as a (M, nrhs) f32 array.
    """
    blocks = launch_plan.resolve(blocks=blocks).blocks
    assert x.ndim == 2, f"x must be (n, nrhs); got {x.shape}"
    bm, bl = blocks
    m = colpak.shape[0]
    nrhs = x.shape[1]
    operands, in_specs = segment_operands(colpak, head, tail1, tail2,
                                          scales, tag=tag, blocks=blocks)
    operands.append(gather_cols(colpak, x, ei_bit))   # (nrhs, M, L)
    in_specs.append(pl.BlockSpec((nrhs, bm, bl),
                                 lambda i, l: (jnp.int32(0), i, l)))

    acc = pl.pallas_call(
        functools.partial(_BODIES[tag], ei_bit=ei_bit, k=scales.shape[1],
                          nrhs=nrhs),
        out_shape=jax.ShapeDtypeStruct((m, nrhs * LANE), jnp.float32),
        grid=(m // bm, colpak.shape[1] // bl),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, nrhs * LANE),
                               lambda i, l: (i, jnp.int32(0))),
        interpret=interpret,
    )(*operands)
    # Reduction epilogue: collapse each column's LANE per-row partials.
    return jnp.sum(acc.reshape(m, nrhs, LANE), axis=2)


gse_spmm_pallas = functools.partial(
    jax.jit,
    static_argnames=("ei_bit", "tag", "blocks", "interpret"),
)(gse_spmm_call)


def gse_spmm_sell_call(buckets, unperm, x, scales, *, ei_bit: int, tag: int,
                       blocks=None, interpret: bool):
    """Sliced-ELL SpMM: the multi-RHS twin of
    :func:`repro.kernels.gse_spmv.gse_spmv_sell_call` -- one tag-
    specialized ``pallas_call`` per width-bucket, same per-bucket operand
    lists, matrix segments streamed once for all ``nrhs`` columns, row
    order restored by the ``unperm`` gather (DESIGN.md §12)."""
    outs = [
        gse_spmm_call(colpak, head, tail1, tail2, x, scales, ei_bit=ei_bit,
                      tag=tag, blocks=blocks, interpret=interpret)
        for colpak, head, tail1, tail2 in buckets
    ]
    y = outs[0] if len(outs) == 1 else jnp.concatenate(outs, axis=0)
    return y[unperm]
