"""Pallas TPU kernel: blocked-ELL SpMV with fused GSE-SEM decode.

The paper's SpMV (Algorithm 2) re-thought for TPU (DESIGN.md §2):

  * rows padded to lane-aligned ELL width L -> dense (BM, BL) tiles;
  * expIdx rides the top EI_BIT bits of colpak (paper III.C.1), leaving
    all 15 non-sign head bits as mantissa;
  * decode = int->f32 convert * LUT scale (no __fns bit scan);
  * x is gathered in XLA before the kernel (``xg = x[col]``, one f32 per
    padded slot) and streamed as one more (BM, BL) tile.  Mosaic lowers
    only 2-D gathers, and pinning all of x in VMEM caps n far below a
    chip's HBM; the streamed tile has neither limit.

Tag specialization (DESIGN.md §2.4): the whole point of GSE-SEM is that a
memory-bound SpMV touches only the bytes the current precision needs --
2/4/8 value bytes per slot for tags 1/2/3.  One generic kernel that
streams all four segment arrays would make tag-1 pay tag-3 bandwidth, so
each tag gets its own kernel body whose ``pallas_call`` operand list
contains ONLY the segments that tag reads:

    tag 1   scales, colpak, head, xg                  (10 B/slot streamed)
    tag 2   scales, colpak, head, tail1, xg           (12 B/slot)
    tag 3   scales, colpak, head, tail1, tail2, xg    (16 B/slot)

(4 B colpak + 2/4/8 value bytes + 4 B gathered x per padded slot;
``core.precision_table.KERNEL_SLOT_BYTES``.)  Callers pass
``tail1=None`` / ``tail2=None`` for tags that do not read them; the
unused arrays never enter the jaxpr, never get a BlockSpec, and never get
DMA'd into VMEM.

Output layout (DESIGN.md §2.3): the kernel accumulates per-lane partial
sums into a lane-aligned (BM, 128) VMEM tile -- a (BM, BL) product tile is
reduced only across its BL/128 sublane groups, so every vector store fills
all 128 lanes instead of 1/128 of them.  A cheap reduction epilogue
(``acc.sum(axis=1)``) collapses the 128 partials per row after the grid
finishes.

Grid: (M/BM, L/BL); the L axis accumulates sequentially into the output
rows.  Padded slots carry col=0, head=0 -> mantissa 0 -> contribute 0.
Index maps return int32 explicitly: under ``jax_enable_x64`` a bare
Python ``0`` would lower as i64, which Mosaic rejects.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from repro.core.precision_table import tag_operand_names
from repro.kernels.gse_decode import (
    _select_scale,
    pinned,
    u16_to_f32,
    u32_to_f32,
)
from repro.perf import plan as launch_plan

__all__ = ["gse_spmv_pallas", "gse_spmv_call", "gse_spmv_sell_call",
           "spmv_operand_names", "decode_tile", "gather_cols",
           "segment_operands", "LANE"]

LANE = 128  # TPU vector-lane count; output accumulator minor dim


def gather_cols(colpak, x, ei_bit: int):
    """``x[col]`` for every padded slot of ``colpak`` (the (..., M, L)
    gathered-x tile the kernels stream), as f32.  ``x`` is (n,) or
    (n, nrhs); the latter gives (nrhs, M, L)."""
    col = (colpak & jnp.uint32((1 << (32 - ei_bit)) - 1)).astype(jnp.int32)
    xf = x.astype(jnp.float32)
    return xf[col] if x.ndim == 1 else xf.T[:, col]


def spmv_operand_names(tag: int) -> tuple:
    """The pallas_call operand list the tag-specialized kernel streams
    (one source of truth: ``core.precision_table.TAG_SEGMENTS``)."""
    return tag_operand_names(tag)


def decode_tile(scales_ref, colpak_ref, head_ref, tail1_ref, tail2_ref, *,
                ei_bit: int, tag: int, k: int):
    """Decode one (BM, BL) tile of GSE-SEM segments -> f32 values.

    The single in-kernel owner of the bit-level layout (expIdx in
    colpak's top ``ei_bit`` bits, 15-bit head mantissa, tail splices):
    the SpMV and SpMM kernel bodies both call this, so the decode cannot
    drift between the single- and multi-RHS pipelines.  Tail refs are
    ``None`` for the tags that skip them.  Mosaic casts no unsigned type
    to float, so every segment goes through int32 first.
    """
    cp = lax.bitcast_convert_type(colpak_ref[...], jnp.int32)
    exp_idx = lax.shift_right_logical(cp, jnp.int32(32 - ei_bit))

    h = head_ref[...].astype(jnp.int32)
    sgn = 1.0 - 2.0 * ((h >> 15) & 0x1).astype(jnp.float32)
    mant = (h & 0x7FFF).astype(jnp.float32)
    if tag >= 2:
        mant = mant * jnp.float32(65536.0) + u16_to_f32(tail1_ref[...])
    if tag == 3:
        mant = mant * jnp.float32(2.0**32) + u32_to_f32(tail2_ref[...])
    return sgn * mant * _select_scale(exp_idx, scales_ref, k)


def _accumulate(scales_ref, colpak_ref, head_ref, tail1_ref, tail2_ref,
                xg_ref, out_ref, *, ei_bit: int, tag: int, k: int):
    """Shared tile math; tail refs are ``None`` for the tags that skip them."""
    @pl.when(pl.program_id(1) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    vals = decode_tile(scales_ref, colpak_ref, head_ref, tail1_ref,
                       tail2_ref, ei_bit=ei_bit, tag=tag, k=k)
    prod = vals * xg_ref[...]             # (BM, BL)
    bm, bl = prod.shape
    # Lane-aligned partial sums: reduce only across the BL/LANE sublane
    # groups so the store fills all LANE lanes (DESIGN.md §2.3).
    out_ref[...] += jnp.sum(prod.reshape(bm, bl // LANE, LANE), axis=1)


def _spmv_body_tag1(scales_ref, colpak_ref, head_ref, xg_ref, out_ref, *,
                    ei_bit: int, k: int):
    _accumulate(scales_ref, colpak_ref, head_ref, None, None, xg_ref, out_ref,
                ei_bit=ei_bit, tag=1, k=k)


def _spmv_body_tag2(scales_ref, colpak_ref, head_ref, tail1_ref, xg_ref,
                    out_ref, *, ei_bit: int, k: int):
    _accumulate(scales_ref, colpak_ref, head_ref, tail1_ref, None, xg_ref,
                out_ref, ei_bit=ei_bit, tag=2, k=k)


def _spmv_body_tag3(scales_ref, colpak_ref, head_ref, tail1_ref, tail2_ref,
                    xg_ref, out_ref, *, ei_bit: int, k: int):
    _accumulate(scales_ref, colpak_ref, head_ref, tail1_ref, tail2_ref,
                xg_ref, out_ref, ei_bit=ei_bit, tag=3, k=k)


_BODIES = {1: _spmv_body_tag1, 2: _spmv_body_tag2, 3: _spmv_body_tag3}


def segment_operands(colpak, head, tail1, tail2, scales, *, tag: int,
                     blocks):
    """The tag-specialized ``(operands, in_specs)`` prefix both the SpMV
    and SpMM kernels stream: scale LUT, colpak, head and the tails
    ``tag`` reads -- the one owner of the per-tag operand list."""
    bm, bl = blocks
    m, L = colpak.shape
    assert m % bm == 0 and L % bl == 0, (colpak.shape, blocks)
    assert bl % LANE == 0, f"BL must be lane-aligned (multiple of {LANE})"
    tile = pl.BlockSpec((bm, bl), lambda i, l: (i, l))
    operands = [scales, colpak, head]
    in_specs = [pl.BlockSpec((1, scales.shape[1]), pinned), tile, tile]
    if tag >= 2:
        assert tail1 is not None, "tag>=2 reads tail1"
        operands.append(tail1)
        in_specs.append(tile)
    if tag == 3:
        assert tail2 is not None, "tag==3 reads tail2"
        operands.append(tail2)
        in_specs.append(tile)
    return operands, in_specs


def gse_spmv_call(colpak, head, tail1, tail2, x, scales, *, ei_bit: int,
                  tag: int, blocks=None, interpret: bool):
    """Unjitted tag-specialized SpMV (exported for jaxpr inspection).

    colpak/head (+tails the tag reads): (M, L); x: (N,); scales: (1, k).
    ``tail1``/``tail2`` may be ``None`` when ``tag`` does not read them;
    arrays passed for unread segments are ignored (not streamed).
    ``blocks=None`` resolves through ``perf.plan.resolve`` to the (8, 128)
    default (DESIGN.md §15).  ``interpret`` has no default: the CPU/TPU
    choice belongs to ``kernels.ops``.  Returns y = A @ x as a (M,) f32
    vector.
    """
    blocks = launch_plan.resolve(blocks=blocks).blocks
    bm, bl = blocks
    m = colpak.shape[0]
    operands, in_specs = segment_operands(colpak, head, tail1, tail2,
                                          scales, tag=tag, blocks=blocks)
    operands.append(gather_cols(colpak, x, ei_bit))
    in_specs.append(pl.BlockSpec((bm, bl), lambda i, l: (i, l)))

    acc = pl.pallas_call(
        functools.partial(_BODIES[tag], ei_bit=ei_bit, k=scales.shape[1]),
        out_shape=jax.ShapeDtypeStruct((m, LANE), jnp.float32),
        grid=(m // bm, colpak.shape[1] // bl),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, LANE), lambda i, l: (i, jnp.int32(0))),
        interpret=interpret,
    )(*operands)
    # Reduction epilogue: collapse the LANE per-row partials.
    return jnp.sum(acc, axis=1)


gse_spmv_pallas = functools.partial(
    jax.jit,
    static_argnames=("ei_bit", "tag", "blocks", "interpret"),
)(gse_spmv_call)


def gse_spmv_sell_call(buckets, unperm, x, scales, *, ei_bit: int, tag: int,
                       blocks=None, interpret: bool):
    """Sliced-ELL SpMV: one tag-specialized ``pallas_call`` per width-bucket
    (DESIGN.md §12), reusing the uniform-ELL kernel body (``decode_tile``)
    unchanged.

    ``buckets`` is a tuple of per-bucket ``(colpak, head, tail1, tail2)``
    segment tuples, each ``(rows_b, w_b)`` with ``rows_b`` a multiple of
    ``blocks[0]`` and ``w_b`` of ``blocks[1]``; tails are ``None`` for the
    tags that skip them, exactly as in :func:`gse_spmv_call` -- the per-
    bucket operand lists stay tag-specialized and jaxpr-checkable.  Bucket
    rows are σ-sorted slice rows; ``unperm`` maps each ORIGINAL row to its
    position in the bucket concatenation, so the epilogue gather restores
    row order.

    Per-row arithmetic is IDENTICAL to the uniform-ELL kernel: a row's
    entries occupy the same in-row slots, the lane-group partial sums run
    over the same ascending slot groups, and trailing all-zero groups the
    uniform layout would add contribute exact zeros -- so SELL and uniform
    ELL outputs are equal (asserted bitwise in tests/test_sell.py).
    """
    outs = [
        gse_spmv_call(colpak, head, tail1, tail2, x, scales, ei_bit=ei_bit,
                      tag=tag, blocks=blocks, interpret=interpret)
        for colpak, head, tail1, tail2 in buckets
    ]
    y = outs[0] if len(outs) == 1 else jnp.concatenate(outs, axis=0)
    return y[unperm]
