"""Pallas TPU kernel: GSE-SEM segment decode -> f32 tiles.

Target: TPU VPU. 8x128-aligned VMEM tiles; the shared-exponent table is a
pre-decoded (1, k) f32 scale LUT (2^(E_sh - bits_used)) selected with an
unrolled k-way ``where`` chain -- no gather, no bit-scan (DESIGN.md §2).

Validated on CPU in interpret mode against ``ref.decode_ref``; Mosaic
casts no unsigned type to float, so segments convert through int32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

__all__ = ["decode_kernel_body", "decode_pallas", "pinned", "u16_to_f32",
           "u32_to_f32"]


def pinned(*_):
    """Index map of a block every grid step re-reads (the scale LUT).
    int32 explicitly: under ``jax_enable_x64`` a bare ``0`` lowers as i64,
    which Mosaic rejects."""
    return jnp.int32(0), jnp.int32(0)


def u16_to_f32(v):
    """u16 -> f32 through int32 (Mosaic casts no unsigned type to float;
    every u16 fits int32, so the value is exact)."""
    return v.astype(jnp.int32).astype(jnp.float32)


def u32_to_f32(v):
    """u32 -> f32 through int32: the two 16-bit halves convert exactly and
    one add rounds, which is the round-to-nearest f32 of the u32 value
    (a plain int32 cast would turn the top bit into a sign)."""
    i = lax.bitcast_convert_type(v, jnp.int32)
    hi = lax.shift_right_logical(i, jnp.int32(16)).astype(jnp.float32)
    return hi * jnp.float32(65536.0) + (i & 0xFFFF).astype(jnp.float32)


def _select_scale(exp_idx, scales_ref, k: int):
    """Unrolled k-way select: TPU-friendly replacement for a VMEM gather."""
    acc = jnp.zeros(exp_idx.shape, jnp.float32)
    for j in range(k):
        acc = jnp.where(exp_idx == j, scales_ref[0, j], acc)
    return acc


def decode_kernel_body(scales_ref, head_ref, tail1_ref, tail2_ref, out_ref, *,
                       ei_bit: int, tag: int, k: int):
    h = head_ref[...].astype(jnp.int32)
    m_h = 15 - ei_bit
    sgn = 1.0 - 2.0 * ((h >> 15) & 0x1).astype(jnp.float32)
    exp_idx = (h >> m_h) & ((1 << ei_bit) - 1)
    mant = (h & ((1 << m_h) - 1)).astype(jnp.float32)
    if tag >= 2:
        mant = mant * jnp.float32(65536.0) + u16_to_f32(tail1_ref[...])
    if tag == 3:
        mant = mant * jnp.float32(2.0**32) + u32_to_f32(tail2_ref[...])
    scale = _select_scale(exp_idx, scales_ref, k)
    out_ref[...] = sgn * mant * scale


@functools.partial(
    jax.jit,
    static_argnames=("ei_bit", "tag", "block", "interpret"),
)
def decode_pallas(head, tail1, tail2, scales, *, ei_bit: int, tag: int,
                  block=(8, 128), interpret: bool):
    """head/tail1: (M, N) u16; tail2: (M, N) u32; scales: (1, k) f32."""
    m, n = head.shape
    bm, bn = block
    assert m % bm == 0 and n % bn == 0, (m, n, block)
    k = scales.shape[1]
    grid = (m // bm, n // bn)
    tile = pl.BlockSpec((bm, bn), lambda i, j: (i, j))
    return pl.pallas_call(
        functools.partial(decode_kernel_body, ei_bit=ei_bit, tag=tag, k=k),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, k), pinned),  # scale LUT
            tile, tile, tile,
        ],
        out_specs=tile,
        interpret=interpret,
    )(scales, head, tail1, tail2)
