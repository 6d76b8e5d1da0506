"""True-wire GSE-SEM compressed all-reduce (shard_map, manual collectives).

pjit/GSPMD cannot express "compress, move u16, decompress" -- the
partitioner sees only the decoded values.  With shard_map the payload that
crosses the interconnect IS the 16-bit head segment:

    per-shard grad -> pack32 (u16 head) -> all_to_all (u16 on the wire)
    -> decode -> psum_scatter-equivalent local sum -> repack -> all_gather
    (u16 on the wire) -> decode

Wire bytes: 2/elem in each phase vs 4 (f32 ring AR) -- the paper's
storage/compute decoupling applied to the interconnect, for the cross-pod
gradient reduction (DESIGN.md §3.3).  Error feedback lives one level up
(distributed.compress).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.core import gse
from repro.core.tagmap import TagMap, normalize_tags

__all__ = ["compressed_psum", "halo_all_gather", "set_wire_fault",
           "wire_checksum"]


# Wire fault-injection hook (robustness harness, DESIGN.md §14).  When
# set, every halo payload passes through ``hook(name, arr)`` AFTER its
# integrity checksum is computed and BEFORE the collective -- i.e. the
# corruption happens "on the wire", which is exactly what the checksum
# side-channel is meant to catch.  ``name`` is the wire segment
# ("raw" for the exact/tag-3 float buffer; "head"/"tail1"/"table" for the
# GSE-segmented payloads).  Production never sets this.
_WIRE_FAULT = None


def set_wire_fault(hook) -> None:
    """Install (or clear, with ``None``) the wire fault-injection hook."""
    global _WIRE_FAULT
    _WIRE_FAULT = hook


def _send(name: str, arr: jnp.ndarray) -> jnp.ndarray:
    return arr if _WIRE_FAULT is None else _WIRE_FAULT(name, arr)


def wire_checksum(arr: jnp.ndarray) -> jnp.ndarray:
    """Traceable position-weighted uint32 checksum of a wire buffer.

    Floats are bitcast to the same-width unsigned integers first, so the
    checksum covers the exact bit pattern on the wire.  Each element is
    weighted by a Knuth-hash of its flat position before summing --
    a plain sum would miss swapped or permuted elements.
    """
    a = jnp.asarray(arr)
    if jnp.issubdtype(a.dtype, jnp.floating):
        bits = {2: jnp.uint16, 4: jnp.uint32, 8: jnp.uint64}[a.dtype.itemsize]
        a = jax.lax.bitcast_convert_type(a, bits)
    a = a.astype(jnp.uint64).ravel()
    # Fold the high half into the low 32 bits BEFORE weighting: the final
    # mod-2^32 mask would otherwise erase any flip in bits 32-63 of a
    # 64-bit element (2^b * w === 0 mod 2^32 for b >= 32).
    a = a ^ (a >> jnp.uint64(32))
    w = jnp.arange(a.shape[0], dtype=jnp.uint64) * jnp.uint64(2654435761) \
        + jnp.uint64(1)
    return ((a * w).sum() & jnp.uint64(0xFFFFFFFF)).astype(jnp.uint32)


def halo_all_gather(bnd: jnp.ndarray, axis_name: str, *, tag,
                    wire: str = "gse", k: int = 8, check: bool = False,
                    slot_tags: jnp.ndarray | None = None):
    """All-gather each shard's boundary buffer at the iteration's tag.

    Must be called INSIDE shard_map with ``axis_name`` manual.  ``bnd`` is
    this shard's packed boundary x-entries, shape ``(B,)`` or ``(B, nrhs)``
    (padded slots are zero).  Returns the gathered pool with a leading
    shard axis, ``(s, B[, nrhs])``, decoded back to ``bnd.dtype``.

    This is the halo-exchange twin of :func:`compressed_psum` -- the GSE
    segmentation applied to the SpMV's wire traffic (DESIGN.md §13):

      * ``wire="gse"``, tag 1: the u16 HEAD segments cross the wire
        (2 B/entry) plus each shard's tiny shared-exponent table;
      * ``wire="gse"``, tag 2: head + tail1 (4 B/entry) + table;
      * tag 3 or ``wire="exact"``: raw IEEE float64 (8 B/entry) -- at full
        precision the segmented 63-bit mantissa costs the same bytes but
        loses dynamic range, so exact bits ride the wire.

    The modeled payload is ``PartitionedGSECSR.halo_wire_bytes``.

    With ``check=True`` returns ``(gathered, ok)``: each sender computes
    a :func:`wire_checksum` of every payload segment before it leaves,
    the tiny u32 checksums ride alongside, and every receiver recomputes
    them on the gathered buffers -- ``ok`` is a replicated bool that goes
    False if ANY shard's payload was corrupted in flight (DESIGN.md §14).

    ``tag`` accepts the full tags axis: a legacy int, or a
    :class:`~repro.core.tagmap.TagMap` (uniform maps normalize to the
    same int path -- bit-identical; non-uniform maps ride at the map's
    MAX tag, since one collective has one payload width).  With a
    non-uniform map pass ``slot_tags`` -- this shard's ``(B,)`` per-slot
    tags (the boundary entry's ROW-group tag,
    ``PartitionedGSECSR.bnd_slot_tags``) -- and a tag-2 wire zeroes the
    tail1 segment of tag-1 slots before it leaves: the wire twin of
    ``kernels.ops.masked_for_tagmap``, so the decoded pool is bitwise
    what per-slot shipping would produce while the blended payload model
    (``halo_wire_bytes(tagmap)``) charges each slot at its own tag.  A
    tag-3 wire ships raw floats for every slot (exact bits never
    perturb); ``slot_tags`` then only informs the byte model.

    The sharded SpMV (``kernels.dist_spmv.local_matvec``) calls it under
    the ``spmv/halo`` device scope (DESIGN.md §16).
    """
    if wire not in ("gse", "exact"):
        raise ValueError(f"unknown wire mode {wire!r}; 'gse' or 'exact'")
    tag = normalize_tags(tag)
    if isinstance(tag, TagMap):
        tag = tag.max_tag
    if wire == "exact" or tag == 3:
        if not check:
            return jax.lax.all_gather(_send("raw", bnd), axis_name)
        ref = jax.lax.all_gather(wire_checksum(bnd), axis_name)
        out = jax.lax.all_gather(_send("raw", bnd), axis_name)
        got = jax.vmap(wire_checksum)(out)
        return out, (got == ref).all()
    b32 = bnd.astype(jnp.float32)
    table = gse.extract_shared_exponents_jnp(b32, k)
    head, tail1 = gse.pack32_jnp(b32, table, k)
    if slot_tags is not None and tag != 1:
        # Per-slot wire precision: tag-1 slots drop their tail1 bits
        # before the payload leaves, exactly as the masked HBM
        # operand drops sub-tag tail segments.
        keep = jnp.asarray(slot_tags) >= 2
        if tail1.ndim > keep.ndim:
            keep = keep[:, None]
        tail1 = jnp.where(keep, tail1, jnp.zeros_like(tail1))
    sums, refs = [], []
    if check:
        sums = [wire_checksum(head), wire_checksum(table)]
        if tag != 1:
            sums.append(wire_checksum(tail1))
        refs = [jax.lax.all_gather(c, axis_name) for c in sums]
    h_all = jax.lax.all_gather(_send("head", head), axis_name)
    tb_all = jax.lax.all_gather(_send("table", table), axis_name)
    if tag == 1:
        dec = jax.vmap(
            lambda h, tb: gse.decode32_jnp(
                tb, h, jnp.zeros(h.shape, jnp.uint16), k, 1, jnp.float32
            )
        )(h_all, tb_all)
        gathered = (h_all, tb_all)
    else:
        t_all = jax.lax.all_gather(_send("tail1", tail1), axis_name)
        dec = jax.vmap(
            lambda h, t, tb: gse.decode32_jnp(tb, h, t, k, 2, jnp.float32)
        )(h_all, t_all, tb_all)
        gathered = (h_all, tb_all, t_all)
    dec = dec.astype(bnd.dtype)
    if not check:
        return dec
    ok = jnp.bool_(True)
    for buf, ref in zip(gathered, refs):
        ok = ok & (jax.vmap(wire_checksum)(buf) == ref).all()
    return dec, ok


def compressed_psum(grads: jnp.ndarray, axis_name: str, k: int = 8):
    """All-reduce ``grads`` over ``axis_name`` moving u16 GSE-SEM heads.

    Must be called INSIDE shard_map with ``axis_name`` manual.  grads:
    (N,) with N divisible by the axis size.  Returns the (approximately)
    summed gradient, decoded to f32.
    """
    n_dev = jax.lax.axis_size(axis_name)
    n = grads.shape[0]
    assert n % n_dev == 0, (n, n_dev)

    # reduce-scatter phase: ship each chunk's u16 head to its owner
    chunks = grads.reshape(n_dev, n // n_dev)
    table = gse.extract_shared_exponents_jnp(grads, k)
    head, tail1 = gse.pack32_jnp(chunks, table, k)
    head_x = jax.lax.all_to_all(head, axis_name, 0, 0, tiled=False)
    tail_x = jax.lax.all_to_all(tail1, axis_name, 0, 0, tiled=False)
    table_x = jax.lax.all_gather(table, axis_name)  # (n_dev, k) tiny
    dec = jax.vmap(
        lambda h, t, tb: gse.decode32_jnp(tb, h, t, k, 2, jnp.float32)
    )(head_x, tail_x, table_x)
    local_sum = jnp.sum(dec, axis=0)  # this shard's reduced chunk

    # all-gather phase: ship the reduced chunk's u16 head back out
    table2 = gse.extract_shared_exponents_jnp(local_sum, k)
    h2, t2 = gse.pack32_jnp(local_sum, table2, k)
    h_all = jax.lax.all_gather(h2, axis_name)
    t_all = jax.lax.all_gather(t2, axis_name)
    tb_all = jax.lax.all_gather(table2, axis_name)
    out = jax.vmap(
        lambda h, t, tb: gse.decode32_jnp(tb, h, t, k, 2, jnp.float32)
    )(h_all, t_all, tb_all)
    return out.reshape(n)
