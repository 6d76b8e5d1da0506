"""shard_map distributed SpMV/SpMM over a row-sharded GSE-SEM operator.

Each shard streams ITS slice of the packed segment arrays through the
SAME tag-specialized decode the single-device solvers use
(``sparse.spmv._decode_gsecsr`` -- the CG/PCG step's decode), then
sums its rows locally over its slot-major store (``sum_rows``).  What
crosses the interconnect is only the boundary x-entries, through the
tag-aware halo exchange (``distributed.wire.halo_all_gather``): a tag-1
iteration ships 2-byte GSE heads, tag 2 head+tail1, tag 3 exact float64
(DESIGN.md §13).

Entry points:

  * ``dist_spmv(part, x, tag)`` / ``dist_spmm(part, x, tag)`` -- one
    distributed y = A @ x over a full replicated ``x`` (``(n,)`` or
    ``(n, nrhs)``), returned gathered.  Output is BITWISE identical to
    ``spmv_gse``/``spmm_gse`` on the unsharded operator when
    ``wire="exact"`` (rows do not span shards, entry order is preserved,
    the decode is shared) -- asserted in tests/test_distributed.py.
  * ``make_sharded_operator(part)`` -- memoized ``apply(v, tag)`` closure
    (traced tag via ``lax.switch``) usable anywhere the solvers accept an
    operator callable: CG/PCG, GMRES, batched, IR.
  * ``local_matvec``/``switched_matvec``/``shard_mesh`` -- building
    blocks the fully-sharded solver loop (``solvers.sharded``) reuses
    inside its own shard_map.

Everything runs on forced host CPU devices
(``XLA_FLAGS=--xla_force_host_platform_device_count=8``) exactly as on a
real multi-device backend; the collectives are the same primitives.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.tagmap import TagMap, normalize_tags
from repro.distributed.partition import AXIS, PartitionedGSECSR
from repro.distributed.wire import halo_all_gather
from repro.obs import trace as OT
from repro.perf import plan as launch_plan
from repro.perf.plan import KernelPlan
from repro.sparse.spmv import _decode_gsecsr, gather_products, sum_rows

__all__ = ["shard_mesh", "local_matvec", "local_products",
           "switched_matvec", "dist_spmv", "dist_spmm",
           "make_sharded_operator", "BLOCK_FIELDS", "block_arrays"]

# The partition's stacked per-shard arrays a shard's matvec reads, in the
# order ``block_arrays`` passes them into ``shard_map``.
BLOCK_FIELDS = ("colpak", "head", "tail1", "tail2", "row_ids", "bnd_idx",
                "halo_idx", "slot_map")


def shard_mesh(part: PartitionedGSECSR) -> Mesh:
    """A 1-D device mesh over the partition's shard count (memoized on the
    partition instance; requires ``jax.device_count() >= n_shards``)."""
    mesh = part.__dict__.get("_mesh")
    if mesh is None:
        devs = jax.devices()
        if len(devs) < part.n_shards:
            raise ValueError(
                f"partition wants {part.n_shards} shards but only "
                f"{len(devs)} devices are visible -- run under "
                "XLA_FLAGS=--xla_force_host_platform_device_count=N"
            )
        mesh = Mesh(np.array(devs[:part.n_shards]), (AXIS,))
        part.__dict__["_mesh"] = mesh
    return mesh


def local_matvec(blk: dict, x_sh: jnp.ndarray, *, tag: int, wire: str,
                 k: int, rows: int, ei_bit: int,
                 acc_dtype=jnp.float64,
                 slot_tags: jnp.ndarray | None = None) -> jnp.ndarray:
    """One shard's y-block at a STATIC tag, called inside shard_map:
    ``local_products`` and then their row sums (``sum_rows``).

    ``blk`` holds this shard's slices of ``BLOCK_FIELDS`` (leading axis
    already dropped) and the ``table``.
    Padding entries add nothing to the row sums: slot-major padding is
    +0.0 times the zero past the halo window, and ``segment_sum`` drops
    the CSR-order padding's row id ``rows`` (bit-identical local row sums
    either way).
    """
    prod = local_products(blk, x_sh, tag=tag, wire=wire, k=k, ei_bit=ei_bit,
                          acc_dtype=acc_dtype, slot_tags=slot_tags)
    return _local_sum(blk, prod, rows)


def local_products(blk: dict, x_sh: jnp.ndarray, *, tag: int, wire: str,
                   k: int, ei_bit: int, acc_dtype=jnp.float64,
                   slot_tags: jnp.ndarray | None = None) -> jnp.ndarray:
    """One shard's products ``val * x[col]`` at a STATIC tag: the halo
    exchange gathers only boundary entries, the decode is the exact
    single-device ``_decode_gsecsr`` on the shard's segments, and the
    window of ``x`` is read in slices where the shard has a
    ``slot_map`` (``gather_products``).  Runs under
    the ``spmv`` scope, the boundary pack, all-gather and halo
    concatenation under ``halo``."""
    with OT.scope(OT.SPMV):
        if blk["bnd_idx"].shape[0] == 0:
            xcat = x_sh  # single shard: every column is local
        else:
            with OT.scope(OT.HALO):
                xcat = _halo_extend(blk, x_sh, tag=tag, wire=wire, k=k,
                                    slot_tags=slot_tags)
        with OT.scope(OT.DECODE):
            val, col = _decode_gsecsr(
                blk["colpak"], blk["head"], blk["tail1"], blk["tail2"],
                blk["table"], ei_bit, tag, acc_dtype,
            )
        return gather_products(val, col, xcat, acc_dtype, blk["slot_map"])


def _local_sum(blk, prod, rows):
    # CSR-order padding carries row id ``rows``: segment_sum drops ids
    # out of range, so no dummy row (and no slice for XLA to fuse into
    # the consumer's dot, which would change its summation order).
    with OT.scope(OT.SPMV):
        return sum_rows(prod, blk["row_ids"], rows)


def switched_matvec(blk: dict, x_sh: jnp.ndarray, tag, *, wire: str,
                    k: int, rows: int, ei_bit: int, acc_dtype=jnp.float64):
    """One shard's y-block at a TRACED tag: ``lax.switch`` over the three
    static-tag ``local_products``, then one row sum after the switch.
    Summed inside each branch, a slot-major store's float64 reduce at
    the root of every branch makes the TPU compiler fail (an internal
    shape check, where the shards' program rewrites the switch's
    result)."""
    branches = [
        partial(local_products, blk, tag=t, wire=wire, k=k, ei_bit=ei_bit,
                acc_dtype=acc_dtype)
        for t in (1, 2, 3)
    ]
    prod = jax.lax.switch(jnp.clip(tag - 1, 0, 2), branches, x_sh)
    return _local_sum(blk, prod, rows)


def _halo_extend(blk, x_sh, *, tag, wire, k, slot_tags):
    """This shard's ``x`` followed by the halo entries its columns read
    from other shards."""
    # Padded boundary slots (bnd_idx == -1) are masked to ZERO before the
    # wire pack: zeros are excluded from the shared-exponent histogram, so
    # a shard with fewer real boundary entries than the padded width B
    # cannot skew its wire table (the padded pool slots are never gathered
    # by halo_idx).
    idx = blk["bnd_idx"]
    valid = idx >= 0
    bnd = x_sh[jnp.clip(idx, 0, None)]
    mask = valid if x_sh.ndim == 1 else valid[:, None]
    bnd = jnp.where(mask, bnd, 0.0)
    pool = halo_all_gather(bnd, AXIS, tag=tag, wire=wire, k=k,
                           slot_tags=slot_tags)
    flat = pool.reshape((-1,) + pool.shape[2:])
    return jnp.concatenate([x_sh, flat[blk["halo_idx"]]], axis=0)


def block_arrays(part: PartitionedGSECSR) -> tuple:
    """The partition's ``BLOCK_FIELDS``, each split over the mesh axis
    inside ``shard_map`` (``slot_map`` may be ``None``)."""
    return tuple(getattr(part, f) for f in BLOCK_FIELDS)


def _blk(arrays, table):
    """Drop the leading per-device axis shard_map leaves on the stacked
    ``block_arrays`` and bundle the shard's block for ``local_matvec``."""
    blk = {f: None if v is None else v[0]
           for f, v in zip(BLOCK_FIELDS, arrays)}
    blk["table"] = table
    return blk


def _dist_matvec_fn(part: PartitionedGSECSR, wire: str, ndim: int,
                    acc_dtype):
    """Jitted shard_map matvec over the stacked partition arrays, memoized
    on the partition instance (same idiom as the solvers' operator memo:
    a fresh closure per call would retrace everything)."""
    key = ("_dist_matvec", wire, ndim, jnp.dtype(acc_dtype).name)
    fn = part.__dict__.get(key)
    if fn is not None:
        return fn
    mesh = shard_mesh(part)
    rows, ei, k = part.rows_per_shard, part.ei_bit, int(part.table.size)

    def run(arrays, table, x, tag):
        blk = _blk(arrays, table)
        return switched_matvec(blk, x, tag, wire=wire, k=k, rows=rows,
                               ei_bit=ei, acc_dtype=acc_dtype)

    sharded = P(AXIS)
    fn = jax.jit(jax.shard_map(
        run, mesh=mesh,
        in_specs=(sharded, P(), sharded, P()),
        out_specs=sharded,
        check_vma=False,
    ))
    part.__dict__[key] = fn
    return fn


def _dist_matvec_map_fn(part: PartitionedGSECSR, tm: TagMap, wire: str,
                        ndim: int, acc_dtype):
    """shard_map matvec for a NON-UNIFORM tag map: the decode rides the
    map's static MAX tag (one collective, one payload width -- exactly the
    masked-operand contract ``kernels.ops.masked_for_tagmap`` documents)
    and the per-slot boundary tags ride as an extra sharded operand so
    tag-1 slots drop their tail segment on the wire.  Memoized per map
    ``crc32`` -- a promoted map can never reuse a stale trace."""
    key = ("_dist_matvec_map", tm.crc32, wire, ndim,
           jnp.dtype(acc_dtype).name)
    fn = part.__dict__.get(key)
    if fn is not None:
        return fn
    mesh = shard_mesh(part)
    rows, ei, k = part.rows_per_shard, part.ei_bit, int(part.table.size)
    tag = tm.max_tag

    def run(arrays, table, slot_tags, x):
        blk = _blk(arrays, table)
        return local_matvec(blk, x, tag=tag, wire=wire, k=k, rows=rows,
                            ei_bit=ei, acc_dtype=acc_dtype,
                            slot_tags=slot_tags[0])

    sharded = P(AXIS)
    fn = jax.jit(jax.shard_map(
        run, mesh=mesh,
        in_specs=(sharded, P(), sharded, sharded),
        out_specs=sharded,
        check_vma=False,
    ))
    part.__dict__[key] = fn
    return fn


def _apply_padded(part: PartitionedGSECSR, x: jnp.ndarray, tag,
                  wire: str, acc_dtype) -> jnp.ndarray:
    n = part.shape[0]
    pad = part.n_padded - n
    if x.shape[0] != n:
        raise ValueError(f"operand wants x with {n} rows, got {x.shape}")
    xp = jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1)) if pad else x
    if isinstance(tag, TagMap):
        fn = _dist_matvec_map_fn(part, tag, wire, x.ndim, acc_dtype)
        st = jnp.asarray(part.bnd_slot_tags(tag).astype(np.int32))
        y = fn(block_arrays(part), part.table, st, xp)
        return y[:n]
    fn = _dist_matvec_fn(part, wire, x.ndim, acc_dtype)
    y = fn(block_arrays(part), part.table, xp, jnp.asarray(tag, jnp.int32))
    return y[:n]


def _resolve_dist_plan(part, tag, nrhs, plan) -> KernelPlan:
    """Uniform launch-plan resolution for the distributed path (DESIGN.md
    §15): explicit plan > tuned cache (layout key "dist") > default.  The
    shard-local matvec rides the jnp decode and row reduction -- there is no
    Pallas block knob here yet -- so the resolved plan records provenance
    and reserves the slot a shard-local kernel will take its blocks from.
    Resolution is skipped for traced tags (the solvers' escalation path
    passes ``tag`` as a traced value).  A ``TagMap`` is static and keys
    the lookup under its CRC32 (``perf.plan.tag_token``)."""
    static_tag = isinstance(tag, (int, np.integer, TagMap))
    if static_tag and not isinstance(tag, TagMap):
        tag = int(tag)
    return launch_plan.resolve(
        part if static_tag else None,
        tag=tag if static_tag else None,
        layout="dist", nrhs=nrhs, plan=plan)


def dist_spmv(part: PartitionedGSECSR, x: jnp.ndarray, tag=1,
              wire: str = "exact", acc_dtype=jnp.float64,
              plan: KernelPlan | None = None) -> jnp.ndarray:
    """Distributed y = A @ x at precision ``tag`` (traced or static).

    ``x`` is the full ``(n,)`` operand; each shard computes its row block
    from its local x window plus the tag-aware halo, and the blocks come
    back gathered.  ``wire="exact"`` is bitwise equal to
    ``spmv_gse(a, x, tag)`` on the unsharded operator; ``wire="gse"``
    additionally compresses the tag-1/2 halo payloads (lossy on the
    boundary entries only -- the monitor's recursive residual still
    converges, it simply sees a slightly stronger low-tag perturbation).

    ``tag`` accepts the full tags axis: a uniform ``TagMap`` normalizes
    to the identical int path; a NON-uniform map decodes at its max tag
    with per-slot wire masking -- per-group semantics then require the
    caller to have partitioned the MASKED operand
    (``partition_gsecsr(kernels.ops.masked_for_tagmap(a, tm), s)``),
    exactly the single-device masked-segment contract.
    """
    if x.ndim != 1:
        raise ValueError(f"dist_spmv wants (n,); got {x.shape}")
    if isinstance(tag, TagMap):
        tag = normalize_tags(tag, part.shape[0])
    _resolve_dist_plan(part, tag, 1, plan)
    return _apply_padded(part, x, tag, wire, acc_dtype)


def dist_spmm(part: PartitionedGSECSR, x: jnp.ndarray, tag=1,
              wire: str = "exact", acc_dtype=jnp.float64,
              plan: KernelPlan | None = None) -> jnp.ndarray:
    """Distributed Y = A @ X over a dense ``(n, nrhs)`` block: the matrix
    segments stream once per shard and every column rides one shared halo
    exchange (boundary entries ship per column; this block path packs ONE
    wire table per call, strictly cheaper than the per-column apply path
    ``halo_wire_bytes(tag, wire, nrhs)`` models)."""
    if x.ndim != 2:
        raise ValueError(f"dist_spmm wants (n, nrhs); got {x.shape}")
    if isinstance(tag, TagMap):
        tag = normalize_tags(tag, part.shape[0])
    _resolve_dist_plan(part, tag, x.shape[1], plan)
    return _apply_padded(part, x, tag, wire, acc_dtype)


def make_sharded_operator(part: PartitionedGSECSR, wire: str = "exact",
                          acc_dtype=jnp.float64,
                          plan: KernelPlan | None = None):
    """Tag-dispatched ``apply(v, tag)`` over the partition, memoized on the
    instance (the closure is a static jit argument in the solvers -- the
    sharded twin of ``solvers.operators.make_gse_operator``).  Accepts ``(n,)``
    vectors and ``(n, nrhs)`` blocks; usable as the operator callable in
    every solver path (CG/PCG, GMRES, batched, IR)."""
    key = ("_sharded_operator", wire, jnp.dtype(acc_dtype).name, plan)
    op = part.__dict__.get(key)
    if op is None:
        def op(v, tag):
            return _apply_padded(part, v, tag, wire, acc_dtype)

        part.__dict__[key] = op
    return op
