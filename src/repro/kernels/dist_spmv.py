"""shard_map distributed SpMV/SpMM over a row-sharded GSE-SEM operator.

Each shard streams ITS slice of the packed segment arrays through the
SAME tag-specialized decode the single-device solvers use
(``sparse.spmv._decode_gsecsr`` -- the fused CG/PCG steps' decode), then
reduces locally with a segment sum over local row ids.  What crosses the
interconnect is only the boundary x-entries, through the tag-aware halo
exchange (``distributed.wire.halo_all_gather``): a tag-1 iteration ships
2-byte GSE heads, tag 2 head+tail1, tag 3 exact float64 (DESIGN.md §13).

Entry points:

  * ``dist_spmv(part, x, tag)`` / ``dist_spmm(part, x, tag)`` -- one
    distributed y = A @ x over a full replicated ``x`` (``(n,)`` or
    ``(n, nrhs)``), returned gathered.  Output is BITWISE identical to
    ``spmv_gse``/``spmm_gse`` on the unsharded operator when
    ``wire="exact"`` (rows do not span shards, entry order is preserved,
    the decode is shared) -- asserted in tests/test_distributed.py.
  * ``make_sharded_operator(part)`` -- memoized ``apply(v, tag)`` closure
    (traced tag via ``lax.switch``) usable anywhere the solvers accept an
    operator callable: generic CG/PCG, GMRES, batched, IR.
  * ``local_matvec``/``shard_mesh`` -- building blocks the fully-sharded
    solver loop (``solvers.sharded``) reuses inside its own shard_map.

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
from repro.perf import plan as launch_plan
from repro.perf.plan import KernelPlan
from repro.sparse.spmv import _decode_gsecsr

__all__ = ["shard_mesh", "local_matvec", "dist_spmv", "dist_spmm",
           "make_sharded_operator"]


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
    """One shard's y-block at a STATIC tag, called inside shard_map.

    ``blk`` holds this shard's slices (leading axis already dropped):
    ``colpak/head/tail1/tail2/row_ids/bnd_idx/halo_idx/table``.  The halo
    exchange gathers only boundary entries; the decode is the exact
    single-device ``_decode_gsecsr`` on the shard's segments, and the
    segment sum drops the padding entries, whose row id is ``rows``
    (bit-identical local row sums).
    """
    if blk["bnd_idx"].shape[0] == 0:
        xcat = x_sh  # single shard: every column is local
    else:
        # Padded boundary slots (bnd_idx == -1) are masked to ZERO before
        # the wire pack: zeros are excluded from the shared-exponent
        # histogram, so a shard with fewer real boundary entries than the
        # padded width B cannot skew its wire table (the padded pool
        # slots are never gathered by halo_idx).
        idx = blk["bnd_idx"]
        valid = idx >= 0
        bnd = x_sh[jnp.clip(idx, 0, None)]
        mask = valid if x_sh.ndim == 1 else valid[:, None]
        bnd = jnp.where(mask, bnd, 0.0)
        pool = halo_all_gather(bnd, AXIS, tag=tag, wire=wire, k=k,
                               slot_tags=slot_tags)
        flat = pool.reshape((-1,) + pool.shape[2:])
        xcat = jnp.concatenate([x_sh, flat[blk["halo_idx"]]], axis=0)
    val, col = _decode_gsecsr(
        blk["colpak"], blk["head"], blk["tail1"], blk["tail2"],
        blk["table"], ei_bit, tag, acc_dtype,
    )
    xg = xcat.astype(acc_dtype)[col]
    prod = val * xg if x_sh.ndim == 1 else val[:, None] * xg
    # Padding entries carry row id ``rows``: segment_sum drops ids out of
    # range, so no dummy row (and no slice for XLA to fuse into the
    # consumer's dot, which would change its summation order).
    return jax.ops.segment_sum(prod, blk["row_ids"], num_segments=rows)


def _blk(colpak, head, tail1, tail2, row_ids, bnd_idx, halo_idx, table):
    """Drop the leading per-device axis shard_map leaves on stacked
    operands and bundle the shard's block for ``local_matvec``."""
    return dict(
        colpak=colpak[0], head=head[0], tail1=tail1[0], tail2=tail2[0],
        row_ids=row_ids[0], bnd_idx=bnd_idx[0], halo_idx=halo_idx[0],
        table=table,
    )


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

    def run(colpak, head, tail1, tail2, row_ids, bnd_idx, halo_idx, table,
            x, tag):
        blk = _blk(colpak, head, tail1, tail2, row_ids, bnd_idx, halo_idx,
                   table)
        branches = [
            partial(local_matvec, blk, tag=t, wire=wire, k=k, rows=rows,
                    ei_bit=ei, acc_dtype=acc_dtype)
            for t in (1, 2, 3)
        ]
        return jax.lax.switch(jnp.clip(tag - 1, 0, 2), branches, x)

    sharded = P(AXIS)
    fn = jax.jit(jax.shard_map(
        run, mesh=mesh,
        in_specs=(sharded,) * 7 + (P(), sharded, P()),
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

    def run(colpak, head, tail1, tail2, row_ids, bnd_idx, halo_idx, table,
            slot_tags, x):
        blk = _blk(colpak, head, tail1, tail2, row_ids, bnd_idx, halo_idx,
                   table)
        return local_matvec(blk, x, tag=tag, wire=wire, k=k, rows=rows,
                            ei_bit=ei, acc_dtype=acc_dtype,
                            slot_tags=slot_tags[0])

    sharded = P(AXIS)
    fn = jax.jit(jax.shard_map(
        run, mesh=mesh,
        in_specs=(sharded,) * 7 + (P(), sharded, sharded),
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
        y = fn(part.colpak, part.head, part.tail1, part.tail2,
               part.row_ids, part.bnd_idx, part.halo_idx, part.table,
               st, xp)
        return y[:n]
    fn = _dist_matvec_fn(part, wire, x.ndim, acc_dtype)
    y = fn(part.colpak, part.head, part.tail1, part.tail2, part.row_ids,
           part.bnd_idx, part.halo_idx, part.table, xp,
           jnp.asarray(tag, jnp.int32))
    return y[:n]


def _resolve_dist_plan(part, tag, nrhs, plan) -> KernelPlan:
    """Uniform launch-plan resolution for the distributed path (DESIGN.md
    §15): explicit plan > tuned cache (layout key "dist") > default.  The
    shard-local matvec rides the jnp segment-sum decode -- there is no
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
    sharded twin of ``solvers.cg._gsecsr_operator``).  Accepts ``(n,)``
    vectors and ``(n, nrhs)`` blocks; usable as the operator callable in
    every solver path (generic CG/PCG, GMRES, batched, IR)."""
    key = ("_sharded_operator", wire, jnp.dtype(acc_dtype).name, plan)
    op = part.__dict__.get(key)
    if op is None:
        def op(v, tag):
            return _apply_padded(part, v, tag, wire, acc_dtype)

        part.__dict__[key] = op
    return op
