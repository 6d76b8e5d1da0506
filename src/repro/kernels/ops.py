"""Jit'd public wrappers around the Pallas kernels.

Handles padding to tile multiples, scale-LUT precomputation, and
interpret-mode selection: ``interpret=None`` resolves here, and only here,
to the interpreter off the TPU and to compiled Mosaic kernels on it.  The
raw kernel entry points take ``interpret`` without a default.
"""
from __future__ import annotations

import dataclasses
import functools
import zlib
from collections import OrderedDict

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.gse import GSEPacked
from repro.core.precision_table import TAG_BITS_USED, TAG_SEGMENTS
from repro.core.tagmap import TagMap
from repro.kernels import ref
from repro.obs import metrics as OM
from repro.obs import trace as OT
from repro.kernels.gse_decode import decode_pallas
from repro.kernels.gse_matmul import gse_matmul_pallas
from repro.kernels.gse_spmm import gse_spmm_pallas, gse_spmm_sell_call
from repro.kernels.gse_spmv import gse_spmv_pallas, gse_spmv_sell_call
from repro.perf import plan as launch_plan
from repro.perf.plan import KernelPlan
from repro.sparse.csr import GSECSR, GSESellC, pack_sell, scatter_rows

__all__ = ["gse_decode", "gse_matmul", "gse_spmv_ell", "gse_spmm_ell",
           "gse_spmv_sell", "gse_spmm_sell", "ell_pack_gsecsr",
           "sell_pack_gsecsr", "spmv_kernel_for", "spmm_kernel_for",
           "sell_kernel_for", "sell_spmm_kernel_for", "PACK_STATS",
           "planned_spmv", "planned_spmm", "masked_for_tagmap",
           "sell_bucket_tags"]

# Operand-pack cache accounting: one entry per (operator instance, layout
# key).  ``hits``/``misses`` are module-global so tests (and the solve
# service) can assert that repeated solves against one registered operator
# perform ZERO host-side re-packing; ``evictions`` counts LRU drops and
# ``corrupt`` counts checksum-mismatch detect-and-repack events
# (DESIGN.md §14).  Storage lives in the metrics registry (DESIGN.md §16)
# -- this dict-shaped view keeps every historical call site working.
PACK_STATS = OM.stats_view(
    "repro_pack_cache_events_total",
    ("hits", "misses", "evictions", "corrupt"),
    help="Operand pack-cache events by outcome.",
)

# Per-operator-instance LRU bound.  Layout keys are few (one per
# (layout, lane/c/sigma) combination a caller sweeps), but a long-lived
# solve service re-registering layouts must not grow host memory without
# limit; exceeding the bound evicts least-recently-used entries.
PACK_CACHE_MAX = 8


def _entry_checksum(entry) -> int:
    """CRC32 over every array leaf of a packed-operand entry.

    Computed once at build time and re-verified on every cache hit: a
    silently corrupted pack (the fault model of DESIGN.md §14 -- host
    memory bit-flips in long-lived service processes) is detected and
    rebuilt instead of feeding garbage segments to every future solve.
    """
    ck = 0
    for leaf in jax.tree_util.tree_leaves(entry):
        ck = zlib.crc32(np.ascontiguousarray(np.asarray(leaf)).tobytes(), ck)
    return ck


def _cached_pack(a, key, build):
    """Memoize a packed-operand build on the operator instance itself.

    Keyed on identity (the instance's ``__dict__``, same idiom as the
    solvers' ``_tag_operator`` memo) + the layout parameters: the packed
    arrays live exactly as long as the operator, and every solver/benchmark
    path asking for the same layout gets the same arrays back without a
    numpy rescatter.

    Entries are ``(packed, crc32)`` in an LRU ``OrderedDict`` bounded by
    :data:`PACK_CACHE_MAX`; a hit re-verifies the checksum and a mismatch
    counts in ``PACK_STATS['corrupt']`` and triggers a repack.
    """
    cache = a.__dict__.setdefault("_pack_cache", OrderedDict())
    hit = key in cache
    if hit:
        entry, ck = cache[key]
        if _entry_checksum(entry) != ck:
            PACK_STATS["corrupt"] += 1
            hit = False  # detected corruption: fall through to repack
        else:
            PACK_STATS["hits"] += 1
            cache.move_to_end(key)
    if not hit:
        PACK_STATS["misses"] += 1
        with OT.span("pack.build", key=str(key)):
            entry = build()
        cache[key] = (entry, _entry_checksum(entry))
        cache.move_to_end(key)
        while len(cache) > PACK_CACHE_MAX:
            cache.popitem(last=False)
            PACK_STATS["evictions"] += 1
    return entry


def _interpret_default() -> bool:
    return jax.default_backend() != "tpu"


def _pad2(a, bm, bn):
    m, n = a.shape
    pm = (-m) % bm
    pn = (-n) % bn
    if pm or pn:
        a = jnp.pad(a, ((0, pm), (0, pn)))
    return a


def gse_decode(packed: GSEPacked, tag: int = 1, block=(8, 128),
               interpret: bool | None = None) -> jnp.ndarray:
    """Decode a dense GSE-SEM tensor to f32 via the Pallas kernel."""
    if interpret is None:
        interpret = _interpret_default()
    shape = packed.head.shape
    head2 = packed.head.reshape(1, -1) if packed.head.ndim == 1 else packed.head
    t1 = packed.tail1.reshape(head2.shape)
    t2 = packed.tail2.reshape(head2.shape)
    bm, bn = block
    m0, n0 = head2.shape
    head2, t1, t2 = _pad2(head2, bm, bn), _pad2(t1, bm, bn), _pad2(t2, bm, bn)
    # Dense path: expIdx steals ei_bit head bits (TAG_BITS_USED assumes
    # the sparse layout's full 15-bit head).
    bits_used = TAG_BITS_USED[tag] - packed.ei_bit
    scales = ref.make_scales(packed.table, bits_used).reshape(1, -1)
    with jax.named_scope(f"gse_decode.tag{tag}"):
        out = decode_pallas(head2, t1, t2, scales, ei_bit=packed.ei_bit,
                            tag=tag, block=block, interpret=interpret)
    return out[:m0, :n0].reshape(shape)


def gse_matmul(x: jnp.ndarray, packed: GSEPacked, tag: int = 1,
               blocks=(8, 128, 128), interpret: bool | None = None):
    """x @ decode(W) with fused in-VMEM dequantization.

    x: (M, K) float; packed: GSE-SEM weights of logical shape (K, N).
    """
    if interpret is None:
        interpret = _interpret_default()
    bm, bn, bk = blocks
    kk, n = packed.head.shape
    m = x.shape[0]
    x2 = _pad2(x, bm, bk)
    head = _pad2(packed.head, bk, bn)
    t1 = _pad2(packed.tail1, bk, bn)
    t2 = _pad2(packed.tail2, bk, bn)
    bits_used = TAG_BITS_USED[tag] - packed.ei_bit
    scales = ref.make_scales(packed.table, bits_used).reshape(1, -1)
    out = gse_matmul_pallas(x2, head, t1, t2, scales, ei_bit=packed.ei_bit,
                            tag=tag, blocks=blocks, interpret=interpret)
    return out[:m, :n]


_SEGMENT_DTYPES = (
    ("colpak", np.uint32),
    ("head", np.uint16),
    ("tail1", np.uint16),
    ("tail2", np.uint32),
)


def ell_pack_gsecsr(a: GSECSR, lane: int | None = None,
                    plan: KernelPlan | None = None):
    """GSE-SEM CSR -> padded uniform-ELL segment arrays for the SpMV kernel.

    Returns (colpak, head, tail1, tail2) each (rows, L) with L lane-aligned.
    Padded slots: colpak=0, head=0 (mantissa 0 -> decodes to +0.0).  The
    scatter is ``csr.scatter_rows`` (shared with ``to_ell`` and the SELL
    packer) and the result is memoized on the operator instance -- repeat
    callers re-scatter nothing.  ``lane`` resolves explicit arg > ``plan``
    > the default 128 (DESIGN.md §15).
    """
    if lane is None:
        lane = (plan or launch_plan.DEFAULT_PLAN).lane

    def build():
        rowptr = np.asarray(a.rowptr, np.int64)
        L = int(max(1, np.diff(rowptr).max(initial=0)))
        L = ((L + lane - 1) // lane) * lane
        c_ord = a.in_csr_order()
        outs, _, _ = scatter_rows(
            rowptr, [(getattr(c_ord, n), d) for n, d in _SEGMENT_DTYPES], L
        )
        return tuple(jnp.asarray(o) for o in outs)

    return _cached_pack(a, ("ell", lane), build)


def sell_pack_gsecsr(a: GSECSR, c: int | None = None,
                     sigma: int | None = None, lane: int | None = None,
                     bucket: str | None = None,
                     plan: KernelPlan | None = None) -> GSESellC:
    """GSE-SEM CSR -> SELL-C-σ packed layout, memoized on the operator
    instance (DESIGN.md §12).

    Layout parameters resolve explicit args > ``plan`` > the pre-PR-7
    defaults (C=8, full-sort σ, lane 128, pow2 width buckets).  The cache
    key is the resolved parameters; repeated solves, benchmark sweeps, and
    the solve service all share ONE host-side pack per operator --
    asserted via :data:`PACK_STATS` in tests/test_sell.py.
    """
    base = plan or launch_plan.DEFAULT_PLAN
    c = base.sell_c if c is None else c
    sigma = base.sell_sigma if sigma is None else sigma
    lane = base.lane if lane is None else lane
    bucket = base.sell_bucket if bucket is None else bucket
    return _cached_pack(
        a, ("sell", c, sigma, lane, bucket),
        lambda: pack_sell(a, c=c, sigma=sigma, lane=lane, bucket=bucket),
    )


def _masked_sell_for_tagmap(sell: GSESellC, tm: TagMap) -> GSESellC:
    """GSESellC twin of :func:`masked_for_tagmap`: per-bucket tail arrays
    masked slot-wise at the symmetric induced tag (max of the slot row's
    and column's group tags; padding slots are already all zero, so their
    nominal tag is irrelevant)."""

    def build():
        perm = np.asarray(sell.perm, np.int64)
        n = sell.shape[0]
        row_tags = tm.row_tags(n)
        cmask = np.uint32((1 << (32 - sell.ei_bit)) - 1)
        t1s, t2s, off = [], [], 0
        for cp, t1, t2 in zip(sell.colpak, sell.tail1, sell.tail2):
            rows = perm[off:off + t1.shape[0]]
            rt = np.where(rows >= 0, row_tags[np.maximum(rows, 0)], 1)
            cols = (np.asarray(cp, np.uint32) & cmask).astype(np.int64)
            ct = row_tags[np.minimum(cols, n - 1)]
            et = np.maximum(rt[:, None], ct)
            t1s.append(jnp.asarray(
                np.where(et >= 2, np.asarray(t1), 0).astype(np.uint16)))
            t2s.append(jnp.asarray(
                np.where(et >= 3, np.asarray(t2), 0).astype(np.uint32)))
            off += t1.shape[0]
        return dataclasses.replace(sell, tail1=tuple(t1s), tail2=tuple(t2s))

    return _cached_pack(sell, ("tagmap", tm.crc32, tm.group_size), build)


def masked_for_tagmap(a, tm: TagMap):
    """Per-group-precision view of ``a``: tail segments below each entry's
    INDUCED tag -- the max of its row's and its column's group tags, so a
    masked SPD operand stays exactly symmetric (CG's contract; see
    ``TagMap.entry_tags``) -- are zeroed (DESIGN.md §18).  ``a`` may be a
    ``GSECSR`` or an already-packed ``GSESellC`` (masked per slot).

    Decoding the masked operand with the map's MAX-tag formula is bitwise
    identical to decoding each entry at its own group tag: the zeroed
    splices contribute exactly 0 and the surviving partial mantissa times
    the max-tag power-of-two scale equals the lower-tag decode exactly
    (``m_head * 2^48 * 2^(e_sh-63) == m_head * 2^(e_sh-15)``; both
    factors are exact powers of two and every partial mantissa fits f64).
    So every existing tag-specialized pipeline -- the solver step, ELL
    and SELL kernels, the reference decode -- applies a non-uniform map
    with NO new kernel bodies.

    The result is memoized under the map's CRC32 (satellite 1: a promoted
    map can never hit a stale masked pack), shares the untouched segment
    arrays with ``a``, and carries its own ``_pack_cache`` so ELL/SELL
    packs of the masked view never collide with packs of ``a`` itself.
    """
    if isinstance(a, GSESellC):
        return _masked_sell_for_tagmap(a, tm)

    def build():
        cols = (np.asarray(a.colpak, np.uint32)
                & np.uint32((1 << (32 - a.ei_bit)) - 1))
        et = tm.entry_tags(np.asarray(a.row_ids), cols)
        t1 = np.where(et >= 2, np.asarray(a.tail1), 0).astype(np.uint16)
        t2 = np.where(et >= 3, np.asarray(a.tail2), 0).astype(np.uint32)
        return GSECSR(
            rowptr=a.rowptr, colpak=a.colpak, head=a.head,
            tail1=jnp.asarray(t1), tail2=jnp.asarray(t2),
            table=a.table, row_ids=a.row_ids, ei_bit=a.ei_bit,
            shape=a.shape, slot_map=a.slot_map,
        )

    return _cached_pack(a, ("tagmap", tm.crc32, tm.group_size), build)


def sell_bucket_tags(sell: GSESellC, tm: TagMap) -> tuple:
    """Per-width-bucket max group tag: the COARSE map unit the SELL kernels
    dispatch at (DESIGN.md §18).

    Each bucket runs one ``pallas_call`` whose operand list matches the
    bucket's max tag, so the lists stay static (jaxpr-checkable) and an
    all-tag-1 bucket genuinely never streams tails.  Entries inside a
    mixed bucket whose group demands less carry zeroed tails (the operand
    must come from :func:`masked_for_tagmap`), so the higher bucket tag
    changes streamed bytes, never values.
    """
    return sell.bucket_tags(tm)


@functools.lru_cache(maxsize=None)
def _sell_mixed_cached(bucket_tags: tuple, ei_bit: int, blocks,
                       interpret: bool, spmm: bool):
    """One jitted per-bucket dispatcher per (bucket-tag tuple, ei_bit,
    blocks): bucket ``i`` runs the tag-``bucket_tags[i]``-specialized
    kernel body, so each bucket's jaxpr operand list matches ITS tag."""
    from repro.kernels.gse_spmm import gse_spmm_call
    from repro.kernels.gse_spmv import gse_spmv_call

    base = gse_spmm_call if spmm else gse_spmv_call

    def run(buckets, unperm, x, scales_by_tag):
        outs = [
            base(cp, hd, t1, t2, x, scales_by_tag[t - 1], ei_bit=ei_bit,
                 tag=t, blocks=blocks, interpret=interpret)
            for (cp, hd, t1, t2), t in zip(buckets, bucket_tags)
        ]
        y = outs[0] if len(outs) == 1 else jnp.concatenate(outs, axis=0)
        return y[unperm]

    return jax.jit(run)


def _gse_sell_tagmap(sell: GSESellC, x, tm: TagMap, blocks, interpret,
                     spmm: bool):
    """Shared TagMap body of ``gse_spmv_sell``/``gse_spmm_sell``: per-
    bucket max-tag dispatch over a masked pack."""
    btags = sell_bucket_tags(sell, tm)
    scales_by_tag = tuple(
        ref.make_scales(sell.table, TAG_BITS_USED[t]).reshape(1, -1)
        for t in (1, 2, 3)
    )
    buckets = tuple(
        (sell.colpak[i], sell.head[i],
         sell.tail1[i] if t >= 2 else None,
         sell.tail2[i] if t == 3 else None)
        for i, t in enumerate(btags)
    )
    kernel = _sell_mixed_cached(btags, sell.ei_bit, blocks, interpret, spmm)
    name = "gse_spmm_sell" if spmm else "gse_spmv_sell"
    with jax.named_scope(f"{name}.map{tm.crc32:08x}"):
        return kernel(buckets, sell.unperm, x, scales_by_tag)


def spmv_kernel_for(tag: int, ei_bit: int, blocks=None,
                    interpret: bool | None = None):
    """Tag-specialized SpMV dispatch: one cached ``pallas_call`` wrapper per
    ``(tag, ei_bit, blocks)`` (DESIGN.md §2.4).

    ``blocks=None`` resolves through the launch-plan dispatcher
    (``perf.plan.resolve``) to today's (8, 128) default; the returned
    callable takes exactly the operands that ``tag`` streams --
    ``(colpak, head, x, scales)`` for tag 1, ``+ tail1`` for tag 2,
    ``+ tail2`` for tag 3 -- so the tag-1/-2 kernels provably never touch
    the tail arrays (10/12/16 bytes per padded slot of HBM traffic for
    tags 1/2/3, gathered-x tile included).
    """
    blocks = launch_plan.resolve(blocks=blocks).blocks
    if interpret is None:
        interpret = _interpret_default()
    return _spmv_kernel_cached(tag, ei_bit, blocks, interpret)


@functools.lru_cache(maxsize=None)
def _spmv_kernel_cached(tag: int, ei_bit: int, blocks, interpret: bool):
    if tag == 1:
        def call(colpak, head, x, scales):
            return gse_spmv_pallas(colpak, head, None, None, x, scales,
                                   ei_bit=ei_bit, tag=1, blocks=blocks,
                                   interpret=interpret)
    elif tag == 2:
        def call(colpak, head, tail1, x, scales):
            return gse_spmv_pallas(colpak, head, tail1, None, x, scales,
                                   ei_bit=ei_bit, tag=2, blocks=blocks,
                                   interpret=interpret)
    elif tag == 3:
        def call(colpak, head, tail1, tail2, x, scales):
            return gse_spmv_pallas(colpak, head, tail1, tail2, x, scales,
                                   ei_bit=ei_bit, tag=3, blocks=blocks,
                                   interpret=interpret)
    else:
        raise ValueError(f"tag must be 1, 2 or 3, got {tag}")
    return call


def spmm_kernel_for(tag: int, ei_bit: int, blocks=None,
                    interpret: bool | None = None):
    """Tag-specialized SpMM dispatch: one cached ``pallas_call`` wrapper per
    ``(tag, ei_bit, blocks)`` -- the multi-RHS twin of ``spmv_kernel_for``
    (DESIGN.md §11).

    ``blocks=None`` resolves through the launch-plan dispatcher to
    today's (8, 128) default.  The returned callable takes exactly the
    operands ``tag`` streams -- ``(colpak, head, x, scales)`` for tag 1,
    ``+ tail1`` for tag 2, ``+ tail2`` for tag 3 -- with ``x`` a dense
    (n, nrhs) block.  The matrix segments are streamed ONCE per call
    however many right-hand sides ride along; the tag-1/-2 kernels
    provably never touch the tail arrays.
    """
    blocks = launch_plan.resolve(blocks=blocks).blocks
    if interpret is None:
        interpret = _interpret_default()
    return _spmm_kernel_cached(tag, ei_bit, blocks, interpret)


@functools.lru_cache(maxsize=None)
def _spmm_kernel_cached(tag: int, ei_bit: int, blocks, interpret: bool):
    if tag == 1:
        def call(colpak, head, x, scales):
            return gse_spmm_pallas(colpak, head, None, None, x, scales,
                                   ei_bit=ei_bit, tag=1, blocks=blocks,
                                   interpret=interpret)
    elif tag == 2:
        def call(colpak, head, tail1, x, scales):
            return gse_spmm_pallas(colpak, head, tail1, None, x, scales,
                                   ei_bit=ei_bit, tag=2, blocks=blocks,
                                   interpret=interpret)
    elif tag == 3:
        def call(colpak, head, tail1, tail2, x, scales):
            return gse_spmm_pallas(colpak, head, tail1, tail2, x, scales,
                                   ei_bit=ei_bit, tag=3, blocks=blocks,
                                   interpret=interpret)
    else:
        raise ValueError(f"tag must be 1, 2 or 3, got {tag}")
    return call


def gse_spmm_ell(ell, table, x: jnp.ndarray, ei_bit: int, tag: int = 1,
                 blocks=None, interpret: bool | None = None,
                 plan: KernelPlan | None = None):
    """Y = A @ X from ELL-packed GSE-SEM segments (Pallas SpMM kernel).

    ``x`` is a dense (n, nrhs) right-hand-side block.  Dispatches to the
    tag-specialized kernel (``spmm_kernel_for``): only the segment arrays
    ``tag`` reads are padded, passed, and streamed -- and they are
    streamed ONCE for all ``nrhs`` columns, so the modeled per-iteration
    traffic is ``iteration_stream_bytes(a, tag, nrhs=nrhs)`` instead of
    ``nrhs`` full SpMV passes (DESIGN.md §11); each column adds its own
    gathered-x tile (``ELLLayout.bytes_touched(tag, nrhs)``).  Launch
    blocks resolve
    explicit ``blocks`` > ``plan`` > the (8, 128) default (§15).
    """
    if interpret is None:
        interpret = _interpret_default()
    blocks = launch_plan.resolve(blocks=blocks, plan=plan).blocks
    colpak, head, t1, t2 = ell
    bm, bl = blocks
    m0 = colpak.shape[0]
    scales = ref.make_scales(table, TAG_BITS_USED[tag]).reshape(1, -1)
    kernel = spmm_kernel_for(tag, ei_bit, blocks, interpret)
    operands = [_pad2(colpak, bm, bl), _pad2(head, bm, bl)]
    if tag >= 2:
        operands.append(_pad2(t1, bm, bl))
    if tag == 3:
        operands.append(_pad2(t2, bm, bl))
    with jax.named_scope(f"gse_spmm_ell.tag{tag}"):
        out = kernel(*operands, x, scales)
    return out[:m0]


def planned_spmv(a: GSECSR, x: jnp.ndarray, tag: int = 1,
                 layout: str = "ell", plan: KernelPlan | None = None,
                 interpret: bool | None = None):
    """Operator-level SpMV with full launch-plan resolution (DESIGN.md §15).

    Resolves ``plan`` (explicit > tuned cache keyed on the operator's
    shape class > default), packs ``a`` with the plan's layout parameters
    (memoized, :func:`ell_pack_gsecsr`/:func:`sell_pack_gsecsr`), and
    dispatches the tag-specialized kernel with the plan's blocks.  This is
    the entry point the autotuner sweeps and the solve service registers.

    ``tag`` may be a :class:`~repro.core.tagmap.TagMap` (DESIGN.md §18):
    the operand is rebuilt through :func:`masked_for_tagmap` (memoized
    under the map's CRC) and the ELL path decodes at the map's max tag
    while the SELL path dispatches each width-bucket at ITS max group
    tag.  Plan resolution keys carry the map CRC, never a scalar tag.
    """
    plan = launch_plan.resolve(a, tag=tag, layout=layout, nrhs=1,
                               plan=plan)
    if isinstance(tag, TagMap):
        a = masked_for_tagmap(a, tag)
        if layout == "ell":
            tag = tag.max_tag  # masked tails: max-tag decode IS the map
    if layout == "sell":
        sell = sell_pack_gsecsr(a, plan=plan)
        blocks = (plan.blocks if plan.compatible_with_sell(sell)
                  else launch_plan.DEFAULT_BLOCKS)
        return gse_spmv_sell(sell, x, tag=tag, blocks=blocks,
                             interpret=interpret)
    if layout != "ell":
        raise ValueError(f"layout must be 'ell' or 'sell', got {layout!r}")
    ell = ell_pack_gsecsr(a, plan=plan)
    return gse_spmv_ell(ell, a.table, x, a.ei_bit, tag=tag,
                        blocks=plan.blocks, interpret=interpret)


def planned_spmm(a: GSECSR, x: jnp.ndarray, tag: int = 1,
                 layout: str = "ell", plan: KernelPlan | None = None,
                 interpret: bool | None = None):
    """Multi-RHS twin of :func:`planned_spmv` (X dense (n, nrhs))."""
    nrhs = x.shape[1]
    plan = launch_plan.resolve(a, tag=tag, layout=layout, nrhs=nrhs,
                               plan=plan)
    if isinstance(tag, TagMap):
        a = masked_for_tagmap(a, tag)
        if layout == "ell":
            tag = tag.max_tag  # masked tails: max-tag decode IS the map
    if layout == "sell":
        sell = sell_pack_gsecsr(a, plan=plan)
        blocks = (plan.blocks if plan.compatible_with_sell(sell)
                  else launch_plan.DEFAULT_BLOCKS)
        return gse_spmm_sell(sell, x, tag=tag, blocks=blocks,
                             interpret=interpret)
    if layout != "ell":
        raise ValueError(f"layout must be 'ell' or 'sell', got {layout!r}")
    ell = ell_pack_gsecsr(a, plan=plan)
    return gse_spmm_ell(ell, a.table, x, a.ei_bit, tag=tag,
                        blocks=plan.blocks, interpret=interpret)


def _sell_dispatch(sell_call, tag: int, ei_bit: int, blocks, interpret):
    """Shared body of ``sell_kernel_for``/``sell_spmm_kernel_for``: pad
    each bucket's tag-specialized operand tuple back to the full
    ``(colpak, head, tail1, tail2)`` signature (absent tails stay
    ``None`` and never enter the jaxpr) and jit one wrapper around the
    per-bucket ``sell_call``."""
    if tag not in (1, 2, 3):
        raise ValueError(f"tag must be 1, 2 or 3, got {tag}")

    def call(buckets, unperm, x, scales):
        full = tuple(b + (None,) * (4 - len(b)) for b in buckets)
        return sell_call(full, unperm, x, scales, ei_bit=ei_bit, tag=tag,
                         blocks=blocks, interpret=interpret)

    return jax.jit(call)


def sell_kernel_for(tag: int, ei_bit: int, blocks=None,
                    interpret: bool | None = None):
    """Tag-specialized SELL-C-σ SpMV dispatch: one cached jitted wrapper
    per ``(tag, ei_bit, blocks)`` -- the sliced-layout twin of
    ``spmv_kernel_for`` (DESIGN.md §12).  ``blocks=None`` resolves
    through the launch-plan dispatcher to today's (8, 128) default.

    The returned callable takes ``(buckets, unperm, x, scales)`` where
    ``buckets`` holds per-width-bucket segment tuples containing exactly
    the operands ``tag`` streams -- ``(colpak, head)`` for tag 1,
    ``+ tail1`` for tag 2, ``+ tail2`` for tag 3.  Each bucket becomes its
    own ``pallas_call`` with the same tag-specialized operand list as the
    uniform-ELL kernel, so tag-1/-2 still provably never touch the tails.
    """
    blocks = launch_plan.resolve(blocks=blocks).blocks
    if interpret is None:
        interpret = _interpret_default()
    return _sell_kernel_cached(tag, ei_bit, blocks, interpret)


@functools.lru_cache(maxsize=None)
def _sell_kernel_cached(tag: int, ei_bit: int, blocks, interpret: bool):
    return _sell_dispatch(gse_spmv_sell_call, tag, ei_bit, blocks, interpret)


def sell_spmm_kernel_for(tag: int, ei_bit: int, blocks=None,
                         interpret: bool | None = None):
    """Multi-RHS twin of ``sell_kernel_for``: per-width-bucket SpMM
    dispatch with the same tag-specialized bucket operand lists."""
    blocks = launch_plan.resolve(blocks=blocks).blocks
    if interpret is None:
        interpret = _interpret_default()
    return _sell_spmm_kernel_cached(tag, ei_bit, blocks, interpret)


@functools.lru_cache(maxsize=None)
def _sell_spmm_kernel_cached(tag: int, ei_bit: int, blocks,
                             interpret: bool):
    return _sell_dispatch(gse_spmm_sell_call, tag, ei_bit, blocks, interpret)


def _sell_buckets(sell: GSESellC, tag: int):
    """Per-bucket operand tuples holding ONLY the segments ``tag`` reads
    (``TAG_SEGMENTS`` is the one source of truth for the tail list)."""
    segs = (sell.colpak, sell.head) + tuple(
        getattr(sell, name) for name in TAG_SEGMENTS[tag])
    return tuple(zip(*segs))


def _check_sell_blocks(sell: GSESellC, blocks) -> None:
    bm, bl = blocks
    if sell.c % bm != 0:
        raise ValueError(
            f"slice height {sell.c} must be a multiple of the row block "
            f"{bm} (bucket rows are not re-padded: that would desync the "
            "row permutation)"
        )
    if any(w % bl != 0 for w in sell.widths):
        raise ValueError(
            f"bucket widths {sell.widths} must be multiples of the lane "
            f"block {bl}"
        )


def _resolve_sell_blocks(sell: GSESellC, tag: int, nrhs: int, blocks,
                         plan: KernelPlan | None):
    """SELL launch-block resolution (DESIGN.md §15): explicit args keep
    today's validate-and-raise contract; a TUNED plan recorded for a
    different pack (its C/widths don't tile this one) silently falls back
    to the default blocks instead of raising."""
    if blocks is not None or plan is not None:
        resolved = launch_plan.resolve(blocks=blocks, plan=plan)
        _check_sell_blocks(sell, resolved.blocks)
        return resolved.blocks
    resolved = launch_plan.resolve(sell, tag=tag, layout="sell", nrhs=nrhs)
    if (resolved.source == "tuned"
            and not resolved.compatible_with_sell(sell)):
        resolved = launch_plan.DEFAULT_PLAN
    _check_sell_blocks(sell, resolved.blocks)
    return resolved.blocks


def gse_spmv_sell(sell: GSESellC, x: jnp.ndarray, tag: int = 1,
                  blocks=None, interpret: bool | None = None,
                  plan: KernelPlan | None = None):
    """y = A @ x from a SELL-C-σ packed GSE-SEM operand (Pallas kernels).

    One tag-specialized ``pallas_call`` per width-bucket; each slice
    streams only ITS lane-aligned width, so the modeled traffic is
    ``sell.bytes_touched(tag)`` -- actual padded slots, not the uniform-
    ELL max-width blowup (DESIGN.md §12).  Output is bitwise identical to
    ``gse_spmv_ell`` on the same operator (tests/test_sell.py).  Launch
    blocks resolve explicit ``blocks`` > ``plan`` > tuned cache entry >
    the (8, 128) default (§15).
    """
    if interpret is None:
        interpret = _interpret_default()
    blocks = _resolve_sell_blocks(sell, tag, 1, blocks, plan)
    if isinstance(tag, TagMap):
        return _gse_sell_tagmap(sell, x, tag, blocks, interpret, spmm=False)
    scales = ref.make_scales(sell.table, TAG_BITS_USED[tag]).reshape(1, -1)
    kernel = sell_kernel_for(tag, sell.ei_bit, blocks, interpret)
    with jax.named_scope(f"gse_spmv_sell.tag{tag}"):
        return kernel(_sell_buckets(sell, tag), sell.unperm, x, scales)


def gse_spmm_sell(sell: GSESellC, x: jnp.ndarray, tag: int = 1,
                  blocks=None, interpret: bool | None = None,
                  plan: KernelPlan | None = None):
    """Y = A @ X from a SELL-C-σ packed GSE-SEM operand, X dense (n, nrhs).

    The multi-RHS twin of ``gse_spmv_sell``: each width-bucket's matrix
    segments are streamed ONCE for all ``nrhs`` columns (DESIGN.md §11 +
    §12); bitwise identical to ``gse_spmm_ell`` on the same operator.
    Launch blocks resolve explicit ``blocks`` > ``plan`` > tuned cache
    entry > the (8, 128) default (§15).
    """
    if interpret is None:
        interpret = _interpret_default()
    blocks = _resolve_sell_blocks(sell, tag, x.shape[1] if x.ndim > 1
                                  else 1, blocks, plan)
    if isinstance(tag, TagMap):
        return _gse_sell_tagmap(sell, x, tag, blocks, interpret, spmm=True)
    scales = ref.make_scales(sell.table, TAG_BITS_USED[tag]).reshape(1, -1)
    kernel = sell_spmm_kernel_for(tag, sell.ei_bit, blocks, interpret)
    with jax.named_scope(f"gse_spmm_sell.tag{tag}"):
        return kernel(_sell_buckets(sell, tag), sell.unperm, x, scales)


def gse_spmv_ell(ell, table, x: jnp.ndarray, ei_bit: int, tag: int = 1,
                 blocks=None, interpret: bool | None = None,
                 plan: KernelPlan | None = None):
    """y = A @ x from ELL-packed GSE-SEM segments (Pallas kernel).

    Dispatches to the tag-specialized kernel (``spmv_kernel_for``): only the
    segment arrays ``tag`` reads are padded, passed, and streamed.  Modeled
    HBM traffic is ``ell_layout(a).bytes_touched(tag)``: 10/12/16 bytes
    per padded slot for tags 1/2/3 (segments + the gathered-x f32 tile).
    Launch blocks resolve explicit ``blocks`` >
    ``plan`` > the (8, 128) default (DESIGN.md §15).
    """
    if interpret is None:
        interpret = _interpret_default()
    blocks = launch_plan.resolve(blocks=blocks, plan=plan).blocks
    colpak, head, t1, t2 = ell
    bm, bl = blocks
    m0 = colpak.shape[0]
    scales = ref.make_scales(table, TAG_BITS_USED[tag]).reshape(1, -1)
    kernel = spmv_kernel_for(tag, ei_bit, blocks, interpret)
    operands = [_pad2(colpak, bm, bl), _pad2(head, bm, bl)]
    if tag >= 2:
        operands.append(_pad2(t1, bm, bl))
    if tag == 3:
        operands.append(_pad2(t2, bm, bl))
    with jax.named_scope(f"gse_spmv_ell.tag{tag}"):
        out = kernel(*operands, x, scales)
    return out[:m0]
