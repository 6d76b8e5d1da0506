"""Per-kernel FLOP + byte ledger (PR 7, DESIGN.md section 15).

One :class:`KernelLedger` per (kernel, tag, layout, nrhs) records what a
single SpMV/SpMM application *should* move and compute, derived from the
same tag-specialized operand lists the kernels stream:

  * ``flops``         -- useful work: ``2 * nnz * nrhs`` (multiply + add
                         per stored entry per column; padded slots
                         multiply exact zeros and are NOT credited);
  * ``matrix_bytes``  -- the slot-honest kernel-stream model
                         (``GSECSR.bytes_touched`` / ``ELLLayout`` /
                         ``GSESellC.bytes_touched``); for the ELL/SELL
                         layouts it includes the gathered-x f32 tile the
                         kernels stream per slot and column;
  * ``vector_bytes``  -- x read + y write per column;
  * ``fp64_bytes``    -- what an fp64 CSR SpMV streams for the SAME math
                         (12 B/nnz + rowptr): dividing by wall time gives
                         the *effective* bandwidth, the fair cross-format
                         axis (a tag-1 kernel at equal wall time delivers
                         the same effective GB/s while reading half the
                         physical bytes).

Three independent cross-checks pin the model (tests/test_perf.py):
``pallas_segment_bytes`` predicts the exact padded operand bytes of a
kernel launch, validated against (a) the jaxpr's integer ``pallas_call``
operands (:func:`jaxpr_pallas_int_bytes`, the PR-1/PR-4 assertion style)
and (b) the compiled HLO's entry parameters
(:func:`launch.hlo.parameter_bytes`).
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.precision_table import COLIDX_BYTES, SLOT_BYTES
from repro.perf.plan import DEFAULT_BLOCKS
from repro.sparse.csr import (
    CSR,
    GSECSR,
    GSESellC,
    ELLLayout,
    ell_layout,
    vector_stream_bytes,
)

__all__ = ["KernelLedger", "spmv_ledger", "pallas_segment_bytes",
           "jaxpr_pallas_int_bytes", "hlo_segment_bytes", "achieved"]


@dataclasses.dataclass(frozen=True)
class KernelLedger:
    kernel: str          # "spmv_ell" / "spmm_sell" / "spmv_csr" / ...
    tag: object          # GSE tag 1/2/3, or a store dtype name for CSR
    layout: str          # "csr" / "ell" / "sell"
    nrhs: int
    nnz: int
    slots: int           # padded slots streamed (== nnz for raw CSR)
    flops: int           # useful FLOPs: 2 * nnz * nrhs
    matrix_bytes: int    # modeled kernel-stream bytes (slot-honest)
    vector_bytes: int    # per-column x/y traffic * nrhs
    fp64_bytes: int      # fp64-CSR-equivalent matrix bytes for same math

    @property
    def bytes(self) -> int:
        return self.matrix_bytes + self.vector_bytes


def _fp64_equiv(a) -> int:
    # fp64 CSR matrix streams: 8 B value + 4 B colidx per nnz + rowptr.
    m = int(a.shape[0])
    return int(a.nnz) * (8 + COLIDX_BYTES) + (m + 1) * 4


def spmv_ledger(a, tag=None, layout=None, nrhs: int = 1,
                vec_dtype=jnp.float64, store_dtype=None,
                jnp_path: bool = False) -> KernelLedger:
    """Ledger for one SpMV/SpMM application of ``a``.

    ``a`` is a ``GSECSR`` (give ``tag``) or a plain ``CSR`` (give
    ``store_dtype``).  ``layout`` selects the byte account: ``None`` (raw
    CSR nnz model), ``"ell"`` (uniform lane-padded), or an
    ``ELLLayout``/``GSESellC`` instance for the exact pack in hand.
    ``jnp_path=True`` charges the reference decode's extra ``row_ids``
    stream (nnz * 4 B -- the Pallas kernels derive rows from the grid and
    do not pay this).
    """
    if nrhs < 1:
        raise ValueError(f"nrhs must be >= 1, got {nrhs}")
    slots = int(a.nnz)
    if isinstance(a, GSESellC) or isinstance(layout, GSESellC):
        lay = a if isinstance(a, GSESellC) else layout
        mat = lay.bytes_touched(tag, nrhs=nrhs)
        slots = lay.slots
        layout_name = "sell"
    elif isinstance(layout, ELLLayout):
        mat = layout.bytes_touched(tag, nrhs=nrhs)
        slots = layout.slots
        layout_name = "ell"
    elif layout == "ell":
        lay = ell_layout(a)
        mat = lay.bytes_touched(tag, nrhs=nrhs)
        slots = lay.slots
        layout_name = "ell"
    elif layout in (None, "csr"):
        if isinstance(a, CSR) or store_dtype is not None:
            dt = store_dtype or jnp.float64
            mat = a.bytes_touched(dt)
            tag = np.dtype(dt).name
        else:
            mat = a.bytes_touched(tag)
        layout_name = "csr"
    else:
        raise ValueError(f"unknown layout {layout!r}")
    if jnp_path:
        mat += int(a.nnz) * 4  # row_ids stream of the segment-sum decode
    kernel = ("spmv" if nrhs == 1 else "spmm") + "_" + layout_name
    return KernelLedger(
        kernel=kernel, tag=tag, layout=layout_name, nrhs=nrhs,
        nnz=int(a.nnz), slots=slots, flops=2 * int(a.nnz) * nrhs,
        matrix_bytes=int(mat),
        vector_bytes=nrhs * vector_stream_bytes(a, dtype=vec_dtype),
        fp64_bytes=_fp64_equiv(a) + nrhs * vector_stream_bytes(a,
                                                               vec_dtype),
    )


def _pad(x: int, b: int) -> int:
    return -(-x // b) * b


def pallas_segment_bytes(src, tag: int, blocks=DEFAULT_BLOCKS,
                         lane: int = 128) -> int:
    """EXACT packed-segment (integer) bytes a kernel launch takes as
    operands -- the gathered-x f32 tile is not counted here.

    For a ``GSECSR`` (uniform-ELL path) this is the (rows, L) pack padded
    to the (BM, BL) grid -- ``ell_pack_gsecsr`` + ``_pad2`` reproduced
    arithmetically; for a ``GSESellC`` it is the per-bucket slot sum
    (buckets are already grid-aligned; incompatible blocks raise, same as
    the dispatcher).  Cross-validated against the jaxpr operand list and
    the compiled HLO parameters in tests/test_perf.py.
    """
    bm, bl = blocks
    if isinstance(src, GSESellC):
        if src.c % bm != 0 or any(w % bl != 0 for w in src.widths):
            raise ValueError(f"blocks {blocks} incompatible with SELL pack "
                             f"(c={src.c}, widths={src.widths})")
        return src.slots * SLOT_BYTES[tag]
    per_row = np.diff(np.asarray(src.rowptr, np.int64))
    L = _pad(int(max(1, per_row.max(initial=0))), lane)
    rows = _pad(int(src.shape[0]), bm)
    return rows * _pad(L, bl) * SLOT_BYTES[tag]


def _iter_eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            leaves = v if isinstance(v, (list, tuple)) else (v,)
            for leaf in leaves:
                inner = getattr(leaf, "jaxpr", None)
                if inner is not None:
                    yield from _iter_eqns(inner)


def jaxpr_pallas_int_bytes(fn, *args) -> int:
    """Sum of integer-dtype operand bytes across every ``pallas_call`` in
    ``fn``'s jaxpr: exactly the packed GSE segments (colpak/head/tails),
    since x/scales are float and row indexing comes from the grid."""
    jaxpr = jax.make_jaxpr(fn)(*args)
    total = 0
    for eqn in _iter_eqns(jaxpr.jaxpr):
        if eqn.primitive.name != "pallas_call":
            continue
        for var in eqn.invars:
            aval = var.aval
            if jnp.issubdtype(aval.dtype, jnp.integer):
                total += int(np.prod(aval.shape)) * aval.dtype.itemsize
    return total


def hlo_segment_bytes(fn, *args) -> int:
    """u16/u32 entry-parameter bytes of the COMPILED lowering of ``fn`` --
    the HLO-level twin of :func:`jaxpr_pallas_int_bytes`, via
    ``launch.hlo.parameter_bytes``."""
    from repro.launch import hlo

    text = jax.jit(fn).lower(*args).compile().as_text()
    return hlo.parameter_bytes(text, dtypes={"u16", "u32"})


def achieved(ledger: KernelLedger, seconds: float, roof=None) -> dict:
    """Wall-time-derived rates for one measured kernel, ledger-priced.

    ``achieved_gbps`` divides the PHYSICAL modeled bytes by time;
    ``effective_gbps`` divides the fp64-equivalent bytes (same math) by
    time -- the fair cross-format axis.  With a ``roofline.host_roofline``
    dict, ``roofline_fraction`` = attainable-time / measured-time where
    attainable = max(bytes/BW, flops/peak): 1.0 means the kernel runs at
    the host's measured roofline, >1 signals cache residency (the smoke
    matrices fit in LLC -- documented, not clipped)."""
    out = {
        "flops": ledger.flops,
        "bytes": ledger.bytes,
        "us": seconds * 1e6,
        "achieved_gbps": ledger.bytes / seconds / 1e9,
        "achieved_gflops": ledger.flops / seconds / 1e9,
        "effective_gbps": ledger.fp64_bytes / seconds / 1e9,
    }
    if roof is not None:
        from repro.perf import roofline as _r

        out["roofline_fraction"] = _r.fraction(
            ledger.flops, ledger.bytes, seconds, roof)
    return out
