"""Compile the solver path's kernels for a described TPU v5e, with no chip.

The Pallas interpreter accepts programs Mosaic refuses (unsigned casts,
1-D gathers, i64 index maps under x64), so interpret-mode tests alone
cannot show that the kernels run on the chip.  These tests hand the TPU
compiler the shapes of ``poisson3d(128)`` -- 2,097,152 rows, ELL width
128 -- for ``topo.devices[0]`` of a ``v5e:2x2`` topology and compile:
the ELL SpMV at tags 1-3, the ELL SpMM, one SELL width bucket, and the
float64 jnp SpMV the solvers run, by gather and by windows of ``x``; and
the sharded CG/PCG loop over all
four chips of the topology, whose tag switch wraps the whole step.  x64 stays on, as ``conftest.py``
sets it.  The topology is described in a fixture, never at import, so
every test worker collects the same tests; where it cannot be described
the tests skip.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.precision_table import TAG_SEGMENTS
from repro.kernels.gse_spmm import gse_spmm_call
from repro.kernels.gse_spmv import gse_spmv_call, gse_spmv_sell_call
from repro.sparse.spmv import _spmv_gse

N = 128 ** 3                      # poisson3d(128) unknowns
L = 128                           # ELL width (7 per row, lane-padded)
K = 8                             # shared-exponent table entries
EI_BIT = 3
NRHS = 4
HBM_BYTES = 16 * 2 ** 30          # one v5e


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        cc.reset_cache()
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler in this installation
            jax.config.update("jax_enable_compilation_cache", was)
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield topo
        jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _segments(sharding, rows, width, tag):
    """(colpak, head, tail1, tail2) shapes; tails the tag skips are None."""
    tile = (rows, width)
    t1 = (_spec(sharding, tile, jnp.uint16)
          if "tail1" in TAG_SEGMENTS[tag] else None)
    t2 = (_spec(sharding, tile, jnp.uint32)
          if "tail2" in TAG_SEGMENTS[tag] else None)
    return (_spec(sharding, tile, jnp.uint32),
            _spec(sharding, tile, jnp.uint16), t1, t2)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert used < HBM_BYTES, f"needs {used / 2**30:.2f} GiB of HBM"
    return compiled.as_text()


@pytest.mark.parametrize("tag", [1, 2, 3])
def test_ell_spmv_compiles_for_v5e(one_chip, tag):
    cp, hd, t1, t2 = _segments(one_chip, N, L, tag)

    def spmv(cp, hd, t1, t2, x, scales):
        return gse_spmv_call(cp, hd, t1, t2, x, scales, ei_bit=EI_BIT,
                             tag=tag, interpret=False)

    text = _compile(spmv, cp, hd, t1, t2, _spec(one_chip, (N,), jnp.float32),
                    _spec(one_chip, (1, K), jnp.float32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("tag", [1, 3])
def test_ell_spmm_compiles_for_v5e(one_chip, tag):
    cp, hd, t1, t2 = _segments(one_chip, N, L, tag)

    def spmm(cp, hd, t1, t2, x, scales):
        return gse_spmm_call(cp, hd, t1, t2, x, scales, ei_bit=EI_BIT,
                             tag=tag, interpret=False)

    text = _compile(spmm, cp, hd, t1, t2,
                    _spec(one_chip, (N, NRHS), jnp.float32),
                    _spec(one_chip, (1, K), jnp.float32))
    assert "tpu_custom_call" in text


def test_sell_bucket_compiles_for_v5e(one_chip):
    """One width bucket (all of poisson3d's rows share width 128), the
    row un-permutation included."""
    tag = 3
    bucket = _segments(one_chip, N, L, tag)

    def spmv(bucket, unperm, x, scales):
        return gse_spmv_sell_call((bucket,), unperm, x, scales,
                                  ei_bit=EI_BIT, tag=tag, interpret=False)

    text = _compile(spmv, bucket, _spec(one_chip, (N,), jnp.int32),
                    _spec(one_chip, (N,), jnp.float32),
                    _spec(one_chip, (1, K), jnp.float32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("tag", [1, 3])
def test_f64_jnp_spmv_compiles_for_v5e(one_chip, tag):
    """The decode + slot-major row reduction SpMV the stepped solvers run,
    in float64 (emulated on the v5e), at poisson3d(128)'s shapes: its
    segments and row ids stored (7, rows) by row slot."""
    s = one_chip

    def spmv(cp, hd, t1, t2, table, row_ids, x):
        return _spmv_gse(cp, hd, t1, t2, table, row_ids, x, EI_BIT, tag,
                         jnp.float64, N)

    slots = (7, N)
    _compile(spmv, _spec(s, slots, jnp.uint32), _spec(s, slots, jnp.uint16),
             _spec(s, slots, jnp.uint16), _spec(s, slots, jnp.uint32),
             _spec(s, (K,), jnp.int32), _spec(s, slots, jnp.int32),
             _spec(s, (N,), jnp.float64))


@pytest.mark.parametrize("tag", [1, 3])
def test_windowed_f64_spmv_compiles_for_v5e(one_chip, tag):
    """The solver step's SpMV over a store ranked by column offset,
    poisson3d(128)'s seven slots: ``x`` read as seven dynamic slices, no
    gather of ``x`` left in the program, nor the CPU's branch."""
    import re

    from repro.sparse.csr import GSECSR
    from repro.sparse.spmv import spmv_operand

    s = one_chip
    slots = (7, N)
    g = GSECSR(rowptr=_spec(s, (N + 1,), jnp.int32),
               colpak=_spec(s, slots, jnp.uint32),
               head=_spec(s, slots, jnp.uint16),
               tail1=_spec(s, slots, jnp.uint16),
               tail2=_spec(s, slots, jnp.uint32),
               table=_spec(s, (K,), jnp.int32),
               row_ids=_spec(s, slots, jnp.int32), ei_bit=EI_BIT,
               shape=(N, N), slot_map=_spec(s, (7,), jnp.int32))
    text = _compile(lambda g, x: spmv_operand(g, x, tag), g,
                    _spec(s, (N,), jnp.float64))
    assert not re.search(r" gather\(", text)
    assert " conditional(" not in text


@pytest.mark.parametrize("kind", ["cg", "pcg"])
def test_sharded_loop_compiles_for_v5e_2x2(topo, kind):
    """The whole sharded loop, its step's tag switch around each shard's
    row sum and psum'd dots, compiles for the four chips (poisson3d(24)
    in z-slabs; the compiler once refused a float64 slot-major reduce at
    the root of a switch branch)."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.core import precision as Prec
    from repro.distributed.partition import AXIS, partition_gsecsr
    from repro.kernels.dist_spmv import block_arrays
    from repro.robustness.guards import DEFAULT_GUARDS
    from repro.solvers import make_jacobi, sharded as S
    from repro.sparse import generators as G
    from repro.sparse.csr import pack_csr

    a = G.poisson3d(24)
    part = partition_gsecsr(pack_csr(a, k=8), 4)
    mesh = Mesh(np.array(topo.devices[:4]), (AXIS,))
    part.__dict__["_mesh"] = mesh
    sh, rep = NamedSharding(mesh, P(AXIS)), NamedSharding(mesh, P())
    if kind == "cg":
        diag, meta = S._empty_diag(part), None
    else:
        pk = make_jacobi(a).packed
        diag = tuple(S._pad_to(t, part.n_padded)
                     for t in (pk.head, pk.tail1, pk.tail2)) + (pk.table,)
        meta = (pk.ei_bit, pk.frac_bits)
    fn = S._sharded_loop_fn(part, kind, "exact", 5000,
                            Prec.MonitorParams.for_cg(), 1, meta,
                            DEFAULT_GUARDS)
    assert part.slot_map is not None      # every shard reads windows
    vec = _spec(sh, (part.n_padded,), jnp.float64)
    scalar = _spec(rep, (), jnp.float64)
    args = ([jax.tree.map(lambda x: _spec(sh, x.shape, x.dtype),
                          block_arrays(part))]
            + [_spec(rep, part.table.shape, part.table.dtype)]
            + [_spec(sh, x.shape, x.dtype) for x in diag[:3]]
            + [_spec(rep, diag[3].shape, diag[3].dtype)]
            + [vec, vec, scalar, scalar])
    text = fn.lower(*args).compile().as_text()
    assert "all-reduce" in text and "conditional" in text
