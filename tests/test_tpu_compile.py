"""Compile the solver path's kernels for a described TPU v5e, with no chip.

The Pallas interpreter accepts programs Mosaic refuses (unsigned casts,
1-D gathers, i64 index maps under x64), so interpret-mode tests alone
cannot show that the kernels run on the chip.  These tests hand the TPU
compiler the shapes of ``poisson3d(128)`` -- 2,097,152 rows, ELL width
128 -- for ``topo.devices[0]`` of a ``v5e:2x2`` topology and compile:
the ELL SpMV at tags 1-3, the ELL SpMM, one SELL width bucket, and the
float64 jnp SpMV every solver runs.  x64 stays on, as ``conftest.py``
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
NNZ = 7 * N - 6 * 128 ** 2        # its 7-point stencil's nonzeros
L = 128                           # ELL width (7 per row, lane-padded)
K = 8                             # shared-exponent table entries
EI_BIT = 3
NRHS = 4
HBM_BYTES = 16 * 2 ** 30          # one v5e


@pytest.fixture(scope="module")
def one_chip():
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
        yield SingleDeviceSharding(topo.devices[0])
        jax.config.update("jax_enable_compilation_cache", was)


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
    """The decode + segment-sum SpMV the stepped solvers run, in float64
    (emulated on the v5e), at poisson3d(128)'s CSR shapes."""
    s = one_chip

    def spmv(cp, hd, t1, t2, table, row_ids, x):
        return _spmv_gse(cp, hd, t1, t2, table, row_ids, x, EI_BIT, tag,
                         jnp.float64, N)

    _compile(spmv, _spec(s, (NNZ,), jnp.uint32), _spec(s, (NNZ,), jnp.uint16),
             _spec(s, (NNZ,), jnp.uint16), _spec(s, (NNZ,), jnp.uint32),
             _spec(s, (K,), jnp.int32), _spec(s, (NNZ,), jnp.int32),
             _spec(s, (N,), jnp.float64))
