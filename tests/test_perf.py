"""Perf subsystem tests (PR 7, DESIGN.md §15).

Four families:

  * precision-table regression -- the centralized per-tag byte constants
    reproduce every pre-PR-7 ``bytes_touched`` figure exactly;
  * launch-plan bit-identity -- with an EMPTY tune cache, every kernel
    entry point resolves to the historical (8, 128) default and the
    plan-resolved outputs are BITWISE identical to explicit-blocks calls
    across tags x layouts x nrhs;
  * ledger cross-checks -- the byte model's ``pallas_segment_bytes``
    matches the jaxpr's integer ``pallas_call`` operands and the
    compiled HLO's u16/u32 entry parameters;
  * tune-cache discipline -- sweep once, hit forever (counter-asserted,
    the PR-4 ``PACK_STATS`` style), checksum-verified on every hit.
"""
import json

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import precision_table as pt
from repro.kernels import ops
from repro.perf import ledger, plan as launch_plan, tunecache
from repro.sparse import generators as G
from repro.sparse.csr import ell_layout, pack_csr


@pytest.fixture()
def tmp_cache(tmp_path, monkeypatch):
    """Point the tune cache at an empty tmp file; restore after."""
    path = tmp_path / "tunecache.json"
    monkeypatch.setenv("REPRO_TUNE_CACHE", str(path))
    tunecache.clear_memory()
    tunecache.reset()
    yield path
    tunecache.clear_memory()


def _operand(n=12, k=8):
    a = G.poisson2d(n)
    return a, pack_csr(a, k=k)


# ---------------------------------------------------------------------------
# precision_table: centralized constants == pre-PR-7 byte figures
# ---------------------------------------------------------------------------

def test_precision_table_values():
    assert pt.TAG_VALUE_BYTES == {1: 2, 2: 4, 3: 8}
    assert pt.COLIDX_BYTES == 4
    assert pt.SLOT_BYTES == {1: 6, 2: 8, 3: 12}
    assert pt.GATHERED_X_BYTES == 4
    assert pt.KERNEL_SLOT_BYTES == {1: 10, 2: 12, 3: 16}
    assert pt.WIRE_ENTRY_BYTES == pt.TAG_VALUE_BYTES
    assert pt.DTYPE_BYTES["u16"] == 2 and pt.DTYPE_BYTES["u32"] == 4
    assert pt.DTYPE_BYTES["f64"] == 8


def test_bytes_touched_regression():
    """Pinned figures on poisson2d(12): the nnz-only encoding model keeps
    its pre-PR-7 values; the ELL/SELL kernel layouts also charge the
    gathered-x f32 tile (4 B per padded slot)."""
    a, g = _operand()
    assert (a.nnz, a.shape[0]) == (672, (144, 144)[0])
    assert [g.bytes_per_nnz(t) for t in (1, 2, 3)] == [6, 8, 12]
    assert [g.bytes_touched(t) for t in (1, 2, 3)] == [4644, 5988, 8676]
    assert a.bytes_touched() == 8644  # fp64 CSR: 12 B/nnz + rowptr
    lay = ell_layout(g)
    assert (lay.slots, lay.bytes_touched(1)) == (18432, 184352)
    sell = ops.sell_pack_gsecsr(g)
    assert (sell.slots, sell.bytes_touched(1)) == (18432, 184928)
    from repro.distributed.partition import WIRE_ENTRY_BYTES
    assert WIRE_ENTRY_BYTES is pt.WIRE_ENTRY_BYTES


# ---------------------------------------------------------------------------
# launch-plan resolution: empty cache == historical defaults, bitwise
# ---------------------------------------------------------------------------

def test_resolve_precedence(tmp_cache):
    assert launch_plan.resolve() is launch_plan.DEFAULT_PLAN
    assert launch_plan.resolve(blocks=(16, 128)).blocks == (16, 128)
    assert launch_plan.resolve(blocks=(16, 128)).source == "explicit"
    p = launch_plan.KernelPlan(blocks=(32, 128))
    assert launch_plan.resolve(plan=p).blocks == (32, 128)
    _, g = _operand(8)
    got = launch_plan.resolve(g, tag=1, layout="ell", nrhs=1)
    assert got == launch_plan.DEFAULT_PLAN and got.source == "default"


@pytest.mark.parametrize("tag", [1, 2, 3])
@pytest.mark.parametrize("layout", ["ell", "sell"])
@pytest.mark.parametrize("nrhs", [1, 4])
def test_empty_cache_bit_identity(tmp_cache, tag, layout, nrhs):
    """Plan-resolved dispatch (no explicit blocks, empty cache) is
    BITWISE identical to the pre-PR-7 explicit (8, 128) calls."""
    _, g = _operand(8)
    n = g.shape[1]
    rng = np.random.default_rng(tag * 10 + nrhs)
    if nrhs == 1:
        x = jnp.asarray(rng.normal(size=n), jnp.float32)
        got = ops.planned_spmv(g, x, tag=tag, layout=layout)
        if layout == "ell":
            ell = ops.ell_pack_gsecsr(g)
            want = ops.gse_spmv_ell(ell, g.table, x, g.ei_bit, tag=tag,
                                    blocks=(8, 128))
        else:
            want = ops.gse_spmv_sell(ops.sell_pack_gsecsr(g), x, tag=tag,
                                     blocks=(8, 128))
    else:
        x = jnp.asarray(rng.normal(size=(n, nrhs)), jnp.float32)
        got = ops.planned_spmm(g, x, tag=tag, layout=layout)
        if layout == "ell":
            ell = ops.ell_pack_gsecsr(g)
            want = ops.gse_spmm_ell(ell, g.table, x, g.ei_bit, tag=tag,
                                    blocks=(8, 128))
        else:
            want = ops.gse_spmm_sell(ops.sell_pack_gsecsr(g), x, tag=tag,
                                     blocks=(8, 128))
    assert np.array_equal(np.asarray(got), np.asarray(want))


def test_kernel_for_default_blocks_unchanged(tmp_cache):
    """blocks=None in every *_kernel_for/*_call resolves to (8, 128)."""
    from repro.kernels.gse_spmv import gse_spmv_call  # noqa: F401
    a = launch_plan.resolve(blocks=None)
    assert a.blocks == launch_plan.DEFAULT_BLOCKS == (8, 128)
    _, g = _operand(8)
    k_none = ops.spmv_kernel_for(1, g.ei_bit)
    k_expl = ops.spmv_kernel_for(1, g.ei_bit, blocks=(8, 128))
    assert k_none is k_expl  # same lru_cache entry -> same launch


# ---------------------------------------------------------------------------
# ledger: model == jaxpr operands == compiled HLO parameters
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("tag", [1, 2, 3])
@pytest.mark.parametrize("layout", ["ell", "sell"])
@pytest.mark.parametrize("nrhs", [1, 4])
def test_ledger_matches_jaxpr(tag, layout, nrhs):
    """Predicted packed-segment bytes == the integer operand bytes of
    every ``pallas_call`` in the jaxpr (matrix segments are streamed once
    regardless of nrhs)."""
    _, g = _operand()
    n = g.shape[1]
    x = jnp.ones((n, nrhs) if nrhs > 1 else n, jnp.float32)
    if layout == "ell":
        src = g
        ell = ops.ell_pack_gsecsr(g)
        if nrhs == 1:
            fn = lambda x: ops.gse_spmv_ell(ell, g.table, x, g.ei_bit,
                                            tag=tag)
        else:
            fn = lambda x: ops.gse_spmm_ell(ell, g.table, x, g.ei_bit,
                                            tag=tag)
    else:
        src = ops.sell_pack_gsecsr(g)
        if nrhs == 1:
            fn = lambda x: ops.gse_spmv_sell(src, x, tag=tag)
        else:
            fn = lambda x: ops.gse_spmm_sell(src, x, tag=tag)
    want = ledger.pallas_segment_bytes(src, tag)
    assert ledger.jaxpr_pallas_int_bytes(fn, x) == want


@pytest.mark.parametrize("tag", [1, 3])
def test_ledger_matches_hlo(tag):
    """Compiled-HLO u16/u32 entry-parameter bytes == the model (the
    exponent table is s32 and the vectors are float, so the filter
    isolates exactly the packed segments; unused tails are dropped by
    jit, matching the tag-specialized operand lists)."""
    _, g = _operand()
    ell = ops.ell_pack_gsecsr(g)
    colpak, head, t1, t2 = ell
    x = jnp.ones((g.shape[1],), jnp.float32)

    def fn(colpak, head, t1, t2):
        return ops.gse_spmv_ell((colpak, head, t1, t2), g.table, x,
                                g.ei_bit, tag=tag)

    got = ledger.hlo_segment_bytes(fn, colpak, head, t1, t2)
    assert got == ledger.pallas_segment_bytes(g, tag)


def test_spmv_ledger_accounts():
    a, g = _operand()
    led = ledger.spmv_ledger(g, tag=1, layout="ell", nrhs=1)
    lay = ell_layout(g)
    assert led.flops == 2 * a.nnz
    assert led.matrix_bytes == lay.bytes_touched(1)
    assert led.bytes == led.matrix_bytes + led.vector_bytes
    # fp64-equivalent bytes price the SAME math on fp64 CSR streams.
    led64 = ledger.spmv_ledger(a)
    assert led.fp64_bytes == led64.matrix_bytes + led64.vector_bytes
    # SpMM streams the matrix segments once, vectors per column; each
    # column also streams its own gathered-x tile through the kernel.
    led4 = ledger.spmv_ledger(g, tag=1, layout="ell", nrhs=4)
    assert led4.matrix_bytes == lay.bytes_touched(1, nrhs=4)
    assert (led4.matrix_bytes - led.matrix_bytes
            == 3 * lay.slots * pt.GATHERED_X_BYTES)
    assert led4.vector_bytes == 4 * led.vector_bytes
    assert led4.flops == 4 * led.flops


# ---------------------------------------------------------------------------
# tune cache: sweep once, hit forever, checksum-verified
# ---------------------------------------------------------------------------

def test_tune_persist_and_replay(tmp_cache):
    from repro.perf import autotune

    _, g = _operand(8)
    plan1, payload1, hit1 = autotune.get_or_tune(g, tag=1, layout="ell",
                                                 iters=1, warmup=1)
    assert not hit1
    assert tunecache.TUNE_STATS["sweeps"] == 1
    assert tunecache.TUNE_STATS["stores"] == 1
    assert payload1["default_us"] >= payload1["us"] > 0
    assert payload1["decode_bound"] == (g.nnz < autotune.DECODE_BOUND_NNZ)
    assert tmp_cache.exists()

    # Same-process replay: in-memory hit, zero re-sweeps.
    plan2, payload2, hit2 = autotune.get_or_tune(g, tag=1, layout="ell")
    assert hit2 and plan2 == plan1
    assert tunecache.TUNE_STATS["sweeps"] == 1

    # Fresh-process replay: drop the image, resolve from the FILE.
    tunecache.clear_memory()
    plan3, _, hit3 = autotune.get_or_tune(g, tag=1, layout="ell")
    assert hit3 and plan3 == plan1
    assert tunecache.TUNE_STATS["sweeps"] == 1

    # The dispatcher itself now resolves to the tuned plan.
    got = launch_plan.resolve(g, tag=1, layout="ell", nrhs=1)
    assert got.blocks == plan1.blocks and got.source == "tuned"

    # Tuned output stays numerically identical to the default plan's
    # (blocks change the launch grid, never the per-lane math).
    x = jnp.asarray(np.random.default_rng(0).normal(size=g.shape[1]),
                    jnp.float32)
    tuned = ops.planned_spmv(g, x, tag=1, layout="ell")
    ell = ops.ell_pack_gsecsr(g)
    default = ops.gse_spmv_ell(ell, g.table, x, g.ei_bit, tag=1,
                               blocks=(8, 128))
    np.testing.assert_allclose(np.asarray(tuned), np.asarray(default),
                               rtol=2e-6, atol=0)


def test_tune_cache_key_names_the_device(tmp_cache, monkeypatch):
    """A plan tuned on the CPU (interpreter) misses on any other device
    kind: the key leads with ``platform:device_kind``."""
    from repro.perf import autotune

    _, g = _operand(8)
    autotune.get_or_tune(g, tag=1, layout="ell", iters=1, warmup=1)
    assert launch_plan.device_token().startswith("cpu:")
    key = next(iter(json.loads(tmp_cache.read_text())["plans"]))
    assert key.startswith(launch_plan.device_token() + "|")
    assert launch_plan.resolve(g, tag=1, layout="ell").source == "tuned"

    monkeypatch.setattr(launch_plan, "device_token",
                        lambda: "tpu:TPU v5 lite")
    tunecache.clear_memory()
    got = launch_plan.resolve(g, tag=1, layout="ell")
    assert got is launch_plan.DEFAULT_PLAN


def test_tune_cache_corruption_detected(tmp_cache):
    from repro.perf import autotune

    _, g = _operand(8)
    autotune.get_or_tune(g, tag=1, layout="ell", iters=1, warmup=1)
    blob = json.loads(tmp_cache.read_text())
    key = next(iter(blob["plans"]))
    blob["plans"][key]["payload"]["us"] = -1.0  # flip payload, keep crc
    tmp_cache.write_text(json.dumps(blob))
    tunecache.clear_memory()
    assert tunecache.lookup(key) is None  # checksum mismatch -> miss
    assert tunecache.TUNE_STATS["corrupt"] == 1
    # get_or_tune recovers by re-sweeping and re-storing a clean entry.
    _, payload, hit = autotune.get_or_tune(g, tag=1, layout="ell",
                                           iters=1, warmup=1)
    assert not hit and payload["us"] > 0


def test_host_roofline_persisted(tmp_cache):
    from repro.perf import roofline as rl

    r1 = rl.host_roofline(quick=True)
    assert r1["probed"] and r1["stream_gbps"] > 0 and r1["peak_gflops"] > 0
    r2 = rl.host_roofline(quick=True)
    assert not r2["probed"]
    assert r2["stream_gbps"] == r1["stream_gbps"]
    att = rl.attainable_seconds(1e9, 1e9, r1)
    assert att > 0
    assert rl.fraction(1e9, 1e9, att, r1) == pytest.approx(1.0)
