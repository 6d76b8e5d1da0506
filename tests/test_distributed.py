"""Distributed row-sharded solver tests (DESIGN.md §13).

Host-side tests (partition round trip, byte model) run on any device
count.  The multi-device tests need 8 forced host CPU devices
(``XLA_FLAGS=--xla_force_host_platform_device_count=8`` -- the CI
distributed-smoke job sets it); under plain tier-1 (single device) they
skip and ``test_suite_under_forced_devices`` re-runs this module in ONE
subprocess with the flag set, so the contracts are exercised either way.
"""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.precision import MonitorParams
from repro.distributed.partition import partition_gsecsr, unshard
from repro.sparse import generators as G
from repro.sparse.csr import iteration_stream_bytes, pack_csr
from repro.sparse.spmv import spmm_gse, spmv, spmv_gse

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NEED = 8
multidevice = pytest.mark.skipif(
    jax.device_count() < NEED,
    reason=f"needs {NEED} devices (XLA_FLAGS=--xla_force_host_platform_"
           f"device_count={NEED}); covered by the subprocess re-run",
)

_PARAMS = MonitorParams(t=40, l=60, m=30, rsd_limit=0.5, reldec_limit=0.45)
# Aggressive stepping schedule: C2 fires at every check (reldec_limit
# above 1 is unreachable), so the tag walks 1 -> 2 -> 3 early and the
# parity tests cover every decode tag inside one trajectory.
_STEP_PARAMS = MonitorParams(t=8, l=10, m=5, rsd_limit=0.0,
                             reldec_limit=1.5, ndec_limit=0)


def _poisson(n=24):
    a = G.poisson2d(n)
    return a, pack_csr(a, k=8)


def _b_for(a, seed=0):
    rng = np.random.default_rng(seed)
    return jnp.asarray(np.asarray(spmv(a, jnp.asarray(
        rng.normal(size=a.shape[1])))))


# ---------------------------------------------------------------------------
# Host-side: partition round trip + byte model (no devices needed)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("shards", [1, 3, 4, 5, 8])
def test_partition_unshard_round_trip(shards):
    """Partitioning is a pure redistribution: reassembling the shard
    blocks recovers the original packed segments bit-for-bit -- including
    shard counts that do not divide n (trailing short block)."""
    a, g = _poisson(20)  # n = 400; 3 and 5 do not divide it evenly
    part = partition_gsecsr(g, shards)
    g2 = unshard(part, g)
    for f in ("colpak", "head", "tail1", "tail2"):
        assert np.array_equal(np.asarray(getattr(g, f)),
                              np.asarray(getattr(g2, f))), f
    assert part.nnz == g.nnz
    assert sum(part.rows_real) == g.shape[0]


@pytest.mark.parametrize("shards", [1, 2, 4, 8])
def test_shard_bytes_sum_to_single_device_stream(shards):
    """The acceptance identity: per-shard matrix bytes + the shared terms
    sum EXACTLY to the single-device iteration_stream_bytes at every tag
    (sharding redistributes the stream, it does not change it)."""
    a, g = _poisson()
    part = partition_gsecsr(g, shards)
    for tag in (1, 2, 3):
        assert (sum(part.shard_stream_bytes(tag))
                + part.shared_stream_bytes()
                == iteration_stream_bytes(g, tag)), (shards, tag)
        assert (part.iteration_stream_bytes(tag, "gse")
                == iteration_stream_bytes(g, tag)
                + part.halo_wire_bytes(tag, "gse"))


def test_halo_wire_byte_ladder_shrinks_with_tag():
    """The GSE segmentation applied to the wire: tag-1 halo payloads
    (u16 heads + tables) must cost < 50% of tag-3's (raw f64), with the
    full ladder monotone -- at 4 and 8 shards."""
    a, g = _poisson()
    for shards in (4, 8):
        part = partition_gsecsr(g, shards)
        w = {t: part.halo_wire_bytes(t, "gse") for t in (1, 2, 3)}
        assert w[1] < 0.5 * w[3], (shards, w)
        assert w[1] < w[2] < w[3], (shards, w)
        # exact wire charges f64 at every tag; tag-3 gse == exact.
        assert part.halo_wire_bytes(3, "gse") == part.halo_wire_bytes(
            3, "exact")
        # nrhs scales the whole per-column payload, tables included (the
        # batched solvers apply the operator column by column).
        assert part.halo_wire_bytes(3, "gse", nrhs=4) == 4 * w[3]
        assert part.halo_wire_bytes(1, "gse", nrhs=4) == 4 * w[1]


def test_one_shard_has_no_wire_traffic():
    a, g = _poisson(8)
    part = partition_gsecsr(g, 1)
    assert part.halo_entries == 0
    for t in (1, 2, 3):
        assert part.halo_wire_bytes(t, "gse") == 0


def test_block_diagonal_operator_has_no_wire_traffic():
    """A (block-)diagonal operator row-shards with ZERO remote columns:
    no exchange runs and the wire model charges nothing (no phantom
    padded-slot or table bytes)."""
    a = G.mass_diagonal(64)
    part = partition_gsecsr(pack_csr(a, k=8), 4)
    assert part.halo_entries == 0
    assert part.bnd_width == 0
    for t in (1, 2, 3):
        assert part.halo_wire_bytes(t, "gse") == 0


def test_partition_rejects_bad_shapes():
    a, g = _poisson(8)
    with pytest.raises(ValueError, match="n_shards"):
        partition_gsecsr(g, 0)


def test_sharded_pcg_rejects_f32_source_precond():
    """An f32-source diagonal pack (pack32: no tail2) supports tags 1/2
    only; the sharded PCG must refuse it up front exactly as the
    single-device decode does, instead of letting the tag-3 branch
    decode garbage."""
    from repro.core import gse
    from repro.solvers import solve_pcg
    from repro.solvers.precond import DiagGSEPrecond

    a, g = _poisson(8)
    bad = DiagGSEPrecond(packed=gse.pack32(np.ones(a.shape[0])),
                         kind="jacobi")
    part = partition_gsecsr(g, 1)
    with pytest.raises(ValueError, match="f32-source"):
        solve_pcg(part, jnp.ones(a.shape[0]), bad, tol=1e-6, maxiter=10,
                  params=_PARAMS)


# ---------------------------------------------------------------------------
# Multi-device: SpMV/SpMM parity, solver contracts
# ---------------------------------------------------------------------------

@multidevice
@pytest.mark.parametrize("shards", [1, 4, 8])
@pytest.mark.parametrize("tag", [1, 2, 3])
def test_dist_spmv_bitwise_equals_reference(shards, tag):
    from repro.kernels.dist_spmv import dist_spmm, dist_spmv

    a, g = _poisson()
    part = partition_gsecsr(g, shards)
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=a.shape[1]))
    ref = spmv_gse(g, x, tag=tag)
    y = dist_spmv(part, x, tag=tag, wire="exact")
    assert np.array_equal(np.asarray(ref), np.asarray(y))
    xb = jnp.asarray(rng.normal(size=(a.shape[1], 3)))
    refm = spmm_gse(g, xb, tag=tag)
    ym = dist_spmm(part, xb, tag=tag, wire="exact")
    assert np.array_equal(np.asarray(refm), np.asarray(ym))
    if tag == 3:  # full-precision halos ride raw IEEE bits: still exact
        assert np.array_equal(
            np.asarray(ref), np.asarray(dist_spmv(part, x, tag=3,
                                                  wire="gse")))


@multidevice
@pytest.mark.parametrize("shards", [3, 5])
@pytest.mark.parametrize("tag", [1, 3])
def test_dist_spmv_ragged_rows_bitwise(shards, tag):
    """Rows of 0..7 entries over shard counts that leave padded rows and
    padding entries: each shard stores its rows slot-major, the padding
    adding nothing, and the sharded SpMV/SpMM stays bitwise the
    single-device one."""
    from repro.kernels.dist_spmv import dist_spmm, dist_spmv
    from repro.sparse.csr import from_coo

    rng = np.random.default_rng(shards)
    n = 203
    lens = rng.integers(0, 8, n)
    rows = np.repeat(np.arange(n), lens)
    cols = np.concatenate([rng.choice(n, k, replace=False) for k in lens])
    a = from_coo(rows, cols, rng.standard_normal(rows.size), (n, n))
    g = pack_csr(a, k=8)
    part = partition_gsecsr(g, shards)
    assert part.colpak.ndim == 3 and part.n_padded > n
    assert len(set(part.nnz_per_shard)) > 1
    x = jnp.asarray(rng.normal(size=n))
    assert np.array_equal(np.asarray(spmv_gse(g, x, tag=tag)),
                          np.asarray(dist_spmv(part, x, tag=tag)))
    xb = jnp.asarray(rng.normal(size=(n, 3)))
    assert np.array_equal(np.asarray(spmm_gse(g, xb, tag=tag)),
                          np.asarray(dist_spmm(part, xb, tag=tag)))


@multidevice
def test_gse_wire_low_tags_close_but_lossy():
    """Tag-1/2 compressed halos perturb ONLY boundary contributions: the
    SpMV error stays at the wire format's mantissa scale."""
    from repro.kernels.dist_spmv import dist_spmv

    a, g = _poisson()
    part = partition_gsecsr(g, 4)
    x = jnp.asarray(np.random.default_rng(2).normal(size=a.shape[1]))
    for tag, bound in ((1, 1e-3), (2, 1e-7)):
        ref = spmv_gse(g, x, tag=tag)
        y = dist_spmv(part, x, tag=tag, wire="gse")
        rel = float(jnp.linalg.norm(y - ref) / jnp.linalg.norm(ref))
        assert 0 < rel < bound, (tag, rel)


@multidevice
def test_gse_wire_pack_ignores_padded_boundary_slots():
    """Regression: boundary buffers are padded to the max per-shard width
    B, and padded slots used to replicate x_sh[0] into the wire pack's
    shared-exponent table.  A shard with ONE real boundary entry but a
    huge unrelated x_sh[0] (1e300 -> inf in the f32 wire cast) would then
    quantize its real boundary entry to garbage.  Padded slots must be
    masked to zero (excluded from the exponent histogram) so x values
    that never cross the wire cannot perturb entries that do."""
    from repro.kernels.dist_spmv import dist_spmv
    from repro.sparse.csr import from_coo

    n, s = 32, 4  # R = 8: shard 0 owns rows/cols 0..7, shard 1 8..15
    rows = list(range(n)) + list(range(8, 16)) + [0]
    cols = list(range(n)) + list(range(0, 8)) + [9]
    vals = [1.0] * len(rows)
    a = from_coo(rows, cols, vals, (n, n))
    g = pack_csr(a, k=8)
    part = partition_gsecsr(g, s)
    # Shard 0 sends 8 boundary entries -> B = 8; shard 1 sends only
    # col 9, so its buffer carries 7 padded slots.
    assert part.bnd_width == 8
    assert part.bnd_counts[1] == 1
    x = np.ones(n)
    x[9] = 1.5     # the one real boundary entry shard 1 ships
    x[8] = 1e300   # shard 1's local slot 0: NOT a boundary entry
    y = dist_spmv(part, jnp.asarray(x), tag=1, wire="gse")
    # Row 0 = x[0] + x[9]: x[9] crosses the wire at tag 1 (lossy but
    # small); a leaked 1e300 pad would zero it out entirely.
    assert abs(float(y[0]) - 2.5) < 0.01


@multidevice
def test_solve_cg_one_shard_bit_identical():
    from repro.solvers import solve_cg

    a, g = _poisson()
    b = _b_for(a)
    kw = dict(tol=1e-8, maxiter=2000, params=_PARAMS)
    ref = solve_cg(g, b, **kw)
    res = solve_cg(partition_gsecsr(g, 1), b, **kw)
    assert np.array_equal(np.asarray(ref.x), np.asarray(res.x))
    assert int(ref.iters) == int(res.iters)
    assert float(ref.relres) == float(res.relres)
    assert np.array_equal(np.asarray(ref.switch_iters),
                          np.asarray(res.switch_iters))


@multidevice
@pytest.mark.parametrize("shards", [4, 8])
@pytest.mark.parametrize("params", [_PARAMS, _STEP_PARAMS],
                         ids=["tag1", "stepped123"])
def test_solve_cg_kshard_trajectory_parity(shards, params):
    """Exact-wire k-shard runs converge to the same relres with the
    trajectory within 1e-10 of single-device -- the only arithmetic
    difference is the psum dot summation order.  The stepped variant
    forces the tag through 1 -> 2 -> 3, covering every decode tag."""
    from repro.solvers import solve_cg

    a, g = _poisson()
    b = _b_for(a)
    kw = dict(tol=1e-8, maxiter=2000, params=params)
    ref = solve_cg(g, b, **kw)
    res = solve_cg(partition_gsecsr(g, shards), b, **kw)
    assert bool(res.converged) and bool(ref.converged)
    assert int(res.iters) == int(ref.iters)
    assert np.array_equal(np.asarray(ref.switch_iters),
                          np.asarray(res.switch_iters))
    scale = float(jnp.max(jnp.abs(ref.x)))
    assert float(jnp.max(jnp.abs(res.x - ref.x))) < 1e-10 * scale
    assert abs(float(res.relres) - float(ref.relres)) < 1e-10


@multidevice
@pytest.mark.parametrize("kind", ["cg", "pcg"])
def test_sharded_windows_follow_the_gathers_trajectory(kind):
    """Four shards of a 3-D Laplacian read their windows through one
    offset a slot; the sharded CG and Jacobi-PCG take the iterations,
    residuals (the flight ring) and iterate of the same shards read by
    ``x[col]``, the tags stepping 1 -> 2 -> 3 on the way."""
    import dataclasses

    from repro.obs import flight as OF
    from repro.solvers import make_jacobi, solve_cg, solve_pcg

    a = G.poisson3d(12)
    part = partition_gsecsr(pack_csr(a, k=8), 4)
    assert part.slot_map is not None
    bare = dataclasses.replace(part, slot_map=None)
    b = _b_for(a)
    kw = dict(tol=1e-10, maxiter=2000, params=_STEP_PARAMS,
              flight=OF.FlightParams(capacity=512), final_correction=True)
    if kind == "cg":
        runs = [solve_cg(p, b, **kw) for p in (part, bare)]
    else:
        m = make_jacobi(a, k=8)
        runs = [solve_pcg(p, b, m, **kw) for p in (part, bare)]
    assert bool(runs[0].converged)
    assert int(runs[0].iters) == int(runs[1].iters)
    assert np.array_equal(np.asarray(runs[0].x), np.asarray(runs[1].x))
    for f0, f1 in zip(jax.tree.leaves(runs[0].flight),
                      jax.tree.leaves(runs[1].flight)):
        assert np.array_equal(np.asarray(f0), np.asarray(f1))


@multidevice
@pytest.mark.parametrize("shards", [4, 8])
def test_solve_cg_gse_wire_converges(shards):
    """The tag-aware compressed halo is lossy at tags 1/2, but the
    recursive residual still reaches tolerance -- the monitor simply sees
    a slightly stronger low-tag perturbation (paper semantics)."""
    from repro.solvers import solve_cg

    a, g = _poisson()
    b = _b_for(a)
    res = solve_cg(partition_gsecsr(g, shards), b, tol=1e-8, maxiter=2000,
                   params=_PARAMS, wire="gse")
    assert bool(res.converged)
    assert float(res.relres) <= 1e-8


@multidevice
def test_solve_cg_sharded_final_correction_certifies_true_residual():
    """With the lossy gse wire the recursive residual can converge against
    the perturbed operator while the TRUE tag-3 residual sits above tol;
    final_correction must certify (and if needed re-achieve) the true
    residual through the sharded resume path."""
    from repro.kernels.dist_spmv import dist_spmv
    from repro.solvers import solve_cg

    a, g = _poisson()
    b = _b_for(a)
    part = partition_gsecsr(g, 4)
    res = solve_cg(part, b, tol=1e-8, maxiter=4000, params=_PARAMS,
                   wire="gse", final_correction=True)
    assert bool(res.converged)
    true_rel = float(
        jnp.linalg.norm(b - dist_spmv(part, res.x, tag=3, wire="exact"))
        / jnp.linalg.norm(b)
    )
    assert true_rel <= 1e-8


@multidevice
def test_solve_pcg_sharded_parity():
    from repro.solvers import make_jacobi, solve_pcg

    a, g = _poisson()
    m = make_jacobi(a, k=8)
    b = _b_for(a)
    kw = dict(tol=1e-8, maxiter=2000, params=_PARAMS)
    ref = solve_pcg(g, b, m, **kw)
    r1 = solve_pcg(partition_gsecsr(g, 1), b, m, **kw)
    assert np.array_equal(np.asarray(ref.x), np.asarray(r1.x))
    r4 = solve_pcg(partition_gsecsr(g, 4), b, m, **kw)
    assert bool(r4.converged)
    assert int(r4.iters) == int(ref.iters)
    scale = float(jnp.max(jnp.abs(ref.x)))
    assert float(jnp.max(jnp.abs(r4.x - ref.x))) < 1e-10 * scale


@multidevice
@pytest.mark.parametrize("nrhs", [1, 3])
def test_solve_cg_batched_sharded_parity(nrhs):
    """Batched solves ride the distributed operator through the generic
    per-column body: column trajectories match the single-device batched
    solve across every active column."""
    from repro.solvers import solve_cg_batched

    a, g = _poisson(16)
    cols = [_b_for(a, seed=j) for j in range(nrhs)]
    b = jnp.stack(cols, axis=1)
    kw = dict(tol=1e-8, maxiter=2000, params=_PARAMS)
    ref = solve_cg_batched(g, b, **kw)
    res = solve_cg_batched(partition_gsecsr(g, 4), b, **kw)
    assert np.asarray(res.converged).all()
    assert np.array_equal(np.asarray(ref.iters), np.asarray(res.iters))
    scale = float(jnp.max(jnp.abs(ref.x)))
    assert float(jnp.max(jnp.abs(res.x - ref.x))) < 1e-10 * scale


@multidevice
def test_gmres_over_sharded_operator_parity():
    """make_sharded_operator is a drop-in operator callable: exact-wire
    applications match make_gse_operator (standalone calls are bitwise equal;
    inlined into GMRES's larger jitted program the scatter-add
    accumulation order may differ in the last ulp across compilations),
    so GMRES trajectories track the single-device run to ~machine
    precision with identical iteration counts."""
    from repro.kernels.dist_spmv import make_sharded_operator
    from repro.solvers import make_gse_operator, solve_gmres

    a = G.convection_diffusion_2d(12)
    g = pack_csr(a, k=8)
    b = _b_for(a)
    kw = dict(tol=1e-8, restart=30, maxiter=600, params=_PARAMS)
    ref = solve_gmres(make_gse_operator(g), b, **kw)
    res = solve_gmres(make_sharded_operator(partition_gsecsr(g, 4)), b, **kw)
    assert bool(res.converged)
    assert int(ref.iters) == int(res.iters)
    scale = float(jnp.max(jnp.abs(ref.x)))
    assert float(jnp.max(jnp.abs(res.x - ref.x))) < 1e-10 * scale


@multidevice
def test_solver_service_sharded_handle():
    from repro.launch.solver_serve import SolverService

    a, g_unused = _poisson(16)
    params = MonitorParams(t=40, l=60, m=30, rsd_limit=0.5,
                           reldec_limit=0.45)
    svc = SolverService(slots=3, params=params, maxiter=4000)
    svc.register("p", a, k=8, sharded=True, shards=4, wire="gse")
    ids = [svc.submit("p", _b_for(a, seed=j), tol=1e-8) for j in range(3)]
    reports = svc.flush()
    for rid in ids:
        r = reports[rid]
        assert r.converged and r.relres <= 1e-8
        assert r.est_bytes > 0
    # Sharded handles charge halo wire traffic on top of the matrix
    # stream: the modeled bytes exceed an unsharded handle's.
    svc2 = SolverService(slots=3, params=params, maxiter=4000)
    svc2.register("p", a, k=8)
    for j in range(3):
        svc2.submit("p", _b_for(a, seed=j), tol=1e-8)
    svc2.flush()
    assert svc.stats["modeled_bytes"] > svc2.stats["modeled_bytes"]


@multidevice
def test_solve_ir_over_sharded_operand():
    """Stepped iterative refinement rides the distributed operator: the
    outer tag-3 residual reads and the inner stepped CG all go through
    the sharded apply, matching the single-device refinement exactly."""
    from repro.solvers import solve_ir

    a, g = _poisson(16)
    b = _b_for(a)
    kw = dict(tol=1e-10, inner_tol=1e-4, inner_maxiter=1500, params=_PARAMS)
    ref = solve_ir(g, b, **kw)
    res = solve_ir(partition_gsecsr(g, 4), b, **kw)
    assert res.converged
    assert res.outer_iters == ref.outer_iters
    scale = float(jnp.max(jnp.abs(ref.x)))
    assert float(jnp.max(jnp.abs(res.x - ref.x))) < 1e-9 * scale


@multidevice
def test_dist_spmv_rejects_too_many_shards():
    from repro.kernels.dist_spmv import dist_spmv

    a, g = _poisson(8)
    part = partition_gsecsr(g, jax.device_count() + 1)
    with pytest.raises(ValueError, match="devices"):
        dist_spmv(part, jnp.zeros(a.shape[1]), tag=1)


# ---------------------------------------------------------------------------
# Single-device fallback: run the whole module under forced devices once
# ---------------------------------------------------------------------------

def test_suite_under_forced_devices():
    """Under plain tier-1 (single real CPU device) the multi-device tests
    above skip; this wrapper re-runs the module in ONE subprocess with
    8 forced host devices so the distributed contracts are always
    exercised.  No-op when the devices are already present (CI job)."""
    if jax.device_count() >= NEED:
        pytest.skip("already running with forced devices")
    env = dict(os.environ,
               PYTHONPATH=os.path.join(REPO, "src"),
               XLA_FLAGS=f"--xla_force_host_platform_device_count={NEED}")
    r = subprocess.run(
        [sys.executable, "-m", "pytest", "-x", "-q",
         os.path.join(REPO, "tests", "test_distributed.py")],
        env=env, capture_output=True, text=True, timeout=1500,
    )
    assert r.returncode == 0, (
        f"forced-device re-run failed:\n{r.stdout[-4000:]}\n{r.stderr[-2000:]}"
    )
