"""Tests for the resilient async solve service (DESIGN.md §17).

Acceptance criteria covered:

  * chunked execution is BIT-IDENTICAL to unchunked for every solver
    family -- cg/pcg (fused and generic), batched, and IR -- across tag
    switches (chunk boundaries are pure extra exit conditions, never
    arithmetic);
  * a column joining a running batched solve at a chunk boundary is
    bit-identical to a solo solve, and the columns already in flight are
    unperturbed (continuous batching);
  * checkpoints round-trip solver state exactly; a CORRUPT checkpoint is
    detected (pytree CRC32) and the solve falls back to the previous
    good one, reproducing the exact trajectory;
  * the per-handle circuit breaker walks closed -> open -> half-open ->
    closed/open with seeded-jitter backoff;
  * a lapsed deadline returns the last checkpoint FLAGGED (never a
    silent drop), and admission control sheds with typed responses.
"""
import os

import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import ckpt as CK
from repro.core import precision as P
from repro.robustness.faults import make_tag_fault_operator
from repro.robustness.guards import DEFAULT_GUARDS
from repro.serve import (
    Accepted,
    AsyncSolveService,
    BatchedChunks,
    BreakerParams,
    CircuitBreaker,
    IRChunks,
    Shed,
    SolveChunks,
)
from repro.solvers.ir import solve_ir
from repro.sparse import generators as G
from repro.sparse.csr import pack_csr
from repro.sparse.spmv import spmv
from repro.solvers import make_gse_operator, make_jacobi, solve_cg, solve_pcg


def _params():
    return P.MonitorParams(t=30, l=30, m=15, rsd_limit=0.5, reldec_limit=0.45)


def _rhs(a, seed):
    rng = np.random.default_rng(seed)
    return spmv(a, jnp.asarray(rng.normal(size=a.shape[1])))


class _Clock:
    """Injectable fake clock: deadline/breaker tests advance time
    explicitly instead of sleeping."""

    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


def _run_chunked(driver, k, budget=500):
    for _ in range(budget):
        driver.run_chunk(k)
        if driver.done:
            break
    assert driver.done
    return driver


# ---------------------------------------------------------------------------
# Chunked == unchunked, bit for bit, per solver family
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("k", [1, 7, 64])
def test_chunked_cg_fused_bit_identical(k):
    a = G.poisson2d(12)
    g = pack_csr(a, k=8)
    b = _rhs(a, 0)
    ref = solve_cg(g, b, tol=1e-10, maxiter=2000, params=_params(),
                   guards=DEFAULT_GUARDS)
    drv = _run_chunked(SolveChunks(g, b, tol=1e-10, maxiter=2000,
                                   params=_params(), guards=DEFAULT_GUARDS),
                       k)
    res = drv.res
    np.testing.assert_array_equal(np.asarray(res.x), np.asarray(ref.x))
    assert int(res.iters) == int(ref.iters)
    assert float(res.relres) == float(ref.relres)
    np.testing.assert_array_equal(np.asarray(res.switch_iters),
                                  np.asarray(ref.switch_iters))


def test_chunked_cg_across_tags_bit_identical():
    # SPD with eigenvalues down to 1e-6: tag-1 CG genuinely stalls, so
    # the monitor MUST step tags mid-solve -- chunk boundaries straddle
    # tag switches and the resumed run must replay the same schedule.
    rng = np.random.default_rng(7)
    n = 200
    q, _ = np.linalg.qr(rng.normal(size=(n, n)))
    eigs = np.logspace(-6, 0, n)
    dense = (q * eigs) @ q.T
    dense = 0.5 * (dense + dense.T)
    rows, cols = np.nonzero(np.ones((n, n)))
    from repro.sparse.csr import from_coo

    a = from_coo(rows, cols, dense[rows, cols], (n, n))
    g = pack_csr(a, k=8)
    b = jnp.asarray(dense @ rng.normal(size=n))
    op = make_gse_operator(g)
    params = P.MonitorParams(t=60, l=60, m=30,
                             rsd_limit=0.5, reldec_limit=0.45)
    ref = solve_cg(op, b, tol=1e-8, maxiter=20000, params=params,
                   guards=DEFAULT_GUARDS)
    assert int(np.asarray(ref.switch_iters)[0]) > 0  # really switched
    drv = _run_chunked(SolveChunks(op, b, tol=1e-8, maxiter=20000,
                                   params=params, guards=DEFAULT_GUARDS),
                       k=97, budget=2000)
    res = drv.res
    np.testing.assert_array_equal(np.asarray(res.x), np.asarray(ref.x))
    assert int(res.iters) == int(ref.iters)
    np.testing.assert_array_equal(np.asarray(res.switch_iters),
                                  np.asarray(ref.switch_iters))


def test_chunked_cg_generic_bit_identical():
    a = G.poisson2d(12)
    g = pack_csr(a, k=8)
    op = make_gse_operator(g)
    b = _rhs(a, 1)
    ref = solve_cg(op, b, tol=1e-8, maxiter=2000, params=_params(),
                   guards=DEFAULT_GUARDS)
    drv = _run_chunked(SolveChunks(op, b, tol=1e-8, maxiter=2000,
                                   params=_params(), guards=DEFAULT_GUARDS),
                       5)
    np.testing.assert_array_equal(np.asarray(drv.res.x), np.asarray(ref.x))
    assert int(drv.res.iters) == int(ref.iters)


def test_chunked_pcg_bit_identical():
    a = G.poisson2d(12)
    g = pack_csr(a, k=8)
    m = make_jacobi(a, k=8)
    b = _rhs(a, 2)
    ref = solve_pcg(g, b, m, tol=1e-10, maxiter=2000, params=_params(),
                    guards=DEFAULT_GUARDS)
    drv = _run_chunked(SolveChunks(g, b, tol=1e-10, maxiter=2000,
                                   params=_params(), guards=DEFAULT_GUARDS,
                                   precond=m),
                       9)
    np.testing.assert_array_equal(np.asarray(drv.res.x), np.asarray(ref.x))
    assert int(drv.res.iters) == int(ref.iters)
    np.testing.assert_array_equal(np.asarray(drv.res.switch_iters),
                                  np.asarray(ref.switch_iters))


def test_chunked_batched_bit_identical():
    from repro.solvers import solve_cg_batched

    a = G.poisson2d(12)
    g = pack_csr(a, k=8)
    b = jnp.stack([_rhs(a, s) for s in range(3)], axis=1)
    ref = solve_cg_batched(g, b, tol=1e-8, maxiter=2000, params=_params(),
                           guards=DEFAULT_GUARDS)
    drv = _run_chunked(BatchedChunks(g, b, tol=1e-8, maxiter=2000,
                                     params=_params(),
                                     guards=DEFAULT_GUARDS),
                       6)
    np.testing.assert_array_equal(np.asarray(drv.res.x), np.asarray(ref.x))
    np.testing.assert_array_equal(np.asarray(drv.res.iters),
                                  np.asarray(ref.iters))
    np.testing.assert_array_equal(np.asarray(drv.res.switch_iters),
                                  np.asarray(ref.switch_iters))


def test_chunked_ir_bit_identical():
    a = G.poisson2d(10)
    g = pack_csr(a, k=8)
    b = _rhs(a, 3)
    ref = solve_ir(g, b, tol=1e-11, max_outer=8, inner_tol=1e-4,
                   params=_params(), guards=DEFAULT_GUARDS)
    drv = IRChunks(g, b, tol=1e-11, max_outer=8, inner_tol=1e-4,
                   params=_params(), guards=DEFAULT_GUARDS)
    while not drv.done:
        drv.run_chunk(1)  # one outer correction per chunk
    res = drv.result()
    np.testing.assert_array_equal(np.asarray(res.x), np.asarray(ref.x))
    assert res.outer_iters == ref.outer_iters
    assert res.inner_iters == ref.inner_iters
    assert res.relres == ref.relres
    np.testing.assert_array_equal(res.history, ref.history)


# ---------------------------------------------------------------------------
# Continuous batching: join at a chunk boundary
# ---------------------------------------------------------------------------

def test_join_at_boundary_column_parity():
    """A column joined mid-run matches a solo solve bitwise, and the
    original column's trajectory is untouched by the join."""
    a = G.poisson2d(12)
    g = pack_csr(a, k=8)
    b0, b1 = _rhs(a, 0), _rhs(a, 1)
    solo0 = solve_cg(g, b0, tol=1e-8, maxiter=2000, params=_params(),
                     guards=DEFAULT_GUARDS)
    solo1 = solve_cg(g, b1, tol=1e-8, maxiter=2000, params=_params(),
                     guards=DEFAULT_GUARDS)

    drv = BatchedChunks(g, b0[:, None], tol=1e-8, maxiter=2000,
                        params=_params(), guards=DEFAULT_GUARDS)
    drv.run_chunk(10)
    drv.run_chunk(10)
    j = drv.join(b1)  # joins 20 iterations into column 0's run
    assert j == 1
    _run_chunked(drv, 10)
    s0, s1 = drv.col_snapshot(0), drv.col_snapshot(1)
    np.testing.assert_array_equal(np.asarray(s0["x"]), np.asarray(solo0.x))
    assert s0["iters"] == int(solo0.iters)
    np.testing.assert_array_equal(np.asarray(s1["x"]), np.asarray(solo1.x))
    assert s1["iters"] == int(solo1.iters)
    np.testing.assert_array_equal(s1["switch_iters"],
                                  np.asarray(solo1.switch_iters))


# ---------------------------------------------------------------------------
# Checkpoint/resume: CRC round-trip, corrupt fallback
# ---------------------------------------------------------------------------

def test_ckpt_roundtrip_resume_bit_identical(tmp_path):
    a = G.poisson2d(12)
    g = pack_csr(a, k=8)
    b = _rhs(a, 4)
    ref = solve_cg(g, b, tol=1e-8, maxiter=2000, params=_params(),
                   guards=DEFAULT_GUARDS)

    path = str(tmp_path / "ck")
    drv = SolveChunks(g, b, tol=1e-8, maxiter=2000, params=_params(),
                      guards=DEFAULT_GUARDS)
    drv.run_chunk(8)
    drv.save_state(path)
    drv.run_chunk(8)
    drv.save_state(path)

    # A fresh driver resumes from the newest checkpoint and finishes with
    # the exact unchunked trajectory.
    drv2 = SolveChunks(g, b, tol=1e-8, maxiter=2000, params=_params(),
                       guards=DEFAULT_GUARDS)
    skipped = drv2.restore_state(path)
    assert skipped == [] and drv2.chunks == 2
    _run_chunked(drv2, 8)
    np.testing.assert_array_equal(np.asarray(drv2.res.x), np.asarray(ref.x))
    assert int(drv2.res.iters) == int(ref.iters)


def test_loop_state_keys_and_cg_state_roundtrip(tmp_path):
    """The one loop keeps each kind's state keys, which chunk checkpoints
    restore into, and a CG chunk state round-trips through
    ``save_state``/``restore_state`` leaf for leaf."""
    import jax

    a = G.poisson2d(12)
    g = pack_csr(a, k=8)
    b = _rhs(a, 4)
    common = {"x", "r", "p", "it", "mon", "switches"}
    want = {"cg": common | {"rs"}, "pcg": common | {"rz", "rr"}}
    for kind, precond in (("cg", None), ("pcg", make_jacobi(a, k=8))):
        for guards, extra in ((None, set()),
                              (DEFAULT_GUARDS, {"g", "ckpt"})):
            drv = SolveChunks(g, b, tol=1e-8, maxiter=2000,
                              params=_params(), guards=guards,
                              precond=precond)
            assert set(drv.init_state()) == want[kind] | extra, kind

    path = str(tmp_path / "ck")
    drv = SolveChunks(g, b, tol=1e-8, maxiter=2000, params=_params(),
                      guards=DEFAULT_GUARDS)
    drv.run_chunk(5)
    drv.save_state(path)
    drv2 = SolveChunks(g, b, tol=1e-8, maxiter=2000, params=_params(),
                       guards=DEFAULT_GUARDS)
    assert drv2.restore_state(path) == []
    got, want_leaves = (jax.tree_util.tree_flatten(d._state)
                        for d in (drv2, drv))
    assert got[1] == want_leaves[1]
    for x, y in zip(got[0], want_leaves[0]):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    assert drv2.iters == 5


def test_ckpt_corrupt_falls_back_to_previous_good(tmp_path):
    a = G.poisson2d(12)
    g = pack_csr(a, k=8)
    b = _rhs(a, 5)
    ref = solve_cg(g, b, tol=1e-8, maxiter=2000, params=_params(),
                   guards=DEFAULT_GUARDS)

    path = str(tmp_path / "ck")
    drv = SolveChunks(g, b, tol=1e-8, maxiter=2000, params=_params(),
                      guards=DEFAULT_GUARDS)
    drv.run_chunk(8)
    drv.save_state(path)
    drv.run_chunk(8)
    drv.save_state(path)

    # Corrupt the NEWEST checkpoint's blob on disk.
    blob = os.path.join(path, "step_00000002", "ckpt.msgpack.zst")
    data = bytearray(open(blob, "rb").read())
    data[len(data) // 2] ^= 0xFF
    open(blob, "wb").write(bytes(data))

    drv2 = SolveChunks(g, b, tol=1e-8, maxiter=2000, params=_params(),
                       guards=DEFAULT_GUARDS)
    skipped = drv2.restore_state(path)
    assert skipped == [2] and drv2.chunks == 1  # previous good step
    _run_chunked(drv2, 8)
    # The lost chunk re-ran; the trajectory is still exact.
    np.testing.assert_array_equal(np.asarray(drv2.res.x), np.asarray(ref.x))
    assert int(drv2.res.iters) == int(ref.iters)


def test_ckpt_tree_crc_detects_content_tamper(tmp_path):
    """The satellite bugfix: a checkpoint whose DECODED contents drift
    from the stamped pytree CRC raises CheckpointCorrupt (the old code
    only hashed the compressed blob)."""
    import json

    tree = {"x": np.arange(8, dtype=np.float64), "it": np.int32(3)}
    path = str(tmp_path / "ck")
    CK.save(path, tree, step=1)
    meta_p = os.path.join(path, "step_00000001", "meta.json")
    meta = json.load(open(meta_p))
    assert "tree_crc32" in meta
    meta["tree_crc32"] ^= 1
    json.dump(meta, open(meta_p, "w"))
    with pytest.raises(CK.CheckpointCorrupt):
        CK.restore(path, 1, tree)
    assert CK.restore_latest_valid(path, tree) is None


# ---------------------------------------------------------------------------
# Circuit breaker state machine
# ---------------------------------------------------------------------------

def test_breaker_open_half_open_close():
    clk = _Clock()
    br = CircuitBreaker(BreakerParams(fail_threshold=3, backoff_s=1.0,
                                      backoff_mult=2.0, jitter=0.0),
                        clock=clk)
    assert br.state == "closed" and br.allow()
    br.record_failure()
    br.record_failure()
    assert br.state == "closed"
    br.record_failure()  # third consecutive failure -> open
    assert br.state == "open"
    assert not br.allow()
    assert br.retry_after() == pytest.approx(1.0)

    clk.t = 1.5  # backoff elapsed -> half-open, ONE probe
    assert br.allow()
    assert br.state == "half_open"
    assert not br.allow()  # second concurrent probe refused
    br.record_failure()    # probe failed -> re-open, backoff doubled
    assert br.state == "open"
    assert br.retry_after() == pytest.approx(2.0)

    clk.t = 4.0
    assert br.allow()
    br.record_success()    # probe healthy -> closed, backoff reset
    assert br.state == "closed"
    assert br.backoff == pytest.approx(1.0)


def test_breaker_jitter_is_seeded():
    clk = _Clock()
    waits = []
    for _ in range(2):
        br = CircuitBreaker(BreakerParams(fail_threshold=1, backoff_s=1.0,
                                          jitter=0.25),
                            clock=clk, seed=7)
        br.record_failure()
        waits.append(br.retry_after())
    assert waits[0] == waits[1]           # deterministic under one seed
    assert 0.75 <= waits[0] <= 1.25       # within the jitter band
    br2 = CircuitBreaker(BreakerParams(fail_threshold=1, backoff_s=1.0,
                                       jitter=0.25), clock=clk, seed=8)
    br2.record_failure()
    assert br2.retry_after() != waits[0]  # seeds decorrelate


# ---------------------------------------------------------------------------
# Service: sheds, breaker trips, deadlines, warm starts
# ---------------------------------------------------------------------------

def test_shed_queue_full():
    a = G.poisson2d(8)
    svc = AsyncSolveService(slots=2, params=_params(), queue_limit=2,
                            chunk_iters=16)
    svc.register("p", a, k=8)
    r1 = svc.submit("p", _rhs(a, 0))
    r2 = svc.submit("p", _rhs(a, 1))
    r3 = svc.submit("p", _rhs(a, 2))
    assert isinstance(r1, Accepted) and isinstance(r2, Accepted)
    assert isinstance(r3, Shed) and r3.reason == "queue_full"
    assert svc.sheds["queue_full"] == 1
    reports = svc.run_until_idle()
    assert set(reports) == {r1.id, r2.id}


def test_breaker_trips_then_sheds_then_recovers():
    """Repeated guard-tripped failures open the handle's breaker; while
    open, submissions shed with reason breaker_open and a retry hint;
    after backoff a probe closes it again."""
    a = G.poisson2d(8)
    g = pack_csr(a, k=8)
    clk = _Clock()
    svc = AsyncSolveService(
        slots=2, params=_params(), chunk_iters=32, queue_limit=8,
        max_retries=0, clock=clk,
        breaker=BreakerParams(fail_threshold=2, backoff_s=1.0, jitter=0.0))
    # Every tag fails (fail_tag=3): each request guard-trips.
    svc.register("bad", a, k=8,
                 operator=make_tag_fault_operator(g, mode="nan", fail_tag=3))

    for s in range(2):
        resp = svc.submit("bad", _rhs(a, s))
        assert isinstance(resp, Accepted)
        reports = svc.run_until_idle()
        assert not reports[resp.id].converged
        assert reports[resp.id].health != "ok"
    assert svc._breaker("bad").state == "open"

    shed = svc.submit("bad", _rhs(a, 9))
    assert isinstance(shed, Shed) and shed.reason == "breaker_open"
    assert shed.retry_after_s > 0
    assert svc.sheds["breaker_open"] == 1

    # After the backoff, one probe is admitted (half-open) -- and the
    # operand is still faulty, so it re-opens.
    clk.t = 1.5
    probe = svc.submit("bad", _rhs(a, 10))
    assert isinstance(probe, Accepted)
    svc.run_until_idle()
    assert svc._breaker("bad").state == "open"


def test_deadline_expiry_returns_flagged_checkpoint():
    """A request whose deadline lapses mid-solve comes back at the next
    chunk boundary with its current iterate, flagged -- never dropped."""
    a = G.poisson2d(16)
    clk = _Clock()

    def stall(svc, key, group):  # chaos: every chunk takes 1 s
        clk.t += 1.0

    svc = AsyncSolveService(slots=2, params=_params(), chunk_iters=4,
                            maxiter=20000, clock=clk, chunk_hook=stall)
    svc.register("p", a, k=8)
    resp = svc.submit("p", _rhs(a, 0), tol=1e-12, deadline_s=0.5)
    assert isinstance(resp, Accepted)
    reports = svc.run_until_idle()
    rep = reports[resp.id]
    assert rep.deadline_exceeded
    assert not rep.converged
    assert rep.health == "deadline"
    assert rep.iters > 0                      # it DID make progress
    x = svc.solution(resp.id)                 # last checkpoint, available
    assert bool(jnp.isfinite(jnp.vdot(x, x)))


def test_warm_start_lru_hits():
    a = G.poisson2d(12)
    svc = AsyncSolveService(slots=2, params=_params(), chunk_iters=32,
                            warm_capacity=4)
    svc.register("p", a, k=8)
    b = _rhs(a, 0)
    r1 = svc.submit("p", b, tol=1e-8)
    svc.run_until_idle()
    assert svc.warm["store"] == 1
    r2 = svc.submit("p", b, tol=1e-8)
    reports = svc.run_until_idle()
    assert svc.warm["hit"] == 1
    # Seeded with the converged solution, the repeat solve is instant.
    assert reports[r2.id].iters == 0
    assert reports[r2.id].converged


def test_pack_corruption_detected_and_repacked():
    """A pack whose bytes rot after registration is caught by the CRC
    verify before the next dispatch and repacked from the CSR."""
    from repro.robustness.faults import corrupt_gsecsr

    a = G.poisson2d(8)
    svc = AsyncSolveService(slots=2, params=_params(), chunk_iters=32)
    svc.register("p", a, k=8)
    svc._ops["p"].gse = corrupt_gsecsr(svc._ops["p"].gse, "table", seed=3)
    resp = svc.submit("p", _rhs(a, 0), tol=1e-8)
    reports = svc.run_until_idle()
    assert svc.pack_faults["detected"] == 1
    assert svc.pack_faults["repacked"] == 1
    assert reports[resp.id].converged  # served off the repacked operand


def test_dwell_class_buckets_requests():
    """Deadline classes map to distinct monitor dwells (and distinct
    groups), so one batch shares one static MonitorParams."""
    from repro.serve.service import _dwell_params

    p = _params()
    cls_t, pt = _dwell_params(p, 0.05, 0.2, 5.0)
    cls_n, pn = _dwell_params(p, 1.0, 0.2, 5.0)
    cls_l, pl = _dwell_params(p, 30.0, 0.2, 5.0)
    assert (cls_t, cls_n, cls_l) == ("tight", "normal", "loose")
    assert pt.t < pn.t < pl.t
    assert pn == p
    assert _dwell_params(p, None, 0.2, 5.0)[0] == "normal"
