"""Robustness tier-1 tests (DESIGN.md §14).

Covers the guardrail + recovery stack end to end: in-loop guard health on
every solver, guards-on/off bit-identity, tag-escalation recovery from
deterministic low-tag faults, the adversarial-input matrix
(NaN/Inf/zero right-hand sides, tol=0, maxiter=0, indefinite operators),
the NaN-mid-window monitor regression, pack/cache/wire integrity
checksums with seeded fault injection, the bounded ``_cached_pack`` LRU,
and the solve service's degradation contract (intake validation, bounded
tag-3 retries, deadlines, never-raise / never-unflagged-nonfinite).

Wire-checksum tests need 2 devices; under plain tier-1 they skip and
``test_wire_suite_under_forced_devices`` re-runs them in one subprocess
with ``XLA_FLAGS=--xla_force_host_platform_device_count=2``.
"""
import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import precision as P
from repro.robustness.faults import (
    GSECSR_SEGMENTS,
    bitflip_array,
    corrupt_gsecsr,
    corrupt_pack_cache,
    gsecsr_checksums,
    make_tag_fault_operator,
    make_wire_fault,
    verify_gsecsr,
)
from repro.robustness.guards import (
    DEFAULT_GUARDS,
    GuardParams,
    HEALTH_BREAKDOWN,
    HEALTH_NONFINITE,
    HEALTH_OK,
    HEALTH_STALLED,
    health_name,
)
from repro.sparse import generators as G
from repro.sparse.csr import pack_csr
from repro.sparse.spmv import spmv
from repro.solvers.cg import solve_cg, solve_pcg
from repro.solvers.gmres import solve_gmres
from repro.solvers.precond import make_jacobi

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NEED_WIRE = 2
wire_devices = pytest.mark.skipif(
    jax.device_count() < NEED_WIRE,
    reason=f"needs {NEED_WIRE} devices; covered by the subprocess re-run",
)

_PARAMS = P.MonitorParams(t=40, l=60, m=30, rsd_limit=0.5, reldec_limit=0.45)


def _sys(n=16, seed=3):
    csr = G.poisson2d(n)
    g = pack_csr(csr, k=8)
    rng = np.random.default_rng(seed)
    b = spmv(csr, jnp.asarray(rng.normal(size=csr.shape[1])))
    return csr, g, b


def _finite(x) -> bool:
    return bool(jnp.isfinite(jnp.vdot(x, x)))


# ---------------------------------------------------------------------------
# Guard health on clean solves + bit-identity with guards off
# ---------------------------------------------------------------------------

def test_clean_solve_health_ok():
    _, g, b = _sys()
    res = solve_cg(g, b, tol=1e-8, maxiter=2000, params=_PARAMS)
    assert bool(res.converged)
    assert int(res.health) == HEALTH_OK
    assert int(res.trip_iter) == -1
    assert health_name(int(res.health)) == "ok"


@pytest.mark.parametrize("pcg", [False, True])
def test_guards_on_off_bit_identical(pcg):
    csr, g, b = _sys()
    kw = dict(tol=1e-8, maxiter=2000, params=_PARAMS)
    if pcg:
        m = make_jacobi(csr)
        on = solve_pcg(g, b, m, guards=DEFAULT_GUARDS, **kw)
        off = solve_pcg(g, b, m, guards=None, **kw)
    else:
        on = solve_cg(g, b, guards=DEFAULT_GUARDS, **kw)
        off = solve_cg(g, b, guards=None, **kw)
    assert int(on.iters) == int(off.iters)
    assert np.array_equal(np.asarray(on.x), np.asarray(off.x))
    assert np.array_equal(np.asarray(on.switch_iters),
                          np.asarray(off.switch_iters))


def test_custom_guard_params_are_static_jit_args():
    _, g, b = _sys()
    tight = GuardParams(div_factor=1e3, stall_window=50)
    res = solve_cg(g, b, tol=1e-8, maxiter=2000, params=_PARAMS, guards=tight)
    assert bool(res.converged) and int(res.health) == HEALTH_OK


# ---------------------------------------------------------------------------
# Tag-escalation recovery from deterministic low-tag faults
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mode", ["indefinite", "nan"])
@pytest.mark.parametrize("fail_tag", [1, 2])
def test_low_tag_fault_recovers_by_escalation(mode, fail_tag):
    _, g, b = _sys()
    op = make_tag_fault_operator(g, mode=mode, fail_tag=fail_tag)
    res = solve_cg(op, b, tol=1e-8, maxiter=3000, params=_PARAMS)
    assert bool(res.converged), (mode, fail_tag)
    assert int(res.health) == HEALTH_OK  # convergence overrides the trip
    assert int(res.trip_iter) >= 0       # ...but the trip is still reported
    assert int(res.tag) > fail_tag       # escaped the faulty rungs
    assert _finite(res.x)
    # the escalation is byte-accounted: the promotion to tag fail_tag+1
    # landed in switch_iters
    assert int(np.asarray(res.switch_iters)[fail_tag - 1]) >= 0


def test_pcg_recovery_from_indefinite_tag1():
    csr, g, b = _sys()
    m = make_jacobi(csr)
    op = make_tag_fault_operator(g, mode="indefinite", fail_tag=1)
    res = solve_pcg(op, b, m, tol=1e-8, maxiter=3000, params=_PARAMS)
    assert bool(res.converged) and int(res.health) == HEALTH_OK
    assert int(res.trip_iter) >= 0 and int(res.tag) > 1


def test_recover_false_reports_breakdown():
    _, g, b = _sys()
    op = make_tag_fault_operator(g, mode="indefinite", fail_tag=1)
    res = solve_cg(op, b, tol=1e-8, maxiter=3000, params=_PARAMS,
                   recover=False)
    assert not bool(res.converged)
    assert int(res.health) == HEALTH_BREAKDOWN
    assert int(res.trip_iter) == 0
    assert _finite(res.x)  # rolled back to the last finite checkpoint


def test_unrecoverable_fault_stays_flagged_and_finite():
    """Indefinite at EVERY tag: escalation runs out of rungs; the result
    must be flagged, unconverged, and still finite (the checkpoint)."""
    _, g, b = _sys()
    op = make_tag_fault_operator(g, mode="indefinite", fail_tag=3)
    res = solve_cg(op, b, tol=1e-8, maxiter=3000, params=_PARAMS)
    assert not bool(res.converged)
    assert int(res.health) == HEALTH_BREAKDOWN
    assert int(res.tag) == 3
    assert _finite(res.x)


# ---------------------------------------------------------------------------
# Adversarial-input matrix (satellite d)
# ---------------------------------------------------------------------------

def _adversarial_b(kind, b):
    if kind == "nan":
        return b.at[0].set(jnp.nan)
    if kind == "inf":
        return b.at[1].set(jnp.inf)
    return jnp.zeros_like(b)  # "zero"


@pytest.mark.parametrize("solver", ["cg", "pcg", "gmres"])
@pytest.mark.parametrize("kind", ["nan", "inf", "zero"])
def test_adversarial_rhs_never_unflagged_nonfinite(solver, kind):
    csr, g, b = _sys()
    b = _adversarial_b(kind, b)
    if solver == "cg":
        res = solve_cg(g, b, tol=1e-8, maxiter=500, params=_PARAMS)
    elif solver == "pcg":
        res = solve_pcg(g, b, make_jacobi(csr), tol=1e-8, maxiter=500,
                        params=_PARAMS)
    else:
        from repro.solvers import make_gse_operator
        res = solve_gmres(make_gse_operator(g), b, tol=1e-8, restart=20,
                          maxiter=200, params=_PARAMS)
    if kind == "zero":
        # ||b|| = 0: the zero solution satisfies the system immediately
        assert bool(res.converged) and int(res.health) == HEALTH_OK
        assert _finite(res.x) and float(jnp.abs(res.x).max()) == 0.0
    else:
        assert not bool(res.converged)
        assert int(res.health) == HEALTH_NONFINITE
        assert _finite(res.x)  # the checkpoint, never the poisoned iterate


@pytest.mark.parametrize("solver", ["cg", "pcg"])
def test_tol_zero_and_maxiter_zero_flag_cleanly(solver):
    csr, g, b = _sys()
    m = make_jacobi(csr)

    def run(**kw):
        if solver == "pcg":
            return solve_pcg(g, b, m, params=_PARAMS, **kw)
        return solve_cg(g, b, params=_PARAMS, **kw)

    res = run(tol=0.0, maxiter=60)
    assert not bool(res.converged)
    assert int(res.health) == HEALTH_STALLED  # clean exhaustion, no trip
    assert _finite(res.x)

    res0 = run(tol=1e-8, maxiter=0)
    assert int(res0.iters) == 0 and not bool(res0.converged)
    assert int(res0.health) == HEALTH_STALLED and int(res0.trip_iter) == -1
    assert _finite(res0.x)


def test_indefinite_operator_flagged_via_generic_path():
    """A genuinely indefinite operator (not a tag fault): CG must trip
    breakdown instead of silently iterating on garbage."""
    _, g, b = _sys()

    def indefinite(v, tag):
        from repro.solvers import make_gse_operator
        return -make_gse_operator(g)(v, tag)

    res = solve_cg(indefinite, b, tol=1e-8, maxiter=500, params=_PARAMS)
    assert not bool(res.converged)
    assert int(res.health) == HEALTH_BREAKDOWN
    assert _finite(res.x)


# ---------------------------------------------------------------------------
# Monitor NaN-mid-window regression (satellite a)
# ---------------------------------------------------------------------------

def test_monitor_survives_nan_mid_window():
    st = P.init(_PARAMS)
    for i in range(10):
        st = P.record(st, jnp.asarray(1.0 / (i + 1)))
    st = P.record(st, jnp.asarray(jnp.nan))   # breakdown iteration
    st = P.record(st, jnp.asarray(jnp.inf))
    for i in range(_PARAMS.t):
        st = P.record(st, jnp.asarray(1e-3 / (i + 1)))
    assert bool(jnp.isfinite(st.hist).all())  # sentinel, not NaN, entered
    rsd, ndec, reldec = P.metrics(st)
    assert bool(jnp.isfinite(rsd)) and bool(jnp.isfinite(reldec))
    # switching is still alive after the poisoned window has rolled off
    st2 = P.update_tag(st, _PARAMS)
    assert int(st2.tag) >= int(st.tag)


# ---------------------------------------------------------------------------
# Pack-segment checksums + seeded bit-flip faults
# ---------------------------------------------------------------------------

def test_bitflip_is_seeded_xor_involution():
    a = np.arange(64, dtype=np.float64)
    once = bitflip_array(a, seed=5, nflips=3)
    assert not np.array_equal(once, a)
    twice = bitflip_array(once, seed=5, nflips=3)  # same positions: undo
    assert np.array_equal(twice, a)


@pytest.mark.parametrize("target", GSECSR_SEGMENTS)
def test_pack_segment_corruption_detected(target):
    _, g, _ = _sys()
    ref = gsecsr_checksums(g)
    assert verify_gsecsr(g, ref) == []  # clean operand verifies clean
    for seed in (0, 1, 2):
        bad = corrupt_gsecsr(g, target, seed)
        assert target in verify_gsecsr(bad, ref), (target, seed)
        assert verify_gsecsr(g, ref) == []  # original untouched


def test_table_flip_perturbs_decode():
    """The shared-exponent table is the high-leverage target: one flip in
    a REFERENCED entry rescales a whole group, so the decoded SpMV must
    actually change.  (A seeded flip may land in an unused padding entry
    -- silent for the decode, though still checksum-detected -- so pick a
    group the packed column indices actually point at.)"""
    import dataclasses

    from repro.sparse.spmv import spmv_gse

    _, g, b = _sys()
    used = int(np.unique(np.asarray(g.colpak) >> (32 - g.ei_bit))[0])
    table = np.asarray(g.table).copy()
    table[used] ^= 1  # +-1 on the shared exponent: the whole group scales
    bad = dataclasses.replace(g, table=jnp.asarray(table))
    y0 = np.asarray(spmv_gse(g, b, tag=1))
    y1 = np.asarray(spmv_gse(bad, b, tag=1))
    assert not np.array_equal(y0, y1)


# ---------------------------------------------------------------------------
# Bounded _cached_pack LRU + checksum detect-and-repack (satellite c)
# ---------------------------------------------------------------------------

def test_pack_cache_lru_bound_and_evictions():
    from repro.kernels.ops import PACK_CACHE_MAX, PACK_STATS, _cached_pack

    class Holder:
        pass

    a = Holder()
    ev0 = PACK_STATS["evictions"]
    extra = 3
    for i in range(PACK_CACHE_MAX + extra):
        _cached_pack(a, ("key", i), lambda i=i: (np.arange(4) + i,))
    assert len(a._pack_cache) == PACK_CACHE_MAX
    assert PACK_STATS["evictions"] == ev0 + extra
    # least-recently-used entries (the first `extra`) were the ones dropped
    assert ("key", 0) not in a._pack_cache
    assert ("key", PACK_CACHE_MAX + extra - 1) in a._pack_cache
    # a hit refreshes recency: touch the oldest survivor, add one more,
    # and the touched entry must survive while its neighbor is evicted
    oldest = ("key", extra)
    _cached_pack(a, oldest, lambda: pytest.fail("hit must not rebuild"))
    _cached_pack(a, ("key", 999), lambda: (np.arange(4),))
    assert oldest in a._pack_cache
    assert ("key", extra + 1) not in a._pack_cache


def test_pack_cache_corruption_detected_and_repacked():
    from repro.kernels.ops import PACK_STATS, sell_pack_gsecsr

    _, g, b = _sys(seed=9)
    clean = sell_pack_gsecsr(g)
    ref = [np.asarray(leaf).copy()
           for leaf in jax.tree_util.tree_leaves(clean)]
    assert corrupt_pack_cache(g, seed=0)
    before = PACK_STATS["corrupt"]
    repacked = sell_pack_gsecsr(g)  # hit -> checksum mismatch -> repack
    assert PACK_STATS["corrupt"] == before + 1
    for got, want in zip(jax.tree_util.tree_leaves(repacked), ref):
        assert np.array_equal(np.asarray(got), want)
    # the repacked entry is healthy: the next hit does not re-detect
    sell_pack_gsecsr(g)
    assert PACK_STATS["corrupt"] == before + 1


# ---------------------------------------------------------------------------
# Wire checksums (2 devices; subprocess re-run under plain tier-1)
# ---------------------------------------------------------------------------

def test_wire_checksum_sees_high_bits_of_f64():
    """Regression: a flip in bits 32-63 of a float64 wire element must
    change the u32 checksum (the mod-2^32 mask would otherwise erase it
    without the high-half fold)."""
    from repro.distributed.wire import wire_checksum

    arr = jnp.asarray(np.random.default_rng(0).normal(size=32))
    ck = wire_checksum(arr)
    raw = np.asarray(arr).copy()
    raw.view(np.uint64)[3] ^= np.uint64(1) << np.uint64(40)
    assert int(wire_checksum(jnp.asarray(raw))) != int(ck)
    for dtype in (np.uint16, np.uint64):
        a = np.arange(16, dtype=dtype)
        bad = bitflip_array(a, seed=1)
        assert int(wire_checksum(jnp.asarray(a))) != \
            int(wire_checksum(jnp.asarray(bad)))


def _wire_harness(tag, wire):
    from jax.sharding import Mesh
    from jax.sharding import PartitionSpec as Spec

    from repro.distributed.wire import halo_all_gather

    mesh = Mesh(np.array(jax.devices()[:2]), ("sh",))
    return jax.shard_map(
        lambda bnd: halo_all_gather(bnd, "sh", tag=tag, wire=wire,
                                    check=True)[1],
        mesh=mesh, in_specs=Spec("sh"), out_specs=Spec(), check_vma=False,
    )


_WIRE_COMBOS = [("gse", 1, "head"), ("gse", 1, "table"),
                ("gse", 2, "tail1"), ("exact", 3, "raw"), ("gse", 3, "raw")]


@wire_devices
@pytest.mark.parametrize("wire,tag,target", _WIRE_COMBOS)
def test_wire_fault_detected_by_receivers(wire, tag, target):
    from repro.distributed.wire import set_wire_fault

    full = jnp.asarray(np.random.default_rng(4).normal(size=64))
    fn = _wire_harness(tag, wire)
    assert bool(fn(full))  # clean payload verifies
    for seed in (0, 1, 2):
        set_wire_fault(make_wire_fault(target, seed))
        try:
            assert not bool(fn(full)), (wire, tag, target, seed)
        finally:
            set_wire_fault(None)
    assert bool(fn(full))  # hook cleared: clean again


def test_wire_suite_under_forced_devices():
    """Re-run the wire tests with 2 forced host devices when tier-1 runs
    on a single device (same pattern as tests/test_distributed.py)."""
    if jax.device_count() >= NEED_WIRE:
        pytest.skip("already running with enough devices")
    env = dict(os.environ,
               PYTHONPATH=os.path.join(REPO, "src"),
               XLA_FLAGS=f"--xla_force_host_platform_device_count"
                         f"={NEED_WIRE}")
    r = subprocess.run(
        [sys.executable, "-m", "pytest", "-x", "-q",
         os.path.join(REPO, "tests", "test_robustness.py"),
         "-k", "wire_fault_detected"],
        env=env, capture_output=True, text=True, timeout=900,
    )
    assert r.returncode == 0, (
        f"forced-device re-run failed:\n{r.stdout[-4000:]}\n{r.stderr[-2000:]}"
    )


# ---------------------------------------------------------------------------
# Batched solver health
# ---------------------------------------------------------------------------

def test_batched_nan_column_isolated_and_flagged():
    from repro.solvers.batched import solve_cg_batched

    csr, g, b1 = _sys()
    rng = np.random.default_rng(8)
    cols = [b1] + [spmv(csr, jnp.asarray(rng.normal(size=csr.shape[1])))
                   for _ in range(2)]
    b = jnp.stack(cols, axis=1).at[0, 1].set(jnp.nan)
    res = solve_cg_batched(g, b, tol=1e-8, maxiter=2000, params=_PARAMS)
    health = np.asarray(res.health)
    conv = np.asarray(res.converged)
    assert not conv[1] and health[1] == HEALTH_NONFINITE
    assert conv[0] and conv[2]
    assert health[0] == HEALTH_OK and health[2] == HEALTH_OK
    assert bool(jnp.isfinite(res.x).all())  # poisoned column frozen finite


def test_batched_guards_on_off_bit_identical():
    from repro.solvers.batched import solve_cg_batched

    csr, g, b1 = _sys()
    rng = np.random.default_rng(12)
    b = jnp.stack(
        [b1, spmv(csr, jnp.asarray(rng.normal(size=csr.shape[1])))], axis=1)
    on = solve_cg_batched(g, b, tol=1e-8, maxiter=2000, params=_PARAMS,
                          guards=DEFAULT_GUARDS)
    off = solve_cg_batched(g, b, tol=1e-8, maxiter=2000, params=_PARAMS,
                           guards=None)
    assert np.array_equal(np.asarray(on.x), np.asarray(off.x))
    assert np.array_equal(np.asarray(on.iters), np.asarray(off.iters))


# ---------------------------------------------------------------------------
# Iterative refinement health
# ---------------------------------------------------------------------------

def test_ir_health_clean_and_poisoned():
    from repro.solvers.ir import solve_ir

    _, g, b = _sys()
    res = solve_ir(g, b, tol=1e-10, inner_tol=1e-4, params=_PARAMS)
    assert bool(res.converged) and res.health == HEALTH_OK

    bad = make_tag_fault_operator(g, mode="nan", fail_tag=3)
    res2 = solve_ir(bad, b, tol=1e-10, max_outer=3, inner_tol=1e-4,
                    params=_PARAMS)
    assert not bool(res2.converged)
    assert res2.health != HEALTH_OK
    assert _finite(res2.x)  # the NaN correction was never folded in


# ---------------------------------------------------------------------------
# Sharded solver health (1 shard runs on any device count)
# ---------------------------------------------------------------------------

def test_sharded_single_shard_health_and_parity():
    from repro.distributed.partition import partition_gsecsr

    _, g, b = _sys()
    part = partition_gsecsr(g, 1)
    res = solve_cg(part, b, tol=1e-8, maxiter=2000, params=_PARAMS)
    ref = solve_cg(g, b, tol=1e-8, maxiter=2000, params=_PARAMS)
    assert bool(res.converged) and int(res.health) == HEALTH_OK
    assert int(res.trip_iter) == -1
    assert np.array_equal(np.asarray(res.x), np.asarray(ref.x))


# ---------------------------------------------------------------------------
# Serving degradation (satellite b + tentpole serving piece)
# ---------------------------------------------------------------------------

def _service(**kw):
    import repro.launch.solver_serve as S

    csr, g, b = _sys()
    svc = S.SolverService(params=_PARAMS, maxiter=3000, **kw)
    svc.register("p", csr, k=8)
    return S, svc, csr, b


def test_submit_validates_shape_dtype_finiteness():
    S, svc, csr, b = _service(slots=2)
    n = csr.shape[0]
    with pytest.raises(KeyError):
        svc.submit("nope", b)
    with pytest.raises(ValueError):
        svc.submit("p", np.zeros(n - 1))
    with pytest.raises(ValueError):
        svc.submit("p", np.arange(n))  # integer dtype
    bad = np.zeros(n)
    bad[5] = np.inf
    with pytest.raises(ValueError):
        svc.submit("p", bad)
    with pytest.raises(ValueError):
        svc.submit("p", b, x0=np.full(n, np.nan))
    with pytest.raises(ValueError):
        svc.submit("p", b, deadline_s=0.0)
    assert svc._pending == []  # nothing slipped through
    rid = svc.submit("p", np.asarray(b).reshape(n, 1))  # (n,1) normalizes
    assert svc.flush()[rid].converged


def test_flush_clean_reports_health_ok():
    S, svc, csr, b = _service(slots=2)
    rid = svc.submit("p", b)
    r = svc.flush()[rid]
    assert r.converged and r.health == "ok" and r.retries == 0
    assert not r.deadline_exceeded
    assert _finite(svc.solution(rid))


def _nan_first_column(real):
    def wrapper(*args, **kw):
        res = real(*args, **kw)
        return res._replace(
            x=res.x.at[:, 0].set(jnp.nan),
            converged=res.converged.at[0].set(False),
            health=jnp.asarray(res.health).at[0].set(HEALTH_NONFINITE),
        )
    return wrapper


def test_degraded_column_recovers_via_tag3_retry(monkeypatch):
    S, svc, csr, b = _service(slots=2, max_retries=1)
    monkeypatch.setattr(S, "solve_cg_batched",
                        _nan_first_column(S.solve_cg_batched))
    rid = svc.submit("p", b)
    r = svc.flush()[rid]
    assert r.converged and r.health == "ok"
    assert r.retries == 1 and r.tag == 3
    assert _finite(svc.solution(rid))
    assert svc.stats["retries"] == 1


def test_lapsed_deadline_suppresses_retry(monkeypatch):
    S, svc, csr, b = _service(slots=2, max_retries=1)
    monkeypatch.setattr(S, "solve_cg_batched",
                        _nan_first_column(S.solve_cg_batched))
    rid = svc.submit("p", b, deadline_s=0.005)
    time.sleep(0.02)
    r = svc.flush()[rid]
    assert not r.converged and r.deadline_exceeded and r.retries == 0
    assert r.health == "nonfinite"  # flagged, not silently wrong
    assert svc.stats["deadline_exceeded"] == 1


def test_flush_never_raises_on_solver_error(monkeypatch):
    S, svc, csr, b = _service(slots=2)

    def boom(*args, **kw):
        raise RuntimeError("synthetic slot failure")

    monkeypatch.setattr(S, "solve_cg_batched", boom)
    rid = svc.submit("p", b)
    r = svc.flush()[rid]  # must not raise
    assert r.health == "error" and not r.converged
    assert svc.stats["errors"] == 1
    with pytest.raises(KeyError):
        svc.solution(rid)  # no solution is published for an errored slot


def test_no_unflagged_nonfinite_solution_ever(monkeypatch):
    """Even with retries exhausted the published solution is either
    finite or its report is flagged."""
    S, svc, csr, b = _service(slots=2, max_retries=0)
    monkeypatch.setattr(S, "solve_cg_batched",
                        _nan_first_column(S.solve_cg_batched))
    rid = svc.submit("p", b)
    r = svc.flush()[rid]
    assert not r.converged and r.health != "ok"
