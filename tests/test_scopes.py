"""Stable names inside the program (DESIGN.md §16): the device scope of
every solve-path stage in the compiled HLO's ``op_name`` metadata, the
host spans of the final correction, ``correction_iters`` on the result,
and program spans on the device trace's clock."""
import json
import os
import re
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import precision as P
from repro.obs import trace as OT
from repro.solvers import make_gse_operator, make_precond_operator, solve_cg
from repro.solvers.cg import _as_arg, _loop
from repro.solvers.fused_cg import cg_step, kdot, static_apply
from repro.solvers.precond import make_jacobi
from repro.sparse import generators as G
from repro.sparse.csr import pack_csr, pack_sell
from repro.sparse.spmv import spmv_gse

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SPMV = {"spmv/decode", "spmv/gather", "spmv/scatter"}
KRYLOV = {"krylov/dot", "krylov/update"}


def _paths(hlo_text):
    """The scope paths (``spmv/scatter``, ``monitor``, ...) named in the
    ``op_name`` metadata of an HLO module's text."""
    found = set()
    for name in re.findall(r'op_name="([^"]*)"', hlo_text):
        parts = name.split("/")
        for i, part in enumerate(parts):
            if part in OT.SCOPES:
                child = parts[i + 1] if i + 1 < len(parts) else None
                found.add(f"{part}/{child}" if child in OT.SCOPES[part]
                          else part)
                break
    return found


def _hlo(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.fixture(scope="module")
def op():
    a = G.poisson3d(5)
    v = jnp.linspace(0.5, 1.5, a.shape[0])
    return a, pack_csr(a, k=8), v


def _forms(kind, form, a, g):
    """The operator and preconditioner of a ``kind`` (cg/pcg) solve as a
    loop takes them: packed (``csr``/``sell``) or as callables."""
    m = make_jacobi(a) if kind == "pcg" else None
    if form == "sell":
        g = pack_sell(g)
    if form == "callable":
        g = make_gse_operator(g)
        m = m if m is None else make_precond_operator(m)
    return _as_arg(g), _as_arg(m)


def _step(a, m, x, r, p, rz, tag):
    return cg_step(static_apply(a), static_apply(m), kdot, x, r, p, rz, tag)


@pytest.mark.parametrize("form", ["csr", "sell", "callable"])
@pytest.mark.parametrize("kind", ["cg", "pcg"])
def test_step_scopes(op, kind, form):
    """The one step names the SpMV and Krylov stages, and the
    preconditioner under PCG, whatever form the operator takes."""
    a, g, v = op
    ga, m = _forms(kind, form, a, g)
    found = _paths(_hlo(_step, ga, m, v, v, v, 1.0, 1))
    want = SPMV | KRYLOV | ({"precond"} if kind == "pcg" else set())
    assert found >= want
    if kind == "cg":
        assert "precond" not in found


MG = {"precond/smooth", "precond/residual", "precond/transfer"}


@pytest.fixture(scope="module")
def mg_op():
    """HPCG's 27-point operator on 8^3 with its four-level V-cycle, built
    under a tracer."""
    from repro.solvers import make_mg

    a = G.hpcg27(8)
    with OT.capture() as tr:
        m = make_mg(a)
    return pack_csr(a, k=8), m, jnp.linspace(0.5, 1.5, a.shape[0]), tr


def test_mg_pcg_step_scopes(mg_op):
    """A lowered MG-PCG iteration names the V-cycle's stages under
    ``precond``, beside the CG SpMV and the Krylov work."""
    g, m, v, _ = mg_op
    found = _paths(_hlo(_step, g, m, v, v, v, 1.0, 1))
    assert found >= SPMV | KRYLOV | MG


def test_mg_counter_and_setup_span(mg_op):
    """``precond.mg.setup`` is recorded with each level's size, and each
    traced V-cycle counts once in ``mg_vcycle_total``."""
    from repro.obs import metrics as OM

    g, m, v, tr = mg_op
    (setup,) = [e for e in tr.events if e["name"] == "precond.mg.setup"]
    assert setup["attrs"]["levels"] == 4
    assert setup["attrs"]["rows"] == [512, 64, 8, 1]
    assert setup["attrs"]["nnz"] == [22 ** 3, 10 ** 3, 4 ** 3, 1]
    counter = OM.REGISTRY.get("mg_vcycle_total").labels(levels="4")
    before = counter.value
    jax.jit(lambda r: m.apply_at(r, 1)).lower(v)
    assert counter.value == before + 1
    jax.jit(lambda r: m.apply(r, 2)).lower(v)      # one per tag branch
    assert counter.value == before + 4


@pytest.mark.parametrize("tag", [1, 2, 3])
def test_spmv_gse_scopes(op, tag):
    _, g, v = op
    found = _paths(_hlo(lambda x: spmv_gse(g, x, tag=tag), v))
    assert found >= SPMV
    assert not found & (KRYLOV | {"monitor", "precond"})


@pytest.mark.parametrize("form", ["csr", "callable"])
@pytest.mark.parametrize("kind", ["cg", "pcg"])
def test_solve_loop_scopes(op, kind, form):
    """The loop adds the monitor around the step's scopes."""
    a, g, v = op
    ga, m = _forms(kind, form, a, g)
    text = _hlo(lambda b: _loop(ga, m, b, jnp.zeros_like(b), 1e-8, 50,
                                P.MonitorParams.for_cg()), v)
    assert _paths(text) >= SPMV | KRYLOV | {"monitor"}


_SHARDED = textwrap.dedent("""
    import json, re, sys
    import jax, jax.numpy as jnp
    jax.config.update("jax_enable_x64", True)
    from repro.core import precision as P
    from repro.distributed.partition import partition_gsecsr
    from repro.kernels.dist_spmv import block_arrays
    from repro.solvers import sharded as S
    from repro.solvers.precond import make_jacobi
    from repro.sparse import generators as G
    from repro.sparse.csr import pack_csr

    a = G.poisson3d(6)
    part = partition_gsecsr(pack_csr(a, k=8), 4)
    b = jnp.linspace(0.5, 1.5, a.shape[0])
    pk = make_jacobi(a).packed
    diag = {"cg": S._empty_diag(part),
            "pcg": tuple(S._pad_to(t, part.n_padded) for t in
                         (pk.head, pk.tail1, pk.tail2)) + (pk.table,)}
    meta = {"cg": None, "pcg": (pk.ei_bit, pk.frac_bits)}
    out = {}
    for kind in ("cg", "pcg"):
        fn = S._sharded_loop_fn(part, kind, "exact", 50,
                                P.MonitorParams.for_cg(), 1, meta[kind])
        bp = S._pad_to(b, part.n_padded)
        text = fn.lower(block_arrays(part), part.table, *diag[kind], bp,
                        jnp.zeros_like(bp),
                        jnp.asarray(1e-8), jnp.linalg.norm(b)
                        ).compile().as_text()
        out[kind] = sorted(set(re.findall(r'op_name="([^"]*)"', text)))
    print(json.dumps(out))
""")


@pytest.fixture(scope="module")
def sharded_op_names():
    """``op_name``s of the sharded CG and PCG loops over 4 shards,
    compiled in a child process with 4 forced CPU devices."""
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"),
               JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    r = subprocess.run([sys.executable, "-c", _SHARDED], env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-4000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("kind", ["cg", "pcg"])
def test_sharded_step_scopes(sharded_op_names, kind):
    text = "".join(f'op_name="{n}"' for n in sharded_op_names[kind])
    want = SPMV | KRYLOV | {"spmv/halo", "monitor"}
    if kind == "pcg":
        want |= {"precond"}
    assert _paths(text) >= want
    # The psum of the dots sits under krylov/dot, the all-gather under
    # spmv/halo.
    kinds = {n.rsplit("/", 1)[-1]: n for n in sharded_op_names[kind]}
    assert "/krylov/dot/" in kinds["psum"]
    assert "/spmv/halo/" in kinds["all_gather"]


def _spans(tracer):
    by_id = {e["id"]: e for e in tracer.events}

    def parent(e):
        return by_id[e["parent"]]["name"] if e["parent"] else None

    return {e["name"]: (e, parent(e)) for e in tracer.events}


def _fast_params(**kw):
    d = dict(t=30, l=30, m=15, rsd_limit=0.5, reldec_limit=0.45)
    d.update(kw)
    return P.MonitorParams(**d)


def test_correction_spans_nest_in_the_entry_span(op):
    a, g, _ = op
    b = jnp.asarray(np.random.default_rng(0).normal(size=a.shape[0]))
    with OT.capture() as tr:
        res = solve_cg(g, b, tol=1e-8, maxiter=500, final_correction=True)
    spans = _spans(tr)
    assert spans["solve.cg"][1] is None
    assert spans["solve.correction"][1] == "solve.cg"
    assert spans["solve.correction.check"][1] == "solve.correction"
    assert "solve.correction.resume" not in spans
    corr = spans["solve.correction"][0]
    assert corr["attrs"]["true_relres"] <= 1e-8
    outer = spans["solve.cg"][0]
    assert outer["t0"] + outer["dur_s"] >= corr["t0"] + corr["dur_s"]
    assert int(res.correction_iters) == 0
    # Without final_correction there is no correction and no count.
    with OT.capture() as tr:
        res = solve_cg(g, b, tol=1e-8, maxiter=500)
    assert res.correction_iters is None
    assert "solve.correction" not in _spans(tr)


def test_correction_resume_span_and_count():
    """A tag-1 start pinned at tag 1 on an operator whose values need tag
    3: the recursive residual converges, the true one does not, and the
    correction resumes."""
    a = G.random_spd(600, seed=5)
    g = pack_csr(a, k=8)
    b = jnp.asarray(np.random.default_rng(5).normal(size=a.shape[0]))
    params = _fast_params(max_tag=1)
    first = solve_cg(g, b, tol=1e-8, maxiter=4000, params=params)
    with OT.capture() as tr:
        res = solve_cg(g, b, tol=1e-8, maxiter=4000, params=params,
                       final_correction=True)
    spans = _spans(tr)
    assert spans["solve.correction.resume"][1] == "solve.correction"
    assert spans["solve.correction"][0]["attrs"]["true_relres"] > 1e-8
    n = int(res.correction_iters)
    assert n > 0
    assert int(res.iters) == int(first.iters) + n


def test_span_t0_on_the_profile_clock(tmp_path):
    """A span's ``t0`` is the start of its ``TraceAnnotation`` in the
    profile (``profile_start_time + start_ns``) within 1 ms, so program
    spans and device ops share one clock."""
    f = jax.jit(lambda x: jnp.sin(x) @ x)
    x = jnp.ones((32, 32))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with OT.capture() as tr:
        for _ in range(3):
            with OT.span("solve.probe"):
                f(x).block_until_ready()
    jax.profiler.stop_trace()
    (path,) = tmp_path.rglob("*.xplane.pb")
    planes = list(jax.profiler.ProfileData.from_file(str(path)).planes)
    start = [v for p in planes for k, v in p.stats
             if k == "profile_start_time"]
    assert len(start) == 1
    got = sorted(start[0] + ev.start_ns
                 for p in planes if p.name.startswith("/host:")
                 for line in p.lines for ev in line.events
                 if ev.name == "solve.probe")
    want = sorted(e["t0"] * 1e9 for e in tr.events)
    assert len(got) == len(want) == 3
    for g_ns, w_ns in zip(got, want):
        assert abs(g_ns - w_ns) < 1e6
