"""The comparison that decides ``correct`` fails what it must.

Each test skips the harness's look for a chip, breaks the timed path
underneath, drives the rest of a run at a tiny grid, and sees ``correct``
come out false.  The faults a solve cell can have: an answer altered
where it is produced, a solve that returns its start unchanged, and on
several chips the halo exchange left out.  The control is the reference
put in the program's place in float32, the precision below the float64
the configurations state; the same reference in float64 passes.
(A cell here solves one right-hand side at a time, so no batch can lose
half its rows.)
"""
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness, operators
from bench.tests.helpers import ONE_CHIP, run_tiny, tiny_cell


def _break_entry(monkeypatch, cell, alter):
    from repro import solvers

    name = cell.config["solver"]["entry"]
    real = getattr(solvers, name)

    def broken(op, b, **kw):
        res = real(op, b, **kw)
        if kw.get("tol", 0) > 1:         # the warm-up's zero-iteration solve
            return res
        return res._replace(x=alter(res.x, b))

    monkeypatch.setattr(solvers, name, broken)


@pytest.mark.parametrize("name", sorted(ONE_CHIP))
def test_altered_answer_fails(monkeypatch, name):
    cell = ONE_CHIP[name]()
    _break_entry(monkeypatch, cell,
                 lambda x, b: x.at[0].add(1e-3 * jnp.max(jnp.abs(x))))
    line = run_tiny(monkeypatch, cell)
    assert line["correct"] is False and line["failed"] == line["attempted"]


@pytest.mark.parametrize("name", sorted(ONE_CHIP))
def test_unchanged_state_fails(monkeypatch, name):
    cell = ONE_CHIP[name]()
    _break_entry(monkeypatch, cell, lambda x, b: jnp.zeros_like(x))
    line = run_tiny(monkeypatch, cell)
    assert line["correct"] is False
    assert line["checks"]["worst_true_relres"]["value"] == pytest.approx(1.0)


def test_exchange_left_out_fails(monkeypatch):
    from repro.distributed import wire

    cell = tiny_cell("lap3d_76.cg.4chip")
    wire.set_wire_fault(lambda seg, arr: jnp.zeros_like(arr))
    try:
        line = run_tiny(monkeypatch, cell)
    finally:
        wire.set_wire_fault(None)
    assert line["correct"] is False


def _reference_in_place(monkeypatch, cell, dtype):
    """Put the benchmark's reference CG, in ``dtype``, in place of the
    program's solve entry."""
    from repro import solvers

    coo = operators.build(cell.config)
    sv = cell.config["solver"]
    name = sv["entry"]
    real = getattr(solvers, name)

    def reference(op, b, **kw):
        res = real(op, b, **kw)
        if kw.get("tol", 0) > 1:
            return res
        x, _ = operators.cg_control(coo, np.asarray(b), kw["tol"],
                                    kw["maxiter"], dtype,
                                    sv["precond"] == "jacobi")
        return res._replace(x=jnp.asarray(x))

    monkeypatch.setattr(solvers, name, reference)


@pytest.mark.parametrize("name", sorted(ONE_CHIP))
def test_float32_control_fails(monkeypatch, name):
    cell = ONE_CHIP[name]()
    _reference_in_place(monkeypatch, cell, jnp.float32)
    line = run_tiny(monkeypatch, cell)
    assert line["correct"] is False
    # The control misses the stated 1e-8 by more than ten times.
    assert line["checks"]["worst_true_relres"]["value"] > 1e-7


@pytest.mark.parametrize("name", sorted(ONE_CHIP))
def test_float64_reference_passes(monkeypatch, name):
    cell = ONE_CHIP[name]()
    _reference_in_place(monkeypatch, cell, jnp.float64)
    assert run_tiny(monkeypatch, cell)["correct"] is True


def test_control_runner_reads_one_number_per_seed():
    from bench import control

    cell = tiny_cell("lap3d_48.cg")
    rows = control.readings(cell, [5, 6, 7], jnp.float32)
    assert len(rows) == 3
    assert all(r["true_relres"] > cell.config["accuracy"]["true_relres"]
               for r in rows)
