"""The least-bytes counts, tied to the arrays the stored format holds, and
blind to the program's own byte model."""
import numpy as np
import pytest

from bench import harness, operators
from bench.work import cg, cg_sharded, pcg_jacobi

SEGMENTS = {1: ("head",), 2: ("head", "tail1"), 3: ("head", "tail1", "tail2")}
GRID = [6, 5, 4]


def _lap():
    nb = [[1, 0, 0, -1.0], [-1, 0, 0, -1.0], [0, 1, 0, -1.0],
          [0, -1, 0, -1.0], [0, 0, 1, -1.0], [0, 0, -1, -1.0]]
    return operators.stencil(GRID, 6.0, nb)


def _packed():
    """A Laplacian rescaled ``D A D``, ``D = 2^U(-4, 4)``, so that its
    values fill every tail segment."""
    from repro.solvers import make_jacobi
    from repro.sparse.csr import from_coo, pack_csr

    lap = _lap()
    d = np.exp2(np.random.default_rng(3).uniform(-4, 4, lap.n))
    coo = operators.Coo(lap.rows, lap.cols,
                        lap.vals * d[lap.rows] * d[lap.cols], lap.n)
    a = from_coo(coo.rows, coo.cols, coo.vals, (coo.n, coo.n))
    return coo, a, pack_csr(a, k=8), make_jacobi(a, k=8)


def _nbytes(*arrays):
    return sum(int(np.asarray(x).nbytes) for x in arrays)


@pytest.mark.parametrize("tag", [1, 2, 3])
def test_counts_are_the_stored_arrays(tag):
    coo, _, g, jac = _packed()
    shape = {"n": coo.n, "nnz": coo.nnz, "halo": 0, "chips": 1}
    seg = _nbytes(*(getattr(g, s) for s in SEGMENTS[tag]))
    assert cg.matrix_bytes(shape, tag) == (
        seg + _nbytes(g.colpak) + _nbytes(g.rowptr))
    x = np.zeros(coo.n)
    assert cg.vector_bytes(shape) == 7 * x.nbytes     # p once, x r p r+w
    assert cg.iteration_bytes(shape, tag) == (
        cg.matrix_bytes(shape, tag) + cg.vector_bytes(shape))
    diag = _nbytes(*(getattr(jac.packed, s) for s in SEGMENTS[tag]))
    assert pcg_jacobi.iteration_bytes(shape, tag) == (
        cg.iteration_bytes(shape, tag) + diag)


@pytest.mark.parametrize("shards", [2, 4])
def test_sharded_count_adds_the_halo(shards):
    from repro.distributed.partition import partition_gsecsr

    coo, _, g, _ = _packed()
    halo = harness.halo_entries(coo, shards)
    assert halo == partition_gsecsr(g, shards).halo_entries
    shape = {"n": coo.n, "nnz": coo.nnz, "halo": halo, "chips": shards}
    assert cg_sharded.iteration_bytes(shape, 3) == (
        cg.iteration_bytes(shape, 3) + 8 * halo + 4 * (shards - 1))


def test_blind_to_the_programs_byte_model(monkeypatch):
    """Doubling the program's own byte model moves none of the counts."""
    from repro.core import precision_table as PT
    from repro.sparse import csr as C

    shape = {"n": 1000, "nnz": 6800, "halo": 300, "chips": 4}
    kinds = (cg, pcg_jacobi, cg_sharded)
    before = [[k.iteration_bytes(shape, t) for t in (1, 2, 3)] for k in kinds]
    for name in ("KERNEL_SLOT_BYTES", "SLOT_BYTES", "TAG_VALUE_BYTES"):
        monkeypatch.setattr(PT, name,
                            {t: 2 * v for t, v in getattr(PT, name).items()})
    monkeypatch.setattr(C.GSECSR, "bytes_touched",
                        lambda self, tag, layout=None: 0)
    after = [[k.iteration_bytes(shape, t) for t in (1, 2, 3)] for k in kinds]
    assert after == before
