"""The graph-partitioned Matrix Market route as the benchmark's
``mtx_partitioned`` input builds it -- a 2D Poisson file written by
``genmatrix``, rows partitioned by the built-in edge-cut bisection, ELL
or DIA local blocks, the ``all_to_all`` halo over four devices, f32 --
against the plain float64 reference and the one-device solve of the
same system; and the partition counters ``cli._solve_route`` reports
for it."""

import numpy as np
import pytest

from benchmark import spec
from benchmark.tests.record_trace import tiny_config

N = 64
RTOL = 1e-6
# relative 2-norm distance between the four-part and one-device answers
AGREE = 1e-5


def _input(name):
    return spec.module(spec.BENCH / "inputs" / f"{name}.py")


@pytest.fixture(scope="module")
def config():
    return tiny_config(
        spec.load_cell("poisson2d-2048-graph4.cg.part4")["config"], N)


@pytest.fixture(scope="module")
def solvers(config, tmp_path_factory):
    """The four-part solver and the one-device solver of the same file,
    each with its manufactured systems."""
    cache = tmp_path_factory.mktemp("cache")
    mesh = _input("mtx_partitioned").build(config, {"nparts": 4},
                                           cache=cache)
    one = _input("mtx_file").build(config, {"nparts": 1}, cache=cache)
    return mesh, one


def solve(solver, b):
    from acg_tpu.solvers.stats import StoppingCriteria

    x = solver.solve(b, criteria=StoppingCriteria(maxits=1000,
                                                  residual_rtol=RTOL),
                     host_result=False, raise_on_divergence=False)
    return np.asarray(x, dtype=np.float64), int(solver.stats.niterations)


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_partitioned_solve_matches_reference_and_one_device(config, solvers,
                                                            seed):
    """(1) The answer, read in the file's row order, has a true
    relative residual within the cells' limit 1e-5 by the float64
    reference.  (2) The four-part solve takes the one-device solve's
    iterations within 5% (the partition changes only the order of the
    f32 sums), and its answer agrees with the one-device answer to
    AGREE: both are the same Krylov iterate up to f32 rounding, which
    after ~130 iterations of reordered sums reads 4-6e-7 here (a few
    f32 epsilons, 1.2e-7, each); a wrong row order or a dropped halo
    moves the answer by O(1)."""
    ref = spec.module(spec.BENCH / "references" / "poisson_stencil.py")
    (mesh, mesh_rhs), (one, one_rhs) = solvers
    b, xsol = mesh_rhs(seed)
    x4, its4 = solve(mesh, b)
    x1, its1 = solve(one, one_rhs(seed)[0])
    res4 = ref.true_residual(config, x4, xsol)
    res1 = ref.true_residual(config, x1, xsol)
    assert res4 <= 1e-5, res4
    assert abs(its4 - its1) <= 0.05 * its1, (its4, its1)
    diff = np.linalg.norm(x4 - x1) / np.linalg.norm(x1)
    assert diff <= AGREE, (diff, res4, res1)


def test_route_reports_the_partition(solvers):
    """The set-up log's route names the partition it ran: the entries
    one halo exchange moves over all parts, the largest part's owned
    rows and ghosts."""
    from acg_tpu.cli import _solve_route

    (mesh, mesh_rhs), _ = solvers
    solve(mesh, mesh_rhs(1)[0])
    route = _solve_route(mesh)
    prob = mesh.problem
    assert route["transport"] == "xla"
    assert route["devices"] == [0, 1, 2, 3]
    assert route["spmv_route"] == prob.local.format
    assert route["halo_entries"] == int(prob.neighbor_counts()[0].sum())
    assert route["halo_entries"] == sum(s.halo.total_recv
                                        for s in prob.subs) > 0
    assert route["rows_max"] == max(s.nowned for s in prob.subs)
    assert route["ghosts_max"] == max(s.nghost for s in prob.subs) > 0
