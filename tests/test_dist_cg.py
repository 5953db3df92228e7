"""Distributed CG over the virtual 8-device CPU mesh vs serial oracles.

The analog of the reference's np=1,2,4,8 operational testing (SURVEY.md
section 4): the same partitioned solve runs over a real (simulated) mesh
with communication exercised, checked against the host solver.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from acg_tpu.graph import partition_matrix
from acg_tpu.io.generators import poisson_mtx
from acg_tpu.matrix import SymCsrMatrix
from acg_tpu.parallel.dist import DistCGSolver, DistributedProblem
from acg_tpu.parallel.halo import build_device_halo, halo_exchange
from acg_tpu.parallel.mesh import PARTS_AXIS, solve_mesh
from acg_tpu.partition import partition_rows
from acg_tpu.solvers import HostCGSolver, StoppingCriteria
from jax.sharding import PartitionSpec as P


@pytest.fixture(scope="module")
def problem2d():
    A = SymCsrMatrix.from_mtx(poisson_mtx(20, dim=2))
    return A.to_csr()


@pytest.fixture(scope="module")
def problem3d():
    A = SymCsrMatrix.from_mtx(poisson_mtx(7, dim=3))
    return A.to_csr()


def manufactured(csr, seed=0):
    rng = np.random.default_rng(seed)
    xsol = rng.standard_normal(csr.shape[0])
    xsol /= np.linalg.norm(xsol)
    return xsol, csr @ xsol


def test_device_count():
    assert len(jax.devices()) >= 8, "conftest must provide 8 virtual devices"


@pytest.mark.parametrize("nparts", [2, 8])
def test_device_halo_exchange(problem2d, nparts):
    """Device halo exchange must deliver exactly the host-plan ghosts."""
    part = partition_rows(problem2d, nparts, seed=0)
    subs = partition_matrix(problem2d, part, nparts)
    halo = build_device_halo(subs)
    nmax = max(s.nowned for s in subs)
    xg = np.random.default_rng(1).standard_normal(problem2d.shape[0])
    stacked = np.zeros((nparts, nmax))
    for p, s in enumerate(subs):
        stacked[p, : s.nowned] = xg[s.global_ids[: s.nowned]]

    mesh = solve_mesh(nparts)
    ghost = jax.jit(jax.shard_map(
        lambda x, si, gs: halo_exchange(x[0], si[0], gs[0])[None],
        mesh=mesh,
        in_specs=(P(PARTS_AXIS),) * 3,
        out_specs=P(PARTS_AXIS), check_vma=False))(
            jnp.asarray(stacked), halo.send_idx, halo.ghost_src)
    ghost = np.asarray(ghost)
    for p, s in enumerate(subs):
        np.testing.assert_array_equal(ghost[p, : s.nghost],
                                      xg[s.global_ids[s.nowned:]])


@pytest.mark.parametrize("pipelined", [False, True])
@pytest.mark.parametrize("nparts", [1, 2, 8])
def test_dist_cg_matches_host(problem2d, nparts, pipelined):
    xsol, b = manufactured(problem2d, 2)
    part = partition_rows(problem2d, nparts, seed=1)
    prob = DistributedProblem.build(problem2d, part, nparts, dtype=jnp.float64)
    solver = DistCGSolver(prob, pipelined=pipelined)
    crit = StoppingCriteria(maxits=3000, residual_rtol=1e-10)
    x = solver.solve(b, criteria=crit)
    assert solver.stats.converged
    assert np.linalg.norm(x - xsol) < 1e-7

    host = HostCGSolver(SymCsrMatrix.from_coo(
        problem2d.shape[0], *_coo(problem2d)))
    host.solve(b, criteria=crit)
    assert abs(solver.stats.niterations - host.stats.niterations) <= 5


def _coo(csr):
    coo = csr.tocoo()
    return coo.row, coo.col, coo.data


def test_dist_cg_3d(problem3d):
    xsol, b = manufactured(problem3d, 3)
    part = partition_rows(problem3d, 8, seed=2)
    prob = DistributedProblem.build(problem3d, part, 8, dtype=jnp.float64)
    solver = DistCGSolver(prob)
    x = solver.solve(b, criteria=StoppingCriteria(maxits=2000, residual_rtol=1e-9))
    assert np.linalg.norm(x - xsol) < 1e-6


def test_dist_cg_irregular_partition_sizes(problem2d):
    """Parts of very different sizes exercise the padding invariants."""
    n = problem2d.shape[0]
    part = np.zeros(n, dtype=np.int32)
    part[n // 8:] = 1
    part[n // 2:] = 2
    prob = DistributedProblem.build(problem2d, part, 3, dtype=jnp.float64)
    xsol, b = manufactured(problem2d, 4)
    solver = DistCGSolver(prob)
    x = solver.solve(b, criteria=StoppingCriteria(maxits=3000, residual_rtol=1e-9))
    assert np.linalg.norm(x - xsol) < 1e-6


def test_dist_cg_maxits_only(problem2d):
    part = partition_rows(problem2d, 4, seed=3)
    prob = DistributedProblem.build(problem2d, part, 4, dtype=jnp.float64)
    solver = DistCGSolver(prob)
    solver.solve(np.ones(problem2d.shape[0]),
                 criteria=StoppingCriteria(maxits=17))
    assert solver.stats.niterations == 17
    assert solver.stats.converged
    assert solver.stats.ops["halo"].n == 18
    assert solver.stats.ops["allreduce"].n == 34


def test_dist_cg_stats_report(problem2d):
    part = partition_rows(problem2d, 2, seed=4)
    prob = DistributedProblem.build(problem2d, part, 2, dtype=jnp.float64)
    solver = DistCGSolver(prob, pipelined=True)
    solver.solve(np.ones(problem2d.shape[0]),
                 criteria=StoppingCriteria(maxits=500, residual_rtol=1e-8))
    text = solver.stats.fwrite()
    assert "total solver time: " in text
    assert solver.stats.ops["allreduce"].n == solver.stats.niterations


# -- stacked block formats (DIA local / compact ghost) ----------------------

@pytest.mark.parametrize("pipelined", [False, True])
def test_dist_cg_band_partition_dia(problem2d, pipelined):
    """A contiguous band partition of a banded matrix must select the
    gather-free DIA local format (the fast TPU path, ops/spmv.py) and
    still match the host solver."""
    nparts = 4
    part = partition_rows(problem2d, nparts, seed=0, method="band")
    prob = DistributedProblem.build(problem2d, part, nparts,
                                    dtype=jnp.float64)
    assert prob.local.format == "dia"
    assert len(prob.local.offsets) <= 5  # 5-point stencil
    xsol, b = manufactured(problem2d)
    solver = DistCGSolver(prob, pipelined=pipelined)
    x = solver.solve(b, criteria=StoppingCriteria(maxits=2000,
                                                  residual_rtol=1e-10))
    assert np.linalg.norm(x - xsol) < 1e-6


def test_dist_cg_scattered_partition_falls_back_to_ell(problem2d):
    """A partition with non-contiguous parts cannot stay banded; the
    builder must fall back to ELL and still solve correctly."""
    n = problem2d.shape[0]
    # pathological random scatter (round-robin would still be banded:
    # stride-4 owned sets keep the +-n stencil neighbours on diagonals)
    part = np.random.default_rng(0).integers(0, 4, n).astype(np.int32)
    prob = DistributedProblem.build(problem2d, part, 4, dtype=jnp.float64)
    assert prob.local.format == "ell"
    xsol, b = manufactured(problem2d)
    solver = DistCGSolver(prob)
    x = solver.solve(b, criteria=StoppingCriteria(maxits=2000,
                                                  residual_rtol=1e-10))
    assert np.linalg.norm(x - xsol) < 1e-6


def test_dist_ghost_block_is_compact(problem2d):
    """The ghost block must cover only coupled (border) rows, not all
    owned rows (the reference's border-rows-only o* block)."""
    part = partition_rows(problem2d, 4, seed=0, method="band")
    prob = DistributedProblem.build(problem2d, part, 4, dtype=jnp.float64)
    nmax_owned = prob.nmax_owned
    assert prob.ghost.bmax < nmax_owned / 2
    # padding row indices are out of bounds -> dropped by scatter-add
    rows = np.asarray(prob.ghost.rows)
    assert rows.max() <= nmax_owned


def test_dist_cg_pallas_kernel_tier(problem2d):
    """kernels="pallas" (interpret off-TPU) on a band partition (DIA
    local blocks) agrees with the XLA tier -- the distributed analog of
    the single-device Pallas SpMV tests."""
    csr = problem2d
    xsol, b = manufactured(csr)
    part = partition_rows(csr, 4, seed=0, method="band")
    prob = DistributedProblem.build(csr, part, 4, dtype=jnp.float64)
    assert prob.local.format == "dia"
    crit = StoppingCriteria(maxits=2000, residual_rtol=1e-10)
    x_xla = DistCGSolver(prob, kernels="xla").solve(b, criteria=crit)
    sp = DistCGSolver(prob, kernels="pallas")
    assert sp.kernels == "pallas-interpret"  # CPU mesh resolves interpret
    x_pal = sp.solve(b, criteria=crit)
    assert np.linalg.norm(x_pal - xsol) < 1e-8
    np.testing.assert_allclose(x_pal, x_xla, rtol=0, atol=1e-9)


def test_dist_cg_pallas_falls_back_on_ell(problem2d):
    """Graph partitions give ELL local blocks; the pallas tier must fall
    back to the XLA path (same contract as the single-device solver for
    non-DIA matrices)."""
    csr = problem2d
    xsol, b = manufactured(csr, seed=2)
    part = partition_rows(csr, 4, seed=0, method="graph")
    prob = DistributedProblem.build(csr, part, 4, dtype=jnp.float64)
    if prob.local.format == "dia":
        pytest.skip("graph partition unexpectedly banded")
    x = DistCGSolver(prob, kernels="pallas").solve(
        b, criteria=StoppingCriteria(maxits=2000, residual_rtol=1e-10))
    assert np.linalg.norm(x - xsol) < 1e-8


def test_dist_binned_ell_local_blocks():
    """Power-law (SuiteSparse-class) workloads trigger the length-binned
    local-block layout on the mesh (round-4 verdict item 3): plain-ELL
    hub-row padding would blow the waste limit.  Solve must match the
    serial oracle, and the format must report binnedell."""
    from acg_tpu.io.generators import irregular_spd_coo
    from acg_tpu.matrix import SymCsrMatrix

    r, c, v, N = irregular_spd_coo(3000, avg_degree=8.0, seed=0)
    csr = SymCsrMatrix.from_coo(N, r, c, v).to_csr()
    Kmax = int(np.diff(csr.indptr).max())
    assert Kmax * N > 3.0 * csr.nnz  # the workload really is skewed
    xsol, b = manufactured(csr, seed=1)
    iters = []
    for nparts in (1, 4, 8):
        part = partition_rows(csr, nparts, seed=0, method="graph")
        prob = DistributedProblem.build(csr, part, nparts,
                                        dtype=jnp.float64)
        assert prob.local.format == "binnedell"
        # mesh-uniform: every bin array's leading axis is nparts and
        # every part's padding rows are out-of-bounds sentinels
        bin_rows = prob.local.arrays[0]
        assert all(a.shape[0] == nparts for a in bin_rows)
        solver = DistCGSolver(prob)
        x = solver.solve(b, criteria=StoppingCriteria(
            maxits=4000, residual_rtol=1e-10))
        assert np.linalg.norm(x - xsol) < 1e-6
        iters.append(solver.stats.niterations)
    # partition-invariant iteration counts (up to rounding)
    assert max(iters) - min(iters) <= max(2, int(0.02 * max(iters)))


def test_dist_binned_ell_matches_ell_spmv():
    """The binned stacked SpMV is numerically the same operator as the
    plain-ELL stacked SpMV on the same problem (format is a layout
    choice, not an arithmetic one)."""
    from acg_tpu.io.generators import irregular_spd_coo
    from acg_tpu.matrix import SymCsrMatrix
    from acg_tpu.parallel.dist import _stack_local_blocks

    r, c, v, N = irregular_spd_coo(1000, avg_degree=6.0, seed=3)
    csr = SymCsrMatrix.from_coo(N, r, c, v).to_csr()
    part = partition_rows(csr, 4, seed=0, method="graph")
    prob = DistributedProblem.build(csr, part, 4, dtype=jnp.float64)
    assert prob.local.format == "binnedell"
    # force the plain-ELL stacking of the same subdomains
    ell = _stack_local_blocks(prob.subs, prob.nmax_owned, jnp.float64,
                              ell_waste_limit=1e9)
    assert ell.format == "ell"
    rng = np.random.default_rng(0)
    for p in range(4):
        x = rng.standard_normal(prob.nmax_owned)
        y_bell = prob.local.shard_mv(
            jax.tree.map(lambda a: jnp.asarray(a[p]), prob.local.arrays),
            jnp.asarray(x))
        y_ell = ell.shard_mv(
            jax.tree.map(lambda a: jnp.asarray(a[p]), ell.arrays),
            jnp.asarray(x))
        np.testing.assert_allclose(np.asarray(y_bell), np.asarray(y_ell),
                                   rtol=0, atol=1e-12)


def test_refined_distributed_solver(problem2d):
    """Mixed-precision refinement over the DISTRIBUTED solver (the CLI's
    --refine --nparts N path): f32 device CG + f64 host residual reaches
    f64-class accuracy."""
    from acg_tpu.solvers.refine import RefinedSolver

    csr = problem2d
    xsol, b = manufactured(csr, seed=4)
    part = partition_rows(csr, 4, seed=0, method="band")
    prob = DistributedProblem.build(csr, part, 4, dtype=jnp.float32)
    inner = DistCGSolver(prob)
    solver = RefinedSolver(inner, csr, inner_rtol=1e-5)
    x = solver.solve(b, criteria=StoppingCriteria(maxits=4000,
                                                  residual_rtol=1e-10))
    assert np.linalg.norm(x - xsol) < 1e-7  # beyond f32's ~1e-6 stall
