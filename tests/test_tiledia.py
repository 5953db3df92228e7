"""Row-tiled DIA local blocks (``StackedLocalBlock`` format ``tiledia``):
graph partitions of stencil matrices, whose parts are diagonal within a
grid row but not across the part, stored as one plane per (row tile,
diagonal offset) segment and applied with no gather of x.

Small grids need small tiles for the planes to stay within the fill
limit, so most cases stack with a ``tile`` of 16 or 32 rows; the kernel
cases use the kernel's own tile (1024 rows) with the limit lifted."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from acg_tpu.io.generators import irregular_spd_coo, poisson_mtx
from acg_tpu.matrix import SymCsrMatrix
from acg_tpu.ops import pallas_kernels as pk
from acg_tpu.ops.spmv import tiledia_mv, tiledia_segments
from acg_tpu.parallel.dist import (TILEDIA_FILL_LIMIT, DistCGSolver,
                                   DistributedProblem, _stack_local_blocks,
                                   make_dist_spmv, make_dist_spmv_overlapped)
from acg_tpu.parallel.mesh import PARTS_AXIS
from acg_tpu.partition import partition_rows
from acg_tpu.solvers.stats import StoppingCriteria


def poisson(n, dim=2):
    return SymCsrMatrix.from_mtx(poisson_mtx(n, dim=dim)).to_csr()


def graph_problem(csr, nparts, tile=16, fill_limit=TILEDIA_FILL_LIMIT,
                  dtype=jnp.float64):
    """A graph partition of ``csr`` whose local blocks are stacked as
    row-tiled DIA with ``tile`` rows a tile."""
    part = partition_rows(csr, nparts, seed=0, method="graph")
    prob = DistributedProblem.build(csr, part, nparts, dtype=dtype)
    prob.local = _stack_local_blocks(prob.subs, prob.nmax_owned, dtype,
                                     tile=tile, fill_limit=fill_limit)
    assert prob.local.format == "tiledia"
    return prob


def part_arrays(block, p):
    return tuple(jnp.asarray(a[p]) for a in block.arrays)


def manufactured(csr, seed=0):
    rng = np.random.default_rng(seed)
    xsol = rng.standard_normal(csr.shape[0])
    xsol /= np.linalg.norm(xsol)
    return xsol, csr @ xsol


@pytest.mark.parametrize("dim,n", [(2, 40), (3, 12)])
@pytest.mark.parametrize("nparts", [4, 8])
def test_shard_mv_matches_scipy(dim, n, nparts):
    """Every part's block applied through ``shard_mv`` equals scipy's
    CSR product on its owned rows, and padding rows stay zero.  Graph
    bisection cuts the grid along diagonals, so some parts end in narrow
    tips whose tiles carry many more offsets than the rest: the
    segment counts per tile are ragged."""
    csr = poisson(n, dim)
    prob = graph_problem(csr, nparts, fill_limit=np.inf)
    blk = prob.local
    ntiles = -(-blk.nrows // blk.tile)
    counts = np.concatenate([np.bincount(t[t < ntiles])
                             for t in blk.arrays[2]])
    assert counts.max() > 2 * counts.min()
    rng = np.random.default_rng(1)
    for p, s in enumerate(prob.subs):
        x = rng.standard_normal(blk.nrows)
        y = np.asarray(blk.shard_mv(part_arrays(blk, p), jnp.asarray(x)))
        np.testing.assert_allclose(y[: s.nowned],
                                   s.A_local @ x[: s.nowned],
                                   rtol=1e-13, atol=1e-13)
        assert not y[s.nowned:].any()


def test_segments_dense_table_matches_unique():
    """Both ways of numbering the (tile, offset) pairs -- the dense
    table of a narrow band and the sort of a wide one -- give the same
    segments, sorted by tile, then offset."""
    r, c, v, N = irregular_spd_coo(600, avg_degree=6.0, seed=2)
    wide = SymCsrMatrix.from_coo(N, r, c, v).to_csr()
    narrow = poisson(30)
    for csr, tile in ((narrow, 16), (narrow, 1024), (wide, 8)):
        st, so, seg, rows = tiledia_segments(csr, tile)
        coo = csr.tocoo()
        keys = np.unique(np.stack([coo.row // tile, coo.col - coo.row]),
                         axis=1)
        np.testing.assert_array_equal(st, keys[0])
        np.testing.assert_array_equal(so, keys[1])
        np.testing.assert_array_equal(st[seg], rows // tile)
        np.testing.assert_array_equal(so[seg], csr.indices - rows)


@pytest.mark.parametrize("case,expect", [
    ("graph-stencil", {"tiledia"}),
    ("graph-stencil-small", {"ell"}),
    ("band-stencil", {"dia"}),
    ("random-sparse", {"ell", "binnedell"}),
])
def test_format_rule(case, expect):
    """The format follows from the blocks alone: a graph partition of a
    stencil stacks row-tiled DIA once its tiles hold few offsets (here a
    16-row tile of a 64^2 grid) and ELL where a tile would hold the
    whole small block's diagonals (the 1024-row tile of a 20^2 grid); a
    band partition keeps DIA; a random sparse matrix keeps ELL or binned
    ELL, its tiles holding hundreds of offsets."""
    nparts = 4
    if case == "random-sparse":
        r, c, v, N = irregular_spd_coo(3000, avg_degree=8.0, seed=0)
        csr = SymCsrMatrix.from_coo(N, r, c, v).to_csr()
    else:
        csr = poisson(20 if case == "graph-stencil-small" else 64)
    method = "band" if case == "band-stencil" else "graph"
    part = partition_rows(csr, nparts, seed=0, method=method)
    prob = DistributedProblem.build(csr, part, nparts, dtype=jnp.float64)
    tile = 1024 if case == "graph-stencil-small" else 16
    blk = _stack_local_blocks(prob.subs, prob.nmax_owned, jnp.float64,
                              tile=tile)
    assert blk.format in expect
    if blk.format == "tiledia":
        nnz = sum(s.A_local.nnz for s in prob.subs)
        assert blk.arrays[0].size <= TILEDIA_FILL_LIMIT * nnz


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_kernel_matches_xla_form(dtype):
    """The gather-free kernel (interpret mode) at its own 1024-row tile
    computes the XLA formulation's product in the same row order: equal
    in f32, and for bf16 storage widened to the same f32 sums."""
    csr = poisson(160)
    prob = graph_problem(csr, 4, tile=pk.TILEDIA_TILE, fill_limit=np.inf,
                         dtype=dtype)
    blk = prob.local
    assert pk.tiledia_fits(blk.nrows, blk.tile, blk.offsets, dtype)
    # more segments than one kernel chunk: the running sums cross steps
    assert blk.arrays[0].shape[1] > 256
    rng = np.random.default_rng(5)
    for p, s in enumerate(prob.subs):
        x = jnp.asarray(rng.standard_normal(blk.nrows), dtype)
        la = part_arrays(blk, p)
        y_x = np.asarray(tiledia_mv(*la, blk.offsets, x), np.float64)
        y_k = np.asarray(pk.tiledia_spmv(*la, x, blk.offsets,
                                         interpret=True), np.float64)
        np.testing.assert_allclose(y_k, y_x, rtol=1e-6, atol=1e-6)
        ref = s.A_local @ np.asarray(x, np.float64)[: s.nowned]
        tol = 1e-5 if dtype == jnp.float32 else 3e-2
        np.testing.assert_allclose(y_k[: s.nowned], ref, rtol=tol, atol=tol)


def test_kernel_tier_solve_agrees_with_xla_tier():
    """``kernels="pallas"`` (interpret off-TPU) takes the row-tiled
    kernel for the local block; a short solve agrees with the XLA
    tier's."""
    csr = poisson(96)
    prob = graph_problem(csr, 4, tile=pk.TILEDIA_TILE, fill_limit=np.inf,
                         dtype=jnp.float32)
    b = np.ones(csr.shape[0])
    crit = StoppingCriteria(maxits=12, residual_rtol=0.0)
    x_xla = DistCGSolver(prob, kernels="xla").solve(b, criteria=crit)
    sp = DistCGSolver(prob, kernels="pallas")
    assert sp.kernels == "pallas-interpret"
    x_pal = sp.solve(b, criteria=crit)
    np.testing.assert_allclose(x_pal, x_xla, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("pipelined", [False, True])
def test_solve_matches_manufactured(pipelined):
    csr = poisson(40)
    prob = graph_problem(csr, 4)
    xsol, b = manufactured(csr, 3)
    s = DistCGSolver(prob, pipelined=pipelined)
    x = s.solve(b, criteria=StoppingCriteria(maxits=2000,
                                             residual_rtol=1e-10))
    assert s.stats.converged
    assert np.linalg.norm(x - xsol) < 1e-7


def test_split_spmv_bitwise_equals_unsplit():
    """The fused tier's interior|border split selects rows of the same
    row-tiled product: bitwise equal to the unsplit SpMV."""
    csr = poisson(40)
    prob = graph_problem(csr, 4)
    unsplit = make_dist_spmv(prob, "xla", True)
    split = make_dist_spmv_overlapped(prob, "xla", True)
    s = DistCGSolver(prob, kernels="fused")
    _, _, la, ga4, sidx, gsrc, gval, scnt, rcnt = s.device_args(
        np.ones(prob.n))
    rng = np.random.default_rng(3)
    x = jax.device_put(
        prob.scatter(rng.standard_normal(prob.n)),
        jax.sharding.NamedSharding(s.mesh, P(PARTS_AXIS)))

    def run(f, ga):
        def body(la, ga, sidx, gsrc, gval, scnt, rcnt, x):
            la, ga = (jax.tree.map(lambda a: a[0], t) for t in (la, ga))
            rest = (a[0] for a in (sidx, gsrc, gval, scnt, rcnt, x))
            sidx, gsrc, gval, scnt, rcnt, x = rest
            return f(x, la, ga, sidx, gsrc, gval, scnt, rcnt)[None]

        specs = (P(PARTS_AXIS),) * 8
        return np.asarray(jax.jit(jax.shard_map(
            body, mesh=s.mesh, in_specs=specs, out_specs=P(PARTS_AXIS),
            check_vma=False))(la, ga, sidx, gsrc, gval, scnt, rcnt, x))

    np.testing.assert_array_equal(run(split, ga4), run(unsplit, ga4[:3]))


def test_fused_solve_trajectory_equals_unsplit():
    csr = poisson(40)
    prob = graph_problem(csr, 4)
    b = np.random.default_rng(1).standard_normal(csr.shape[0])
    crit = StoppingCriteria(maxits=300, residual_rtol=1e-9)
    ref = DistCGSolver(prob, kernels="xla")
    x_ref = ref.solve(b, criteria=crit)
    s = DistCGSolver(prob, kernels="fused")
    x = s.solve(b, criteria=crit)
    assert s.stats.converged and ref.stats.converged
    assert s.stats.niterations == ref.stats.niterations
    np.testing.assert_array_equal(x, x_ref)


@pytest.mark.parametrize("tile,dtype", [(16, jnp.float64),
                                        (pk.TILEDIA_TILE, jnp.float32)])
def test_local_mv_multi_matches_columns(tile, dtype):
    """The batched tier's multi-column apply equals ``shard_mv`` column
    by column, through the XLA formulation (f64) and through the kernel
    (f32 at the kernel's tile, interpret mode)."""
    from acg_tpu.parallel.dist_batched import _local_mv_multi

    csr = poisson(96 if tile == pk.TILEDIA_TILE else 40)
    prob = graph_problem(csr, 4, tile=tile, fill_limit=np.inf, dtype=dtype)
    blk = prob.local
    X = jnp.asarray(np.random.default_rng(2).standard_normal(
        (blk.nrows, 3)), dtype)
    tol = 1e-14 if dtype == jnp.float64 else 1e-6
    for p in range(prob.nparts):
        la = part_arrays(blk, p)
        Y = np.asarray(_local_mv_multi(blk, la, X))
        for c in range(X.shape[1]):
            y = np.asarray(blk.shard_mv(la, X[:, c]))
            np.testing.assert_allclose(Y[:, c], y, rtol=tol, atol=tol)


def test_batched_solver_runs_tiledia():
    from acg_tpu.parallel.dist_batched import BatchedDistCGSolver

    csr = poisson(40)
    prob = graph_problem(csr, 4)
    xsol, b = manufactured(csr, 4)
    B = np.stack([b, 2.0 * b], axis=1)
    s = BatchedDistCGSolver(prob)
    X = s.solve(B, criteria=StoppingCriteria(maxits=2000,
                                             residual_rtol=1e-10))
    np.testing.assert_allclose(X[:, 0], xsol, atol=1e-7)
    np.testing.assert_allclose(X[:, 1], 2.0 * xsol, atol=1e-7)


def test_jacobi_diagonal_and_preconditioned_solves():
    """The Jacobi state read from the planes is the inverse of each
    part's diagonal; jacobi and bjacobi solves converge."""
    from acg_tpu.precond import stacked_jacobi_state

    csr = poisson(40)
    prob = graph_problem(csr, 4)
    (dinv,) = stacked_jacobi_state(prob, np.float64)
    for p, s in enumerate(prob.subs):
        np.testing.assert_allclose(dinv[p, : s.nowned],
                                   1.0 / s.A_local.diagonal())
        assert not dinv[p, s.nowned:].any()
    xsol, b = manufactured(csr, 5)
    for pc in ("jacobi", "bjacobi:4"):
        s = DistCGSolver(prob, precond=pc)
        x = s.solve(b, criteria=StoppingCriteria(maxits=2000,
                                                 residual_rtol=1e-10))
        assert s.stats.converged, pc
        assert np.linalg.norm(x - xsol) < 1e-7, pc


def test_solve_route_counts_tiles_segments_and_fill():
    """The set-up log's route names the format with its tile, the
    largest part's segment count and the planes' fill over the local
    nonzeros."""
    from acg_tpu.cli import _solve_route

    csr = poisson(64)
    prob = graph_problem(csr, 4)
    got = _solve_route(DistCGSolver(prob))
    planes, _, seg_tile = prob.local.arrays
    ntiles = -(-prob.nmax_owned // 16)
    nnz = sum(s.A_local.nnz for s in prob.subs)
    assert got["spmv_route"] == "tiledia"
    assert got["spmv_tile"] == 16
    assert got["spmv_segments_max"] == max(
        int((t < ntiles).sum()) for t in seg_tile)
    assert got["spmv_fill"] == pytest.approx(planes.size / nnz)
    assert 1.0 < got["spmv_fill"] <= TILEDIA_FILL_LIMIT


@pytest.mark.parametrize("given_x0", [False, True])
@pytest.mark.parametrize("tier", ["auto", "fused", "batched"])
def test_operator_placed_once_per_solver(tier, given_x0):
    """A mesh solver places its operator (blocks, ghost block, halo plan,
    counts, zero x0) once and holds it: two solves with different b on
    one solver are bitwise those of two fresh solvers, ``device_args``
    hands back the same operator arrays each time, the stats count one
    placement, and a given x0 is still the one the solve starts from."""
    from acg_tpu.parallel.dist_batched import BatchedDistCGSolver

    csr = poisson(32)
    prob = graph_problem(csr, 4)
    rng = np.random.default_rng(7)
    cols = 2 if tier == "batched" else 1
    shape = (prob.n, cols) if cols > 1 else (prob.n,)
    bs = [rng.standard_normal(shape) for _ in range(2)]
    x0 = rng.standard_normal(shape) if given_x0 else None
    crit = StoppingCriteria(maxits=6, residual_rtol=0.0)

    def make():
        if tier == "batched":
            return BatchedDistCGSolver(prob)
        return DistCGSolver(prob, kernels=tier)

    s = make()
    held = [s.device_args(b, x0) for b in bs]
    xs = [s.solve(b, x0=x0, criteria=crit) for b in bs]
    held.append(s.device_args(bs[0], x0))
    for b, x in zip(bs, xs):
        np.testing.assert_array_equal(x, make().solve(b, x0=x0,
                                                      criteria=crit))
    assert not np.array_equal(xs[0], xs[1])
    first = jax.tree.leaves(held[0][2:])
    for dev in held[1:]:
        assert all(a is c for a, c in zip(jax.tree.leaves(dev[2:]), first))
    if tier != "batched" and not given_x0:
        assert all(dev[1] is held[0][1] for dev in held)
    assert s.stats.operator_placements == 1
    assert s.stats.to_dict()["operator_placements"] == 1
    assert s.stats.timings["place"] > 0
    x0nrm2 = 0.0 if x0 is None else np.linalg.norm(x0, axis=0).max()
    assert s.stats.x0nrm2 == pytest.approx(x0nrm2, rel=1e-12)
