"""Pallas kernel correctness (interpret mode on the CPU mesh) against the
XLA formulations, and the kernels="pallas" solver path end to end.

The reference validates its device-kernel tier operationally through the
manufactured-solution flow (SURVEY.md section 4); here each kernel also
gets a direct unit oracle.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from acg_tpu.io.generators import poisson_mtx
from acg_tpu.matrix import SymCsrMatrix
from acg_tpu.ops.pallas_kernels import dia_spmv, fused_pipelined_update
from acg_tpu.ops.spmv import device_matrix_from_csr, dia_mv
from acg_tpu.solvers import HostCGSolver, StoppingCriteria
from acg_tpu.solvers.jax_cg import JaxCGSolver


from acg_tpu.ops.pallas_kernels import TILE


@pytest.mark.parametrize("n,offsets", [
    (1000, (-32, -1, 0, 1, 32)),          # ragged: padded fallback
    (20000, (-141, -1, 0, 1, 141)),       # ragged: padded fallback
    (500, (0,)),
    (700, (-3, 2)),                        # asymmetric offsets
    (2 * TILE, (-128, -1, 0, 1, 128)),     # fast path, 2 tiles
    (TILE, (-64, 0, 64)),                  # fast path, single tile
    (4 * TILE, (-TILE, -1, 0, 1, TILE)),   # fast path, band == tile
])
def test_dia_spmv_matches_xla(n, offsets):
    rng = np.random.default_rng(0)
    planes = tuple(jnp.asarray(rng.standard_normal(n), jnp.float32)
                   for _ in offsets)
    x = jnp.asarray(rng.standard_normal(n), jnp.float32)
    want = dia_mv(planes, offsets, n, x)
    got = dia_spmv(planes, offsets, x, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_fused_pipelined_update_matches_xla():
    rng = np.random.default_rng(1)
    n = 20000
    x, r, w, p, t, z, q = (jnp.asarray(rng.standard_normal(n), jnp.float32)
                           for _ in range(7))
    a, b = jnp.float32(0.37), jnp.float32(0.81)
    zn = q + b * z
    tn = w + b * t
    pn = r + b * p
    want = (x + a * pn, r - a * tn, w - a * zn, pn, tn, zn)
    got = fused_pipelined_update(x, r, w, p, t, z, q, a, b, interpret=True)
    for g, wv in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(wv),
                                   rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("pipelined", [False, True])
def test_solver_pallas_kernels_match_host(pipelined):
    """kernels="pallas" (interpret mode off-TPU) must solve to the same
    answer as the host oracle."""
    A = SymCsrMatrix.from_mtx(poisson_mtx(20, dim=2))
    csr = A.to_csr()
    rng = np.random.default_rng(2)
    xsol = rng.standard_normal(csr.shape[0])
    xsol /= np.linalg.norm(xsol)
    b = csr @ xsol
    dev = device_matrix_from_csr(csr, dtype=jnp.float64)
    solver = JaxCGSolver(dev, pipelined=pipelined, kernels="pallas")
    assert solver.kernels == "pallas-interpret"  # CPU in CI
    x = solver.solve(b, criteria=StoppingCriteria(maxits=2000,
                                                  residual_rtol=1e-10))
    assert np.linalg.norm(x - xsol) < 1e-6


def test_solver_auto_kernels_off_tpu_is_xla():
    A = SymCsrMatrix.from_mtx(poisson_mtx(8, dim=2))
    dev = device_matrix_from_csr(A.to_csr(), dtype=jnp.float64)
    assert jax.default_backend() != "tpu"  # CPU mesh in CI
    assert JaxCGSolver(dev, kernels="auto").kernels == "xla"


def _plane_order_f32(planes, offsets, x):
    """The kernel's arithmetic in numpy: ``acc = acc + plane * x[i+off]``
    over the planes in ``offsets`` order, in f32, x zero off either end;
    returns the f32 accumulator (the kernel stores it in x's dtype)."""
    n = x.shape[0]
    xs = np.asarray(x).astype(np.float32)
    acc = np.zeros(n, np.float32)
    for plane, off in zip(planes, offsets):
        shifted = np.zeros(n, np.float32)
        lo, hi = max(0, -off), min(n, n - off)
        shifted[lo:hi] = xs[lo + off:hi + off]
        acc = acc + np.asarray(plane).astype(np.float32) * shifted
    return acc


# (n, offsets, tile): far offsets one, two and four tiles away, grids of
# 2s+1 tiles or fewer (every tile an edge tile of the ring) and longer
# ones (the ring wraps), one-sided far clusters, a central cluster with
# no left halo, and the grown tile.  ``tile=None`` takes the route's
# tile through the public entry points; a shift of one tile is never
# routed (a window of that band fits VMEM as the cheaper "fast" route),
# so those cases give the kernel its tile
@pytest.mark.parametrize("n,offsets,tile,shift", [
    (3 * TILE, (-TILE, -3, 0, 3, TILE), TILE, 1),
    (8 * TILE, (-TILE, -3, 0, 3, TILE), TILE, 1),
    (3 * TILE, (-2 * TILE, 0, 5), None, 2),
    (5 * TILE, (-2 * TILE, -3, 0, 3, 2 * TILE), None, 2),
    (7 * TILE, (-3, 0, 3, 2 * TILE), None, 2),
    (9 * TILE, (-4 * TILE, -3, 0, 3, 4 * TILE), None, 4),
    (64 * TILE, (-32 * TILE, -3, 0, 3, 32 * TILE), None, None),
], ids=["s1-grid3", "s1-grid8", "s2-grid3-noleft", "s2-grid5",
        "s2-grid7-right", "s4-grid9", "grown"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("with_dot", [False, True], ids=["y", "dot"])
def test_dia_spmv_clustered_route_and_numerics(n, offsets, tile, shift,
                                               dtype, with_dot):
    """Clustered-offset stencils (3D Poisson shape: far +-n^2 diagonals)
    take the ring kernel; ``y`` must equal the plane-order f32
    evaluation bit for bit, including the zero-filled tiles off both
    edges, and the fused dot the f64 dot."""
    from acg_tpu.ops.pallas_kernels import (_dia_spmv_clustered,
                                            dia_spmv_dot, dia_spmv_route)

    # the real 512^3 shape routes clustered, on one chip and as the
    # four-chip shard window (a quarter of the rows plus +-n^2 halos)
    for rows in (512 ** 3, 512 ** 3 // 4 + 2 * 512 ** 2):
        r = dia_spmv_route((-262144, -512, -1, 0, 1, 512, 262144),
                           rows, np.float32)
        assert r == ("clustered", (-512, -1, 0, 1, 512),
                     (-262144, 262144), 1024, 1024, 131072, 1024)
    # off-tile far offsets cannot cluster -> xla fallback
    assert dia_spmv_route((-32 * TILE + 7, 0, 1), 64 * TILE,
                          np.float32)[0] == "xla"

    # values k/64 (k < 64): every product and partial sum is exact in
    # f32, so the comparison is bitwise whether or not a backend
    # contracts the multiply-adds (the CPU's does, shape by shape), and
    # a wrong x position still shows
    rng = np.random.default_rng(0)

    def draw():
        return jnp.asarray(rng.integers(1, 64, n) / 64, dtype)

    planes = tuple(draw() for _ in offsets)
    x = draw()
    if tile is None:
        route = dia_spmv_route(offsets, n, dtype)
        assert route[0] == "clustered"
        if shift is not None:
            assert {abs(o) // route[5] for o in route[2]} == {shift}
        if with_dot:
            y, d = dia_spmv_dot(planes, offsets, x, interpret=True)
        else:
            y = dia_spmv(planes, offsets, x, interpret=True)
    else:
        align = 1024 if dtype == jnp.float32 else 2048
        far = tuple(o for o in offsets if abs(o) >= TILE)
        out = _dia_spmv_clustered(planes, offsets, x, far, align, align,
                                  tile, align, True, with_dot=with_dot)
        y, d = (out[0], out[1][0]) if with_dot else (out, None)
    acc = _plane_order_f32(planes, offsets, x)
    np.testing.assert_array_equal(np.asarray(y),
                                  acc.astype(np.asarray(x).dtype))
    if with_dot:
        # f64 ground truth, as in test_dia_spmv_dot_fused; the public
        # entry returns the scalar in bf16 storage's own precision
        dref = float(np.asarray(x, np.float64) @ acc.astype(np.float64))
        rel = 2 ** -8 if tile is None and dtype == jnp.bfloat16 else 3e-4
        assert float(d) == pytest.approx(dref, rel=rel)


@pytest.mark.parametrize("n,offsets,route,reads", [
    (64 * TILE, (-32 * TILE, -3, 0, 3, 32 * TILE), "clustered", 1.0),
    (2 * TILE, (-2048, -1, 0, 1, 2048), "fast", (TILE + 2 * 2048) / TILE),
], ids=["clustered", "fast"])
def test_solve_route_reports_spmv_x_reads(n, offsets, route, reads):
    """The solve's route report (the ``--stats-json`` manifest and the
    benchmark's set-up log) gives the HBM reads of x per SpMV: once on
    the clustered route's ring, the body plus its band halos on the
    fast route (1.25 for the 2D n=2048 stencil)."""
    from acg_tpu.cli import _solve_route
    from acg_tpu.ops.spmv import DiaMatrix

    planes = tuple(jnp.zeros((n,), jnp.float32) for _ in offsets)
    A = DiaMatrix(data=planes, offsets=offsets, nrows=n, ncols_padded=n)
    got = _solve_route(JaxCGSolver(A, kernels="pallas"))
    assert got["spmv_route"] == "dia-" + route
    assert got["spmv_x_reads"] == reads


def test_dia_spmv_dot_fused():
    """Fused (y, dot(x,y)) matches separate ops on every route, and the
    classic solver using the pallas tier (which routes through it)
    still matches the host oracle."""
    import numpy as np

    from acg_tpu.ops.pallas_kernels import TILE, dia_spmv_dot
    from acg_tpu.ops.spmv import dia_mv

    rng = np.random.default_rng(1)
    for n, offsets in [(3 * TILE, (-3, -1, 0, 1, 3)),
                       (64 * TILE, (-32 * TILE, -3, 0, 3, 32 * TILE)),
                       (1000, (-3, 0, 3))]:
        planes = tuple(jnp.asarray(rng.random(n), jnp.float32)
                       for _ in offsets)
        x = jnp.asarray(rng.random(n), jnp.float32)
        y, d = dia_spmv_dot(planes, offsets, x, interpret=True)
        yref = dia_mv(planes, offsets, n, x)
        np.testing.assert_allclose(np.asarray(y), np.asarray(yref),
                                   rtol=2e-6)
        # f64 ground truth: both the fused f32 accumulation and XLA's
        # pairwise f32 dot carry ~sqrt(n)*eps error in different
        # directions; compare each to the exact value instead
        dref = float(np.asarray(x, np.float64)
                     @ np.asarray(yref, np.float64))
        assert float(d) == pytest.approx(dref, rel=3e-4)


def test_classic_solver_pallas_tier_matches_xla():
    """End-to-end: JaxCGSolver(kernels=pallas) on a DIA matrix solves
    to the same answer as the xla tier."""
    import numpy as np

    from acg_tpu.io.generators import poisson2d_coo
    from acg_tpu.matrix import SymCsrMatrix
    from acg_tpu.ops.spmv import device_matrix_from_csr
    from acg_tpu.solvers.jax_cg import JaxCGSolver
    from acg_tpu.solvers.stats import StoppingCriteria

    r, c, v, N = poisson2d_coo(24)
    csr = SymCsrMatrix.from_coo(N, r, c, v).to_csr()
    A = device_matrix_from_csr(csr, dtype=jnp.float64, format="dia")
    b = np.ones(N)
    crit = StoppingCriteria(maxits=2000, residual_rtol=1e-10)
    x_xla = JaxCGSolver(A, kernels="xla").solve(b, criteria=crit)
    x_pal = JaxCGSolver(A, kernels="pallas").solve(b, criteria=crit)
    np.testing.assert_allclose(x_pal, x_xla, atol=1e-9)
