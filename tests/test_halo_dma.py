"""Pallas remote-DMA halo transport (--comm dma) vs the XLA collective
transport, on the virtual CPU mesh (interpret mode).

The reference validates its NVSHMEM transport by running the same solve
with --comm mpi|nccl|nvshmem and comparing (scripts/*_combined.sh); these
tests do the same for xla vs dma.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from acg_tpu.io.generators import poisson2d_coo
from acg_tpu.matrix import SymCsrMatrix
from acg_tpu.parallel.dist import DistCGSolver, DistributedProblem
from acg_tpu.parallel.halo_dma import _exchange
from acg_tpu.parallel.mesh import PARTS_AXIS, solve_mesh
from acg_tpu.partition import partition_rows
from acg_tpu.solvers.stats import StoppingCriteria

NDEV = len(jax.devices())

pytestmark = pytest.mark.skipif(NDEV < 4, reason="needs a multi-device mesh")


def test_exchange_routes_all_pairs():
    """recvbuf[p, q] must equal sendbuf[q, p] for every pair."""
    nparts, maxcnt = 4, 3
    sb = np.zeros((nparts, nparts, maxcnt), np.float32)
    for p in range(nparts):
        for q in range(nparts):
            sb[p, q] = 100 * p + 10 * q + np.arange(maxcnt)
    scnt = jnp.full((nparts, nparts), maxcnt, jnp.int32)
    mesh = solve_mesh(nparts)
    pspec = P(PARTS_AXIS)

    def body(sbuf, sc, rc):
        return _exchange(sbuf[0], sc[0], rc[0], PARTS_AXIS, True)[None]

    f = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=(pspec,) * 3,
                                 out_specs=pspec, check_vma=False))
    out = np.asarray(f(jnp.asarray(sb), scnt, scnt))
    for p in range(nparts):
        for q in range(nparts):
            if q == p:
                continue
            np.testing.assert_allclose(out[p, q],
                                       100 * q + 10 * p + np.arange(maxcnt))


def test_exchange_count_gating_ring():
    """Count-gated puts on a ring neighbour structure (gate pattern
    globally uniform per rotation round, so interpret mode can run it):
    only real neighbours' rows arrive; the rest stay unwritten."""
    nparts, maxcnt = 4, 3
    sb = np.zeros((nparts, nparts, maxcnt), np.float32)
    for p in range(nparts):
        for q in range(nparts):
            sb[p, q] = 100 * p + 10 * q + np.arange(maxcnt)
    scnt = np.zeros((nparts, nparts), np.int32)
    for p in range(nparts):
        scnt[p, (p + 1) % nparts] = maxcnt
        scnt[p, (p - 1) % nparts] = maxcnt
    rcnt = scnt.T.copy()
    mesh = solve_mesh(nparts)
    pspec = P(PARTS_AXIS)

    def body(sbuf, sc, rc):
        return _exchange(sbuf[0], sc[0], rc[0], PARTS_AXIS, True,
                         gate_by_counts=True)[None]

    f = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=(pspec,) * 3,
                                 out_specs=pspec, check_vma=False))
    out = np.asarray(f(jnp.asarray(sb), jnp.asarray(scnt),
                       jnp.asarray(rcnt)))
    for p in range(nparts):
        for q in range(nparts):
            if scnt[q, p] > 0:
                np.testing.assert_allclose(
                    out[p, q], 100 * q + 10 * p + np.arange(maxcnt))


@pytest.fixture(scope="module")
def small_problem():
    r, c, v, N = poisson2d_coo(20)
    csr = SymCsrMatrix.from_coo(N, r, c, v).to_csr()
    nparts = min(NDEV, 8)
    part = partition_rows(csr, nparts, seed=0)
    prob = DistributedProblem.build(csr, part, nparts, dtype=jnp.float32)
    return csr, prob


def test_dma_matches_xla_transport(small_problem):
    csr, prob = small_problem
    N = csr.shape[0]
    rng = np.random.default_rng(1)
    xsol = rng.standard_normal(N).astype(np.float32)
    xsol /= np.linalg.norm(xsol)
    b = (csr @ xsol).astype(np.float32)
    crit = StoppingCriteria(maxits=60, residual_rtol=1e-4)
    xs = {}
    for comm in ("xla", "dma"):
        solver = DistCGSolver(prob, comm=comm)
        xs[comm] = solver.solve(b, criteria=crit)
        assert solver.stats.converged
    # same algorithm, same data, different transport: identical to f32
    # rounding noise
    np.testing.assert_allclose(xs["dma"], xs["xla"], atol=1e-5)


def test_dma_pipelined(small_problem):
    csr, prob = small_problem
    N = csr.shape[0]
    b = np.ones(N, np.float32)
    solver = DistCGSolver(prob, comm="dma", pipelined=True)
    x = solver.solve(b, criteria=StoppingCriteria(maxits=40))
    assert np.isfinite(x).all()
    assert solver.stats.niterations == 40


def test_dma_rejects_unknown_comm(small_problem):
    _, prob = small_problem
    with pytest.raises(ValueError):
        DistCGSolver(prob, comm="nvshmem")


def test_exchange_count_gating_distance2():
    """Count-gated puts with a two-ring neighbour structure (distances 1
    and 2, both directions -- gates uniform per rotation round, so
    interpret mode can execute the gated kernel): multiple gated
    neighbours per shard exercise the multi-round gating arithmetic the
    single-ring test cannot."""
    nparts, maxcnt = min(NDEV, 8), 3
    sb = np.zeros((nparts, nparts, maxcnt), np.float32)
    for p in range(nparts):
        for q in range(nparts):
            sb[p, q] = 100 * p + 10 * q + np.arange(maxcnt)
    scnt = np.zeros((nparts, nparts), np.int32)
    for p in range(nparts):
        for d in (1, 2):
            scnt[p, (p + d) % nparts] = maxcnt
            scnt[p, (p - d) % nparts] = maxcnt
    rcnt = scnt.T.copy()
    mesh = solve_mesh(nparts)
    pspec = P(PARTS_AXIS)

    def body(sbuf, sc, rc):
        return _exchange(sbuf[0], sc[0], rc[0], PARTS_AXIS, True,
                         gate_by_counts=True)[None]

    f = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=(pspec,) * 3,
                                 out_specs=pspec, check_vma=False))
    out = np.asarray(f(jnp.asarray(sb), jnp.asarray(scnt),
                       jnp.asarray(rcnt)))
    for p in range(nparts):
        for q in range(nparts):
            if scnt[q, p] > 0:
                np.testing.assert_allclose(
                    out[p, q], 100 * q + 10 * p + np.arange(maxcnt))


def _topology_partition(csr, kind, nparts, side):
    """Partition vectors with qualitatively different neighbour graphs."""
    n = csr.shape[0]
    if kind == "line":
        # chain of bands: each part talks to at most 2 neighbours
        from acg_tpu.partition import partition_rows_band
        return partition_rows_band(csr, nparts)
    if kind == "star":
        # hub-and-spokes: part 0 is a central patch touching every other
        part = np.zeros((side, side), np.int32)
        c0, c1 = side // 4, 3 * side // 4
        # spokes: quadrants
        part[: side // 2, : side // 2] = 1
        part[: side // 2, side // 2:] = 2
        part[side // 2:, : side // 2] = 3
        part[side // 2:, side // 2:] = min(4, nparts - 1)
        part[c0:c1, c0:c1] = 0  # hub overwrites the centre
        return part.reshape(-1) % nparts
    if kind == "clustered":
        # random scatter: dense neighbour graph, ragged window sizes
        return np.random.default_rng(0).integers(0, nparts, n).astype(np.int32)
    raise ValueError(kind)


def test_halo_exchange_dma_parity_8part(small_problem):
    """Interpret-mode parity of the TRANSPORT itself (not a whole
    solve): the ghost vector halo_exchange_dma delivers on the 8-part
    CPU mesh equals the xla all_to_all transport's, slot for slot
    (scripts/dma_probe.py promoted from a dated one-off note into CI).
    The dma unpack masks padding ghost slots (ghost_valid); the xla
    unpack reads zero-filled receive rows there, so both sides are
    comparable everywhere."""
    from acg_tpu.parallel.dist import DistCGSolver
    from acg_tpu.parallel.halo import halo_exchange
    from acg_tpu.parallel.halo_dma import halo_exchange_dma

    csr, prob = small_problem
    s = DistCGSolver(prob, comm="xla")
    b, x0, la, ga, sidx, gsrc, gval, scnt, rcnt = s.device_args(
        np.ones(prob.n))
    rng = np.random.default_rng(11)
    x = jax.device_put(
        prob.scatter(rng.standard_normal(prob.n).astype(np.float32)),
        jax.sharding.NamedSharding(s.mesh, P(PARTS_AXIS)))
    pspec = P(PARTS_AXIS)

    def body(sidx, gsrc, gval, scnt, rcnt, x):
        sidx, gsrc, gval, scnt, rcnt, x = (
            a[0] for a in (sidx, gsrc, gval, scnt, rcnt, x))
        g_dma = halo_exchange_dma(x, sidx, gsrc, gval, scnt, rcnt,
                                  PARTS_AXIS, interpret=True)
        g_xla = halo_exchange(x, sidx, gsrc, PARTS_AXIS)
        # mask the xla side like the dma unpack: padding slots beyond a
        # part's real ghost count are never consumed by the SpMV
        g_xla = jnp.where(gval, g_xla, 0)
        return g_dma[None], g_xla[None]

    f = jax.jit(jax.shard_map(body, mesh=s.mesh, in_specs=(pspec,) * 6,
                              out_specs=(pspec, pspec), check_vma=False))
    g_dma, g_xla = f(sidx, gsrc, gval, scnt, rcnt, x)
    np.testing.assert_array_equal(np.asarray(g_dma), np.asarray(g_xla))


def test_dma_to_xla_fallback_under_halo_fault(small_problem, monkeypatch):
    """The recovery ladder's transport rung under ``halo:`` fault
    injection: a breakdown that RECURS on the dma transport (a faulty
    one-sided link keeps corrupting payloads, so the first restart does
    not cure it) makes the driver retire dma for the xla collectives --
    its own rung, not billed to the restart budget -- and the solve
    converges there.  The injector's one-shot ``shift`` is patched to
    keep the fault armed exactly while the solver is still on dma: the
    persistent-transport-fault scenario the rung exists for."""
    from acg_tpu import faults
    from acg_tpu.parallel.dist import DistCGSolver
    from acg_tpu.solvers.resilience import RecoveryPolicy

    csr, prob = small_problem
    N = csr.shape[0]
    b = np.ones(N, np.float32)
    pol = RecoveryPolicy(max_restarts=3, fallback_comm=True,
                         fallback_host=False)
    solver = DistCGSolver(prob, comm="dma", recovery=pol)
    orig_shift = faults.FaultSpec.shift

    def shift_persistent_while_dma(spec, consumed):
        if solver.comm == "dma":
            return spec           # the faulty link keeps corrupting
        return orig_shift(spec, consumed)

    monkeypatch.setattr(faults.FaultSpec, "shift",
                        shift_persistent_while_dma)
    faults.install(faults.parse_fault_spec("halo:nan@5"))
    try:
        x = solver.solve(b, criteria=StoppingCriteria(
            maxits=200, residual_rtol=1e-4))
    finally:
        faults.install(None)
    st = solver.stats
    assert st.converged
    assert solver.comm == "xla", "dma transport was not retired"
    assert st.nfallbacks >= 1
    assert "dma -> xla" in st.fwrite()
    assert np.isfinite(x).all()


@pytest.mark.parametrize("kind", ["line", "star", "clustered"])
def test_dma_matches_xla_topologies(kind):
    """xla-vs-dma agreement across qualitatively different partition
    topologies (star/line/clustered): same solve, different transport,
    same answer.  The reference's mpi/nccl/nvshmem cross-validation
    (scripts/*_combined.sh) for varied communication patterns."""
    side = 24
    r, c, v, N = poisson2d_coo(side)
    csr = SymCsrMatrix.from_coo(N, r, c, v).to_csr()
    nparts = min(NDEV, 5)
    part = _topology_partition(csr, kind, nparts, side)
    nparts = int(part.max()) + 1
    prob = DistributedProblem.build(csr, part, nparts, dtype=jnp.float64)
    rng = np.random.default_rng(7)
    xsol = rng.standard_normal(N)
    xsol /= np.linalg.norm(xsol)
    b = csr @ xsol
    crit = StoppingCriteria(maxits=300, residual_rtol=1e-8)
    xs = {}
    for comm in ("xla", "dma"):
        solver = DistCGSolver(prob, comm=comm)
        xs[comm] = solver.solve(b, criteria=crit)
        assert solver.stats.converged
    np.testing.assert_allclose(xs["dma"], xs["xla"], atol=1e-9)
