"""Distributed fused iteration (``kernels='fused'`` on the mesh tier):
interior|border overlapped SpMV with the halo exchange in flight.

ISSUE 13 acceptance: the fused tier is the builder's classic/pipelined
emission over ``make_dist_spmv_overlapped`` -- the halo puts are issued
first, the interior rows' SpMV runs while they are in flight, and the
border rows are finished after the receive side lands (the reference's
device-initiated interior/border split, ``cg-kernels-cuda.cu:713-899``).
The split is BITWISE equal to the unsplit SpMV per row, so the fused
programs' trajectories equal the unsplit ones exactly; the armed
collective counts are pinned at the HLO level and the disarmed
(``kernels='auto'``) program lowers byte-identical to the xla tier.
"""

import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from acg_tpu.errors import AcgError
from acg_tpu.io.generators import poisson2d_coo
from acg_tpu.matrix import SymCsrMatrix
from acg_tpu.parallel.dist import (DistCGSolver, DistributedProblem,
                                   interior_border_split, make_dist_spmv,
                                   make_dist_spmv_overlapped)
from acg_tpu.parallel.mesh import PARTS_AXIS
from acg_tpu.partition import partition_rows
from acg_tpu.solvers.stats import StoppingCriteria

NDEV = len(jax.devices())

pytestmark = pytest.mark.skipif(NDEV < 4, reason="needs a multi-device mesh")


def _problem(side=20, nparts=None, method="band", dtype=jnp.float64):
    r, c, v, N = poisson2d_coo(side)
    csr = SymCsrMatrix.from_coo(N, r, c, v).to_csr()
    nparts = min(NDEV, 8) if nparts is None else nparts
    part = partition_rows(csr, nparts, seed=0, method=method)
    return csr, DistributedProblem.build(csr, part, nparts, dtype=dtype)


@pytest.fixture(scope="module")
def problem():
    return _problem()


def test_interior_border_split_partitions_owned_rows(problem):
    """Interior + border must partition each part's owned rows: the
    split is exhaustive and disjoint, with border == the stacked ghost
    block's coupled-row list."""
    _, prob = problem
    irows = interior_border_split(prob)
    brows = np.asarray(prob.ghost.rows)
    for p, s in enumerate(prob.subs):
        ir = irows[p][irows[p] < prob.nmax_owned]
        br = brows[p][brows[p] < prob.nmax_owned]
        assert np.intersect1d(ir, br).size == 0
        got = np.sort(np.concatenate([ir, br]))
        np.testing.assert_array_equal(got, np.arange(s.nowned))
        # every border row really couples to ghosts
        coupled = np.flatnonzero(np.diff(s.A_ghost.indptr))
        np.testing.assert_array_equal(br, coupled)


@pytest.mark.parametrize("comm", ["xla", "dma"])
def test_split_spmv_bitwise_equals_unsplit(problem, comm):
    """The acceptance pin: interior+border results are bitwise equal to
    the unsplit SpMV on the multi-part CPU mesh, for both transports."""
    csr, prob = problem
    interpret = True
    unsplit = make_dist_spmv(prob, comm, interpret)
    split = make_dist_spmv_overlapped(prob, comm, interpret)
    s = DistCGSolver(prob, kernels="fused", comm=comm)
    b, x0, la, ga4, sidx, gsrc, gval, scnt, rcnt = s.device_args(
        np.ones(prob.n))
    ga3 = ga4[:3]
    rng = np.random.default_rng(3)
    x = jax.device_put(
        prob.scatter(rng.standard_normal(prob.n)),
        jax.sharding.NamedSharding(s.mesh, P(PARTS_AXIS)))
    pspec = P(PARTS_AXIS)

    def body_unsplit(la, ga, sidx, gsrc, gval, scnt, rcnt, x):
        la, ga = (jax.tree.map(lambda a: a[0], t) for t in (la, ga))
        sidx, gsrc, gval, scnt, rcnt, x = (
            a[0] for a in (sidx, gsrc, gval, scnt, rcnt, x))
        return unsplit(x, la, ga, sidx, gsrc, gval, scnt, rcnt)[None]

    def body_split(la, ga, sidx, gsrc, gval, scnt, rcnt, x):
        la, ga = (jax.tree.map(lambda a: a[0], t) for t in (la, ga))
        sidx, gsrc, gval, scnt, rcnt, x = (
            a[0] for a in (sidx, gsrc, gval, scnt, rcnt, x))
        return split(x, la, ga, sidx, gsrc, gval, scnt, rcnt)[None]

    specs = (pspec,) * 8
    yu = jax.jit(jax.shard_map(body_unsplit, mesh=s.mesh, in_specs=specs,
                               out_specs=pspec, check_vma=False))(
        la, ga3, sidx, gsrc, gval, scnt, rcnt, x)
    ys = jax.jit(jax.shard_map(body_split, mesh=s.mesh, in_specs=specs,
                               out_specs=pspec, check_vma=False))(
        la, ga4, sidx, gsrc, gval, scnt, rcnt, x)
    np.testing.assert_array_equal(np.asarray(ys), np.asarray(yu))


@pytest.mark.parametrize("pipelined", [False, True])
def test_fused_solve_bitwise_matches_unsplit_tier(problem, pipelined):
    """classic AND pipelined ride the fused tier (the acceptance), and
    -- because the split SpMV is bitwise-equal and the builder bodies
    trace the same scalar ladder -- the whole solve trajectory equals
    the unsplit tier's exactly."""
    csr, prob = problem
    N = csr.shape[0]
    rng = np.random.default_rng(1)
    b = rng.standard_normal(N)
    crit = StoppingCriteria(maxits=200, residual_rtol=1e-9)
    ref = DistCGSolver(prob, pipelined=pipelined, kernels="xla")
    x_ref = ref.solve(b, criteria=crit)
    s = DistCGSolver(prob, pipelined=pipelined, kernels="fused")
    x = s.solve(b, criteria=crit)
    assert s.stats.converged and ref.stats.converged
    assert s.stats.niterations == ref.stats.niterations
    np.testing.assert_array_equal(x, x_ref)


def test_fused_dma_transport(problem):
    """The fused tier composes with the one-sided transport: same
    answer as fused/xla to transport rounding."""
    csr, prob = problem
    N = csr.shape[0]
    b = np.ones(N)
    crit = StoppingCriteria(maxits=200, residual_rtol=1e-8)
    xs = {}
    for comm in ("xla", "dma"):
        s = DistCGSolver(prob, kernels="fused", comm=comm)
        xs[comm] = s.solve(b, criteria=crit)
        assert s.stats.converged
    np.testing.assert_allclose(xs["dma"], xs["xla"], atol=1e-9)


def test_fused_scattered_partition_rides_ell(problem):
    """Scattered (graph) partitions stack ELL local blocks; the split
    SpMV's ELL gather form must agree bitwise with the unsplit tier."""
    csr, _ = _problem(side=16, nparts=min(NDEV, 4), method="graph")
    _, prob = _problem(side=16, nparts=min(NDEV, 4), method="graph")
    if prob.local.format == "dia":
        pytest.skip("partition stayed banded; ELL form not exercised")
    b = np.ones(csr.shape[0])
    crit = StoppingCriteria(maxits=150, residual_rtol=1e-9)
    ref = DistCGSolver(prob, kernels="xla").solve(b, criteria=crit)
    s = DistCGSolver(prob, kernels="fused")
    x = s.solve(b, criteria=crit)
    np.testing.assert_array_equal(x, ref)


# -- HLO pins --------------------------------------------------------------

def _counts(txt):
    return (len(re.findall(r"all_reduce", txt)),
            len(re.findall(r"all_to_all", txt)))


def test_fused_collective_counts_pinned(problem):
    """Armed collective inventory of the fused programs (the
    test_hlo_structure discipline): the overlapped split adds ZERO
    collectives -- classic keeps 5 all_reduces / 2 all_to_alls,
    pipelined 5 / 3 (identical to the unsplit tier: the overlap is a
    dependency restructuring, not extra traffic).  Under comm='dma'
    the halo leaves the all_to_all inventory entirely (the one-sided
    DMA path), allreduces unchanged."""
    _, prob = problem
    b = np.ones(prob.n)
    for pipelined, want in ((False, (5, 2)), (True, (5, 3))):
        s = DistCGSolver(prob, pipelined=pipelined, kernels="fused")
        assert _counts(s.lower_solve(b).as_text()) == want
        d = DistCGSolver(prob, pipelined=pipelined, kernels="fused",
                         comm="dma")
        ar, ata = _counts(d.lower_solve(b).as_text())
        assert ar == want[0] and ata == 0


def test_fused_disarmed_is_byte_identical(problem):
    """kernels='auto' must lower byte-identical HLO to a build that
    never mentions the fused tier (the disarmament contract): auto
    resolves to the xla program off-TPU, untouched by the fused
    plumbing."""
    _, prob = problem
    b = np.ones(prob.n)
    auto = DistCGSolver(prob, kernels="auto").lower_solve(b).as_text()
    xla = DistCGSolver(prob, kernels="xla").lower_solve(b).as_text()
    assert auto == xla
    fused = DistCGSolver(prob, kernels="fused").lower_solve(b).as_text()
    assert fused != xla


# -- composition refusals (the could-never-fire discipline) ---------------

def test_fused_refusals(problem):
    _, prob = problem
    from acg_tpu.checkpoint import CheckpointConfig
    from acg_tpu.health import make_spec
    from acg_tpu.solvers.resilience import RecoveryPolicy

    with pytest.raises(ValueError, match="fused"):
        DistCGSolver(prob, kernels="fused", precise_dots=True)
    with pytest.raises(ValueError, match="fused"):
        DistCGSolver(prob, kernels="fused", precond="jacobi")
    with pytest.raises(ValueError, match="fused"):
        DistCGSolver(prob, kernels="fused", health=make_spec(every=4))
    with pytest.raises(ValueError, match="fused"):
        DistCGSolver(prob, kernels="fused",
                     ckpt=CheckpointConfig(path="/tmp/_fused_ck",
                                           every=8))
    with pytest.raises(ValueError, match="fused"):
        DistCGSolver(prob, kernels="fused", algorithm="sstep:4")
    with pytest.raises(ValueError, match="fused"):
        DistCGSolver(prob, kernels="fused", recovery=RecoveryPolicy())
    with pytest.raises(ValueError, match="fused"):
        DistCGSolver(prob, kernels="fused", trace=64)


def test_fused_refuses_diff_criteria_and_faults(problem):
    from acg_tpu import faults

    _, prob = problem
    s = DistCGSolver(prob, kernels="fused")
    with pytest.raises(ValueError, match="residual"):
        s.solve(np.ones(prob.n),
                criteria=StoppingCriteria(maxits=10, diff_atol=1e-3))
    faults.install(faults.parse_fault_spec("halo:nan@3"))
    try:
        with pytest.raises(AcgError, match="fused"):
            s.solve(np.ones(prob.n),
                    criteria=StoppingCriteria(maxits=10))
    finally:
        faults.install(None)


def test_fused_refuses_binnedell_local_blocks():
    """The length-binned stacked layout has no per-row gather form; the
    fused tier must say so at setup."""
    from acg_tpu.io.generators import irregular_spd_coo

    r, c, v, N = irregular_spd_coo(600, avg_degree=7.0, seed=0)
    csr = SymCsrMatrix.from_coo(N, r, c, v).to_csr()
    nparts = min(NDEV, 4)
    part = partition_rows(csr, nparts, seed=0, method="graph")
    prob = DistributedProblem.build(csr, part, nparts,
                                    dtype=jnp.float32)
    if prob.local.format != "binnedell":
        pytest.skip("workload did not bin (plain ELL waste in bounds)")
    with pytest.raises(ValueError, match="fused"):
        DistCGSolver(prob, kernels="fused")


# -- ledger + explain overlap model ---------------------------------------

def test_fused_comm_profile_declares_overlap(problem):
    _, prob = problem
    s = DistCGSolver(prob, kernels="fused")
    led = s.comm_profile()
    ov = led["overlap"]
    assert ov["split"] == "interior|border"
    assert ov["interior_rows"] > 0 and ov["border_rows"] > 0
    assert 0 < ov["interior_nnz"] < prob.nnz_total
    assert ov["interior_matrix_bytes"] > 0
    # the unsplit tier declares no overlap
    assert "overlap" not in DistCGSolver(prob).comm_profile()


def test_predicted_overlap_seconds_model():
    """The --explain comm verdict's overlap pricing: exposed halo
    seconds = max(0, halo - interior SpMV), hidden_frac comparable to
    the measured overlap-efficiency score."""
    from acg_tpu.perfmodel import predicted_overlap_seconds

    led = {"halo_bytes_per_iteration": 90_000,
           "overlap": {"interior_matrix_bytes": 450_000}}
    # 90 kB halo at 45 GB/s = 2e-6 s; 450 kB interior at 100 GB/s =
    # 4.5e-6 s -> fully hidden
    ov = predicted_overlap_seconds(led, bw_gbs=100.0, ici_gbs=45.0)
    assert ov["exposed_halo_s"] == 0.0
    assert ov["hidden_frac"] == 1.0
    # starve the interior work -> partially exposed
    led["overlap"]["interior_matrix_bytes"] = 100_000
    ov = predicted_overlap_seconds(led, bw_gbs=100.0, ici_gbs=45.0)
    assert 0 < ov["exposed_halo_s"] < ov["halo_s"]
    assert 0 < ov["hidden_frac"] < 1
    assert predicted_overlap_seconds(led, None, 45.0) is None


def test_fused_single_part_runs_plain(problem):
    """nparts=1 (no halo at all): the fused tier still dispatches (the
    plain-jit bypass) and matches the xla tier bitwise."""
    csr, _ = _problem(side=12, nparts=1)
    _, prob = _problem(side=12, nparts=1)
    b = np.ones(csr.shape[0])
    crit = StoppingCriteria(maxits=100, residual_rtol=1e-9)
    x_ref = DistCGSolver(prob, kernels="xla").solve(b, criteria=crit)
    x = DistCGSolver(prob, kernels="fused").solve(b, criteria=crit)
    np.testing.assert_array_equal(x, x_ref)


# -- multi-controller dma downgrade (the capability-probe satellite) ------

def test_dma_multicontroller_downgrade(problem, monkeypatch):
    """Multi-controller comm='dma' no longer hard-refuses: the
    capability probe downgrades to the xla transport with a recorded
    self-describing event."""
    from acg_tpu.parallel import dist as dist_mod
    from acg_tpu.parallel import halo_dma

    from acg_tpu.parallel.mesh import solve_mesh

    _, prob = problem
    mesh = solve_mesh(prob.nparts)   # built BEFORE the patched topology
    monkeypatch.setattr(dist_mod.jax, "process_count", lambda: 2)
    monkeypatch.setattr(halo_dma, "_dma_status",
                        (False, "probe says no (test)"))
    s = DistCGSolver(prob, comm="dma", mesh=mesh)
    assert s.comm == "xla"
    assert "probe says no" in s._comm_downgrade
    # single-controller arming is untouched: no downgrade, no caveat
    monkeypatch.setattr(dist_mod.jax, "process_count", lambda: 1)
    s1 = DistCGSolver(prob, comm="dma", mesh=mesh)
    assert s1.comm == "dma" and s1._comm_downgrade is None


def test_dma_transport_status_single_controller():
    from acg_tpu.parallel.halo_dma import dma_transport_status

    ok, why = dma_transport_status(refresh=True)
    assert ok and why == ""
