"""Communication-avoiding recurrences (acg_tpu.recurrence): s-step CG
and deep-pipelined p(l)-CG across the solver tiers, plus the builder's
spec/schedule surfaces.

The HLO-level pins (builder byte-identity, collective counts) live in
tests/test_hlo_structure.py; this file covers the numerics -- host-
oracle trajectory parity, single<->dist parity, the aniso-family
convergence acceptance, the p(l) Lanczos-recovery identity -- and the
integration surfaces (telemetry ring alignment, kappa estimation,
health gates, comm ledger, CLI, refusals)."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest
import scipy.sparse as sp

import jax.numpy as jnp

from acg_tpu import recurrence as rec
from acg_tpu.io.generators import aniso_poisson2d_coo, poisson2d_coo
from acg_tpu.matrix import SymCsrMatrix
from acg_tpu.ops.spmv import device_matrix_from_csr
from acg_tpu.parallel.dist import DistCGSolver, DistributedProblem
from acg_tpu.partition import partition_rows
from acg_tpu.solvers.jax_cg import JaxCGSolver
from acg_tpu.solvers.stats import StoppingCriteria

RTOL = 1e-8


def _aniso(n=32, eps=0.1):
    r, c, v, N = aniso_poisson2d_coo(n, eps)
    csr = SymCsrMatrix.from_coo(N, r, c, v).to_csr()
    Asp = sp.coo_matrix((v, (r, c)), shape=(N, N)).tocsr()
    return csr, Asp, N


@pytest.fixture(scope="module")
def aniso():
    csr, Asp, N = _aniso()
    rng = np.random.default_rng(7)
    return {
        "csr": csr, "Asp": Asp, "N": N,
        "A": device_matrix_from_csr(csr, dtype=jnp.float64),
        "b": rng.standard_normal(N),
    }


@pytest.fixture(scope="module")
def classic_iters(aniso):
    s = JaxCGSolver(aniso["A"], kernels="xla")
    s.solve(aniso["b"], criteria=StoppingCriteria(residual_rtol=RTOL,
                                                  maxits=5000))
    return s.stats.niterations


# -- spec parsing ----------------------------------------------------------

def test_parse_algorithm():
    assert rec.parse_algorithm(None) is None
    assert rec.parse_algorithm("auto") is None
    assert rec.parse_algorithm("classic").kind == "classic"
    assert rec.parse_algorithm("pipelined").kind == "pipelined"
    s4 = rec.parse_algorithm("sstep:4")
    assert (s4.kind, s4.param) == ("sstep", 4)
    assert s4.basis == "chebyshev" and s4.needs_lam
    s2 = rec.parse_algorithm("sstep:2")
    assert s2.basis == "monomial" and not s2.needs_lam
    p2 = rec.parse_algorithm("pipelined:2")
    assert (p2.kind, p2.param) == ("pl", 2) and p2.needs_lam
    assert str(s4) == "sstep:4" and str(p2) == "pipelined:2"
    # the solver names deliberately avoid the "pipelined" substring
    # (health.spectrum_estimate keys its re-alignment on it)
    assert "pipelined" not in s4.solver_name("cg")
    assert "pipelined" not in p2.solver_name("dist-cg")
    for bad in ("sstep:1", "sstep:99", "pipelined:0", "pipelined:9",
                "nope"):
        with pytest.raises(ValueError):
            rec.parse_algorithm(bad)


def test_reduction_schedule():
    s8 = rec.reduction_schedule(rec.RecurrenceSpec("sstep", 8), False)
    assert s8["allreduce_per_iteration"] == pytest.approx(1 / 8)
    assert s8["allreduce_scalars"] == 17 * 17
    assert s8["spmv_per_iteration"] == pytest.approx(15 / 8)
    p3 = rec.reduction_schedule(rec.RecurrenceSpec("pl", 3), False)
    assert p3["allreduce_per_iteration"] == 1.0
    assert p3["allreduce_scalars"] == 8
    assert p3["reduction_latency_hidden"] == 3
    assert rec.reduction_schedule(None, False)[
        "allreduce_per_iteration"] == 2.0
    assert rec.reduction_schedule(None, True)[
        "allreduce_per_iteration"] == 1.0


# -- s-step: host-oracle trajectory parity + convergence acceptance --------

def test_sstep_host_oracle_trajectory_parity(aniso):
    """The compiled s-step program's telemetry ring records the SAME
    (gamma, alpha, beta) trajectory as the eager f64 host oracle --
    per-scalar, not just the iteration count."""
    s = 4
    lam = rec.estimate_lam(aniso["A"], aniso["N"], jnp.float64)
    _, k_h, _, traj = rec.host_sstep_cg(
        aniso["Asp"], aniso["b"], rtol=RTOL, maxits=5000, s=s, lam=lam)
    solver = JaxCGSolver(aniso["A"], kernels="xla",
                         algorithm=f"sstep:{s}", trace=4096)
    solver.solve(aniso["b"],
                 criteria=StoppingCriteria(residual_rtol=RTOL,
                                           maxits=5000))
    assert solver.stats.niterations == k_h
    recs = np.asarray(solver.last_trace.records, dtype=np.float64)
    th = np.asarray(traj, dtype=np.float64)
    m = min(len(th), recs.shape[0])
    assert m > 50
    # same recurrence, same arithmetic order: tight relative agreement
    # (from_ring converts the stored ||r||^2 to norms -- sqrt here too).
    # The device program and numpy round differently, and the scalars
    # are quadratic forms of Gram matrices whose rounding stays at the
    # scale of the starting residual: a scalar formed at ||r_i|| carries
    # a relative rounding floor of ulps * ||r_0|| / ||r_i|| (measured:
    # the last record, at ||r_i|| = 1e-8 ||r_0||, differs by 5.8e-6 in
    # the norm and 3.1e-6 in alpha; every earlier one by under 6e-7)
    floor = 2 ** 10 * np.finfo(np.float64).eps * recs[0, 0] / recs[:m, 0]
    for col, want in ((0, np.sqrt(th[:m, 0])), (1, th[:m, 1])):
        err = np.abs(recs[:m, col] - want)
        assert np.all(err <= (1e-6 + floor) * np.abs(want)), col


@pytest.mark.parametrize("s", [2, 4, 8])
def test_sstep_convergence_acceptance(aniso, classic_iters, s):
    """The aniso-family acceptance: s-step converges to the standard
    rtol with an iteration count inside the CA-CG stability band
    (measured: EXACT parity with classic in f64 for all three S)."""
    solver = JaxCGSolver(aniso["A"], kernels="xla",
                         algorithm=f"sstep:{s}")
    x = solver.solve(aniso["b"],
                     criteria=StoppingCriteria(residual_rtol=RTOL,
                                               maxits=5000))
    assert solver.stats.converged
    # true-residual check, not just the recurrence's word
    rel = (np.linalg.norm(aniso["b"] - aniso["Asp"] @ np.asarray(x))
           / np.linalg.norm(aniso["b"]))
    assert rel < 10 * RTOL
    # the CA-CG stability band: within one block of classic
    assert abs(solver.stats.niterations - classic_iters) <= s


def test_sstep_dist_matches_single(aniso, classic_iters):
    """8-part mesh parity: the dist s-step program (same recurrence
    code, dist TierOps) converges with the same iteration count."""
    part = partition_rows(aniso["csr"], 8, seed=0, method="band")
    prob = DistributedProblem.build(aniso["csr"], part, 8,
                                    dtype=jnp.float64)
    solver = DistCGSolver(prob, algorithm="sstep:4")
    x = solver.solve(aniso["b"],
                     criteria=StoppingCriteria(residual_rtol=RTOL,
                                               maxits=5000))
    assert solver.stats.converged
    rel = (np.linalg.norm(aniso["b"] - aniso["Asp"] @ np.asarray(x))
           / np.linalg.norm(aniso["b"]))
    assert rel < 10 * RTOL
    assert abs(solver.stats.niterations - classic_iters) <= 4


def test_sstep_unbounded_runs_exactly_maxits(aniso):
    solver = JaxCGSolver(aniso["A"], kernels="xla", algorithm="sstep:4")
    solver.solve(aniso["b"], criteria=StoppingCriteria(maxits=37))
    assert solver.stats.niterations == 37
    assert solver.stats.converged  # unbounded semantics


# -- p(l): convergence via restarts + the Lanczos-recovery identity --------

@pytest.mark.parametrize("l", [1, 2, 3])
def test_pl_convergence_acceptance(aniso, classic_iters, l):
    """Restarted p(l)-CG reaches the standard rtol on the aniso family.
    The sqrt breakdown of the deep pipeline restarts from the current
    iterate through the standard recovery ladder (armed by default for
    p(l)); the measured band is <= ~1.9x classic, pinned at 3x."""
    solver = JaxCGSolver(aniso["A"], kernels="xla",
                         algorithm=f"pipelined:{l}")
    x = solver.solve(aniso["b"],
                     criteria=StoppingCriteria(residual_rtol=RTOL,
                                               maxits=5000))
    assert solver.stats.converged
    rel = (np.linalg.norm(aniso["b"] - aniso["Asp"] @ np.asarray(x))
           / np.linalg.norm(aniso["b"]))
    assert rel < 10 * RTOL
    assert solver.stats.niterations <= 3 * classic_iters


def test_pl_dist_converges(aniso, classic_iters):
    part = partition_rows(aniso["csr"], 8, seed=0, method="band")
    prob = DistributedProblem.build(aniso["csr"], part, 8,
                                    dtype=jnp.float64)
    solver = DistCGSolver(prob, algorithm="pipelined:2")
    x = solver.solve(aniso["b"],
                     criteria=StoppingCriteria(residual_rtol=RTOL,
                                               maxits=5000))
    assert solver.stats.converged
    rel = (np.linalg.norm(aniso["b"] - aniso["Asp"] @ np.asarray(x))
           / np.linalg.norm(aniso["b"]))
    assert rel < 10 * RTOL
    assert solver.stats.niterations <= 3 * classic_iters


def test_pl_recovers_reference_lanczos(aniso):
    """The deep pipeline's WHOLE correctness argument: the T entries it
    recovers with lag l from the z-window Gram are the true Lanczos
    coefficients.  The telemetry ring records (q^2, 1/d, l^2, d) at
    solution-advance time; d_k (the LDL pivot of T_k) recomputed from a
    reference f64 Lanczos must match the ring's pAp column."""
    l = 2
    N = aniso["N"]
    b = aniso["b"]
    Asp = aniso["Asp"]
    solver = JaxCGSolver(aniso["A"], kernels="xla",
                         algorithm=f"pipelined:{l}", trace=4096)
    # fixed 30 advances: well inside the first attempt (the aniso
    # sqrt breakdown arrives ~iteration 50+), so the ring is the
    # UNrestarted trajectory the reference Lanczos reproduces
    solver.solve(b, criteria=StoppingCriteria(maxits=30))
    recs = np.asarray(solver.last_trace.records, dtype=np.float64)
    # reference Lanczos + LDL pivots from the same start
    r0 = b.astype(np.float64)
    eta = np.linalg.norm(r0)
    v_prev = np.zeros(N)
    v_cur = r0 / eta
    beta_prev = 0.0
    deltas, gammas = [], []
    for _ in range(40):
        w = Asp @ v_cur - beta_prev * v_prev
        a = w @ v_cur
        w = w - a * v_cur
        g = np.linalg.norm(w)
        deltas.append(a)
        gammas.append(g)
        v_prev, v_cur, beta_prev = v_cur, w / g, g
    ds = [deltas[0]]
    for k in range(1, 40):
        ds.append(deltas[k] - gammas[k - 1] ** 2 / ds[k - 1])
    m = min(30, recs.shape[0])
    # ring pAp column = d_k: exact recurrence parity, with only the
    # finite-precision drift of the lag-l recovery (measured ~1e-6
    # relative by iteration 30 in f64) as the tolerance
    np.testing.assert_allclose(recs[:m, 3], ds[:m], rtol=1e-4)


def test_pl_restart_budget_and_events(aniso):
    """p(l) arms the restart ladder by default (no recovery passed):
    sqrt breakdowns surface as recorded restarts, not raises."""
    solver = JaxCGSolver(aniso["A"], kernels="xla",
                         algorithm="pipelined:1")
    solver.solve(aniso["b"],
                 criteria=StoppingCriteria(residual_rtol=RTOL,
                                           maxits=5000))
    assert solver.stats.converged
    assert solver.stats.nrestarts >= 1
    assert solver.recovery.max_restarts == rec.PL_RESTART_BUDGET


# -- telemetry / health alignment ------------------------------------------

def test_sstep_kappa_estimate(aniso):
    """The Lanczos (alpha, beta) re-alignment learns the s-step layout:
    classic-aligned rows, so spectrum_estimate's kappa lands in the
    PR-6 acceptance band against eigsh."""
    from scipy.sparse.linalg import eigsh

    from acg_tpu.health import spectrum_estimate

    solver = JaxCGSolver(aniso["A"], kernels="xla", algorithm="sstep:4",
                         trace=4096)
    solver.solve(aniso["b"],
                 criteria=StoppingCriteria(residual_rtol=RTOL,
                                           maxits=5000))
    est = spectrum_estimate(solver.last_trace)
    assert est is not None and est["kappa"] is not None
    lmax = float(eigsh(aniso["Asp"], k=1,
                       return_eigenvectors=False)[0])
    lmin = float(eigsh(aniso["Asp"], k=1, which="SA",
                       return_eigenvectors=False)[0])
    kappa_true = lmax / lmin
    assert 0.5 * kappa_true <= est["kappa"] <= 1.05 * kappa_true


def test_pl_kappa_estimate(aniso):
    """Same for p(l): the ring's (1/d, l^2) columns satisfy the classic
    identity by construction, so the estimator needs no shift."""
    from scipy.sparse.linalg import eigsh

    from acg_tpu.health import spectrum_estimate

    solver = JaxCGSolver(aniso["A"], kernels="xla",
                         algorithm="pipelined:2", trace=4096)
    # fixed 40 advances: inside the first attempt (no restart window
    # truncation), long enough for the Ritz lower bound to close
    solver.solve(aniso["b"], criteria=StoppingCriteria(maxits=40))
    est = spectrum_estimate(solver.last_trace)
    assert est is not None and est["kappa"] is not None
    lmax = float(eigsh(aniso["Asp"], k=1,
                       return_eigenvectors=False)[0])
    lmin = float(eigsh(aniso["Asp"], k=1, which="SA",
                       return_eigenvectors=False)[0])
    kappa_true = lmax / lmin
    assert 0.5 * kappa_true <= est["kappa"] <= 1.1 * kappa_true


def test_sstep_health_audit_fires(aniso):
    """The health tier reaches s-step: the block-granular audit
    (audit_update_crossing) recomputes b - A x through the tier's own
    SpMV whenever the cadence boundary falls inside a block."""
    from acg_tpu.health import make_spec

    solver = JaxCGSolver(aniso["A"], kernels="xla", algorithm="sstep:4",
                         health=make_spec(every=10))
    solver.solve(aniso["b"],
                 criteria=StoppingCriteria(residual_rtol=RTOL,
                                           maxits=5000))
    assert solver.stats.converged
    assert solver.stats.health.get("naudits", 0) > 0
    # converged cleanly: the recorded gap is tiny in f64
    assert solver.stats.health["gap_max"] < 1e-8


def test_sstep_gap_replace_hook(aniso):
    """The residual-replacement hook into the PR-6 gates: an armed
    --on-gap replace whose threshold any finite gap exceeds trips the
    breakdown path and restarts from the recomputed true residual --
    and the solve still converges."""
    from acg_tpu.health import make_spec
    from acg_tpu.solvers.resilience import RecoveryPolicy

    solver = JaxCGSolver(aniso["A"], kernels="xla", algorithm="sstep:4",
                         health=make_spec(every=20, threshold=1e-300,
                                          action="replace"),
                         recovery=RecoveryPolicy(max_restarts=64,
                                                 fallback_host=False))
    solver.solve(aniso["b"],
                 criteria=StoppingCriteria(residual_rtol=RTOL,
                                           maxits=8000))
    assert solver.stats.converged
    assert solver.stats.nrestarts >= 1


# -- comm ledger -----------------------------------------------------------

def test_comm_ledger_reduction_drop(aniso):
    part = partition_rows(aniso["csr"], 8, seed=0, method="band")
    prob = DistributedProblem.build(aniso["csr"], part, 8,
                                    dtype=jnp.float64)
    base = DistCGSolver(prob).comm_profile()
    led_s = DistCGSolver(prob, algorithm="sstep:8").comm_profile()
    led_p = DistCGSolver(prob, algorithm="pipelined:2").comm_profile()
    assert base["allreduce_per_iteration"] == 2
    assert led_s["allreduce_per_iteration"] == pytest.approx(1 / 8)
    assert led_s["iterations_per_reduction"] == 8
    assert led_s["algorithm"] == "sstep:8"
    assert led_s["halo_exchanges_per_iteration"] == pytest.approx(15 / 8)
    assert led_p["allreduce_per_iteration"] == 1.0
    assert led_p["allreduce_scalars"] == 6
    assert led_p["reduction_latency_hidden"] == 2


def test_sharded_gen_direct_rides_builder():
    """The sharded gen-direct tier (ShardedDiaCGSolver) inherits the
    CA recurrences through the callable-SpMV hook, ledger included."""
    from acg_tpu.parallel.sharded_dia import build_sharded_poisson_solver

    solver = build_sharded_poisson_solver(24, 2, nparts=8,
                                          dtype=jnp.float64,
                                          algorithm="sstep:2")
    b = np.ones(solver.A.nrows)
    x = solver.solve(b, criteria=StoppingCriteria(residual_rtol=1e-6,
                                                  maxits=2000))
    assert solver.stats.converged
    led = solver.comm_profile()
    assert led["algorithm"] == "sstep:2"
    assert led["allreduce_per_iteration"] == pytest.approx(0.5)


# -- refusals (the could-never-fire discipline) ----------------------------

def test_refusals(aniso):
    A = aniso["A"]
    with pytest.raises(ValueError, match="unpreconditioned"):
        JaxCGSolver(A, algorithm="sstep:4", precond="jacobi")
    with pytest.raises(ValueError, match="precise_dots"):
        JaxCGSolver(A, algorithm="sstep:4", precise_dots=True)
    with pytest.raises(ValueError, match="pipelined flag"):
        JaxCGSolver(A, algorithm="sstep:4", pipelined=True)
    with pytest.raises(ValueError, match="replace_every"):
        JaxCGSolver(A, algorithm="sstep:4", replace_every=10,
                    vector_dtype=jnp.bfloat16)
    with pytest.raises(ValueError, match="bf16"):
        JaxCGSolver(A, algorithm="pipelined:2",
                    vector_dtype=jnp.bfloat16)
    from acg_tpu.checkpoint import CheckpointConfig
    # checkpointing now composes with CA recurrences (the ISSUE-16
    # carry); the narrowed refusal matrix (repartition carry, p(l) +
    # trace) lives in tests/test_checkpoint.py
    JaxCGSolver(A, algorithm="sstep:4",
                ckpt=CheckpointConfig(path="/tmp/x.ckpt", every=10))
    with pytest.raises(ValueError, match="trace"):
        JaxCGSolver(A, algorithm="pipelined:2", trace=8,
                    ckpt=CheckpointConfig(path="/tmp/x.ckpt", every=10))
    from acg_tpu.health import make_spec
    with pytest.raises(ValueError, match="audit"):
        JaxCGSolver(A, algorithm="pipelined:2",
                    health=make_spec(every=10))
    with pytest.raises(ValueError, match="abft"):
        JaxCGSolver(A, algorithm="sstep:4",
                    health=make_spec(every=10, abft=True))
    # diff criteria refuse at dispatch
    s = JaxCGSolver(A, algorithm="sstep:4")
    with pytest.raises(ValueError, match="residual criteria"):
        s.solve(aniso["b"],
                criteria=StoppingCriteria(diff_rtol=1e-6, maxits=10))
    # classic/pipelined aliases resolve onto the classic/pipelined programs
    s = JaxCGSolver(A, algorithm="pipelined")
    assert s.algo is None and s.pipelined


def test_fault_refusals(aniso):
    from acg_tpu import faults
    from acg_tpu.errors import AcgError

    s = JaxCGSolver(aniso["A"], algorithm="sstep:4")
    with faults.injected("spmv:nan@3"):
        with pytest.raises(AcgError, match="block boundaries"):
            s.solve(aniso["b"],
                    criteria=StoppingCriteria(residual_rtol=RTOL,
                                              maxits=100))
    p = JaxCGSolver(aniso["A"], algorithm="pipelined:2")
    with faults.injected("dot:nan@3"):
        with pytest.raises(AcgError, match="no site"):
            p.solve(aniso["b"],
                    criteria=StoppingCriteria(residual_rtol=RTOL,
                                              maxits=100))


def test_sstep_fault_detected_and_recovered(aniso):
    """A block-aligned SpMV fault fires, is caught by the breakdown
    guard, and the recovery ladder restarts past it."""
    from acg_tpu import faults
    from acg_tpu.solvers.resilience import RecoveryPolicy

    s = JaxCGSolver(aniso["A"], algorithm="sstep:4",
                    recovery=RecoveryPolicy(max_restarts=3,
                                            fallback_host=False))
    with faults.injected("spmv:nan@8"):
        x = s.solve(aniso["b"],
                    criteria=StoppingCriteria(residual_rtol=RTOL,
                                              maxits=5000))
    assert s.stats.converged
    assert s.stats.nbreakdowns >= 1
    rel = (np.linalg.norm(aniso["b"] - aniso["Asp"] @ np.asarray(x))
           / np.linalg.norm(aniso["b"]))
    assert rel < 10 * RTOL


def test_pl_fault_detected_and_recovered(aniso):
    """A p(l) SpMV fault (keyed on the auxiliary-basis counter) fires,
    breaks the pipeline, and the restart ladder retires it in the
    z-counter frame -- the fault must NOT deterministically re-trigger
    across restarts (the FaultSpec.shift contract)."""
    from acg_tpu import faults

    s = JaxCGSolver(aniso["A"], kernels="xla", algorithm="pipelined:2")
    with faults.injected("spmv:nan@10"):
        x = s.solve(aniso["b"],
                    criteria=StoppingCriteria(residual_rtol=RTOL,
                                              maxits=5000))
    assert s.stats.converged
    assert s.stats.nrestarts >= 1
    rel = (np.linalg.norm(aniso["b"] - aniso["Asp"] @ np.asarray(x))
           / np.linalg.norm(aniso["b"]))
    assert rel < 10 * RTOL


def test_dist_census_matches_schedule(aniso):
    """The dist tier's op census bills the SAME SpMV-equivalents per
    iteration as the ledger/schedule declares (and as the single-device
    census does) -- the two tiers' stats for one algorithm must agree."""
    part = partition_rows(aniso["csr"], 8, seed=0, method="band")
    prob = DistributedProblem.build(aniso["csr"], part, 8,
                                    dtype=jnp.float64)
    solver = DistCGSolver(prob, algorithm="sstep:8")
    solver.solve(aniso["b"], criteria=StoppingCriteria(maxits=80))
    niter = solver.stats.niterations
    sched = rec.reduction_schedule(rec.RecurrenceSpec("sstep", 8), False)
    gemv = solver.stats.ops["gemv"].n
    assert gemv == int(niter * sched["spmv_per_iteration"]) + 1
    ar = solver.stats.ops["allreduce"].n
    assert ar == max(int(round(niter / 8)), 1)


# -- CLI -------------------------------------------------------------------

def _cli(args, env_extra=None):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
    if env_extra:
        env.update(env_extra)
    return subprocess.run(
        [sys.executable, "-m", "acg_tpu"] + args,
        capture_output=True, text=True, env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def test_cli_sstep_end_to_end(tmp_path):
    sj = tmp_path / "stats.json"
    p = _cli(["gen:poisson2d:24", "--aniso", "0.5", "--algorithm",
              "sstep:4", "--max-iterations", "2000",
              "--residual-rtol", "1e-6", "--warmup", "0",
              "--stats-json", str(sj)])
    assert p.returncode == 0, p.stderr[-2000:]
    doc = json.loads(sj.read_text())
    assert doc["stats"]["converged"] is True


def test_cli_pl_end_to_end():
    p = _cli(["gen:poisson2d:24", "--aniso", "0.5", "--algorithm",
              "pipelined:2", "--max-iterations", "2000",
              "--residual-rtol", "1e-6", "--warmup", "0"])
    assert p.returncode == 0, p.stderr[-2000:]


def test_cli_dist_sstep_end_to_end():
    p = _cli(["gen:poisson2d:24", "--nparts", "8", "--algorithm",
              "sstep:2", "--max-iterations", "2000",
              "--residual-rtol", "1e-5", "--warmup", "0"])
    assert p.returncode == 0, p.stderr[-2000:]


def test_cli_refusals():
    p = _cli(["gen:poisson2d:24", "--algorithm", "sstep:4",
              "--precond", "jacobi", "--warmup", "0"])
    assert p.returncode != 0
    assert "does not support" in p.stderr
    p = _cli(["gen:poisson2d:24", "--algorithm", "sstep:33",
              "--warmup", "0"])
    assert p.returncode != 0
    p = _cli(["gen:poisson2d:24", "--algorithm", "pipelined:2",
              "--explain", "--warmup", "0"])
    assert p.returncode != 0
    assert "does not support" in p.stderr


def test_cli_algorithm_aliases():
    """--algorithm pipelined is the Ghysels-Vanroose solver (the
    existing name), not p(l)."""
    p = _cli(["gen:poisson2d:16", "--algorithm", "pipelined",
              "--max-iterations", "800", "--residual-rtol", "1e-6",
              "--warmup", "0"])
    assert p.returncode == 0, p.stderr[-2000:]


def test_buildinfo_row():
    p = _cli(["--buildinfo", "x"])
    assert "communication-avoiding recurrences" in p.stdout
    assert "sstep:S" in p.stdout
