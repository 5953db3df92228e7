"""Fault-injection + solver-resilience subsystem (acg_tpu.faults,
acg_tpu.solvers.resilience).

The reference suite ships no fault injection; this matrix exercises the
TPU build's hardening on the virtual 8-device CPU mesh: deterministic
NaN/Inf/scalar faults at chosen iterations are detected in the jitted
loops, recovered by bounded host-side restarts (converging to the SAME
tolerance as the fault-free run), escalated down the fallback ladder
(dma->xla transport, host solver), agreed across controllers
(erragree), and bounded at the platform layer (the backend probe that
fixes the round-5 dryrun wedge).
"""

import os
import socket
import subprocess
import sys
import time

import numpy as np
import pytest

import jax.numpy as jnp

from acg_tpu import faults
from acg_tpu.errors import BreakdownError
from acg_tpu.io.generators import poisson2d_coo
from acg_tpu.matrix import SymCsrMatrix
from acg_tpu.ops.spmv import device_matrix_from_csr
from acg_tpu.parallel.dist import DistCGSolver, DistributedProblem
from acg_tpu.partition import partition_rows
from acg_tpu.solvers import HostCGSolver, StoppingCriteria
from acg_tpu.solvers.jax_cg import JaxCGSolver
from acg_tpu.solvers.resilience import RecoveryPolicy

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _disarm():
    """No test may leak an armed injector into the rest of the suite --
    neither the installed spec nor the env var the CLI exports for its
    subprocess children."""
    yield
    faults.install(None)
    os.environ.pop(faults.ENV_VAR, None)


@pytest.fixture(scope="module")
def problem():
    r, c, v, N = poisson2d_coo(20)
    csr = SymCsrMatrix.from_coo(N, r, c, v).to_csr()
    return csr, np.ones(N)


# -- spec grammar -------------------------------------------------------

def test_parse_fault_spec_grammar():
    s = faults.parse_fault_spec("spmv:nan@7")
    assert (s.site, s.mode, s.iteration) == ("spmv", "nan", 7)
    s = faults.parse_fault_spec("halo:inf@3:part=2:seed=5")
    assert (s.site, s.part, s.seed) == ("halo", 2, 5)
    s = faults.parse_fault_spec("peer:dead:proc=1")
    assert (s.site, s.mode, s.proc) == ("peer", "dead", 1)
    s = faults.parse_fault_spec("solve:slow@4:secs=0.5")
    assert (s.site, s.secs) == ("solve", 0.5)
    for bad in ("spmv", "spmv:frob@1", "nosite:nan@1", "dot:nan@x",
                "spmv:nan@1:bogus=2", "spmv:nan"):
        with pytest.raises(ValueError):
            faults.parse_fault_spec(bad)


def test_fault_spec_shift():
    s = faults.parse_fault_spec("spmv:nan@7")
    assert s.shift(3).iteration == 4
    assert s.shift(8) is None          # already fired: restarts are clean
    p = faults.parse_fault_spec("peer:dead:proc=1")
    assert p.shift(100) is p           # non-device sites never shift


# -- single-device detection + recovery --------------------------------

@pytest.mark.parametrize("pipelined", [False, True])
@pytest.mark.parametrize("spec", ["spmv:nan@5", "spmv:inf@5", "dot:neg@4",
                                  "dot:nan@4"])
def test_jax_cg_fault_detected_restarted_converges(problem, spec, pipelined):
    """The acceptance contract: a mid-solve fault is detected, the solve
    restarts from the recomputed true residual, and converges to the
    SAME tolerance as the fault-free run -- restart visible in stats."""
    csr, b = problem
    crit = StoppingCriteria(maxits=500, residual_rtol=1e-8)
    clean = JaxCGSolver(device_matrix_from_csr(csr, dtype=jnp.float64),
                        pipelined=pipelined)
    x_clean = clean.solve(b, criteria=crit)

    s = JaxCGSolver(device_matrix_from_csr(csr, dtype=jnp.float64),
                    pipelined=pipelined, recovery=RecoveryPolicy())
    with faults.injected(spec):
        x = s.solve(b, criteria=crit)
    st = s.stats
    assert st.converged
    assert st.nbreakdowns >= 1 and st.nrestarts >= 1
    # same tolerance as fault-free: the restarted solve honours the
    # ORIGINAL residual target
    assert st.rnrm2 <= crit.residual_rtol * st.r0nrm2 * (1 + 1e-6)
    rel = np.linalg.norm(x - x_clean) / np.linalg.norm(x_clean)
    assert rel < 1e-6
    report = st.fwrite()
    assert "resilience:" in report and "restart" in report


def test_unfireable_fault_configs_refuse(problem):
    """An armed injector that could never fire must refuse, not report
    a clean 'fault-tested' solve: halo faults on haloless topologies,
    and any device fault under the replacement-segment program."""
    from acg_tpu.errors import AcgError
    csr, b = problem
    crit = StoppingCriteria(maxits=100, residual_rtol=1e-4)
    with faults.injected("halo:nan@3"):
        with pytest.raises(AcgError, match="no halo"):
            JaxCGSolver(device_matrix_from_csr(csr, dtype=jnp.float64)
                        ).solve(b, criteria=crit)
    with faults.injected("spmv:nan@3"):
        s = JaxCGSolver(device_matrix_from_csr(csr, dtype=jnp.bfloat16),
                        replace_every=8)
        with pytest.raises(AcgError, match="replacement-segment"):
            s.solve(np.ones(len(b), np.float32), criteria=crit)


def test_jax_cg_fault_without_recovery_raises(problem):
    """An injected fault with no recovery policy must surface as a
    BreakdownError, never launder into a returned x."""
    csr, b = problem
    s = JaxCGSolver(device_matrix_from_csr(csr, dtype=jnp.float64))
    with faults.injected("spmv:nan@5"):
        with pytest.raises(BreakdownError):
            s.solve(b, criteria=StoppingCriteria(maxits=200,
                                                 residual_rtol=1e-8))
    assert s.stats.nbreakdowns == 1 and s.stats.nrestarts == 0


def test_jax_cg_host_fallback_rung(problem):
    """Retries exhausted + a host matrix available -> the final rung
    re-solves on the host oracle and still returns a good x."""
    csr, b = problem
    crit = StoppingCriteria(maxits=400, residual_rtol=1e-8)
    s = JaxCGSolver(device_matrix_from_csr(csr, dtype=jnp.float64),
                    recovery=RecoveryPolicy(max_restarts=0),
                    host_matrix=csr)
    with faults.injected("spmv:nan@5"):
        x = s.solve(b, criteria=crit)
    st = s.stats
    assert st.converged and st.nfallbacks == 1
    assert "fallback: host reference solver" in st.fwrite()
    assert np.linalg.norm(b - csr @ np.asarray(x, np.float64)) \
        <= 1e-7 * np.linalg.norm(b)


def test_host_cg_fault_detected_and_restarted(problem):
    """The eager host solver runs the same detect-restart policy."""
    csr, b = problem
    crit = StoppingCriteria(maxits=400, residual_rtol=1e-10)
    clean = HostCGSolver(csr)
    x_clean = clean.solve(b, criteria=crit)
    s = HostCGSolver(csr, recovery=RecoveryPolicy())
    with faults.injected("spmv:nan@6"):
        x = s.solve(b, criteria=crit)
    assert s.stats.converged and s.stats.nrestarts == 1
    assert np.linalg.norm(x - x_clean) <= 1e-8 * np.linalg.norm(x_clean)
    with faults.injected("dot:zero@3"):
        with pytest.raises(BreakdownError):
            HostCGSolver(csr).solve(b, criteria=crit)


# -- distributed (8-part virtual mesh) ---------------------------------

@pytest.mark.parametrize("spec", ["spmv:nan@3:part=2", "halo:nan@2",
                                  "dot:neg@4"])
def test_dist_cg_fault_recovers_on_mesh(problem, spec):
    """NaN at iteration k on the 8-part mesh -> detected (the flag is
    psum-derived, so the early exit is mesh-uniform), restarted,
    converges to the fault-free solution."""
    csr, b = problem
    part = partition_rows(csr, 8, seed=0)
    crit = StoppingCriteria(maxits=500, residual_rtol=1e-8)
    prob0 = DistributedProblem.build(csr, part, 8, dtype=jnp.float64)
    clean = DistCGSolver(prob0)
    x_clean = clean.solve(b, criteria=crit)

    prob = DistributedProblem.build(csr, part, 8, dtype=jnp.float64)
    s = DistCGSolver(prob, recovery=RecoveryPolicy())
    with faults.injected(spec):
        x = s.solve(b, criteria=crit)
    st = s.stats
    assert st.converged and st.nbreakdowns >= 1 and st.nrestarts >= 1
    assert np.linalg.norm(x - x_clean) <= 1e-6 * np.linalg.norm(x_clean)
    assert "resilience:" in st.fwrite()


def test_dist_cg_host_fallback_rung(problem):
    csr, b = problem
    part = partition_rows(csr, 8, seed=0)
    crit = StoppingCriteria(maxits=400, residual_rtol=1e-8)
    prob = DistributedProblem.build(csr, part, 8, dtype=jnp.float64)
    s = DistCGSolver(prob, recovery=RecoveryPolicy(max_restarts=0))
    with faults.injected("spmv:nan@3"):
        x = s.solve(b, criteria=crit)
    st = s.stats
    assert st.converged and st.nfallbacks == 1
    assert np.linalg.norm(b - csr @ x) <= 1e-7 * np.linalg.norm(b)


# -- CLI wiring ---------------------------------------------------------

def test_cli_fault_inject_restart_in_stats(capsys):
    """--fault-inject through the CLI: the solve recovers and the stats
    block surfaces the restart (acceptance criterion)."""
    from acg_tpu import cli
    rc = cli.main(["gen:poisson2d:16", "--fault-inject", "spmv:nan@4",
                   "--nparts", "1", "--max-iterations", "500",
                   "--residual-rtol", "1e-8", "--dtype", "f64",
                   "--warmup", "0", "--quiet"])
    err = capsys.readouterr().err
    assert rc == 0, err
    assert "resilience:" in err and "restart 1/" in err
    faults.install(None)


def test_cli_rejects_bad_fault_spec():
    from acg_tpu import cli
    with pytest.raises(SystemExit):
        cli.main(["gen:poisson2d:8", "--fault-inject", "spmv:frobnicate",
                  "--quiet"])


# -- dead peer -> erragree abort ---------------------------------------

def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("", 0))
        return s.getsockname()[1]


def test_dead_peer_fault_trips_erragree_watchdog():
    """peer:dead:proc=1 kills controller 1 at its checkpoint; controller
    0's error-agreement watchdog must abort it within the timeout."""
    from acg_tpu.parallel.erragree import PEER_LOST_EXIT
    port = _free_port()
    code = ("import jax; jax.config.update('jax_platforms', 'cpu'); "
            "import sys; sys.path.insert(0, {root!r}); "
            "from acg_tpu.parallel.multihost import initialize; "
            "initialize('localhost:{port}', 2, {pid}); "
            "jax.devices(); "
            "from acg_tpu.parallel.erragree import agree_status; "
            "rc = agree_status(0, what='ingest', timeout=8); "
            "raise SystemExit(rc)")
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    env[faults.ENV_VAR] = "peer:dead:proc=1"
    procs = [subprocess.Popen(
        [sys.executable, "-c",
         code.format(root=ROOT, port=port, pid=pid)],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        env=env, cwd=ROOT) for pid in range(2)]
    t0 = time.monotonic()
    outs = [p.communicate(timeout=120) for p in procs]
    elapsed = time.monotonic() - t0
    assert procs[1].returncode == 86          # the injected death
    assert procs[0].returncode != 0           # survivor aborts...
    assert elapsed < 60                       # ...within the timeout
    if procs[0].returncode == PEER_LOST_EXIT:
        assert ("timed out" in outs[0][1]
                or "peer controller died" in outs[0][1])
