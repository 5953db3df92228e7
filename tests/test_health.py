"""Numerical-health tier (acg_tpu.health): in-loop true-residual
audits, Lanczos spectrum estimation, accuracy gates, and the
surrounding surfaces (telemetry audit column, metrics, soak, CLI,
bench_diff satellite).

The PR-6 acceptance in test form: the fp32 pipelined solver on the
ill-conditioned aniso-Poisson family shows a measurably larger
residual gap than classic CG at the same budget (ground truth from
f64 host arithmetic), ``--on-gap replace`` recovers the solve to the
requested tolerance through the recovery driver, kappa estimates from
the recorded (alpha, beta) land within a documented band of
``scipy.sparse.linalg.eigsh``, and single vs 8-part audit records
agree.
"""

import json
import math
import os
import subprocess
import sys

import numpy as np
import pytest

from acg_tpu import health, telemetry
from acg_tpu.io.generators import aniso_poisson2d_coo, poisson2d_coo
from acg_tpu.matrix import SymCsrMatrix
from acg_tpu.solvers.stats import SolverStats, StoppingCriteria

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def aniso_csr():
    """The ill-conditioned SPD family (diagonal varies ~1/eps)."""
    r, c, v, N = aniso_poisson2d_coo(24, 0.1)
    return SymCsrMatrix.from_coo(N, r, c, v).to_csr()


@pytest.fixture(scope="module")
def poisson_csr():
    r, c, v, N = poisson2d_coo(16)
    return SymCsrMatrix.from_coo(N, r, c, v).to_csr()


def _jax_solver(csr, dtype=None, **kw):
    import jax.numpy as jnp

    from acg_tpu.ops.spmv import device_matrix_from_csr
    from acg_tpu.solvers.jax_cg import JaxCGSolver

    A = device_matrix_from_csr(csr, dtype=dtype or jnp.float64)
    return JaxCGSolver(A, kernels="xla", **kw)


# -- spec semantics -------------------------------------------------------

def test_spec_validation():
    assert health.make_spec() is None
    assert health.make_spec(every=0, stall_window=0) is None
    spec = health.make_spec(every=5)
    assert spec.armed and not spec.arms_detect
    assert health.make_spec(stall_window=3).arms_detect
    assert health.make_spec(every=5, threshold=1e-4,
                            action="replace").arms_detect
    with pytest.raises(ValueError, match="on-gap action"):
        health.make_spec(every=5, action="replace")  # no threshold
    with pytest.raises(ValueError, match="on-gap action"):
        health.make_spec(threshold=1e-4, action="abort")  # no audit
    with pytest.raises(ValueError, match="unknown on-gap"):
        health.make_spec(every=5, action="explode")
    with pytest.raises(ValueError):
        health.make_spec(every=-1)


def test_solver_refusals(poisson_csr):
    import jax.numpy as jnp

    from acg_tpu.ops.spmv import device_matrix_from_csr
    from acg_tpu.solvers.jax_cg import JaxCGSolver

    A16 = device_matrix_from_csr(poisson_csr, dtype=jnp.bfloat16)
    with pytest.raises(ValueError, match="replace_every"):
        JaxCGSolver(A16, replace_every=10,
                    health=health.make_spec(every=5))
    with pytest.raises(ValueError, match="HealthSpec"):
        _jax_solver(poisson_csr, health="audit-every=5")


# -- device-helper semantics (stall counter + trip) -----------------------

def test_stall_and_trip_primitives():
    import jax
    import jax.numpy as jnp

    spec = health.HealthSpec(every=2, threshold=0.5, action="replace",
                             stall_window=3)

    @jax.jit
    def run(progress_seq):
        aud = health.audit_init(jnp.float32)

        def body(i, aud):
            return health.stall_update(aud, spec, progress_seq[i])

        return jax.lax.fori_loop(0, progress_seq.shape[0], body, aud)

    # decreasing -> counter stays 0; three flat iterations trip
    aud = run(jnp.asarray([True, True, False, False, False]))
    assert float(aud[health.AUD_STALL]) == 3.0
    assert bool(health.trip(aud, spec))
    aud = run(jnp.asarray([False, False, True, False, False]))
    assert float(aud[health.AUD_STALL]) == 2.0
    assert not bool(health.trip(aud, spec))
    # a gap past the threshold trips regardless of the stall counter
    aud2 = aud.at[health.AUD_GAP].set(0.6)
    assert bool(health.trip(aud2, spec))
    # NaN gap (never audited) never trips
    assert not bool(health.trip(health.audit_init(jnp.float32), spec))


# -- the audit oracle: pipelined drift vs classic, f64 ground truth -------

@pytest.fixture
def unreplaced(monkeypatch):
    """The pipelined recurrence without the residual replacement it
    runs over f32 vectors (recurrence.REPLACE_DROP emptied): the drift
    the audit exists to see.  The rule is read while a program traces,
    so compiled programs are dropped on the way in and out."""
    import jax

    from acg_tpu import recurrence

    jax.clear_caches()
    monkeypatch.setattr(recurrence, "REPLACE_DROP", {})
    yield
    jax.clear_caches()


def test_fp32_pipelined_gap_exceeds_classic(aniso_csr, unreplaced):
    """The communication-hiding trade-off, measured: at the same f32
    budget on the ill-conditioned family, the unreplaced pipelined
    recurrence drifts measurably further from b - Ax than classic CG's
    -- both by the in-loop audit and by independent f64 host
    arithmetic."""
    import jax.numpy as jnp

    n = aniso_csr.shape[0]
    b = np.ones(n)
    gaps, true_gaps = {}, {}
    for pipelined in (False, True):
        s = _jax_solver(aniso_csr, dtype=jnp.float32,
                        pipelined=pipelined,
                        health=health.make_spec(every=10))
        x = s.solve(b, criteria=StoppingCriteria(maxits=400,
                                                 residual_rtol=1e-6),
                    raise_on_divergence=False)
        gaps[pipelined] = s.stats.health["gap_last"]
        # f64 ground truth: the reported recurrence residual vs the
        # true one -- |  ||b - Ax||_f64 - rnrm2_reported | / ||b|| is a
        # lower bound on ||r_true - r_rec|| / ||b||
        rtrue = float(np.linalg.norm(b - aniso_csr
                                     @ np.asarray(x, np.float64)))
        true_gaps[pipelined] = abs(rtrue - s.stats.rnrm2) / np.linalg.norm(b)
    assert gaps[True] > 5.0 * gaps[False], (gaps, true_gaps)
    assert true_gaps[True] > 5.0 * true_gaps[False], (gaps, true_gaps)
    # the in-loop audit must AGREE with the oracle: the measured drift
    # cannot exceed what the audit reported (plus f32 noise)
    assert true_gaps[True] <= 2.0 * gaps[True] + 1e-6


def test_fp32_pipelined_replaced_gap_within_classic(aniso_csr):
    """With its residual replacement (f32 vectors) the pipelined
    recurrence ends no further from b - Ax than classic CG, by the
    audit and by f64 host arithmetic, and it replaced at least once."""
    import jax.numpy as jnp

    b = np.ones(aniso_csr.shape[0])
    gaps, true_gaps = {}, {}
    for pipelined in (False, True):
        s = _jax_solver(aniso_csr, dtype=jnp.float32, pipelined=pipelined,
                        health=health.make_spec(every=10))
        x = s.solve(b, criteria=StoppingCriteria(maxits=400,
                                                 residual_rtol=1e-6),
                    raise_on_divergence=False)
        gaps[pipelined] = s.stats.health["gap_last"]
        rtrue = float(np.linalg.norm(b - aniso_csr
                                     @ np.asarray(x, np.float64)))
        true_gaps[pipelined] = abs(rtrue - s.stats.rnrm2) / np.linalg.norm(b)
        if pipelined:
            assert s.stats.nreplacements >= 1
    assert gaps[True] <= 2.0 * gaps[False], (gaps, true_gaps)
    assert true_gaps[True] <= 2.0 * true_gaps[False], (gaps, true_gaps)


def test_on_gap_replace_recovers_to_tolerance(aniso_csr, unreplaced):
    """--on-gap replace: the gap trip exits through the breakdown path
    and the recovery driver's restart recomputes the true residual (a
    residual-replacement restart) -- the f32 pipelined solve then
    reaches the tolerance its ungated twin misses by an order of
    magnitude (f64 host arithmetic as the judge)."""
    import jax.numpy as jnp

    from acg_tpu.solvers.resilience import RecoveryPolicy

    n = aniso_csr.shape[0]
    b = np.ones(n)
    rtol = 1e-5
    crit = StoppingCriteria(maxits=4000, residual_rtol=rtol)
    bnrm = float(np.linalg.norm(b))

    def true_rel(x):
        return float(np.linalg.norm(
            b - aniso_csr @ np.asarray(x, np.float64))) / bnrm

    ungated = _jax_solver(aniso_csr, dtype=jnp.float32, pipelined=True)
    x0 = ungated.solve(b, criteria=crit, raise_on_divergence=False)

    gated = _jax_solver(
        aniso_csr, dtype=jnp.float32, pipelined=True,
        recovery=RecoveryPolicy(max_restarts=25, fallback_host=False),
        health=health.make_spec(every=10, threshold=1e-4,
                                action="replace"))
    x1 = gated.solve(b, criteria=crit, raise_on_divergence=False)
    assert gated.stats.converged
    assert gated.stats.nrestarts >= 1
    assert any(ev["kind"] == "accuracy_degraded"
               for ev in gated.stats.events)
    # the health summary MERGES across restart attempts: the recovered
    # solve still shows the worst gap of the whole solve (a converged
    # final attempt by itself could never exceed the threshold -- it
    # would have tripped), and naudits accumulates
    assert gated.stats.health["gap_max"] > 1e-4
    assert gated.stats.health["naudits"] >= gated.stats.nrestarts
    # recovered: the TRUE residual lands within the requested tolerance
    # plus the gap threshold's drift allowance...
    assert true_rel(x1) <= rtol + 2e-4
    # ...and beats the ungated solve decisively
    assert true_rel(x1) < 0.2 * true_rel(x0), (true_rel(x1),
                                               true_rel(x0))


def test_on_gap_abort_raises(aniso_csr):
    import jax.numpy as jnp

    from acg_tpu.errors import BreakdownError

    s = _jax_solver(aniso_csr, dtype=jnp.float32, pipelined=True,
                    health=health.make_spec(every=10, threshold=1e-4,
                                            action="abort"))
    # the raise names the REAL cause (the accuracy gate), not the
    # generic arithmetic-breakdown diagnosis -- host-tier parity
    with pytest.raises(BreakdownError, match="true-residual gap"):
        s.solve(np.ones(aniso_csr.shape[0]),
                criteria=StoppingCriteria(maxits=4000,
                                          residual_rtol=1e-6))
    assert any(ev["kind"] == "accuracy_degraded"
               for ev in s.stats.events)


def test_on_gap_abort_ignores_restart_budget(aniso_csr):
    """abort must stay a hard stop even when a recovery policy is
    armed: the restart budget belongs to replace, and silently
    restarting would turn the abort gate the user asked for into
    replace (host-tier parity -- host_cg aborts unconditionally)."""
    import jax.numpy as jnp

    from acg_tpu.errors import BreakdownError
    from acg_tpu.solvers.resilience import RecoveryPolicy

    s = _jax_solver(aniso_csr, dtype=jnp.float32, pipelined=True,
                    recovery=RecoveryPolicy(max_restarts=25,
                                            fallback_host=False),
                    health=health.make_spec(every=10, threshold=1e-4,
                                            action="abort"))
    with pytest.raises(BreakdownError, match=r"--on-gap abort"):
        s.solve(np.ones(aniso_csr.shape[0]),
                criteria=StoppingCriteria(maxits=4000,
                                          residual_rtol=1e-6))
    assert s.stats.nrestarts == 0, s.stats.recovery_log
    assert s.stats.converged is False


# -- Lanczos spectrum estimation ------------------------------------------

def test_kappa_estimate_vs_eigsh(aniso_csr):
    """kappa from the Lanczos tridiagonal of a traced f64 solve lands
    within a documented band of scipy's exact extremal eigenvalues on
    the generated SPD family.  Ritz values converge from INSIDE the
    spectrum, so the estimate is a lower bound that tightens with the
    iteration count -- the band pins [0.5x, 1.05x]."""
    from scipy.sparse.linalg import eigsh

    n = aniso_csr.shape[0]
    s = _jax_solver(aniso_csr, trace=4096)
    s.solve(np.ones(n), criteria=StoppingCriteria(maxits=2000,
                                                  residual_rtol=1e-12),
            raise_on_divergence=False)
    est = health.spectrum_estimate(s.last_trace)
    assert est is not None and est["kappa"] is not None
    lmax_true = float(eigsh(aniso_csr, k=1, which="LA",
                            return_eigenvectors=False)[0])
    lmin_true = float(eigsh(aniso_csr, k=1, which="SA",
                            return_eigenvectors=False)[0])
    kappa_true = lmax_true / lmin_true
    assert 0.5 * kappa_true <= est["kappa"] <= 1.05 * kappa_true, (
        est, kappa_true)
    assert est["lambda_max"] <= 1.05 * lmax_true
    assert est["lambda_min"] >= 0.95 * lmin_true


def test_kappa_pipelined_trace_aligns_with_classic(poisson_csr):
    """The pipelined trace records beta shifted by one iteration (the
    GV recurrence computes it at the top of the loop); the re-aligned
    Lanczos build must land on the same kappa as the classic trace."""
    ests = {}
    for pipelined in (False, True):
        s = _jax_solver(poisson_csr, pipelined=pipelined, trace=2048)
        s.solve(np.ones(poisson_csr.shape[0]),
                criteria=StoppingCriteria(maxits=500,
                                          residual_rtol=1e-11))
        ests[pipelined] = health.spectrum_estimate(s.last_trace)
    k0, k1 = ests[False]["kappa"], ests[True]["kappa"]
    assert k0 and k1
    assert abs(k1 - k0) / k0 < 0.2, (k0, k1)


def test_predicted_iterations_bound(poisson_csr):
    """The CG bound is an upper bound on a worst-case spectrum: the
    measured f64 iteration count must come in at or under it."""
    rtol = 1e-10
    s = _jax_solver(poisson_csr, trace=2048)
    s.solve(np.ones(poisson_csr.shape[0]),
            criteria=StoppingCriteria(maxits=2000, residual_rtol=rtol))
    rep = health.convergence_report(s.last_trace, s.stats.niterations,
                                    rtol)
    assert rep["predicted_iterations"] >= rep["measured_iterations"]
    # monotonicity sanity of the bound itself
    assert (health.predicted_iterations(1e6, 1e-9)
            > health.predicted_iterations(1e3, 1e-9)
            > health.predicted_iterations(1e3, 1e-3))
    assert health.predicted_iterations(0, 1e-9) is None
    assert health.predicted_iterations(100.0, 0.0) is None


def test_lanczos_wrapped_window_and_poisoned_tail():
    """A wrapped ring (window_start > 0) drops the boundary row whose
    beta_{k-1}/alpha_{k-1} predates the window; a poisoned tail (NaN
    alpha, the breakdown evidence) is trimmed, not propagated."""
    alphas = np.full(20, 0.5)
    betas = np.full(20, 0.25)
    d, e = health.lanczos_tridiagonal(alphas, betas, window_start=7)
    assert d is not None and d.size == 19  # leading row dropped
    alphas[-3:] = np.nan
    d2, e2 = health.lanczos_tridiagonal(alphas, betas, window_start=0)
    assert d2.size >= 16 and np.isfinite(d2).all()
    assert np.isfinite(e2).all()
    # too-short windows refuse
    assert health.lanczos_tridiagonal([0.5], [0.1]) == (None, None)


# -- dist parity: single vs 8-part audit records --------------------------

def test_dist_audit_parity_single_vs_8part(poisson_csr):
    """The audited dist solve over the 8-device CPU mesh produces the
    SAME audit record as the single-device program: same audit count,
    same audited iterations in the gap column, f64 gaps at rounding
    level on both."""
    import jax.numpy as jnp

    from acg_tpu.parallel.dist import DistCGSolver, DistributedProblem
    from acg_tpu.partition import partition_rows

    n = poisson_csr.shape[0]
    b = np.ones(n)
    crit = StoppingCriteria(maxits=300, residual_rtol=1e-10)
    spec = health.make_spec(every=8)

    s1 = _jax_solver(poisson_csr, health=spec, trace=128)
    s1.solve(b, criteria=crit)

    part = partition_rows(poisson_csr, 8, seed=0, method="band")
    prob = DistributedProblem.build(poisson_csr, part, 8,
                                    dtype=jnp.float64)
    s8 = DistCGSolver(prob, health=spec, trace=128)
    s8.solve(b, criteria=crit)

    h1, h8 = s1.stats.health, s8.stats.health
    assert s1.stats.niterations == s8.stats.niterations
    assert h1["naudits"] == h8["naudits"] > 0
    assert h1["gap_max"] < 1e-11 and h8["gap_max"] < 1e-11
    gi = s1.last_trace.fields.index("gap")
    audited1 = s1.last_trace.iterations[
        np.isfinite(s1.last_trace.records[:, gi])]
    audited8 = s8.last_trace.iterations[
        np.isfinite(s8.last_trace.records[:, gi])]
    np.testing.assert_array_equal(audited1, audited8)


def test_sharded_gen_direct_audit():
    """The fourth tier: the sharded gen-direct solver inherits the
    audited programs unchanged -- the audit's roll SpMV partitions
    into the usual boundary collective-permutes and the gap psums
    through sharding propagation like the CG scalars."""
    import jax.numpy as jnp

    from acg_tpu.parallel.sharded_dia import build_sharded_poisson_solver

    s = build_sharded_poisson_solver(
        16, 2, nparts=8, dtype=jnp.float64, pipelined=True,
        health=health.make_spec(every=8))
    s.solve(s.ones_b(), criteria=StoppingCriteria(maxits=300,
                                                  residual_rtol=1e-9),
            host_result=False)
    h = s.stats.health
    assert h["naudits"] > 0 and h["gap_max"] < 1e-11


# -- telemetry audit column: meta + round trip (the small-fix satellite) --

def test_audit_column_roundtrip_and_tail_note(poisson_csr, tmp_path):
    s = _jax_solver(poisson_csr, health=health.make_spec(every=6),
                    trace=64)
    s.solve(np.ones(poisson_csr.shape[0]),
            criteria=StoppingCriteria(maxits=200, residual_rtol=1e-9))
    t = s.last_trace
    assert t.fields == ("rnrm2", "alpha", "beta", "pAp", "gap")
    # the meta line declares the audit column so mixed windows never
    # misalign; NaN (unaudited) survives as a "nan" string
    path = tmp_path / "c.jsonl"
    t.write_jsonl(path)
    meta, records = telemetry.read_convergence_log(path)
    assert meta["fields"] == ["rnrm2", "alpha", "beta", "pAp", "gap"]
    assert t.to_dict()["records"] == records
    audited = [r for r in records if isinstance(r["gap"], float)]
    unaudited = [r for r in records if isinstance(r["gap"], str)]
    assert audited and unaudited  # a genuinely mixed window
    assert all((r["it"] + 1) % 6 == 0 for r in audited)
    # tail_summary flags the column and quotes the gap inline
    tail = t.tail_summary(8)
    assert "[audit gap column present]" in tail
    assert "(gap " in tail
    # an unaudited trace keeps the pre-/5 4-field layout exactly
    s2 = _jax_solver(poisson_csr, trace=16)
    s2.solve(np.ones(poisson_csr.shape[0]),
             criteria=StoppingCriteria(maxits=50),
             raise_on_divergence=False)
    assert s2.last_trace.fields == ("rnrm2", "alpha", "beta", "pAp")
    assert "audit" not in s2.last_trace.tail_summary()


def test_host_oracle_audit_and_replace(poisson_csr):
    """The eager f64 twin: audits fire on the device schedule, the gap
    column rides the recorder, replacement applies literally, abort
    raises."""
    from acg_tpu.errors import BreakdownError
    from acg_tpu.solvers.host_cg import HostCGSolver

    n = poisson_csr.shape[0]
    hs = HostCGSolver(poisson_csr, trace=128,
                      health=health.make_spec(every=5))
    hs.solve(np.ones(n), criteria=StoppingCriteria(maxits=300,
                                                   residual_rtol=1e-10))
    h = hs.stats.health
    assert h["naudits"] > 0 and h["gap_max"] < 1e-12
    gi = hs.last_trace.fields.index("gap")
    assert np.isfinite(hs.last_trace.records[:, gi]).sum() > 0

    # an (artificially) hair-trigger threshold: every audit replaces --
    # bounded by the SAME restart budget the compiled tiers consume,
    # and counted on the same resilience counters
    from acg_tpu.solvers.resilience import RecoveryPolicy

    hr = HostCGSolver(poisson_csr,
                      recovery=RecoveryPolicy(max_restarts=100,
                                              fallback_host=False),
                      health=health.make_spec(every=5, threshold=1e-300,
                                              action="replace"))
    hr.solve(np.ones(n), criteria=StoppingCriteria(maxits=300,
                                                   residual_rtol=1e-10))
    assert hr.stats.converged
    assert hr.stats.nrestarts >= 1  # each replacement consumes budget
    assert any("residual replacement" in ev
               for ev in hr.stats.recovery_log)
    assert any(ev["kind"] == "accuracy_degraded"
               for ev in hr.stats.events)

    # without a policy the replacement budget is zero: the first trip
    # raises with the gap named (never an unbounded replacement loop)
    hz = HostCGSolver(poisson_csr,
                      health=health.make_spec(every=5, threshold=1e-300,
                                              action="replace"))
    with pytest.raises(BreakdownError, match="gap"):
        hz.solve(np.ones(n), criteria=StoppingCriteria(
            maxits=300, residual_rtol=1e-10))
    assert hz.stats.health["naudits"] >= 1  # audit evidence survives

    ha = HostCGSolver(poisson_csr,
                      health=health.make_spec(every=5, threshold=1e-300,
                                              action="abort"))
    with pytest.raises(BreakdownError, match="gap"):
        ha.solve(np.ones(n), criteria=StoppingCriteria(
            maxits=300, residual_rtol=1e-10))


# -- metrics / soak / stats surfaces --------------------------------------

def test_health_metrics_and_section(poisson_csr):
    from acg_tpu import metrics

    was = metrics.armed()
    try:
        metrics.arm()
        g0 = metrics.HEALTH_AUDITS.value
        s = _jax_solver(poisson_csr, health=health.make_spec(every=5),
                        trace=64)
        s.solve(np.ones(poisson_csr.shape[0]),
                criteria=StoppingCriteria(maxits=200,
                                          residual_rtol=1e-9))
        assert metrics.HEALTH_AUDITS.value > g0
        assert math.isfinite(metrics.HEALTH_GAP.value)
        txt = metrics.expose()
        for fam in ("acg_health_residual_gap", "acg_health_audits_total",
                    "acg_health_kappa_estimate",
                    "acg_health_gap_trips_total"):
            assert fam in txt
    finally:
        if not was:
            metrics.disarm()


def test_health_section_appends_only():
    """Like soak:/precond:, health: appends strictly after the
    reference-format block -- a report without it is a byte-prefix of
    one with it, and the /5 twin carries the full structure."""
    st = SolverStats(unknowns=7)
    st.precond.update({"kind": "jacobi"})
    base = st.fwrite()
    st.health.update({"audit_every": 10, "gap_last": 1e-6,
                      "spectrum": {"kappa": 123.4}})
    txt = st.fwrite()
    assert txt.startswith(base)
    assert "health:" in txt[len(base):]
    d = st.to_dict()
    assert d["health"]["spectrum"]["kappa"] == 123.4
    assert telemetry.STATS_SCHEMA == "acg-tpu-stats/12"
    json.dumps(telemetry.stats_document(st))


def test_soak_tracks_gap(poisson_csr):
    from acg_tpu.soak import run_soak

    s = _jax_solver(poisson_csr, health=health.make_spec(every=5))
    _x, report = run_soak(
        s, np.ones(poisson_csr.shape[0]), nsolves=3,
        criteria=StoppingCriteria(maxits=200, residual_rtol=1e-9))
    gap = report["gap"]
    assert math.isfinite(gap["first"]) and math.isfinite(gap["last"])
    assert gap["max"] >= gap["last"] > 0


# -- explain convergence verdict ------------------------------------------

def test_explain_convergence_verdict(aniso_csr, capsys):
    import io
    import types

    from acg_tpu.perfmodel import _explain_convergence
    from acg_tpu.precond import parse_precond

    args = types.SimpleNamespace(residual_rtol=1e-8, max_iterations=400,
                                 _precond=parse_precond("jacobi"))
    err = io.StringIO()
    rep = _explain_convergence(args, aniso_csr, [], err)
    out = err.getvalue()
    assert rep is not None and rep["kappa"] > 1
    assert rep["precond_effectiveness"] > 1  # jacobi compresses here
    assert "explain: convergence" in out
    assert "preconditioner effectiveness" in out
    assert "predicted" in out


# -- satellites: bench_diff backend-unavailable capture -------------------

def test_bench_diff_unavailable_capture(tmp_path):
    """A capture recording only bench_backend_unavailable (a run whose
    backend never came up) exits 2 with the re-baseline message instead
    of attempting a comparison."""
    script = os.path.join(REPO, "scripts", "bench_diff.py")
    r04 = tmp_path / "BENCH_ok.json"
    r04.write_text(json.dumps({"n": 4, "rc": 0, "parsed": {
        "metric": "cg_iters_per_sec_poisson2d_n2048_f32",
        "value": 7000.0, "unit": "iters/s"}}))
    r05 = tmp_path / "BENCH_unavailable.json"
    r05.write_text(json.dumps({"n": 5, "rc": 2, "parsed": {
        "metric": "bench_backend_unavailable", "value": 0,
        "unit": "iters/s", "error": "backend init failed"}}))
    r04, r05 = str(r04), str(r05)
    r = subprocess.run([sys.executable, script, r04, r05],
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 2, r.stdout + r.stderr
    assert "re-baseline before trusting --fail-on-regress" in r.stderr
    assert "bench_backend_unavailable" in r.stderr
    # the sentinel as BASELINE refuses the same way
    r = subprocess.run([sys.executable, script, r05, r04],
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 2
    assert "re-baseline" in r.stderr
    # real captures still compare (no false refusals)
    good = tmp_path / "good.jsonl"
    good.write_text(json.dumps({"metric": "case_a", "value": 10.0})
                    + "\n")
    r = subprocess.run([sys.executable, script, str(good), str(good)],
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stdout + r.stderr


def test_split_unavailable_keeps_real_cases():
    from acg_tpu.perfmodel import split_unavailable

    cases, had = split_unavailable({"bench_backend_unavailable": 0.0,
                                    "cg_iters": 100.0})
    assert had and cases == {"cg_iters": 100.0}
    cases, had = split_unavailable({"cg_iters": 100.0})
    assert not had and cases == {"cg_iters": 100.0}


# -- CLI end-to-end -------------------------------------------------------

ENV_KEYS = {"JAX_PLATFORMS": "cpu",
            "XLA_FLAGS": "--xla_force_host_platform_device_count=8"}


def run_cli(argv, **kw):
    env = dict(os.environ)
    env.update(ENV_KEYS)
    kw.setdefault("timeout", 600)
    return subprocess.run([sys.executable, "-m", "acg_tpu.cli", *argv],
                          capture_output=True, text=True, env=env, **kw)


def test_cli_health_end_to_end(tmp_path):
    """--audit-every on a dist solve over the 8-device mesh: health:
    section + /5 stats doc with a spectrum estimate + gap column in
    the convergence log + acg_health_* families in the textfile."""
    stats = tmp_path / "s.json"
    conv = tmp_path / "c.jsonl"
    prom = tmp_path / "m.prom"
    r = run_cli(["gen:poisson2d:24", "--nparts", "8",
                 "--max-iterations", "300", "--residual-rtol", "1e-8",
                 "--warmup", "0", "--quiet", "--audit-every", "10",
                 "--convergence-log", str(conv),
                 "--metrics-file", str(prom),
                 "--stats-json", str(stats)])
    assert r.returncode == 0, r.stderr
    assert "health:" in r.stderr
    doc = json.loads(stats.read_text())
    assert doc["schema"] == "acg-tpu-stats/12"
    h = doc["stats"]["health"]
    assert h["naudits"] > 0 and isinstance(h["gap_last"], float)
    assert h["spectrum"]["kappa"] > 1
    assert h["spectrum"]["predicted_iterations"] >= 1
    meta, records = telemetry.read_convergence_log(conv)
    assert "gap" in meta["fields"]
    assert any(isinstance(rec.get("gap"), float) for rec in records)
    txt = prom.read_text()
    assert "acg_health_residual_gap" in txt
    assert "acg_health_kappa_estimate" in txt


def test_cli_health_flag_validation():
    r = run_cli(["gen:poisson2d:8", "--comm", "none", "--quiet",
                 "--gap-threshold", "1e-4"])
    assert r.returncode != 0
    assert "--gap-threshold needs --audit-every" in r.stderr
    r = run_cli(["gen:poisson2d:8", "--comm", "none", "--quiet",
                 "--audit-every", "5", "--on-gap", "replace"])
    assert r.returncode != 0
    assert "gap threshold" in r.stderr
    r = run_cli(["gen:poisson2d:8", "--comm", "none", "--quiet",
                 "--solver", "host-native", "--audit-every", "5"])
    assert r.returncode != 0
    assert "no audit hooks" in r.stderr


def test_cli_buildinfo_advertises_health():
    r = run_cli(["--buildinfo"])
    assert r.returncode == 0, r.stderr
    assert "--audit-every" in r.stdout
    assert "--on-gap" in r.stdout
    assert "acg-tpu-stats/12" in r.stdout


def test_plot_convergence_renders_gap(tmp_path):
    """The plotting satellite: a gap-bearing log renders the audit
    trail in the text fallback."""
    t = telemetry.ConvergenceTrace(
        capacity=8, niterations=8,
        records=np.column_stack([
            np.logspace(0, -7, 8), np.ones(8), np.ones(8), np.ones(8),
            [math.nan, 1e-7, math.nan, 1e-6, math.nan, 1e-5,
             math.nan, 1e-4]]),
        iterations=np.arange(8), wrapped=False,
        fields=("rnrm2", "alpha", "beta", "pAp", "gap"))
    path = tmp_path / "c.jsonl"
    t.write_jsonl(path)
    script = os.path.join(REPO, "scripts", "plot_convergence.py")
    r = subprocess.run([sys.executable, script, str(path), "--ascii"],
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr
    assert "audit gap max 1.000e-04" in r.stdout
    assert "gap:" in r.stdout
