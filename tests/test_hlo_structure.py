"""Structural (HLO-level) properties of the compiled distributed solve.

The pipelined variant's entire reason to exist is communication
avoidance: both CG scalars ride ONE allreduce per iteration where
classic CG needs two (``cgcuda.c:1730-1737``; our ``pdot2_fused``).
These tests pin that property at the compiler-artifact level -- if a
refactor accidentally splits the fused psum or adds a collective to the
loop body, the lowered program's collective counts change and this
fails, no timing required.
"""

import re

import numpy as np
import pytest

import jax.numpy as jnp

from acg_tpu.io.generators import poisson2d_coo
from acg_tpu.matrix import SymCsrMatrix
from acg_tpu.parallel.dist import DistCGSolver, DistributedProblem
from acg_tpu.partition import partition_rows


@pytest.fixture(scope="module")
def prob():
    r, c, v, N = poisson2d_coo(16)
    csr = SymCsrMatrix.from_coo(N, r, c, v).to_csr()
    part = partition_rows(csr, 4, seed=0, method="band")
    return DistributedProblem.build(csr, part, 4, dtype=jnp.float64)


def _lowered_text(prob, pipelined):
    s = DistCGSolver(prob, pipelined=pipelined)
    b, x0, la, ga, sidx, gsrc, gval, scnt, rcnt = s.device_args(
        np.ones(prob.n))
    tols = jnp.zeros(4)
    args = (la, ga, sidx, gsrc, gval, scnt, rcnt, b, x0, tols, jnp.int32(5))
    return s._program.lower(*args, unbounded=True,
                            needs_diff=False).as_text()


def _counts(txt):
    return (len(re.findall(r"all_reduce", txt)),
            len(re.findall(r"all_to_all", txt)),
            len(re.findall(r"stablehlo\.while|\bwhile\b", txt)))


def test_collective_counts_f32_pipelined_replacement():
    """The f32 pipelined program (residual replacement armed,
    recurrence.replaces): ONE fused in-loop allreduce -- five
    scalars now -- in each of the loop's two legs (the first leg is
    peeled, recurrence._iterate) beside the 4 setup psums; the
    replacement's four SpMVs, each with its halo, in the loop between
    the legs and under no conditional: 2 setup + 2 in-loop + 4
    replacement all_to_alls, three while loops."""
    r, c, v, N = poisson2d_coo(16)
    csr = SymCsrMatrix.from_coo(N, r, c, v).to_csr()
    part = partition_rows(csr, 4, seed=0, method="band")
    prob32 = DistributedProblem.build(csr, part, 4, dtype=jnp.float32)
    txt = _lowered_text(prob32, pipelined=True)
    ar, ata, wl = _counts(txt)
    assert wl == 3
    assert ar == 6, f"f32 pipelined program has {ar} all_reduces"
    assert ata == 8, f"f32 pipelined program has {ata} all_to_alls"
    assert not re.findall(r"stablehlo\.case", txt)


def test_collective_counts(prob):
    """Static collective inventory of the whole-solve programs.

    The loop body appears once in the program text, so whole-program
    counts decompose as setup + body:
      classic:   3 setup psums (||b||, ||x0||, gamma0) + 2 in-loop
                 ((p,t) and (r,r))                         -> 5 ARs
                 1 setup SpMV (r0) + 1 in-loop SpMV        -> 2 A2As
      pipelined: 4 setup psums (+ final fresh ||r||)
                 + 1 in-loop FUSED psum                    -> 5 ARs
                 2 setup SpMVs (r0, w=Ar) + 1 in-loop      -> 3 A2As
    """
    ar_c, ata_c, wl_c = _counts(_lowered_text(prob, pipelined=False))
    ar_p, ata_p, wl_p = _counts(_lowered_text(prob, pipelined=True))
    assert wl_c >= 1 and wl_p >= 1, "solve loop not compiled as while"
    assert ar_c == 5, f"classic program has {ar_c} all_reduces, expected 5"
    assert ata_c == 2, f"classic program has {ata_c} all_to_alls, expected 2"
    assert ar_p == 5, f"pipelined program has {ar_p} all_reduces, expected 5"
    assert ata_p == 3, f"pipelined program has {ata_p} all_to_alls, expected 3"
    # the communication-avoiding property, stated relatively: same AR
    # total despite one extra setup psum => one FEWER in-loop allreduce
    assert ar_p - 4 == 1 and ar_c - 3 == 2


def test_precise_dots_keep_fusion(prob):
    """Compensated dots widen each psum payload (hi+lo pairs) but must
    not add collectives: the pipelined loop still has ONE allreduce."""
    s = DistCGSolver(prob, pipelined=True, precise_dots=True)
    b, x0, la, ga, sidx, gsrc, gval, scnt, rcnt = s.device_args(
        np.ones(prob.n))
    tols = jnp.zeros(4)
    args = (la, ga, sidx, gsrc, gval, scnt, rcnt, b, x0, tols, jnp.int32(5))
    txt = s._program.lower(*args, unbounded=True, needs_diff=False).as_text()
    ar, ata, _ = _counts(txt)
    assert ar == 5, f"precise-dots pipelined program has {ar} all_reduces"
    assert ata == 3


# -- preconditioning tier: none = byte-identical; PCG keeps the
# communication-avoiding structure --------------------------------------

def test_precond_none_is_byte_identical(prob):
    """--precond none must lower BYTE-IDENTICAL programs to a build
    that never mentions the preconditioner -- single-chip and
    distributed (the telemetry/faults/perfmodel disarmament contract,
    extended to the PCG tier)."""
    from acg_tpu.io.generators import poisson2d_coo as _p2
    from acg_tpu.matrix import SymCsrMatrix as _S
    from acg_tpu.ops.spmv import device_matrix_from_csr
    from acg_tpu.solvers.jax_cg import JaxCGSolver

    r, c, v, N = _p2(12)
    csr = _S.from_coo(N, r, c, v).to_csr()
    b1 = np.ones(N)
    A = device_matrix_from_csr(csr, dtype=jnp.float64)
    plain = JaxCGSolver(A, kernels="xla").lower_solve(b1).as_text()
    none = JaxCGSolver(A, kernels="xla",
                       precond="none").lower_solve(b1).as_text()
    armed = JaxCGSolver(A, kernels="xla",
                        precond="jacobi").lower_solve(b1).as_text()
    assert none == plain
    assert armed != plain

    b2 = np.ones(prob.n)
    d_plain = DistCGSolver(prob).lower_solve(b2).as_text()
    d_none = DistCGSolver(prob, precond="none").lower_solve(b2).as_text()
    assert d_none == d_plain


def test_pcg_collective_counts(prob):
    """PCG keeps the tiers' communication structure: the classic loop
    still runs 2 in-loop allreduces (the second FUSES (r, z) with
    (r, r)), the pipelined loop keeps its SINGLE fused in-loop
    allreduce (now 3 scalars), and cheby:K adds exactly K halo'd SpMVs
    per apply site (setup + loop = 2K extra all_to_alls)."""
    b = np.ones(prob.n)

    def counts(pipelined, pc):
        s = DistCGSolver(prob, pipelined=pipelined, precond=pc)
        return _counts(s.lower_solve(b).as_text())[:2]

    # jacobi/bjacobi: zero extra collectives anywhere
    assert counts(False, "jacobi") == (5, 2)
    assert counts(True, "jacobi") == (5, 3)
    assert counts(False, "bjacobi:16") == (5, 2)
    assert counts(True, "bjacobi:16") == (5, 3)
    # cheby:2 -> 2 apply sites x 2 SpMVs, allreduce count unchanged
    assert counts(False, "cheby:2") == (5, 2 + 4)
    assert counts(True, "cheby:2") == (5, 3 + 4)


# -- numerical-health tier: disarmed audit = byte-identical; armed adds
# exactly the conditional audit collectives ------------------------------

def test_health_disarmed_is_byte_identical(prob):
    """--audit-every 0 (default) must lower BYTE-IDENTICAL programs to
    a build that never mentions the health tier -- single-chip and
    distributed (the telemetry/faults/precond/perfmodel disarmament
    contract, extended to the audit)."""
    from acg_tpu.health import make_spec
    from acg_tpu.io.generators import poisson2d_coo as _p2
    from acg_tpu.ops.spmv import device_matrix_from_csr
    from acg_tpu.solvers.jax_cg import JaxCGSolver

    r, c, v, N = _p2(12)
    csr = SymCsrMatrix.from_coo(N, r, c, v).to_csr()
    b1 = np.ones(N)
    A = device_matrix_from_csr(csr, dtype=jnp.float64)
    for pipelined in (False, True):
        plain = JaxCGSolver(A, pipelined=pipelined,
                            kernels="xla").lower_solve(b1).as_text()
        none = JaxCGSolver(A, pipelined=pipelined, kernels="xla",
                           health=None).lower_solve(b1).as_text()
        armed = JaxCGSolver(
            A, pipelined=pipelined, kernels="xla",
            health=make_spec(every=4)).lower_solve(b1).as_text()
        assert none == plain
        assert armed != plain

    b2 = np.ones(prob.n)
    for pipelined in (False, True):
        d_plain = DistCGSolver(prob,
                               pipelined=pipelined).lower_solve(
                                   b2).as_text()
        d_none = DistCGSolver(prob, pipelined=pipelined,
                              health=None).lower_solve(b2).as_text()
        assert d_none == d_plain


def test_health_armed_collective_counts(prob):
    """The armed audit adds EXACTLY one conditional halo'd SpMV (one
    all_to_all region) and one psum (one all_reduce region) to the
    distributed program text -- the audit reuses the tier's own
    machinery, nothing else moves."""
    from acg_tpu.health import make_spec

    b = np.ones(prob.n)

    def counts(pipelined, hs):
        s = DistCGSolver(prob, pipelined=pipelined, health=hs)
        return _counts(s.lower_solve(b).as_text())[:2]

    assert counts(False, None) == (5, 2)
    assert counts(False, make_spec(every=4)) == (6, 3)
    assert counts(True, None) == (5, 3)
    assert counts(True, make_spec(every=4)) == (6, 4)


# -- perfmodel tier: disarmed observability changes NOTHING ---------------

def test_lower_solve_is_the_dispatched_program(prob):
    """The perfmodel hook (DistCGSolver.lower_solve) must hand out the
    program solve() dispatches -- byte-identical StableHLO to lowering
    the cached program by hand with solve()'s own argument
    construction.  A hook that rebuilt or re-parameterised the program
    could silently analyse something the solve never runs."""
    s = DistCGSolver(prob)
    b, x0, la, ga, sidx, gsrc, gval, scnt, rcnt = s.device_args(
        np.ones(prob.n))
    tols = jnp.zeros(4)
    args = (la, ga, sidx, gsrc, gval, scnt, rcnt, b, x0, tols,
            jnp.int32(100))
    direct = s._program.lower(*args, unbounded=True, needs_diff=False,
                              detect=False).as_text()
    hook = s.lower_solve(np.ones(prob.n)).as_text()
    assert hook == direct


def test_perfmodel_analysis_leaves_programs_byte_identical(prob):
    """Disarmed perfmodel (like disarmed telemetry): running a full
    analysis pass -- lower, compile, cost/memory extraction, comm
    ledger -- must leave the solver's lowered solve program
    byte-identical, single-chip and distributed."""
    from acg_tpu import perfmodel
    from acg_tpu.io.generators import poisson2d_coo as _p2
    from acg_tpu.ops.spmv import device_matrix_from_csr
    from acg_tpu.solvers.jax_cg import JaxCGSolver

    r, c, v, N = _p2(12)
    csr = SymCsrMatrix.from_coo(N, r, c, v).to_csr()
    b1 = np.ones(N)
    s1 = JaxCGSolver(device_matrix_from_csr(csr, dtype=jnp.float64),
                     kernels="xla")
    before = s1.lower_solve(b1).as_text()
    perfmodel.analyze_solver(s1, b1)
    perfmodel.per_iteration_cost(s1, b1)
    assert s1.lower_solve(b1).as_text() == before

    s2 = DistCGSolver(prob)
    b2 = np.ones(prob.n)
    before2 = s2.lower_solve(b2).as_text()
    perfmodel.analyze_solver(s2, b2)
    perfmodel.comm_ledger(s2)
    assert s2.lower_solve(b2).as_text() == before2


def test_metrics_layer_leaves_programs_byte_identical(prob):
    """The service-metrics tier is host-side bookkeeping only: arming
    the registry, recording solves/phases/events, and a full soak pass
    must leave the lowered solve programs byte-identical, single-chip
    and distributed (the telemetry/faults/perfmodel disarmament
    contract, extended to PR 4's layer)."""
    from acg_tpu import metrics, soak
    from acg_tpu.io.generators import poisson2d_coo as _p2
    from acg_tpu.ops.spmv import device_matrix_from_csr
    from acg_tpu.solvers.jax_cg import JaxCGSolver
    from acg_tpu.solvers.stats import StoppingCriteria

    r, c, v, N = _p2(12)
    csr = SymCsrMatrix.from_coo(N, r, c, v).to_csr()
    b1 = np.ones(N)
    s1 = JaxCGSolver(device_matrix_from_csr(csr, dtype=jnp.float64),
                     kernels="xla")
    s2 = DistCGSolver(prob)
    b2 = np.ones(prob.n)
    before1 = s1.lower_solve(b1).as_text()
    before2 = s2.lower_solve(b2).as_text()
    was = metrics.armed()
    try:
        metrics.arm()
        soak.run_soak(s1, b1, nsolves=3,
                      criteria=StoppingCriteria(maxits=20),
                      solve_kwargs={"raise_on_divergence": False})
        s2.solve(b2, criteria=StoppingCriteria(maxits=10),
                 raise_on_divergence=False)
        assert s1.lower_solve(b1).as_text() == before1
        assert s2.lower_solve(b2).as_text() == before2
    finally:
        if not was:
            metrics.disarm()


def test_tracing_layer_leaves_programs_byte_identical(prob):
    """The timeline-tracing tier is host-side bookkeeping only: arming
    the span recorder, recording phase spans / instants, and solving
    under it must leave the lowered solve programs byte-identical,
    single-chip and distributed (the metrics-layer disarmament
    contract, extended to PR 8's layer)."""
    from acg_tpu import tracing
    from acg_tpu.io.generators import poisson2d_coo as _p2
    from acg_tpu.ops.spmv import device_matrix_from_csr
    from acg_tpu.solvers.jax_cg import JaxCGSolver
    from acg_tpu.solvers.stats import StoppingCriteria

    r, c, v, N = _p2(12)
    csr = SymCsrMatrix.from_coo(N, r, c, v).to_csr()
    b1 = np.ones(N)
    s1 = JaxCGSolver(device_matrix_from_csr(csr, dtype=jnp.float64),
                     kernels="xla")
    s2 = DistCGSolver(prob)
    b2 = np.ones(prob.n)
    before1 = s1.lower_solve(b1).as_text()
    before2 = s2.lower_solve(b2).as_text()
    try:
        tracing.arm()
        s1.solve(b1, criteria=StoppingCriteria(maxits=10),
                 raise_on_divergence=False)
        s2.solve(b2, criteria=StoppingCriteria(maxits=10),
                 raise_on_divergence=False)
        assert tracing.nspans() > 0  # the hooks DID record
        assert s1.lower_solve(b1).as_text() == before1
        assert s2.lower_solve(b2).as_text() == before2
    finally:
        tracing.disarm()


def test_reqtrace_layer_leaves_programs_byte_identical(prob, tmp_path):
    """The request observatory is host-side stdlib bookkeeping only:
    serving requests with the access ledger armed and request spans
    riding the tracing recorder must leave the lowered solve programs
    byte-identical, single-chip and distributed (the
    metrics/tracing/planner disarmament contract, extended to the
    per-request layer)."""
    from acg_tpu import observatory, tracing
    from acg_tpu.io.generators import poisson2d_coo as _p2
    from acg_tpu.ops.spmv import device_matrix_from_csr
    from acg_tpu.serve import ServeConfig, ServeDaemon
    from acg_tpu.solvers.jax_cg import JaxCGSolver
    from acg_tpu.solvers.stats import StoppingCriteria

    r, c, v, N = _p2(12)
    csr = SymCsrMatrix.from_coo(N, r, c, v).to_csr()
    b1 = np.ones(N)
    s1 = JaxCGSolver(device_matrix_from_csr(csr, dtype=jnp.float64),
                     kernels="xla")
    s2 = DistCGSolver(prob)
    b2 = np.ones(prob.n)
    before1 = s1.lower_solve(b1).as_text()
    before2 = s2.lower_solve(b2).as_text()
    d = ServeDaemon(ServeConfig(
        port=0, default_timeout=60.0,
        access_log=str(tmp_path / "access.jsonl")))
    d.start()
    try:
        tracing.arm()
        status, body = d.submit({"matrix": "gen:poisson2d:12",
                                 "rtol": 1e-8, "maxits": 300,
                                 "request_id": "pin-1"})
        assert status == 200 and body["request_id"] == "pin-1"
        assert tracing.nspans() > 0  # the request lanes DID record
        s1.solve(b1, criteria=StoppingCriteria(maxits=10),
                 raise_on_divergence=False)
        assert s1.lower_solve(b1).as_text() == before1
        assert s2.lower_solve(b2).as_text() == before2
    finally:
        tracing.disarm()
        d.stop()
        observatory._clear_slo()


def test_planner_leaves_programs_byte_identical(prob):
    """The decision observatory is host arithmetic only: building a
    full ranked plan (kappa oracle, candidate pricing, rendering) must
    leave the lowered solve programs byte-identical, single-chip and
    distributed -- disarmed (no --autotune/--plan), the planner never
    touches program emission (the perfmodel/metrics/tracing
    disarmament contract, extended to the planner's layer)."""
    from acg_tpu import planner
    from acg_tpu.io.generators import poisson2d_coo as _p2
    from acg_tpu.ops.spmv import device_matrix_from_csr
    from acg_tpu.solvers.jax_cg import JaxCGSolver

    r, c, v, N = _p2(12)
    csr = SymCsrMatrix.from_coo(N, r, c, v).to_csr()
    b1 = np.ones(N)
    s1 = JaxCGSolver(device_matrix_from_csr(csr, dtype=jnp.float64),
                     kernels="xla")
    s2 = DistCGSolver(prob)
    b2 = np.ones(prob.n)
    before1 = s1.lower_solve(b1).as_text()
    before2 = s2.lower_solve(b2).as_text()
    kappa, src = planner.kappa_estimate(csr, 1e-6, 200)
    doc = planner.build_plan(
        csr, matrix_id="gen:poisson2d:12", nparts=4,
        dtype_name="float64", rtol=1e-6, maxits=200,
        mat_itemsize=8, vec_itemsize=8, kappa=kappa,
        kappa_source=src)
    assert doc["ranked"]
    planner.render_plan(doc)
    assert s1.lower_solve(b1).as_text() == before1
    assert s2.lower_solve(b2).as_text() == before2


def test_tracing_section_appends_only():
    """Like costmodel:/soak:/ckpt:, the tracing: section appends
    strictly after every existing section -- a report without it is a
    byte-prefix of one with it, so pre-/7 consumers see the exact
    historical block."""
    from acg_tpu.solvers.stats import SolverStats

    st = SolverStats(unknowns=7)
    st.timings["solve"] = 0.25
    st.ckpt.update({"every": 8})
    base = st.fwrite()
    st.tracing.update({"available": True,
                       "op_seconds": {"dot": 0.01},
                       "overlap_efficiency": 0.75,
                       "timeline": {"nspans": 5, "nparts": 2}})
    txt = st.fwrite()
    assert txt.startswith(base)
    tail = txt[len(base):]
    assert tail.index("tracing:") >= 0
    assert base.index("ckpt:") < len(base)  # tracing: renders after it
    d = st.to_dict()
    assert d["tracing"]["timeline"]["nparts"] == 2


def test_soak_section_appends_only():
    """Like costmodel:/memory:, the soak: section appends strictly
    after the reference-format block -- a report without it is a
    byte-prefix of one with it."""
    from acg_tpu.solvers.stats import SolverStats

    st = SolverStats(unknowns=7)
    st.timings["solve"] = 0.25
    st.costmodel.update({"flops": 1.0})
    base = st.fwrite()
    st.soak.update({"nsolves": 3,
                    "latency": {"p50": 0.001, "p95": 0.002},
                    "drift": {"tripped": False}})
    txt = st.fwrite()
    assert txt.startswith(base)
    assert "soak:" in txt[len(base):]
    assert st.to_dict()["soak"]["latency"]["p50"] == 0.001


def test_explain_sections_append_only():
    """--explain never mutates the reference-format stats block: the
    costmodel:/memory: sections (like timings:) append strictly AFTER
    it, so the report with them set starts byte-for-byte with the
    report without them."""
    from acg_tpu.solvers.stats import SolverStats

    st = SolverStats(unknowns=7)
    st.timings["solve"] = 0.25  # an existing optional section, for order
    base = st.fwrite()
    st.costmodel.update({"flops": 123.0,
                         "comm": {"halo_bytes_per_iteration": 64,
                                  "neighbors": [{"src": 0, "dst": 1}]}})
    st.memory.update({"argument_bytes": 10, "total_hbm_bytes": 10})
    txt = st.fwrite()
    assert txt.startswith(base)
    tail = txt[len(base):]
    assert tail.index("costmodel:") < tail.index("memory:")
    # lists render summarised in text (full form lives in the JSON twin)
    assert "[1 entries -- see --stats-json]" in tail
    # and the JSON twin round-trips the full structure
    d = st.to_dict()
    assert d["costmodel"]["comm"]["neighbors"] == [{"src": 0, "dst": 1}]


# -- recurrence builder (acg_tpu.recurrence): the communication-avoiding
# collective pins ------------------------------------------------------

def _ca_counts(prob, algorithm):
    s = DistCGSolver(prob, algorithm=algorithm)
    txt = s.lower_solve(np.ones(prob.n)).as_text()
    return _counts(txt)


def test_sstep_collective_counts(prob):
    """s-step CG's communication-avoiding property at the HLO level:
    exactly ONE in-loop allreduce per s-iteration block, for every S --
    whole-program decomposition: 3 setup psums (||b||, ||x0||, gamma0)
    + 1 in-loop Gram -> 4 allreduces REGARDLESS of S (classic: 5, with
    2 in-loop); all_to_alls = 1 setup SpMV + the 2S-1 in-loop basis
    products."""
    for S in (2, 4, 8):
        ar, ata, wl = _ca_counts(prob, f"sstep:{S}")
        assert wl >= 1
        assert ar == 4, f"sstep:{S} lowered {ar} all_reduces, expected 4"
        assert ata == 2 * S, (f"sstep:{S} lowered {ata} all_to_alls, "
                              f"expected {2 * S} (1 setup + 2S-1 basis)")
    # the comparison the tier exists for: classic carries 2 in-loop
    # allreduces (5 total), s-step carries 1 per BLOCK (4 total)
    ar_c, _, _ = _counts(_lowered_text(prob, pipelined=False))
    assert ar_c == 5


def test_pl_collective_counts(prob):
    """p(l)-CG keeps ONE fused allreduce per iteration (the 2l+2-scalar
    z-window reduction) for every depth: 3 setup psums + 1 in-loop ->
    4 allreduces, 1 setup + 1 in-loop SpMV -> 2 all_to_alls."""
    for L in (2, 3):
        ar, ata, wl = _ca_counts(prob, f"pipelined:{L}")
        assert wl >= 1
        assert ar == 4, (f"pipelined:{L} lowered {ar} all_reduces, "
                         f"expected 4")
        assert ata == 2, (f"pipelined:{L} lowered {ata} all_to_alls, "
                          f"expected 2")


# -- matrix-free operator tier (acg_tpu.ops.operator): disarmed =
# byte-identical; armed keeps the assembled collective pins ---------------

def _armed_matfree_prob():
    from acg_tpu.ops.operator import poisson_stencil
    from acg_tpu.parallel.dist import arm_matfree

    r, c, v, N = poisson2d_coo(16)
    csr = SymCsrMatrix.from_coo(N, r, c, v).to_csr()
    part = partition_rows(csr, 4, seed=0, method="band")
    p = DistributedProblem.build(csr, part, 4, dtype=jnp.float64)
    arm_matfree(p, poisson_stencil(16, 2, dtype=jnp.float64))
    return p


def test_matfree_dist_collective_counts(prob):
    """Matrix-free dist programs keep the assembled collective pins
    EXACTLY -- classic 5 AR / 2 A2A, pipelined 5/3: only the local
    plane reads vanished, the halo/reduction machinery is untouched --
    and comm='dma' drops the all_to_alls entirely (the one-sided
    transport, unchanged under the operator)."""
    for pipelined, want in ((False, (5, 2)), (True, (5, 3))):
        ar, ata, wl = _counts(_lowered_text(_armed_matfree_prob(),
                                            pipelined))
        assert wl >= 1
        assert (ar, ata) == want, \
            f"matfree pipelined={pipelined}: {(ar, ata)} != {want}"
    s = DistCGSolver(_armed_matfree_prob(), comm="dma")
    ar, ata, _ = _counts(s.lower_solve(np.ones(16 * 16)).as_text())
    assert ata == 0, f"comm='dma' matfree kept {ata} all_to_alls"
    assert ar == 5


def test_operator_disarmed_is_byte_identical(prob):
    """--operator absent lowers byte-identical programs on every tier
    (the precond/health/telemetry disarmament contract, extended to
    the operator): arming a matfree TWIN problem leaves the plain
    build's lowered text unchanged, and the armed program itself
    differs (its local planes are gone)."""
    from acg_tpu.io.generators import poisson2d_coo as _p2
    from acg_tpu.ops.operator import poisson_stencil
    from acg_tpu.ops.spmv import device_matrix_from_csr
    from acg_tpu.solvers.jax_cg import JaxCGSolver

    b = np.ones(prob.n)
    plain_before = DistCGSolver(prob).lower_solve(b).as_text()
    armed_txt = DistCGSolver(_armed_matfree_prob()).lower_solve(
        b).as_text()
    plain_after = DistCGSolver(prob).lower_solve(b).as_text()
    assert plain_after == plain_before
    assert armed_txt != plain_before

    r, c, v, N = _p2(12)
    csr = SymCsrMatrix.from_coo(N, r, c, v).to_csr()
    b1 = np.ones(N)
    A = device_matrix_from_csr(csr, dtype=jnp.float64)
    assembled_before = JaxCGSolver(A, kernels="xla").lower_solve(
        b1).as_text()
    op_txt = JaxCGSolver(poisson_stencil(12, 2, dtype=jnp.float64),
                         kernels="xla").lower_solve(b1).as_text()
    assembled_after = JaxCGSolver(A, kernels="xla").lower_solve(
        b1).as_text()
    assert assembled_after == assembled_before
    assert op_txt != assembled_before
