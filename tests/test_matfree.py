"""Matrix-free operator tier (ISSUE 15, acg_tpu.ops.operator).

The contract under test: ``A`` as a jitted apply rides EVERY solver
tier through the ops.spmv dispatch with trajectories BITWISE-equal to
the assembled-DIA tier of the same system -- classic/pipelined, the CA
recurrences, precond (jacobi via the analytic diagonal, cheby via
applies), ABFT (checksum through the apply), the batched multi-RHS
tier, and the distributed mesh (generated local planes behind the
existing halo/ghost machinery, incl. the fused interior|border split
and the one-sided DMA transport).  Refusals are typed and
self-describing (the could-never-fire discipline).
"""

import subprocess
import sys

import numpy as np
import pytest

import jax.numpy as jnp

from acg_tpu.errors import AcgError
from acg_tpu.io.generators import (aniso_poisson2d_coo, poisson2d_coo,
                                   poisson3d_coo)
from acg_tpu.matrix import SymCsrMatrix
from acg_tpu.ops.operator import (aniso2d_stencil, build_operator,
                                  parse_operator_spec, poisson_stencil,
                                  register_operator, user_operator)
from acg_tpu.ops.spmv import dia_from_csr, matrix_diagonal, spmv
from acg_tpu.solvers.jax_cg import JaxCGSolver
from acg_tpu.solvers.stats import StoppingCriteria


def _poisson1d_csr(n):
    idx = np.arange(n)
    r = np.concatenate([idx, idx[1:], idx[:-1]])
    c = np.concatenate([idx, idx[:-1], idx[1:]])
    v = np.concatenate([np.full(n, 2.0), np.full(2 * (n - 1), -1.0)])
    return SymCsrMatrix.from_coo(n, r, c, v).to_csr()


@pytest.fixture(scope="module")
def aniso_pair():
    """(csr, assembled DIA, operator) of the variable-coefficient
    family -- the stencil whose tables exercise the pre-rounding
    contract."""
    n, eps = 16, 0.1
    r, c, v, N = aniso_poisson2d_coo(n, eps)
    csr = SymCsrMatrix.from_coo(N, r, c, v).to_csr()
    return csr, dia_from_csr(csr, dtype=jnp.float64), \
        aniso2d_stencil(n, eps, dtype=jnp.float64)


# -- apply / diagonal parity ----------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.float64])
def test_apply_bitwise_parity_per_stencil(dtype):
    """Every built-in stencil's generated apply equals the assembled
    DIA SpMV BITWISE (same values, same dia_mv accumulation), and the
    analytic diagonal/nnz match the assembled extraction exactly."""
    cases = []
    for dim, n in ((1, 15), (2, 11), (3, 5)):
        if dim == 1:
            csr = _poisson1d_csr(n)
        else:
            gen = poisson2d_coo if dim == 2 else poisson3d_coo
            r, c, v, N = gen(n)
            csr = SymCsrMatrix.from_coo(N, r, c, v).to_csr()
        cases.append((csr, poisson_stencil(n, dim, dtype=dtype)))
    r, c, v, N = aniso_poisson2d_coo(11, 0.07)
    cases.append((SymCsrMatrix.from_coo(N, r, c, v).to_csr(),
                  aniso2d_stencil(11, 0.07, dtype=dtype)))
    rng = np.random.default_rng(0)
    for csr, op in cases:
        A = dia_from_csr(csr, dtype=dtype)
        assert op.offsets == A.offsets
        x = jnp.asarray(rng.standard_normal(csr.shape[0]), dtype)
        assert np.array_equal(np.asarray(spmv(A, x)),
                              np.asarray(spmv(op, x)))
        assert np.array_equal(np.asarray(matrix_diagonal(A)),
                              np.asarray(matrix_diagonal(op)))
        assert int(op.matfree_nnz()) == csr.nnz


# -- single-device solver tiers -------------------------------------------

@pytest.mark.parametrize("kw", [dict(), dict(algorithm="sstep:4"),
                                dict(precond="jacobi")])
def test_solver_trajectory_parity_bitwise(aniso_pair, kw):
    """Tiers whose applies consume/produce LOOP-CARRIED state --
    classic (the headline bench protocol), s-step, jacobi PCG -- take
    the same iterations matrix-free vs assembled, with iterates equal
    to an ulp bound of float64.  Per apply the structured products are
    bitwise the assembled ones (test_apply_bitwise_parity_per_stencil),
    but the two are different programs: XLA fuses the generated planes
    and the stored planes into the loop differently, so they round
    differently (one ulp from the first iteration on), and CG amplifies
    that near convergence -- measured on this system 149,488 ulp of
    max|x| (classic), 34,092 (s-step) and 2.2 (jacobi)."""
    _, A, op = aniso_pair
    b = np.random.default_rng(0).standard_normal(A.nrows)
    crit = StoppingCriteria(maxits=600, residual_rtol=1e-9)
    sa = JaxCGSolver(A, kernels="xla", **kw)
    sm = JaxCGSolver(op, kernels="xla", **kw)
    xa = np.asarray(sa.solve(b, criteria=crit))
    xm = np.asarray(sm.solve(b, criteria=crit))
    assert sa.stats.niterations == sm.stats.niterations
    ulps = {"": 2 ** 20, "sstep:4": 2 ** 18, "jacobi": 16}[
        str(kw.get("algorithm", kw.get("precond", "")))]
    np.testing.assert_allclose(
        xm, xa, rtol=0, atol=ulps * np.finfo(xa.dtype).eps
        * np.abs(xa).max())


@pytest.mark.parametrize("kw", [dict(pipelined=True),
                                dict(algorithm="pipelined:2"),
                                dict(precond="cheby:2")])
def test_solver_trajectory_parity_chained(aniso_pair, kw):
    """Tiers that CHAIN applies inside one fused region (the pipelined
    setup's w = A(b - A x0), cheby's K-apply polynomial) let XLA
    contract the fused multiply-adds differently than the assembled
    build: per apply the structured form is bitwise-equal (pinned in
    test_apply_bitwise_parity_per_stencil), in-program the
    trajectories agree to FMA reassociation -- solutions match to
    ~1e-8 relative and iteration counts within the rounding jitter
    any ulp perturbation produces near the tolerance."""
    _, A, op = aniso_pair
    b = np.random.default_rng(0).standard_normal(A.nrows)
    crit = StoppingCriteria(maxits=600, residual_rtol=1e-9)
    sa = JaxCGSolver(A, kernels="xla", **kw)
    sm = JaxCGSolver(op, kernels="xla", **kw)
    xa = sa.solve(b, criteria=crit)
    xm = sm.solve(b, criteria=crit)
    assert abs(sa.stats.niterations - sm.stats.niterations) <= 3
    np.testing.assert_allclose(np.asarray(xm), np.asarray(xa),
                               rtol=1e-7, atol=1e-9)


def test_abft_and_health_through_apply(aniso_pair):
    """The health tier's true-residual audit AND the Huang-Abraham ABFT
    checksum (c = A^T 1 computed through the apply at setup) run
    matrix-free: audits fire, checks count, the solve converges to the
    assembled answer (the setup checksum chains an apply into the
    fused setup region, so this is the FMA-equivalence contract)."""
    from acg_tpu.health import make_spec
    _, A, op = aniso_pair
    b = np.random.default_rng(1).standard_normal(A.nrows)
    crit = StoppingCriteria(maxits=600, residual_rtol=1e-9)
    hs = make_spec(every=7, abft=True)
    sa = JaxCGSolver(A, kernels="xla", health=hs)
    sm = JaxCGSolver(op, kernels="xla", health=hs)
    xa = sa.solve(b, criteria=crit)
    xm = sm.solve(b, criteria=crit)
    np.testing.assert_allclose(np.asarray(xm), np.asarray(xa),
                               rtol=1e-7, atol=1e-9)
    assert sm.stats.health["naudits"] > 0
    assert sm.stats.health["abft"]["nchecks"] > 0


def test_bjacobi_refuses_matfree(aniso_pair):
    """bjacobi factors stored blocks; an armed spec over an operator
    refuses self-describingly at state setup."""
    _, _, op = aniso_pair
    s = JaxCGSolver(op, kernels="xla", precond="bjacobi:8")
    with pytest.raises(AcgError, match="bjacobi"):
        s.solve(np.ones(op.nrows),
                criteria=StoppingCriteria(maxits=5),
                raise_on_divergence=False)


def test_bf16_vectors_refuse_matfree(aniso_pair):
    _, _, op = aniso_pair
    with pytest.raises(ValueError, match="bf16"):
        JaxCGSolver(op, kernels="xla", vector_dtype=jnp.bfloat16)


def test_batched_matfree_parity(aniso_pair):
    """The batched multi-RHS tier rides the operator's multi-column
    apply: per-column results bitwise-equal to the assembled batched
    solve."""
    from acg_tpu.solvers.batched import BatchedCGSolver
    _, A, op = aniso_pair
    n = A.nrows
    B = np.random.default_rng(2).standard_normal((n, 3))
    crit = StoppingCriteria(maxits=600, residual_rtol=1e-9)
    sa = BatchedCGSolver(A)
    sm = BatchedCGSolver(op)
    xa = sa.solve(B, criteria=crit)
    xm = sm.solve(B, criteria=crit)
    assert np.array_equal(np.asarray(xa), np.asarray(xm))


# -- user-operator registration hook --------------------------------------

def test_user_operator_registration():
    """A registered jitted operator solves through every hook: apply in
    the loop, diagonal_fn arming jacobi; registration is validated."""
    n = 64
    d = np.linspace(1.0, 4.0, n)

    register_operator(
        "testdiag",
        lambda caps, x: caps[0] * x,
        diagonal_fn=lambda caps: caps[0],
        nnz=n)
    op = user_operator("testdiag", n, dtype=jnp.float64,
                       captures=(jnp.asarray(d),))
    b = np.random.default_rng(3).standard_normal(n)
    s = JaxCGSolver(op, kernels="xla", precond="jacobi")
    x = s.solve(b, criteria=StoppingCriteria(maxits=200,
                                             residual_rtol=1e-12))
    assert np.allclose(np.asarray(x), b / d, rtol=1e-10)

    register_operator("testdiag_nodiag", lambda caps, x: caps[0] * x)
    op2 = user_operator("testdiag_nodiag", n, dtype=jnp.float64,
                        captures=(jnp.asarray(d),))
    s2 = JaxCGSolver(op2, kernels="xla", precond="jacobi")
    with pytest.raises(AcgError, match="diagonal_fn"):
        s2.solve(b, criteria=StoppingCriteria(maxits=5),
                 raise_on_divergence=False)

    with pytest.raises(AcgError, match="not registered"):
        user_operator("no_such_operator", n)
    with pytest.raises(ValueError, match="callable"):
        register_operator("bad", "not-a-function")


# -- the Pallas stencil path ----------------------------------------------

def test_pallas_stencil_kernel_interpret():
    """The in-kernel-generated stencil SpMV (interpret mode) matches
    the XLA matfree apply -- bitwise on the 1D/2D shapes, to FMA
    reassociation (1 ulp) on 3D -- and degrades to the XLA apply off
    the supported route."""
    from acg_tpu.ops.pallas_kernels import stencil_spmv
    rng = np.random.default_rng(0)
    for dim, n, tile in ((1, 512, 128), (2, 32, 256)):
        op = poisson_stencil(n, dim, dtype=jnp.float32)
        x = jnp.asarray(rng.standard_normal(n ** dim), jnp.float32)
        y = stencil_spmv(op, x, interpret=True, tile=tile, align=8)
        assert np.array_equal(np.asarray(y),
                              np.asarray(op.matfree_apply(x)))
    op = poisson_stencil(8, 3, dtype=jnp.float32)
    x = jnp.asarray(rng.standard_normal(512), jnp.float32)
    y = stencil_spmv(op, x, interpret=True, tile=128, align=8)
    np.testing.assert_allclose(np.asarray(y),
                               np.asarray(op.matfree_apply(x)),
                               rtol=2e-6, atol=2e-6)
    # ragged shape: no route -> the operator's own XLA apply
    op = poisson_stencil(10, 2, dtype=jnp.float32)
    x = jnp.ones(100, jnp.float32)
    assert np.array_equal(np.asarray(stencil_spmv(op, x, interpret=True)),
                          np.asarray(op.matfree_apply(x)))


def test_pallas_kernels_solver_route(aniso_pair):
    """kernels='pallas-interpret' over an operator dispatches the
    stencil kernel for const-Poisson and falls back to the XLA apply
    for kinds without one -- both converge to the assembled answer."""
    n = 16
    r, c, v, N = poisson2d_coo(n)
    csr = SymCsrMatrix.from_coo(N, r, c, v).to_csr()
    op = poisson_stencil(n, 2, dtype=jnp.float64)
    b = np.random.default_rng(4).standard_normal(N)
    crit = StoppingCriteria(maxits=400, residual_rtol=1e-10)
    x_ref = JaxCGSolver(dia_from_csr(csr, dtype=jnp.float64),
                        kernels="xla").solve(b, criteria=crit)
    x_pal = JaxCGSolver(op, kernels="pallas").solve(b, criteria=crit)
    np.testing.assert_allclose(np.asarray(x_pal), np.asarray(x_ref),
                               rtol=1e-8, atol=1e-8)


# -- distributed tier ------------------------------------------------------

def _dist_pair(csr, op, nparts=4, **kw):
    from acg_tpu.parallel.dist import (DistCGSolver, DistributedProblem,
                                       arm_matfree)
    from acg_tpu.partition import partition_rows
    part = partition_rows(csr, nparts, seed=0, method="band")
    pa = DistributedProblem.build(csr, part, nparts, dtype=jnp.float64)
    pm = DistributedProblem.build(csr, part, nparts, dtype=jnp.float64)
    arm_matfree(pm, op)
    return DistCGSolver(pa, **kw), DistCGSolver(pm, **kw)


@pytest.mark.parametrize("kw", [dict(), dict(pipelined=True),
                                dict(kernels="fused"),
                                dict(kernels="fused", pipelined=True),
                                dict(comm="dma"),
                                dict(precond="jacobi")])
def test_dist_matfree_parity(aniso_pair, kw):
    """The armed matfree local block is bitwise-equal to the assembled
    stacked DIA planes across the dist tiers: classic/pipelined, the
    fused interior|border OVERLAPPED split applied to the stencil
    apply, the one-sided DMA transport, and stacked-jacobi PCG."""
    csr, _, op = aniso_pair
    b = np.random.default_rng(5).standard_normal(csr.shape[0])
    crit = StoppingCriteria(maxits=600, residual_rtol=1e-9)
    sa, sm = _dist_pair(csr, op, **kw)
    xa = sa.solve(b, criteria=crit)
    xm = sm.solve(b, criteria=crit)
    assert sa.stats.niterations == sm.stats.niterations
    assert np.array_equal(np.asarray(xa), np.asarray(xm))


def test_dist_matfree_matches_single(aniso_pair):
    """Single-device matfree and 4-part matfree agree (the dist solve
    reassembles to the same answer at tolerance)."""
    csr, _, op = aniso_pair
    b = np.random.default_rng(6).standard_normal(csr.shape[0])
    crit = StoppingCriteria(maxits=600, residual_rtol=1e-10)
    x1 = JaxCGSolver(op, kernels="xla").solve(b, criteria=crit)
    _, sm = _dist_pair(csr, op)
    xm = sm.solve(b, criteria=crit)
    np.testing.assert_allclose(np.asarray(xm), np.asarray(x1),
                               rtol=1e-8, atol=1e-8)


def test_dist_matfree_ledger(aniso_pair):
    """The comm ledger declares the operator: identity, the matrix-free
    marker, and the table-bytes matrix term (the --explain input)."""
    csr, _, op = aniso_pair
    _, sm = _dist_pair(csr, op)
    led = sm.comm_profile()
    assert led["matrix_free"] is True
    assert led["operator"] == op.identity()
    # three f64 tables of n, n+1, n rows
    n = op.grid[0]
    assert led["matrix_bytes_per_spmv"] == 8 * (3 * n + 1)
    # the fused tier's overlap stanza prices ZERO interior matrix bytes
    _, sf = _dist_pair(csr, op, kernels="fused")
    ov = sf.comm_profile()["overlap"]
    dbl = 8
    assert ov["interior_matrix_bytes"] == 2 * ov["interior_rows"] * dbl


def test_dist_matfree_refusals(aniso_pair):
    """Typed refusals: scattered partitions, wrong sizes, user
    operators, restricted builds."""
    from acg_tpu.parallel.dist import DistributedProblem, arm_matfree
    from acg_tpu.partition import partition_rows
    csr, _, op = aniso_pair
    part = partition_rows(csr, 4, seed=0, method="graph")
    prob = DistributedProblem.build(csr, part, 4, dtype=jnp.float64)
    with pytest.raises(AcgError, match="band partition"):
        arm_matfree(prob, op)
    part = partition_rows(csr, 4, seed=0, method="band")
    prob = DistributedProblem.build(csr, part, 4, dtype=jnp.float64)
    with pytest.raises(AcgError, match="rows"):
        arm_matfree(prob, poisson_stencil(8, 2, dtype=jnp.float64))
    with pytest.raises(AcgError, match="dtype"):
        arm_matfree(prob, aniso2d_stencil(16, 0.1, dtype=jnp.float32))
    register_operator("dist_refusal_probe", lambda caps, x: x)
    with pytest.raises(AcgError, match="single-device"):
        arm_matfree(prob, user_operator("dist_refusal_probe",
                                        csr.shape[0]))


# -- spec parsing / case keys ---------------------------------------------

def test_operator_spec_parsing():
    assert parse_operator_spec(None) is None
    assert parse_operator_spec("none") is None
    assert parse_operator_spec("stencil") == ("auto",)
    assert parse_operator_spec("stencil:poisson2d:64") == \
        ("poisson", 2, 64)
    assert parse_operator_spec("stencil:aniso2d:32:0.05") == \
        ("aniso2d", 32, 0.05)
    assert parse_operator_spec("user:myop") == ("user", "myop")
    for bad in ("stencil:poisson4d:8", "stencil:poisson2d",
                "stencil:aniso2d:8", "wat", "user:"):
        with pytest.raises(ValueError):
            parse_operator_spec(bad)
    # explicit spec validated against the gen: matrix being solved --
    # the match must be AFFIRMATIVE: a non-matching kind or a missing
    # --aniso must refuse, never silently solve a different system
    gen = ("poisson", 2, 16, 256, None)
    with pytest.raises(ValueError, match="does not compute"):
        build_operator(("poisson", 2, 32), jnp.float64, gen=gen)
    with pytest.raises(ValueError, match="does not compute"):
        build_operator(("poisson", 2, 16), jnp.float64,
                       gen=("irregular", 0, 256, 256, 16.0))
    with pytest.raises(ValueError, match="constant-coefficient"):
        # aniso stencil against the PLAIN poisson matrix (no --aniso)
        build_operator(("aniso2d", 16, 0.01), jnp.float64, gen=gen,
                       aniso=None)
    with pytest.raises(ValueError, match="disagrees"):
        build_operator(("aniso2d", 16, 0.01), jnp.float64, gen=gen,
                       aniso=0.5)
    with pytest.raises(ValueError, match="gen:poisson"):
        build_operator(("auto",), jnp.float64, gen=None)
    # and the affirmative matches still build
    assert build_operator(("poisson", 2, 16), jnp.float64,
                          gen=gen).identity() == "stencil:poisson2d:16"
    assert build_operator(("aniso2d", 16, 0.25), jnp.float64, gen=gen,
                          aniso=0.25).identity() \
        == "stencil:aniso2d:16:0.25"


def test_operator_case_key():
    """The bench/bench_diff case key grows the operator selection (the
    _precond_keyed pattern): matrix-free and assembled captures never
    alias."""
    from acg_tpu.perfmodel import _operator_keyed, _row_case, _doc_case
    assert _operator_keyed("m", None) == "m"
    assert _operator_keyed("m", "none") == "m"
    assert _operator_keyed("m", "stencil:poisson2d:64") == \
        "m|operator=stencil:poisson2d:64"
    k, v = _row_case({"metric": "m", "value": 2.0,
                      "operator": "stencil:poisson2d:64"})
    assert k == "m|operator=stencil:poisson2d:64" and v == 2.0
    doc = {"manifest": {"metric": "m",
                        "operator": "stencil:poisson2d:64"},
           "stats": {"tsolve": 1.0, "niterations": 10}}
    k, v = _doc_case(doc)
    assert k == "m|operator=stencil:poisson2d:64" and v == 10.0


# -- CLI -------------------------------------------------------------------

def test_cli_operator_refusals_fast():
    """Refusal matrix, in-process (these fire before jax init)."""
    from acg_tpu.cli import main
    base = ["gen:poisson2d:12", "--operator", "stencil", "--comm",
            "none", "--quiet"]
    for extra in (["--dtype", "bf16"], ["--solver", "host"],
                  ["--replace-every", "8"], ["--refine"],
                  ["--spmv-format", "ell"], ["--epsilon", "0.5"],
                  ["--distributed-read"],
                  ["--nrhs", "2", "--block-cg"]):
        with pytest.raises(SystemExit):
            main(base + extra)
    # a file matrix cannot pair with a stencil spec
    with pytest.raises(SystemExit):
        main(["some_file.mtx", "--operator", "stencil:poisson2d:12",
              "--comm", "none", "--quiet"])


def test_cli_operator_e2e(tmp_path):
    """End-to-end: an 8-part matrix-free stencil solve converges, the
    manifest carries the operator identity, and the solution equals the
    assembled run's BYTE-identically (the trajectory bitwise
    contract, observed through the printed vector)."""
    import json
    env_args = ["gen:poisson2d:20", "--nparts", "8",
                "--max-iterations", "300", "--residual-rtol", "1e-8",
                "--warmup", "0"]
    out_a = tmp_path / "xa.mtx"
    out_m = tmp_path / "xm.mtx"
    sj = tmp_path / "mf.json"
    ra = run_cli(env_args + ["-o", str(out_a), "--quiet"])
    rm = run_cli(env_args + ["--operator", "stencil", "-o", str(out_m),
                             "--quiet", "--stats-json", str(sj)])
    assert ra.returncode == 0, ra.stderr
    assert rm.returncode == 0, rm.stderr
    assert out_a.read_bytes() == out_m.read_bytes()
    doc = json.loads(sj.read_text())
    assert doc["manifest"]["operator"] == "stencil:poisson2d:20"
    assert doc["stats"]["converged"] is True


def run_cli(argv, **kw):
    import os
    env = dict(os.environ)
    env.update({"JAX_PLATFORMS": "cpu",
                "XLA_FLAGS": "--xla_force_host_platform_device_count=8"})
    kw.setdefault("timeout", 600)
    return subprocess.run([sys.executable, "-m", "acg_tpu.cli", *argv],
                          capture_output=True, text=True, env=env, **kw)
