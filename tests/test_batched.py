"""Batched multi-RHS CG + block-CG tier (acg_tpu.solvers.batched,
acg_tpu.parallel.dist_batched).

The acceptance surface of ISSUE 11: per-column parity with the
single-RHS tiers (bitwise where the recurrences are identical),
mask-freeze correctness, block-CG's iteration-count win on the aniso
family, B-INVARIANT collective counts at the HLO level, B=1
byte-identity (the disarmed-identity discipline), batched
checkpoint/resume parity, and the per-RHS soak percentiles."""

import re

import numpy as np
import pytest

import jax.numpy as jnp

from acg_tpu.io.generators import (aniso_poisson2d_coo, batched_rhs,
                                   poisson2d_coo)
from acg_tpu.matrix import SymCsrMatrix
from acg_tpu.ops.spmv import device_matrix_from_csr
from acg_tpu.partition import partition_rows
from acg_tpu.parallel.dist import DistCGSolver, DistributedProblem
from acg_tpu.parallel.dist_batched import BatchedDistCGSolver
from acg_tpu.solvers.batched import BatchedCGSolver, spmv_multi
from acg_tpu.solvers.host_cg import host_batched_cg, host_block_cg
from acg_tpu.solvers.jax_cg import JaxCGSolver
from acg_tpu.solvers.stats import StoppingCriteria


@pytest.fixture(scope="module")
def sys16():
    r, c, v, N = poisson2d_coo(16)
    csr = SymCsrMatrix.from_coo(N, r, c, v).to_csr()
    A = device_matrix_from_csr(csr, dtype=jnp.float64)
    B = batched_rhs(N, 3, seed=0)
    return csr, A, B


@pytest.fixture(scope="module")
def dist_prob(sys16):
    csr, _, _ = sys16
    part = partition_rows(csr, 4, seed=0, method="band")
    return DistributedProblem.build(csr, part, 4, dtype=jnp.float64)


CRIT = StoppingCriteria(maxits=500, residual_rtol=1e-10)


# -- multi-vector SpMV ----------------------------------------------------

@pytest.mark.parametrize("fmt", ["dia", "ell", "coo", "bell"])
def test_spmv_multi_matches_columns(sys16, fmt):
    csr, _, B = sys16
    A = device_matrix_from_csr(csr, dtype=jnp.float64, format=fmt)
    Y = np.asarray(spmv_multi(A, jnp.asarray(B)))
    assert np.allclose(Y, csr @ B, atol=1e-12)


# -- batched parity: per-column trajectories ARE the single-RHS ones ------

def test_batched_classic_matches_independent_bitwise(sys16):
    _, A, B = sys16
    s = BatchedCGSolver(A)
    X = s.solve(B, criteria=CRIT)
    assert s.stats.batch["nrhs"] == 3
    for j in range(3):
        s1 = JaxCGSolver(A, kernels="xla")
        x1 = s1.solve(B[:, j], criteria=CRIT)
        assert s.stats.batch["iterations"][j] == s1.stats.niterations
        assert np.array_equal(X[:, j], x1)   # bitwise


def test_batched_pipelined_matches_independent_bitwise(sys16):
    _, A, B = sys16
    s = BatchedCGSolver(A, mode="pipelined")
    X = s.solve(B, criteria=CRIT)
    for j in range(3):
        s1 = JaxCGSolver(A, kernels="xla", pipelined=True)
        x1 = s1.solve(B[:, j], criteria=CRIT)
        assert s.stats.batch["iterations"][j] == s1.stats.niterations
        assert np.array_equal(X[:, j], x1)


def test_batched_precond_matches_independent(sys16):
    _, A, B = sys16
    s = BatchedCGSolver(A, precond="jacobi")
    X = s.solve(B, criteria=CRIT)
    for j in range(3):
        s1 = JaxCGSolver(A, kernels="xla", precond="jacobi")
        x1 = s1.solve(B[:, j], criteria=CRIT)
        assert s.stats.batch["iterations"][j] == s1.stats.niterations
        assert np.allclose(X[:, j], x1, atol=1e-12)


def test_batched_matches_host_oracle(sys16):
    csr, A, B = sys16
    s = BatchedCGSolver(A)
    X = s.solve(B, criteria=CRIT)
    Xh, iters_h, _ = host_batched_cg(csr, B, criteria=CRIT)
    assert np.allclose(X, Xh, atol=1e-8)
    assert s.stats.batch["iterations"] == [int(v) for v in iters_h]


# -- mask freeze ----------------------------------------------------------

def test_converged_column_freezes(sys16):
    """A column converged at ENTRY (x0 = its solution, absolute
    tolerance) must stay bitwise frozen at 0 iterations while the rest
    of the batch runs to convergence."""
    csr, A, B = sys16
    x0 = np.zeros_like(B)
    x0[:, 0] = np.linalg.solve(csr.toarray(), B[:, 0])
    s = BatchedCGSolver(A)
    X = s.solve(B, x0=x0,
                criteria=StoppingCriteria(maxits=300,
                                          residual_atol=1e-8))
    batch = s.stats.batch
    assert batch["iterations"][0] == 0
    assert np.array_equal(X[:, 0], x0[:, 0])   # frozen bitwise
    assert all(batch["converged"])
    assert batch["iterations"][1] > 0 and batch["iterations"][2] > 0


def test_early_converged_column_stays_frozen(sys16):
    """A column that converges mid-run freezes: its final value equals
    an independent solve that STOPPED at the same tolerance, while the
    batch ran on to its slowest column."""
    csr, A, B = sys16
    # column 1 gets a much looser effective target via a larger b
    # norm: scale so its relative tolerance is met many iterations
    # before the others'
    crit = StoppingCriteria(maxits=500, residual_atol=1e-3)
    Bs = B.copy()
    Bs[:, 1] *= 1e-3   # tiny b -> absolute target met early
    s = BatchedCGSolver(A)
    X = s.solve(Bs, criteria=crit)
    its = s.stats.batch["iterations"]
    assert its[1] < its[0] and its[1] < its[2]
    s1 = JaxCGSolver(A, kernels="xla")
    x1 = s1.solve(Bs[:, 1], criteria=crit)
    assert s1.stats.niterations == its[1]
    assert np.array_equal(X[:, 1], x1)


# -- block CG -------------------------------------------------------------

def test_block_cg_solves_and_matches_oracle(sys16):
    csr, A, B = sys16
    s = BatchedCGSolver(A, mode="block")
    X = s.solve(B, criteria=CRIT)
    Xd = np.linalg.solve(csr.toarray(), B)
    assert np.allclose(X, Xd, atol=1e-8)
    Xh, _, _, trips_h = host_block_cg(csr, B, criteria=CRIT)
    assert np.allclose(X, Xh, atol=1e-8)
    # device and host block recurrences take the same trip count
    assert abs(s.stats.batch["block_iterations"] - trips_h) <= 2


def test_block_cg_beats_independent_on_aniso():
    """The ISSUE-11 acceptance: block-CG total iterations (trips x B)
    <= 0.7x the summed iterations of B independent solves on the
    anisotropic family."""
    r, c, v, N = aniso_poisson2d_coo(48, 0.05)
    csr = SymCsrMatrix.from_coo(N, r, c, v).to_csr()
    A = device_matrix_from_csr(csr, dtype=jnp.float64)
    B = batched_rhs(N, 8, seed=0)
    crit = StoppingCriteria(maxits=20000, residual_rtol=1e-8)
    s = BatchedCGSolver(A, mode="block")
    s.solve(B, criteria=crit)
    trips = s.stats.batch["block_iterations"]
    indep = 0
    for j in range(8):
        s1 = JaxCGSolver(A, kernels="xla")
        s1.solve(B[:, j], criteria=crit)
        indep += s1.stats.niterations
    assert trips * 8 <= 0.7 * indep, (trips, indep)


def test_block_cg_deflates_parallel_rhs(sys16):
    """Exactly parallel RHS columns collapse the block to rank 1 --
    the deflated Gram solves must converge anyway (rank deflation on
    breakdown), to the same answer."""
    csr, A, B = sys16
    Bp = np.column_stack([B[:, 0], 2.0 * B[:, 0], B[:, 1]])
    s = BatchedCGSolver(A, mode="block")
    X = s.solve(Bp, criteria=StoppingCriteria(maxits=500,
                                              residual_rtol=1e-8))
    Xd = np.linalg.solve(csr.toarray(), Bp)
    assert np.allclose(X, Xd, atol=1e-6)
    assert all(s.stats.batch["converged"])


# -- dist tier ------------------------------------------------------------

def test_dist_batched_matches_independent_bitwise(dist_prob, sys16):
    _, _, B = sys16
    s = BatchedDistCGSolver(dist_prob)
    X = s.solve(B, criteria=CRIT)
    for j in range(3):
        s1 = DistCGSolver(dist_prob)
        x1 = s1.solve(B[:, j], criteria=CRIT)
        assert s.stats.batch["iterations"][j] == s1.stats.niterations
        assert np.array_equal(X[:, j], x1)


def test_dist_batched_pipelined_matches_independent(dist_prob, sys16):
    """Same iterations as the single-RHS mesh program, and the same
    iterates to an ulp bound: the batched loop is a different program
    (a trailing batch axis on every vector), which XLA fuses and so
    rounds differently, and the pipelined recurrence amplifies that
    (measured 733 ulp of max|x|)."""
    _, _, B = sys16
    s = BatchedDistCGSolver(dist_prob, pipelined=True)
    X = s.solve(B, criteria=CRIT)
    for j in range(3):
        s1 = DistCGSolver(dist_prob, pipelined=True)
        x1 = s1.solve(B[:, j], criteria=CRIT)
        assert s.stats.batch["iterations"][j] == s1.stats.niterations
        np.testing.assert_allclose(X[:, j], x1, rtol=0, atol=2 ** 12
                                   * np.finfo(x1.dtype).eps
                                   * np.abs(x1).max())


# -- HLO pins: collective count invariant in B ----------------------------

def _counts(txt):
    return (len(re.findall(r"all_reduce", txt)),
            len(re.findall(r"all_to_all", txt)))


def test_dist_batched_collectives_invariant_in_B(dist_prob):
    """The tentpole's communication contract, pinned at the compiler
    artifact: the batched programs' allreduce/all_to_all counts do not
    change with B, and they EQUAL the single-RHS tier's pinned counts
    (classic 5 ARs / 2 A2As, pipelined 5 ARs / 3 A2As -- the 2-psum /
    1-fused-psum in-loop structure of tests/test_hlo_structure.py)."""
    n = dist_prob.n
    crit = StoppingCriteria(maxits=5)
    for pipelined, want in ((False, (5, 2)), (True, (5, 3))):
        got = []
        for nb in (2, 4, 8):
            s = BatchedDistCGSolver(dist_prob, pipelined=pipelined)
            txt = s.lower_solve(batched_rhs(n, nb, seed=0),
                                criteria=crit).as_text()
            got.append(_counts(txt))
        assert got[0] == got[1] == got[2] == want, (pipelined, got)


def test_precise_dots_keep_fused_counts(dist_prob):
    """Compensated column dots widen the psum payloads (hi+lo pairs)
    but must not add collectives."""
    n = dist_prob.n
    crit = StoppingCriteria(maxits=5)
    s = BatchedDistCGSolver(dist_prob, pipelined=True,
                            precise_dots=True)
    txt = s.lower_solve(batched_rhs(n, 4, seed=0),
                        criteria=crit).as_text()
    assert _counts(txt) == (5, 3)


# -- B=1 byte-identity (the disarmed-identity discipline) -----------------

def test_single_column_is_byte_identical(sys16, dist_prob):
    _, A, B = sys16
    b1 = B[:, :1]
    batched = BatchedCGSolver(A).lower_solve(b1, criteria=CRIT).as_text()
    plain = JaxCGSolver(A, kernels="xla").lower_solve(
        B[:, 0], criteria=CRIT).as_text()
    assert batched == plain
    d_b = BatchedDistCGSolver(dist_prob).lower_solve(
        b1, criteria=CRIT).as_text()
    d_p = DistCGSolver(dist_prob).lower_solve(
        B[:, 0], criteria=CRIT).as_text()
    assert d_b == d_p


def test_cli_flag_absent_routes_unbatched():
    """--nrhs absent (or 1) never arms the batched dispatch."""
    from acg_tpu.cli import make_parser
    args = make_parser().parse_args(["gen:poisson2d:8"])
    assert args.nrhs == 0 and not args.block_cg


# -- checkpoint: a batch survives preemption ------------------------------

def test_batched_ckpt_chunked_is_bitwise(sys16, tmp_path):
    from acg_tpu.checkpoint import CheckpointConfig
    _, A, B = sys16
    Xp = BatchedCGSolver(A).solve(B, criteria=CRIT)
    ck = BatchedCGSolver(A, ckpt=CheckpointConfig(
        path=str(tmp_path / "ck"), every=10))
    Xc = ck.solve(B, criteria=CRIT)
    assert np.array_equal(Xp, Xc)
    assert ck.stats.ckpt["snapshots"] > 0
    assert ck.stats.batch["nrhs"] == 3


def test_batched_resume_continues_exactly(sys16, tmp_path):
    from acg_tpu.checkpoint import CheckpointConfig, load_snapshot
    _, A, B = sys16
    Xp = BatchedCGSolver(A).solve(B, criteria=CRIT)
    t = BatchedCGSolver(A, ckpt=CheckpointConfig(
        path=str(tmp_path / "ck"), every=10))
    t.solve(B, criteria=StoppingCriteria(maxits=25,
                                         residual_rtol=1e-10),
            raise_on_divergence=False)
    snap = load_snapshot(str(tmp_path / "ck"))
    assert snap.meta["nrhs"] == 3
    assert "done" in snap.arrays and "iters" in snap.arrays
    res = BatchedCGSolver(A, ckpt=CheckpointConfig(
        path=str(tmp_path / "ck2"), every=10, resume=snap))
    Xr = res.solve(B, criteria=CRIT)
    assert np.array_equal(Xp, Xr)
    assert res.stats.ckpt["resumed_from"] == snap.iteration


def test_batched_ckpt_unbounded_chunks_continue(sys16, tmp_path):
    """Unbounded (fixed-work) chunked solves must CONTINUE across
    chunk boundaries -- the result's converged=ran-the-budget flag
    must not leak into the carry and freeze later chunks -- and the
    per-RHS iteration counts must report trajectory totals, not the
    last chunk's length.  The answers agree to a few ulp, not bitwise:
    the unchunked unbounded solve is one fori_loop and the chunks are
    while loops over a carry, two programs that XLA fuses, and so
    rounds, differently (measured 0.45 ulp of max|x|)."""
    from acg_tpu.checkpoint import CheckpointConfig
    _, A, B = sys16
    crit = StoppingCriteria(maxits=100)   # no tolerance: unbounded
    Xp = BatchedCGSolver(A).solve(B, criteria=crit,
                                  raise_on_divergence=False)
    ck = BatchedCGSolver(A, ckpt=CheckpointConfig(
        path=str(tmp_path / "ck"), every=20))
    Xc = ck.solve(B, criteria=crit, raise_on_divergence=False)
    assert ck.stats.batch["iterations"] == [100, 100, 100]
    np.testing.assert_allclose(Xc, Xp, rtol=0, atol=4 * np.finfo(
        Xp.dtype).eps * np.abs(Xp).max())


def test_batched_resume_refuses_wrong_nrhs(sys16, tmp_path):
    from acg_tpu.checkpoint import CheckpointConfig, load_snapshot
    from acg_tpu.errors import AcgError
    _, A, B = sys16
    t = BatchedCGSolver(A, ckpt=CheckpointConfig(
        path=str(tmp_path / "ck"), every=10))
    t.solve(B, criteria=StoppingCriteria(maxits=25,
                                         residual_rtol=1e-10),
            raise_on_divergence=False)
    snap = load_snapshot(str(tmp_path / "ck"))
    res = BatchedCGSolver(A, ckpt=CheckpointConfig(
        path=str(tmp_path / "ck2"), every=10, resume=snap))
    with pytest.raises(AcgError, match="right-hand-side count"):
        res.solve(batched_rhs(A.nrows, 5, seed=1), criteria=CRIT)


def test_dist_batched_ckpt_and_repartition(dist_prob, sys16, tmp_path):
    """A 4-part batched snapshot resumes bitwise on the same mesh AND
    restores onto a 2-part mesh via --resume-repartition (the per-RHS
    leaves reassemble through the row-permutation sidecar)."""
    from acg_tpu.checkpoint import CheckpointConfig, load_snapshot
    csr, _, B = sys16
    Xp = BatchedDistCGSolver(dist_prob).solve(B, criteria=CRIT)
    t = BatchedDistCGSolver(dist_prob, ckpt=CheckpointConfig(
        path=str(tmp_path / "ck"), every=10))
    t.solve(B, criteria=StoppingCriteria(maxits=25,
                                         residual_rtol=1e-10),
            raise_on_divergence=False)
    snap = load_snapshot(str(tmp_path / "ck"))
    res = BatchedDistCGSolver(dist_prob, ckpt=CheckpointConfig(
        path=str(tmp_path / "ck2"), every=10, resume=snap))
    Xr = res.solve(B, criteria=CRIT)
    assert np.array_equal(Xp, Xr)
    snap2 = load_snapshot(str(tmp_path / "ck"))
    part2 = partition_rows(csr, 2, seed=0, method="band")
    prob2 = DistributedProblem.build(csr, part2, 2, dtype=jnp.float64)
    rep = BatchedDistCGSolver(prob2, ckpt=CheckpointConfig(
        path=str(tmp_path / "ck3"), every=10, resume=snap2,
        repartition=True))
    Xrep = rep.solve(B, criteria=CRIT)
    assert np.abs(Xrep - Xp).max() < 1e-10
    assert rep.stats.ckpt["repartitioned_from"]["nparts"] == 4
    assert all(rep.stats.batch["converged"])


# -- telemetry / soak / status --------------------------------------------

def test_batched_trace_per_rhs_columns(sys16, tmp_path):
    from acg_tpu.telemetry import read_convergence_log
    _, A, B = sys16
    s = BatchedCGSolver(A, trace=64)
    s.solve(B, criteria=CRIT)
    tr = s.last_trace
    assert tr.nrhs == 3
    assert tr.records.shape[1] == 3
    # per-column residual histories are monotone-ish and end at the
    # per-RHS final residuals
    assert np.allclose(tr.records[-1], s.stats.batch["rnrm2"],
                       rtol=1e-6)
    path = tmp_path / "fan.jsonl"
    tr.write_jsonl(str(path))
    meta, recs = read_convergence_log(str(path))
    assert meta["nrhs"] == 3
    assert len(recs[0]["rnrm2"]) == 3
    assert "worst" in recs[0]


def test_batched_soak_per_rhs_percentiles(sys16):
    from acg_tpu import soak
    _, A, B = sys16
    s = BatchedCGSolver(A)
    _, report = soak.run_soak(
        s, B, nsolves=3, criteria=CRIT,
        solve_kwargs={"raise_on_divergence": False})
    pr = report["per_rhs"]
    assert pr["nrhs"] == 3
    assert pr["iterations"]["p50"] > 0
    assert pr["latency"]["p99"] >= pr["latency"]["p50"] > 0


def test_observatory_batch_block(sys16):
    from acg_tpu import observatory
    _, A, B = sys16
    was = observatory.armed()
    try:
        observatory.arm()
        s = BatchedCGSolver(A)
        s.solve(B, criteria=CRIT)
        doc = observatory.STATUS.document()
        batch = doc["solve"]["batch"]
        assert batch["nrhs"] == 3
        assert batch["unconverged"] == 0
        assert 0 <= batch["slowest_rhs"] < 3
        assert len(batch["residuals"]) == 3
    finally:
        if not was:
            observatory.disarm()


# -- case keys ------------------------------------------------------------

def test_batch_joins_bench_diff_case_key():
    from acg_tpu.perfmodel import _batch_keyed, _row_case
    assert _batch_keyed("m", None) == "m"
    assert _batch_keyed("m", 1) == "m"
    assert _batch_keyed("m", 8) == "m|nrhs=8"
    assert _batch_keyed("m", 8, True) == "m|nrhs=8|block"
    key, val = _row_case({"metric": "m", "value": 2.0, "nrhs": 4})
    assert key == "m|nrhs=4" and val == 2.0


# -- refusals -------------------------------------------------------------

def test_batched_refusals(sys16):
    _, A, B = sys16
    with pytest.raises(ValueError, match="multi-vector"):
        BatchedCGSolver(A, kernels="pallas")
    with pytest.raises(ValueError, match="unknown batched mode"):
        BatchedCGSolver(A, mode="what")
    from acg_tpu.errors import AcgError
    s = BatchedCGSolver(A)
    with pytest.raises(AcgError, match="residual criteria only"):
        s.solve(B, criteria=StoppingCriteria(maxits=5, diff_rtol=1e-3))


def test_dist_batched_refuses_precond(dist_prob):
    with pytest.raises(ValueError, match="unpreconditioned"):
        BatchedDistCGSolver(dist_prob, precond="jacobi")
