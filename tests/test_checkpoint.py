"""Survivability tier: solver-state checkpoint/restore, the ABFT
checksum SpMV, and the rollback rung (acg_tpu.checkpoint).

The acceptance contract (ISSUE 7):
  * a chunked (--ckpt) solve follows the IDENTICAL trajectory as an
    uninterrupted one (bitwise x) on every tier;
  * a solve killed by crash:exit@K and relaunched with --resume reaches
    the original tolerance with pre-crash + post-resume iterations
    within 10% of the uninterrupted count (measured: exactly equal);
  * an injected sdc:flip fault -- finite, invisible to every non-finite
    guard -- is detected on device by the ABFT checksum test and routed
    through the rollback rung; disarmed, the same fault converges to a
    WRONG answer (the negative control);
  * disarmed programs lower byte-identical code; armed collective
    deltas are pinned.
"""

import json
import os
import re
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

import jax.numpy as jnp

from acg_tpu import faults, health
from acg_tpu.checkpoint import (CheckpointConfig, SolverSnapshot,
                                agree_seq, ca_carry_names, carry_names,
                                load_snapshot, save_snapshot,
                                validate_resume, vector_checksum)
from acg_tpu.errors import AcgError
from acg_tpu.io.generators import poisson_mtx
from acg_tpu.matrix import SymCsrMatrix
from acg_tpu.ops.spmv import device_matrix_from_csr
from acg_tpu.parallel.dist import DistCGSolver, DistributedProblem
from acg_tpu.partition import partition_rows
from acg_tpu.solvers import HostCGSolver, StoppingCriteria
from acg_tpu.solvers.jax_cg import JaxCGSolver
from acg_tpu.solvers.resilience import RecoveryDriver, RecoveryPolicy
from acg_tpu.solvers.stats import SolverStats

ENV_KEYS = {"JAX_PLATFORMS": "cpu",
            "XLA_FLAGS": "--xla_force_host_platform_device_count=8"}


def run_cli(argv, **kw):
    env = dict(os.environ)
    env.update(ENV_KEYS)
    kw.setdefault("timeout", 600)
    return subprocess.run([sys.executable, "-m", "acg_tpu.cli", *argv],
                          capture_output=True, text=True, env=env, **kw)


@pytest.fixture(scope="module")
def system():
    csr = SymCsrMatrix.from_mtx(poisson_mtx(20, dim=2)).to_csr()
    rng = np.random.default_rng(0)
    xsol = rng.standard_normal(csr.shape[0])
    xsol /= np.linalg.norm(xsol)
    return csr, xsol, csr @ xsol


@pytest.fixture(scope="module")
def prob8(system):
    csr, _, _ = system
    part = partition_rows(csr, 8, seed=0)
    return DistributedProblem.build(csr, part, 8, dtype=jnp.float64)


CRIT = StoppingCriteria(residual_rtol=1e-8, maxits=2000)


# -- the snapshot container ----------------------------------------------

def test_snapshot_roundtrip_preserves_scalars(tmp_path):
    """Scalar carry leaves (gamma/alpha/rr) must survive as 0-d arrays:
    a (1,)-promoted scalar re-entering the loop carry breaks the while
    predicate (the ascontiguousarray 0-d promotion regression)."""
    p = str(tmp_path / "s")
    save_snapshot(p, {"iteration": 3},
                  {"x": np.arange(5.0, dtype=np.float32),
                   "gamma": np.float32(2.5)})
    s = load_snapshot(p)
    assert s.iteration == 3
    assert s.arrays["x"].shape == (5,)
    assert s.arrays["gamma"].shape == ()
    assert float(s.arrays["gamma"]) == 2.5
    assert s.arrays["x"].dtype == np.float32


def test_corrupted_snapshot_refuses(tmp_path):
    """Any integrity failure -- bad magic, truncation, a flipped byte
    in header or payload -- must refuse with a typed error: a resumed
    solve must never start from garbage."""
    p = str(tmp_path / "s")
    save_snapshot(p, {"iteration": 1}, {"x": np.ones(64)})
    blob = open(p, "rb").read()

    def expect_refusal(mutated, why):
        bad = str(tmp_path / "bad")
        with open(bad, "wb") as f:
            f.write(mutated)
        with pytest.raises(AcgError):
            load_snapshot(bad)

    expect_refusal(b"NOTACKPT" + blob[8:], "magic")
    expect_refusal(blob[: len(blob) // 2], "truncated")
    # flip one byte inside the payload (the trailing array bytes)
    flipped = bytearray(blob)
    flipped[-7] ^= 0xFF
    expect_refusal(bytes(flipped), "payload crc")
    # flip one byte inside the JSON header region
    hdr = bytearray(blob)
    idx = blob.index(b'"arrays"')
    hdr[idx + 1] ^= 0x01
    expect_refusal(bytes(hdr), "header crc")
    with pytest.raises(AcgError):
        load_snapshot(str(tmp_path / "never-written"))


def test_validate_resume_refuses_mismatches():
    snap = SolverSnapshot(
        meta={"tier": "jax-cg", "pipelined": False, "precond": None,
              "n": 64, "dtype": "float32", "b_crc": 7, "iteration": 5},
        arrays={})
    ok = dict(tier="jax-cg", pipelined=False, precond=None, n=64,
              dtype=np.float32, b_crc=7)
    validate_resume(snap, **ok)
    for key, bad in (("tier", "dist-cg"), ("pipelined", True),
                     ("precond", "jacobi"), ("n", 65),
                     ("dtype", np.float64), ("b_crc", 8)):
        kw = dict(ok)
        kw[key] = bad
        with pytest.raises(AcgError):
            validate_resume(snap, **kw)


def test_carry_names_layouts():
    assert carry_names(False, False) == ("x", "r", "p", "gamma")
    assert carry_names(False, True) == ("x", "r", "p", "gamma", "rr")
    assert carry_names(True, False) == ("x", "r", "w", "p", "t", "z",
                                        "gamma", "alpha")
    assert carry_names(True, True)[-1] == "rr"
    assert len(carry_names(True, True)) == 11
    # the residual replacement's window (recurrence.replaces)
    assert carry_names(True, False, True) == (
        carry_names(True, False) + ("gmax", "pending"))


def test_agree_seq_single_process_is_free():
    agree_seq(3, 48)  # no coordination service: must return instantly


# -- chunked trajectory parity + resume, per tier ------------------------

@pytest.mark.parametrize("pipelined", [False, True])
@pytest.mark.parametrize("precond", [None, "jacobi"])
def test_single_device_chunk_parity_and_resume(system, tmp_path,
                                               pipelined, precond):
    """--ckpt chunks the solve WITHOUT changing the trajectory (bitwise
    x), and --resume continues it so pre-crash + post-resume iterations
    EQUAL the uninterrupted count."""
    csr, _, b = system
    A = device_matrix_from_csr(csr, dtype=jnp.float64)
    ref = JaxCGSolver(A, pipelined=pipelined, precond=precond)
    x_ref = ref.solve(b, criteria=CRIT)
    it_ref = ref.stats.niterations

    p = str(tmp_path / "ck")
    s1 = JaxCGSolver(A, pipelined=pipelined, precond=precond,
                     ckpt=CheckpointConfig(path=p, every=16))
    x_ck = s1.solve(b, criteria=CRIT)
    assert np.array_equal(np.asarray(x_ref), np.asarray(x_ck))
    assert s1.stats.niterations == it_ref
    assert s1.stats.ckpt["snapshots"] >= 2

    snap = load_snapshot(p)
    assert snap.meta["tier"] == "jax-cg"
    s2 = JaxCGSolver(A, pipelined=pipelined, precond=precond,
                     ckpt=CheckpointConfig(resume=snap))
    x_rs = s2.solve(b, criteria=CRIT)
    total = snap.iteration + s2.stats.niterations
    # the acceptance criterion allows 10% slack; the carry makes it 0
    assert total == it_ref
    assert np.allclose(np.asarray(x_rs), np.asarray(x_ref),
                       rtol=1e-7, atol=1e-10)
    assert s2.stats.ckpt["resumed_from"] == snap.iteration


@pytest.mark.parametrize("pipelined", [False, True])
def test_dist8_chunk_parity_and_resume(system, prob8, tmp_path,
                                       pipelined):
    """The 8-part explicit-mesh twin of the single-device parity: the
    shard_map'd chunked solve is bitwise-identical and resumes to the
    exact uninterrupted iteration count (per-part state committed
    under one agreed sequence number)."""
    csr, _, b = system
    ref = DistCGSolver(prob8, pipelined=pipelined)
    x_ref = ref.solve(b, criteria=CRIT)
    it_ref = ref.stats.niterations

    p = str(tmp_path / "ck")
    s1 = DistCGSolver(prob8, pipelined=pipelined,
                      ckpt=CheckpointConfig(path=p, every=16))
    x_ck = s1.solve(b, criteria=CRIT)
    assert np.array_equal(x_ref, x_ck)
    assert s1.stats.niterations == it_ref

    snap = load_snapshot(p)
    assert snap.meta["tier"] == "dist-cg"
    assert snap.meta["nparts"] == 8
    assert snap.arrays["x"].shape[0] == 8  # stacked per-part leaves
    s2 = DistCGSolver(prob8, pipelined=pipelined,
                      ckpt=CheckpointConfig(resume=snap))
    x_rs = s2.solve(b, criteria=CRIT)
    assert snap.iteration + s2.stats.niterations == it_ref
    assert np.allclose(x_rs, x_ref, rtol=1e-7, atol=1e-10)


@pytest.fixture(scope="module")
def prob8_f32(system):
    csr, _, _ = system
    part = partition_rows(csr, 8, seed=0)
    return DistributedProblem.build(csr, part, 8, dtype=jnp.float32)


@pytest.mark.parametrize("tier", ["single", "dist8"])
def test_f32_pipelined_chunk_parity_carries_the_replacement(
        system, prob8_f32, tmp_path, tier):
    """Over f32 vectors the pipelined recurrence replaces residuals; its
    window (the largest ||r||^2 since the last replacement, and a
    replacement pending at the chunk's last iteration) rides the
    checkpoint carry, so 16-iteration chunks -- shorter than a residual
    decade -- keep the uninterrupted trajectory bitwise, with the same
    replacements, and a resume continues it to the same count."""
    csr, _, b = system
    crit = StoppingCriteria(residual_rtol=1e-6, maxits=2000)

    def make(**kw):
        if tier == "single":
            A = device_matrix_from_csr(csr, dtype=jnp.float32)
            return JaxCGSolver(A, pipelined=True, **kw)
        return DistCGSolver(prob8_f32, pipelined=True, **kw)

    ref = make()
    x_ref = np.asarray(ref.solve(b, criteria=crit))
    it_ref, nrep = ref.stats.niterations, ref.stats.nreplacements
    assert nrep >= 3 and it_ref > 3 * 16

    p = str(tmp_path / "ck")
    s1 = make(ckpt=CheckpointConfig(path=p, every=16))
    x_ck = np.asarray(s1.solve(b, criteria=crit))
    assert np.array_equal(x_ref, x_ck)
    assert s1.stats.niterations == it_ref
    assert s1.stats.nreplacements == nrep

    snap = load_snapshot(p)
    assert {"gmax", "pending"} <= set(snap.arrays)
    s2 = make(ckpt=CheckpointConfig(resume=snap))
    x_rs = np.asarray(s2.solve(b, criteria=crit))
    assert snap.iteration + s2.stats.niterations == it_ref
    assert np.array_equal(x_rs, x_ref)


# -- CA recurrence checkpoint carry (ROADMAP 4c, ISSUE 16) ----------------

def test_ca_carry_names_layouts():
    assert ca_carry_names("sstep") == ("x", "r", "p", "gamma")
    pl = ca_carry_names("pl")
    assert pl[:2] == ("x", "q")
    assert "j" in pl and "adv" in pl  # frame-absolute pipe counters
    assert len(pl) == 12


@pytest.mark.parametrize("algorithm", ["sstep:4", "pipelined:2"])
def test_ca_chunk_parity_and_resume(system, tmp_path, algorithm):
    """--ckpt under the CA recurrences: the chunked solve is bitwise
    identical to the monolithic one (the sstep carry snapshots at
    BLOCK boundaries, where its state is exactly classic-shaped; the
    pl carry round-trips the full pipeline working set), and --resume
    continues to the exact uninterrupted iteration count."""
    csr, _, b = system
    A = device_matrix_from_csr(csr, dtype=jnp.float64)
    ref = JaxCGSolver(A, algorithm=algorithm)
    x_ref = ref.solve(b, criteria=CRIT)
    it_ref = ref.stats.niterations

    p = str(tmp_path / "ck")
    # every=16 keeps each chunk boundary s-aligned for sstep:4 (an
    # unaligned cap would truncate a masked block -- a mathematically
    # equivalent restart, not the monolithic mid-block state)
    s1 = JaxCGSolver(A, algorithm=algorithm,
                     ckpt=CheckpointConfig(path=p, every=16))
    x_ck = s1.solve(b, criteria=CRIT)
    assert np.array_equal(np.asarray(x_ref), np.asarray(x_ck))
    assert s1.stats.niterations == it_ref
    assert s1.stats.ckpt["snapshots"] >= 2

    snap = load_snapshot(p)
    assert snap.meta["algorithm"] == algorithm
    for name in ca_carry_names(algorithm.split(":")[0]
                               .replace("pipelined", "pl")):
        assert name in snap.arrays
    s2 = JaxCGSolver(A, algorithm=algorithm,
                     ckpt=CheckpointConfig(resume=snap))
    x_rs = s2.solve(b, criteria=CRIT)
    assert snap.iteration + s2.stats.niterations == it_ref
    assert np.allclose(np.asarray(x_rs), np.asarray(x_ref),
                       rtol=1e-7, atol=1e-10)
    assert s2.stats.ckpt["resumed_from"] == snap.iteration


def test_ca_cross_recurrence_resume_refuses(system, tmp_path):
    """A snapshot names its recurrence; resuming it under ANY other
    recurrence must refuse -- the sstep block-boundary carry is
    byte-shaped exactly like the classic carry, so only the declared
    algorithm key separates them."""
    csr, _, b = system
    A = device_matrix_from_csr(csr, dtype=jnp.float64)
    p = str(tmp_path / "ck")
    JaxCGSolver(A, algorithm="sstep:4",
                ckpt=CheckpointConfig(path=p, every=16)).solve(
        b, criteria=CRIT)
    snap = load_snapshot(p)
    for other in ("pipelined:2", None):
        s = JaxCGSolver(A, algorithm=other,
                        ckpt=CheckpointConfig(resume=snap))
        with pytest.raises(AcgError, match="recurrence"):
            s.solve(b, criteria=CRIT)
    # and the reverse: a classic snapshot refused under a CA resume
    p2 = str(tmp_path / "ck2")
    JaxCGSolver(A, ckpt=CheckpointConfig(path=p2, every=16)).solve(
        b, criteria=CRIT)
    s = JaxCGSolver(A, algorithm="sstep:4",
                    ckpt=CheckpointConfig(resume=load_snapshot(p2)))
    with pytest.raises(AcgError, match="recurrence"):
        s.solve(b, criteria=CRIT)


def test_ca_ckpt_refusal_matrix(system, tmp_path):
    """The two combinations the CA carry cannot honour stay typed
    refusals: repartition (the carry layout is not in the
    field-compatible set) and pl+trace (absolute vs chunk-relative
    iteration frames)."""
    csr, _, b = system
    A = device_matrix_from_csr(csr, dtype=jnp.float64)
    p = str(tmp_path / "ck")
    JaxCGSolver(A, ckpt=CheckpointConfig(path=p, every=16)).solve(
        b, criteria=CRIT)
    snap = load_snapshot(p)
    with pytest.raises(ValueError, match="repartition"):
        JaxCGSolver(A, algorithm="sstep:4",
                    ckpt=CheckpointConfig(resume=snap,
                                          repartition=True))
    with pytest.raises(ValueError, match="trace"):
        JaxCGSolver(A, algorithm="pipelined:2", trace=8,
                    ckpt=CheckpointConfig(path=p, every=16))
    # sstep keeps its trace ring (classic iteration frame), and both
    # CA kinds keep plain --ckpt
    JaxCGSolver(A, algorithm="sstep:4", trace=8,
                ckpt=CheckpointConfig(path=p, every=16))
    JaxCGSolver(A, algorithm="pipelined:2",
                ckpt=CheckpointConfig(path=p, every=16))


def test_cross_tier_resume_refuses(system, prob8, tmp_path):
    """A single-device snapshot must not resume on the mesh (and vice
    versa): the carry layouts are tier-specific, and continuing the
    wrong one would converge to a green wrong answer."""
    csr, _, b = system
    A = device_matrix_from_csr(csr, dtype=jnp.float64)
    p = str(tmp_path / "ck")
    JaxCGSolver(A, ckpt=CheckpointConfig(path=p, every=8)).solve(
        b, criteria=CRIT)
    snap = load_snapshot(p)
    s = DistCGSolver(prob8, ckpt=CheckpointConfig(resume=snap))
    with pytest.raises(AcgError, match="does not match this solve"):
        s.solve(b, criteria=CRIT)
    # and a different right-hand side refuses via the stored checksum
    s2 = JaxCGSolver(A, ckpt=CheckpointConfig(resume=snap))
    with pytest.raises(AcgError, match="right-hand-side checksum"):
        s2.solve(b + 1.0, criteria=CRIT)


def test_sharded_dia_chunk_parity_and_resume(tmp_path):
    from acg_tpu.parallel.sharded_dia import build_sharded_poisson_solver

    crit = StoppingCriteria(residual_rtol=1e-8, maxits=2000)
    ref = build_sharded_poisson_solver(24, 2, dtype=jnp.float64)
    xsol, b = ref.manufactured()
    x_ref = ref.solve(b, criteria=crit)
    it_ref = ref.stats.niterations

    p = str(tmp_path / "ck")
    s1 = build_sharded_poisson_solver(
        24, 2, dtype=jnp.float64, ckpt=CheckpointConfig(path=p, every=16))
    x_ck = s1.solve(b, criteria=crit)
    assert np.array_equal(np.asarray(x_ref), np.asarray(x_ck))
    snap = load_snapshot(p)
    assert snap.meta["tier"] == "sharded-dia"
    s2 = build_sharded_poisson_solver(
        24, 2, dtype=jnp.float64, ckpt=CheckpointConfig(resume=snap))
    s2.solve(b, criteria=crit)
    assert snap.iteration + s2.stats.niterations == it_ref


def test_host_chunk_parity_resume_and_rollback(system, tmp_path):
    """The eager oracle: same contract, plus the rollback rung restores
    the exact snapshot state on a detected breakdown."""
    csr, xsol, b = system
    crit = StoppingCriteria(residual_rtol=1e-10, maxits=2000)
    ref = HostCGSolver(csr)
    x_ref = ref.solve(b, criteria=crit)
    it_ref = ref.stats.niterations

    p = str(tmp_path / "ck")
    s1 = HostCGSolver(csr, ckpt=CheckpointConfig(path=p, every=16))
    x_ck = s1.solve(b, criteria=crit)
    assert np.array_equal(x_ref, x_ck)
    snap = load_snapshot(p)
    assert snap.meta["tier"] == "host-cg"
    s2 = HostCGSolver(csr, ckpt=CheckpointConfig(resume=snap))
    s2.solve(b, criteria=crit)
    assert snap.iteration + s2.stats.niterations == it_ref

    # rollback: an injected flip at an audited iteration rolls the
    # eager Krylov state back to the last snapshot and still converges
    spec = faults.parse_fault_spec("sdc:flip@9")
    hs = health.make_spec(every=5, abft=True)
    with faults.injected(spec):
        s3 = HostCGSolver(csr, health=hs, recovery=RecoveryPolicy(),
                          ckpt=CheckpointConfig(path=str(tmp_path / "r"),
                                                every=8))
        x3 = s3.solve(b, criteria=crit)
    assert s3.stats.nrollbacks == 1
    assert s3.stats.converged
    assert np.linalg.norm(b - csr @ x3) / np.linalg.norm(b) < 1e-8


# -- ABFT: detection where every other guard is blind --------------------

@pytest.mark.parametrize("pipelined", [False, True])
def test_abft_detects_sdc_flip_and_rolls_back(system, tmp_path,
                                              pipelined):
    """The acceptance proof: a sign-flipped SpMV element at an audited
    iteration is FINITE -- no non-finite guard can see it -- yet the
    checksum test trips on device, the breakdown routes into the
    rollback rung, and the solve still converges to a RIGHT answer."""
    csr, _, b = system
    A = device_matrix_from_csr(csr, dtype=jnp.float64)
    spec = faults.parse_fault_spec("sdc:flip@9")
    hs = health.make_spec(every=5, abft=True)
    with faults.injected(spec):
        s = JaxCGSolver(A, pipelined=pipelined, health=hs,
                        recovery=RecoveryPolicy(),
                        ckpt=CheckpointConfig(path=str(tmp_path / "ck"),
                                              every=8))
        x = s.solve(b, criteria=CRIT)
    ab = s.stats.health["abft"]
    assert ab["ntrips"] >= 1
    # a flipped element's signature is macroscopic (~2/n of the
    # denominator), many orders above the rounding-noise floor
    assert ab["rel_max"] > 1e-6
    assert s.stats.nrollbacks == 1
    assert s.stats.nrestarts == 0  # rollback spends its OWN budget
    assert s.stats.converged
    assert np.linalg.norm(b - csr @ np.asarray(x)) / np.linalg.norm(b) \
        < 1e-7


def test_sdc_flip_without_abft_is_a_wrong_answer(system):
    """The negative control: the same fault with ABFT disarmed sails
    through every guard (and a record-only gap audit) to a CONVERGED
    report whose true residual misses the tolerance by orders of
    magnitude -- exactly the failure class ABFT exists for."""
    csr, _, b = system
    A = device_matrix_from_csr(csr, dtype=jnp.float64)
    spec = faults.parse_fault_spec("sdc:flip@9")
    # record-only audit: measures the drift but gates nothing
    hs = health.make_spec(every=5)
    with faults.injected(spec):
        s = JaxCGSolver(A, health=hs)
        x = s.solve(b, criteria=CRIT, raise_on_divergence=False)
    assert s.stats.converged  # the recurrence lied
    true_rel = (np.linalg.norm(b - csr @ np.asarray(x))
                / np.linalg.norm(b))
    assert true_rel > 1e-5  # vs the requested 1e-8: wrong answer
    # the gap audit SAW the drift (evidence) but could not act on it
    assert s.stats.health["gap_max"] > 1e-7


@pytest.mark.parametrize("pipelined", [False, True])
def test_abft_dist8(system, prob8, tmp_path, pipelined):
    """Mesh twin: the checksum test rides ONE fused psum, the gap is
    replicated, and the rollback restores the agreed snapshot."""
    csr, _, b = system
    spec = faults.parse_fault_spec("sdc:flip@9")
    hs = health.make_spec(every=5, abft=True)
    with faults.injected(spec):
        s = DistCGSolver(prob8, pipelined=pipelined, health=hs,
                         recovery=RecoveryPolicy(),
                         ckpt=CheckpointConfig(path=str(tmp_path / "ck"),
                                               every=8))
        x = s.solve(b, criteria=CRIT)
    assert s.stats.health["abft"]["ntrips"] >= 1
    assert s.stats.nrollbacks == 1
    assert np.linalg.norm(b - csr @ x) / np.linalg.norm(b) < 1e-7


def test_abft_spec_validation():
    with pytest.raises(ValueError, match="audit cadence"):
        health.HealthSpec(abft=True)
    with pytest.raises(ValueError, match="abft_threshold needs abft"):
        health.HealthSpec(every=4, abft_threshold=1e-3)
    spec = health.make_spec(every=4, abft=True)
    assert spec.arms_detect  # an ABFT trip must be able to exit the loop
    assert "abft" in str(spec)


# -- the rollback rung in the recovery ladder ----------------------------

def test_rollback_rung_ordering():
    """on_rollback spends its OWN budget (max_rollbacks), leaves the
    restart budget untouched, and refuses once exhausted -- the caller
    then falls through to on_breakdown's restart rung."""
    st = SolverStats(unknowns=8)
    drv = RecoveryDriver(RecoveryPolicy(max_restarts=2, max_rollbacks=1),
                         st, "test")
    drv.note_breakdown(10)
    assert st.nbreakdowns == 1
    assert drv.on_rollback(10, 8) is True
    assert st.nrollbacks == 1 and st.nrestarts == 0
    # budget exhausted: the second breakdown falls to the restart rung
    drv.note_breakdown(12)
    assert drv.on_rollback(12, 8) is False
    assert drv.on_breakdown(12, noted=True) is True
    assert st.nrestarts == 1 and st.nbreakdowns == 2
    # rollbacks disabled entirely
    drv0 = RecoveryDriver(RecoveryPolicy(max_rollbacks=0),
                          SolverStats(unknowns=8), "test")
    assert drv0.on_rollback(5, 0) is False


def test_crash_refuses_without_ckpt(system):
    csr, _, b = system
    A = device_matrix_from_csr(csr, dtype=jnp.float64)
    with faults.injected(faults.parse_fault_spec("crash:exit@5")):
        with pytest.raises(AcgError, match="crash:exit"):
            JaxCGSolver(A).solve(b, criteria=CRIT)
        with pytest.raises(AcgError, match="crash:exit"):
            HostCGSolver(csr).solve(b, criteria=CRIT)


def test_fault_spec_parsing_new_sites():
    s = faults.parse_fault_spec("sdc:flip@7")
    assert s.site == "sdc" and s.mode == "flip" and s.iteration == 7
    assert s.device_site
    c = faults.parse_fault_spec("crash:exit@20")
    assert c.site == "crash" and c.iteration == 20
    assert not c.device_site
    with pytest.raises(ValueError):
        faults.parse_fault_spec("crash:exit")     # needs @K
    with pytest.raises(ValueError):
        faults.parse_fault_spec("sdc:nan@7")      # flip only
    with pytest.raises(ValueError):
        faults.parse_fault_spec("crash:boom@7")


def test_maybe_crash_crossing_semantics():
    """crash:exit fires when the chunk CROSSES K -- a resumed solve
    whose snapshot already lies at-or-past K must not re-kill itself."""
    calls = []
    with faults.injected(faults.parse_fault_spec("crash:exit@20")):
        orig = os._exit
        os._exit = lambda code: calls.append(code)
        try:
            faults.maybe_crash(0, 16)    # not yet crossed
            assert calls == []
            faults.maybe_crash(24, 32)   # resumed past K: no re-fire
            assert calls == []
            faults.maybe_crash(16, 24)   # crossing: fires
            assert calls == [94]
        finally:
            os._exit = orig


# -- CLI end-to-end: crash at K, then --resume ---------------------------

def test_cli_crash_then_resume(tmp_path):
    """The acceptance flow on the single-device tier: kill a solve
    mid-flight via crash:exit@K (exit 94), relaunch with --resume,
    converge with total iterations within 10% of uninterrupted."""
    ck = str(tmp_path / "ck")
    base = ["gen:poisson2d:24", "--manufactured-solution", "--dtype",
            "f32", "--comm", "none", "--max-iterations", "500",
            "--residual-rtol", "1e-5", "--warmup", "0", "--quiet"]
    r0 = run_cli(base + ["--stats-json", str(tmp_path / "ref.json")])
    assert r0.returncode == 0, r0.stderr
    ref = json.load(open(tmp_path / "ref.json"))["stats"]

    r1 = run_cli(base + ["--ckpt", ck, "--ckpt-every", "8",
                         "--fault-inject", "crash:exit@20"])
    assert r1.returncode == 94, (r1.returncode, r1.stderr)
    assert os.path.exists(ck)

    r2 = run_cli(base + ["--resume", ck,
                         "--stats-json", str(tmp_path / "res.json")])
    assert r2.returncode == 0, r2.stderr
    doc = json.load(open(tmp_path / "res.json"))
    st = doc["stats"]
    assert st["converged"] is True
    resumed_from = st["ckpt"]["resumed_from"]
    total = resumed_from + st["niterations"]
    assert abs(total - ref["niterations"]) <= 0.1 * ref["niterations"]
    assert doc["schema"] == "acg-tpu-stats/12"
    # the resume event is in the structured sink
    assert any(e["kind"] == "resume" for e in st["events"])


def test_cli_crash_then_resume_dist8(tmp_path):
    """The 8-part mesh twin of the crash/resume acceptance flow."""
    ck = str(tmp_path / "ck")
    base = ["gen:poisson2d:20", "--manufactured-solution", "--nparts",
            "8", "--max-iterations", "500", "--residual-rtol", "1e-8",
            "--warmup", "0", "--quiet"]
    r0 = run_cli(base + ["--stats-json", str(tmp_path / "ref.json")])
    assert r0.returncode == 0, r0.stderr
    ref = json.load(open(tmp_path / "ref.json"))["stats"]

    r1 = run_cli(base + ["--ckpt", ck, "--ckpt-every", "8",
                         "--fault-inject", "crash:exit@20"])
    assert r1.returncode == 94, (r1.returncode, r1.stderr)

    r2 = run_cli(base + ["--resume", ck,
                         "--stats-json", str(tmp_path / "res.json")])
    assert r2.returncode == 0, r2.stderr
    st = json.load(open(tmp_path / "res.json"))["stats"]
    assert st["converged"] is True
    total = st["ckpt"]["resumed_from"] + st["niterations"]
    assert abs(total - ref["niterations"]) <= 0.1 * ref["niterations"]


def test_cli_flag_validation(tmp_path):
    r = run_cli(["gen:poisson2d:12", "--ckpt", str(tmp_path / "c")])
    assert r.returncode != 0 and "--ckpt-every" in r.stderr
    r = run_cli(["gen:poisson2d:12", "--ckpt-every", "8"])
    assert r.returncode != 0 and "--ckpt" in r.stderr
    r = run_cli(["gen:poisson2d:12", "--abft"])
    assert r.returncode != 0 and "--audit-every" in r.stderr
    r = run_cli(["gen:poisson2d:12", "--fault-inject", "crash:exit@5"])
    assert r.returncode != 0 and "crash:exit" in r.stderr
    # a corrupted snapshot refuses BEFORE anything expensive
    bad = tmp_path / "bad"
    bad.write_bytes(b"ACGCKPT1\ngarbage")
    r = run_cli(["gen:poisson2d:12", "--resume", str(bad)])
    assert r.returncode != 0 and "snapshot" in r.stderr
    # --resume under --soak would re-resume every repetition
    ok = tmp_path / "ok"
    save_snapshot(str(ok), {"iteration": 1}, {"x": np.ones(4)})
    r = run_cli(["gen:poisson2d:12", "--resume", str(ok), "--soak", "3"])
    assert r.returncode != 0 and "--soak" in r.stderr


def test_cli_soak_with_ckpt_bills_ckpt_phase(tmp_path):
    """--soak + --ckpt: snapshots carry across the repetitions, the
    serialisation bills to its OWN timings phase, and the latency
    histogram/percentiles describe the solves alone."""
    r = run_cli(["gen:poisson2d:16", "--comm", "none",
                 "--max-iterations", "200", "--residual-rtol", "1e-6",
                 "--warmup", "0", "--quiet", "--soak", "3",
                 "--ckpt", str(tmp_path / "ck"), "--ckpt-every", "16",
                 "--stats-json", str(tmp_path / "s.json"),
                 "--metrics-file", str(tmp_path / "m.prom")])
    assert r.returncode == 0, r.stderr
    doc = json.load(open(tmp_path / "s.json"))
    st = doc["stats"]
    assert st["soak"]["nsolves"] == 3
    assert st["timings"].get("ckpt", 0) > 0
    assert st["ckpt"]["snapshots"] >= 1
    # the ckpt write seconds live in their OWN histogram, and solve
    # latency percentiles are finite (not polluted into absurdity)
    m = doc["metrics"]
    assert m["acg_ckpt_snapshots_total"]["samples"][0]["value"] >= 1
    prom = open(tmp_path / "m.prom").read()
    assert "acg_ckpt_write_seconds_bucket" in prom


# -- disarmed byte-identity + armed collective pins ----------------------

def test_disarmed_state_io_is_byte_identical(system, prob8):
    """A lowering that never names state_io/carry/k_offset and one that
    passes the disarmed defaults must be the SAME program text --
    single-device and mesh (the --ckpt off = byte-identical pin)."""
    csr, _, b = system
    A = device_matrix_from_csr(csr, dtype=jnp.float64)
    for pipelined in (False, True):
        s = JaxCGSolver(A, pipelined=pipelined, kernels="xla")
        b_dev = jnp.asarray(b)
        program, base, kwargs, _tr = s._select_program(
            b_dev, jnp.zeros_like(b_dev), CRIT, detect=False, fault=None)
        plain = program.lower(*base, **kwargs).as_text()
        explicit = program.lower(*base, state_io=False, carry=None,
                                 k_offset=None, **kwargs).as_text()
        assert explicit == plain

    for pipelined in (False, True):
        s = DistCGSolver(prob8, pipelined=pipelined)
        dev = s.device_args(b)
        bb, x0, la, ga, sidx, gsrc, gval, scnt, rcnt = dev
        tols = jnp.zeros(4)
        args = (la, ga, sidx, gsrc, gval, scnt, rcnt, bb, x0, tols,
                jnp.int32(5))
        kw = dict(unbounded=True, needs_diff=False)
        plain = s._program.lower(*args, **kw).as_text()
        explicit = s._program.lower(*args, carry=None, k_offset=None,
                                    **kw).as_text()
        assert explicit == plain
        # the state_io chunk program is a DIFFERENT program (it returns
        # the carry) but must add ZERO collectives to the loop
        chunk = s._compile(state_io=True)
        ctxt = chunk.lower(*args, **kw).as_text()
        assert ctxt != plain

        def counts(txt):
            return (len(re.findall(r"all_reduce", txt)),
                    len(re.findall(r"all_to_all", txt)))

        assert counts(ctxt) == counts(plain)


def test_abft_armed_collective_counts(prob8):
    """The ABFT test rides the audit: armed, the dist program gains
    EXACTLY one fused psum (+1 all_reduce) for the 3-scalar checksum
    reduction and one setup SpMV (+1 all_to_all) for the column
    checksum, on top of the audit's own +1/+1."""
    b = np.ones(prob8.n)

    def counts(pipelined, hs):
        s = DistCGSolver(prob8, pipelined=pipelined, health=hs)
        txt = s.lower_solve(b).as_text()
        return (len(re.findall(r"all_reduce", txt)),
                len(re.findall(r"all_to_all", txt)))

    base_c = counts(False, None)
    audit_c = counts(False, health.make_spec(every=4))
    abft_c = counts(False, health.make_spec(every=4, abft=True))
    assert audit_c == (base_c[0] + 1, base_c[1] + 1)
    assert abft_c == (audit_c[0] + 1, audit_c[1] + 1)
    base_p = counts(True, None)
    abft_p = counts(True, health.make_spec(every=4, abft=True))
    assert abft_p == (base_p[0] + 2, base_p[1] + 2)


# -- the deadline heartbeat ----------------------------------------------

class _FakeCoordClient:
    def __init__(self):
        self.kv = {}
        self.lock = threading.Lock()

    def key_value_set(self, k, v):
        with self.lock:
            self.kv[k] = v

    def key_value_dir_get(self, prefix):
        with self.lock:
            return [(k, v) for k, v in self.kv.items()
                    if k.startswith(prefix)]


def test_heartbeat_detects_dead_peer():
    from acg_tpu.parallel.erragree import DeadlineHeartbeat

    lost = []
    hb = DeadlineHeartbeat(period=0.05, deadline=0.2,
                           on_lost=lambda p, a: lost.append(p),
                           client=_FakeCoordClient(), nprocs=2, me=0)
    hb.start()
    deadline = time.monotonic() + 5.0
    while not lost and time.monotonic() < deadline:
        time.sleep(0.05)
    hb.stop()
    assert lost and lost[0] == 1


def test_heartbeat_tolerates_healthy_peer():
    from acg_tpu.parallel.erragree import DeadlineHeartbeat

    lost = []
    client = _FakeCoordClient()
    hb = DeadlineHeartbeat(period=0.05, deadline=0.35,
                           on_lost=lambda p, a: lost.append(p),
                           client=client, nprocs=2, me=0)
    hb.start()
    stop = threading.Event()

    def beat():
        i = 0
        while not stop.wait(0.05):
            i += 1
            client.key_value_set(
                f"acg_tpu/heartbeat/{hb._gen}/1/{i}", "1")

    t = threading.Thread(target=beat, daemon=True)
    t.start()
    time.sleep(1.0)
    stop.set()
    hb.stop()
    assert lost == []


def test_heartbeat_validation_and_noop():
    from acg_tpu.parallel.erragree import DeadlineHeartbeat

    with pytest.raises(ValueError):
        DeadlineHeartbeat(period=5.0, deadline=5.0)
    with pytest.raises(ValueError):
        DeadlineHeartbeat(period=0.0, deadline=1.0)
    # single-process: start is a no-op (no thread, no client needed)
    hb = DeadlineHeartbeat(period=1.0, deadline=5.0, nprocs=1, me=0)
    with hb:
        assert hb._thread is None


# -- config validation ---------------------------------------------------

def test_checkpoint_config_validation(system):
    csr, _, _ = system
    with pytest.raises(ValueError, match="snapshot cadence"):
        CheckpointConfig(path="x", every=0)
    with pytest.raises(ValueError, match="snapshot path"):
        CheckpointConfig()
    A = device_matrix_from_csr(csr, dtype=jnp.float64)
    with pytest.raises(ValueError, match="replace_every"):
        JaxCGSolver(device_matrix_from_csr(csr, dtype=jnp.bfloat16),
                    replace_every=10,
                    ckpt=CheckpointConfig(path="x", every=4))
    with pytest.raises(ValueError, match="ckpt must be"):
        JaxCGSolver(A, ckpt="not-a-config")


def test_buildinfo_advertises_survivability():
    r = run_cli(["--buildinfo", "gen:ignored"])
    out = r.stdout
    assert "survivability" in out
    for token in ("--ckpt", "--resume", "--abft", "sdc:flip",
                  "crash:exit", "--heartbeat", "acg-tpu-stats/12"):
        assert token in out, token
