"""Elastic-mesh recovery: shape-portable checkpoints, the
survivor-mesh supervisor, and the chaos campaign (ISSUE 10).

The acceptance contract:
  * an N-part snapshot resumed with repartition onto M parts (or the
    single-device / host tiers) converges to the ORIGINAL tolerance
    with total (pre + post) iterations within a small band of the
    uninterrupted count;
  * a corrupted row-permutation sidecar REFUSES instead of resuming a
    scrambled Krylov state;
  * crash:exit mid-solve on the 8-part mesh -> the supervisor
    relaunches with --resume --resume-repartition on fewer parts ->
    the final true relative residual meets the original rtol;
  * a seeded chaos campaign ends every schedule converged or
    agreed-abort -- zero wrong-answer-green;
  * the exit-code contract is one registry (errors.ExitCode) and
    --buildinfo renders it.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

import jax.numpy as jnp

from acg_tpu import faults, observatory
from acg_tpu.checkpoint import (CheckpointConfig, SolverSnapshot,
                                load_snapshot, reassemble_global,
                                save_snapshot, validate_resume)
from acg_tpu.errors import (AcgError, ExitCode, PEER_LOST_CODES,
                            RELAUNCHABLE_CODES, exit_code_table)
from acg_tpu.io.generators import poisson_mtx
from acg_tpu.matrix import SymCsrMatrix
from acg_tpu.ops.spmv import device_matrix_from_csr
from acg_tpu.parallel.dist import DistCGSolver, DistributedProblem
from acg_tpu.partition import is_permutation, partition_rows
from acg_tpu.solvers import HostCGSolver, StoppingCriteria
from acg_tpu.solvers.jax_cg import JaxCGSolver
from acg_tpu import supervisor as sup

ENV_KEYS = {"JAX_PLATFORMS": "cpu",
            "XLA_FLAGS": "--xla_force_host_platform_device_count=8"}


def run_cli(argv, extra_env=None, **kw):
    env = dict(os.environ)
    env.update(ENV_KEYS)
    if extra_env:
        env.update(extra_env)
    kw.setdefault("timeout", 600)
    return subprocess.run([sys.executable, "-m", "acg_tpu.cli", *argv],
                          capture_output=True, text=True, env=env, **kw)


@pytest.fixture(scope="module")
def system():
    csr = SymCsrMatrix.from_mtx(poisson_mtx(20, dim=2)).to_csr()
    b = csr @ (np.ones(csr.shape[0]) / np.sqrt(csr.shape[0]))
    return csr, b


@pytest.fixture(scope="module")
def prob8(system):
    csr, _ = system
    return DistributedProblem.build(csr, partition_rows(csr, 8, seed=0),
                                    8, dtype=jnp.float64)


@pytest.fixture(scope="module")
def prob4(system):
    csr, _ = system
    return DistributedProblem.build(csr, partition_rows(csr, 4, seed=1),
                                    4, dtype=jnp.float64)


CRIT = StoppingCriteria(residual_rtol=1e-8, maxits=2000)


@pytest.fixture(scope="module")
def snap8(system, prob8, tmp_path_factory):
    """A mid-solve 8-part snapshot (the last one committed before
    convergence) plus the uninterrupted iteration count."""
    csr, b = system
    ref = DistCGSolver(prob8)
    ref.solve(b, criteria=CRIT)
    p = str(tmp_path_factory.mktemp("snap") / "ck8")
    s = DistCGSolver(prob8, ckpt=CheckpointConfig(path=p, every=16))
    s.solve(b, criteria=CRIT)
    return load_snapshot(p), ref.stats.niterations


# -- the exit-code contract (satellite 3) --------------------------------

def test_exit_code_registry_is_the_single_source():
    """The scattered rc constants all resolve to the registry."""
    from acg_tpu.checkpoint import CRASH_EXIT_CODE
    from acg_tpu.observatory import SLO_EXIT_CODE
    from acg_tpu.parallel.erragree import PEER_LOST_EXIT
    from acg_tpu.soak import DRIFT_EXIT_CODE

    assert CRASH_EXIT_CODE == int(ExitCode.CRASH_INJECTED) == 94
    assert PEER_LOST_EXIT == int(ExitCode.PEER_LOST) == 97
    assert DRIFT_EXIT_CODE == int(ExitCode.DRIFT) == 7
    assert SLO_EXIT_CODE == int(ExitCode.SLO_BREACH) == 8
    assert int(ExitCode.PEER_DEAD_INJECTED) == 86
    assert int(ExitCode.RELAUNCH_BUDGET) == 95
    assert int(ExitCode.WRONG_ANSWER) == 96
    codes = [c for c, _, _ in exit_code_table()]
    assert codes == sorted(codes)
    assert set(RELAUNCHABLE_CODES) >= {86, 94, 97}
    assert PEER_LOST_CODES == {86, 97}
    # every registry row names an origin and a meaning
    assert all(origin and meaning
               for _, origin, meaning in exit_code_table())


def test_buildinfo_renders_exit_table_and_elastic_row():
    import io

    from acg_tpu.cli import _buildinfo
    out = io.StringIO()
    assert _buildinfo(out) == 0
    text = out.getvalue()
    assert "exit codes:" in text
    assert "\n   94  [faults/checkpoint]" in text
    assert "elastic recovery: --supervise" in text
    assert "--resume-repartition" in text


# -- cadence: --ckpt-secs (satellite 2) ----------------------------------

def test_ckpt_config_refuses_double_cadence():
    with pytest.raises(ValueError, match="EITHER"):
        CheckpointConfig(path="x", every=8, secs=1.0)
    with pytest.raises(ValueError, match="cadence"):
        CheckpointConfig(path="x")
    # secs alone is a valid cadence; chunk sizing adapts to the
    # measured rate (probe chunk first, then secs / s_per_iter)
    c = CheckpointConfig(path="x", secs=2.0)
    assert c.chunk_for(None) == CheckpointConfig.PROBE_CHUNK
    assert c.chunk_for(0.01) == 200
    assert CheckpointConfig(path="x", every=8).chunk_for(0.01) == 8
    with pytest.raises(ValueError, match="resume"):
        CheckpointConfig(path="x", every=8, repartition=True)


def test_ckpt_secs_commits_and_keeps_trajectory(system, tmp_path):
    csr, b = system
    A = device_matrix_from_csr(csr, dtype=jnp.float64)
    ref = JaxCGSolver(A)
    x_ref = ref.solve(b, criteria=CRIT)
    s = JaxCGSolver(A, ckpt=CheckpointConfig(path=str(tmp_path / "c"),
                                             secs=1e-4))
    x = s.solve(b, criteria=CRIT)
    assert s.stats.ckpt["snapshots"] >= 1
    assert s.stats.ckpt["secs"] == 1e-4
    # chunking never changes the trajectory, whatever the cadence
    assert s.stats.niterations == ref.stats.niterations
    assert np.array_equal(np.asarray(x), np.asarray(x_ref))


def test_cli_refuses_both_cadences():
    r = run_cli(["gen:poisson2d:8", "--comm", "none", "--quiet",
                 "--ckpt", "/tmp/nope", "--ckpt-every", "8",
                 "--ckpt-secs", "1"])
    assert r.returncode != 0
    assert "mutually exclusive" in r.stderr


# -- shape-portable snapshots (tentpole leg 1) ---------------------------

def test_fault_spec_str_roundtrips():
    for text in ("crash:exit@20", "sdc:flip@7:seed=99",
                 "spmv:nan@3:part=2", "peer:dead:proc=1",
                 "solve:slow@10:secs=0.05"):
        spec = faults.parse_fault_spec(text)
        assert faults.parse_fault_spec(str(spec)) == spec


def test_is_permutation():
    assert is_permutation(np.arange(5), 5)
    assert is_permutation(np.array([3, 0, 2, 1]), 4)
    assert not is_permutation(np.array([0, 0, 2, 1]), 4)
    assert not is_permutation(np.arange(4), 5)
    assert not is_permutation(np.array([0.0, 1.0]), 2)
    assert not is_permutation(np.array([0, 1, 4]), 3)


def test_validate_resume_repartition_relaxes_only_shape():
    snap = SolverSnapshot(
        meta={"tier": "dist-cg", "pipelined": False, "precond": None,
              "n": 64, "dtype": "float64", "b_crc": 7, "nparts": 8,
              "iteration": 5},
        arrays={})
    ok = dict(tier="jax-cg", pipelined=False, precond=None, n=64,
              dtype=np.float64, b_crc=7)
    # tier + nparts mismatch: refused plain, allowed with repartition
    with pytest.raises(AcgError):
        validate_resume(snap, **ok)
    validate_resume(snap, repartition=True, **ok)
    validate_resume(snap, repartition=True, nparts=4, **{**ok,
                    "tier": "dist-cg"})
    # everything else still refuses under repartition
    for key, bad in (("pipelined", True), ("precond", "jacobi"),
                     ("n", 65), ("dtype", np.float32), ("b_crc", 8)):
        with pytest.raises(AcgError):
            validate_resume(snap, repartition=True, **{**ok, key: bad})
    # tiers outside the repartition set refuse even with the opt-in
    sh = SolverSnapshot(meta={**snap.meta, "tier": "sharded-dia"},
                        arrays={})
    with pytest.raises(AcgError, match="repartition resume supports"):
        validate_resume(sh, repartition=True, **ok)


def test_reassemble_global_identity_and_stacked():
    # single-part snapshots pass through untouched
    s1 = SolverSnapshot(meta={"tier": "jax-cg", "n": 4},
                        arrays={"x": np.arange(4.0)})
    assert reassemble_global(s1) is s1
    # a 2-part stacked snapshot reassembles through the sidecar
    perm = np.array([2, 0, 3, 1], dtype=np.int64)  # slots -> rows
    stacked = np.array([[10.0, 11.0, -1.0], [12.0, 13.0, -1.0]])
    s2 = SolverSnapshot(
        meta={"tier": "dist-cg", "n": 4, "nparts": 2,
              "part_rows": [2, 2]},
        arrays={"x": stacked, "gamma": np.float64(2.5),
                "_rowperm": perm})
    g = reassemble_global(s2)
    assert np.array_equal(g.arrays["x"],
                          np.array([11.0, 13.0, 10.0, 12.0]))
    assert float(g.arrays["gamma"]) == 2.5
    assert "_rowperm" not in g.arrays
    assert g.meta["repartitioned_from"] == {"tier": "dist-cg",
                                            "nparts": 2}


def test_reassemble_refuses_corruption():
    perm = np.array([2, 0, 3, 1], dtype=np.int64)
    stacked = np.zeros((2, 2))
    base = {"tier": "dist-cg", "n": 4, "nparts": 2,
            "part_rows": [2, 2]}

    def snap(meta=None, arrays=None):
        a = {"x": stacked, "_rowperm": perm}
        a.update(arrays or {})
        return SolverSnapshot(meta={**base, **(meta or {})}, arrays=a)

    bad_perm = perm.copy()
    bad_perm[0] = bad_perm[1]                       # duplicate row
    with pytest.raises(AcgError, match="not a permutation"):
        reassemble_global(snap(arrays={"_rowperm": bad_perm}))
    with pytest.raises(AcgError, match="part_rows"):
        reassemble_global(snap(meta={"part_rows": [3, 2]}))
    with pytest.raises(AcgError, match="sidecar"):
        reassemble_global(SolverSnapshot(meta=dict(base),
                                         arrays={"x": stacked}))
    with pytest.raises(AcgError, match="stacked layout"):
        reassemble_global(snap(arrays={"x": np.zeros(4)}))


def test_corrupted_sidecar_refuses_through_save_load(system, prob4,
                                                     snap8, tmp_path):
    """The satellite-5 refusal end-to-end: a snapshot whose permutation
    sidecar was corrupted ON DISK (valid checksums, wrong content)
    refuses at resume instead of scrambling the carry."""
    csr, b = system
    snap, _ = snap8
    arrays = dict(snap.arrays)
    rp = arrays["_rowperm"].copy()
    rp[:2] = rp[0]                                   # now a repeat
    arrays["_rowperm"] = rp
    p = str(tmp_path / "bad")
    save_snapshot(p, dict(snap.meta), arrays)
    bad = load_snapshot(p)
    s = DistCGSolver(prob4, ckpt=CheckpointConfig(resume=bad,
                                                  repartition=True))
    with pytest.raises(AcgError, match="not a permutation"):
        s.solve(b, criteria=CRIT)


def test_repartition_parity_8_to_4_and_single_and_host(system, prob4,
                                                       snap8):
    """The satellite-5 parity bar: an 8-part snapshot resumed at
    4 parts, on the single-device tier, and on the host oracle all
    converge to the original tolerance with total (pre + post)
    iterations within a small band of uninterrupted (measured: exactly
    equal -- the global Krylov state continues; only dot-product
    re-association can move the count)."""
    csr, b = system
    snap, it_ref = snap8
    assert snap.meta["nparts"] == 8 and snap.iteration < it_ref
    band = (it_ref, int(it_ref * 1.15) + 3)

    s4 = DistCGSolver(prob4, ckpt=CheckpointConfig(resume=snap,
                                                   repartition=True))
    x4 = s4.solve(b, criteria=CRIT)
    total = snap.iteration + s4.stats.niterations
    assert band[0] - 3 <= total <= band[1]
    assert s4.stats.ckpt["repartitioned_from"] == {"tier": "dist-cg",
                                                   "nparts": 8}
    assert np.linalg.norm(b - csr @ np.asarray(x4)) \
        / np.linalg.norm(b) < 1e-7

    A = device_matrix_from_csr(csr, dtype=jnp.float64)
    s1 = JaxCGSolver(A, ckpt=CheckpointConfig(resume=snap,
                                              repartition=True))
    x1 = s1.solve(b, criteria=CRIT)
    assert band[0] - 3 <= snap.iteration + s1.stats.niterations \
        <= band[1]
    assert np.linalg.norm(b - csr @ np.asarray(x1)) \
        / np.linalg.norm(b) < 1e-7

    sh = HostCGSolver(csr, ckpt=CheckpointConfig(resume=snap,
                                                 repartition=True))
    xh = sh.solve(b, criteria=CRIT)
    assert band[0] - 3 <= snap.iteration + sh.stats.niterations \
        <= band[1]
    assert any(e["kind"] == "repartition" for e in sh.stats.events)
    assert np.linalg.norm(b - csr @ xh) / np.linalg.norm(b) < 1e-7

    # and WITHOUT the opt-in the same mismatch still refuses
    with pytest.raises(AcgError, match="does not match this solve"):
        DistCGSolver(prob4, ckpt=CheckpointConfig(resume=snap)).solve(
            b, criteria=CRIT)


def test_repartition_single_to_dist(system, prob4, tmp_path):
    """The reverse direction: a single-device snapshot (global vectors,
    no sidecar needed) re-slices onto the mesh."""
    csr, b = system
    A = device_matrix_from_csr(csr, dtype=jnp.float64)
    ref = JaxCGSolver(A)
    ref.solve(b, criteria=CRIT)
    p = str(tmp_path / "ck1")
    JaxCGSolver(A, ckpt=CheckpointConfig(path=p, every=16)).solve(
        b, criteria=CRIT)
    snap = load_snapshot(p)
    s = DistCGSolver(prob4, ckpt=CheckpointConfig(resume=snap,
                                                  repartition=True))
    s.solve(b, criteria=CRIT)
    total = snap.iteration + s.stats.niterations
    assert abs(total - ref.stats.niterations) <= 3


# -- env provenance (satellite 1) ----------------------------------------

def test_snapshot_records_env_and_resume_mismatch_warns(
        system, tmp_path, capsys):
    csr, b = system
    A = device_matrix_from_csr(csr, dtype=jnp.float64)
    p = str(tmp_path / "ck")
    JaxCGSolver(A, ckpt=CheckpointConfig(path=p, every=16)).solve(
        b, criteria=CRIT)
    snap = load_snapshot(p)
    import jax
    assert snap.meta["env"]["jax"] == jax.__version__
    assert snap.meta["env"]["backend"] == "cpu"

    meta = dict(snap.meta)
    meta["env"] = {"jax": "0.0.1", "jaxlib": "0.0.1", "backend": "tpu"}
    doctored = SolverSnapshot(meta=meta, arrays=snap.arrays)
    s = JaxCGSolver(A, ckpt=CheckpointConfig(resume=doctored))
    s.solve(b, criteria=CRIT)
    assert any(e["kind"] == "resume-env-mismatch"
               for e in s.stats.events)
    err = capsys.readouterr().err
    assert "environment change" in err and "'tpu' -> 'cpu'" in err
    # a matching environment stays silent
    s2 = JaxCGSolver(A, ckpt=CheckpointConfig(resume=snap))
    s2.solve(b, criteria=CRIT)
    assert not any(e["kind"] == "resume-env-mismatch"
                   for e in s2.stats.events)


# -- live-status peers + degraded (satellite 4) --------------------------

def test_status_document_peers_and_degraded_blocks():
    class StubHeartbeat:
        deadline = 30.0

        def peer_ages(self):
            return {1: 2.5, 2: 0.4}

    observatory.arm()
    try:
        observatory.set_heartbeat(StubHeartbeat())
        observatory.STATUS.note_degraded(8, 4, "peer-lost")
        doc = observatory.status_document()
        assert doc["peers"]["deadline_seconds"] == 30.0
        assert doc["peers"]["last_beat_age_seconds"] == {"1": 2.5,
                                                         "2": 0.4}
        assert doc["degraded"] == {"from": 8, "to": 4,
                                   "reason": "peer-lost"}
    finally:
        observatory.shutdown()
    # shutdown clears both planes
    assert "peers" not in observatory.status_document()


def test_degraded_env_pickup(monkeypatch):
    monkeypatch.setenv(observatory.DEGRADED_ENV, "8:4:crash")
    observatory.arm()
    try:
        doc = observatory.status_document()
        assert doc["degraded"] == {"from": 8, "to": 4,
                                   "reason": "crash"}
    finally:
        observatory.shutdown()


def test_heartbeat_peer_ages_from_watch_thread():
    """peer_ages() reflects the watcher's bookkeeping (a fake KV
    client, the DeadlineHeartbeat test convention)."""
    import time as _time

    from acg_tpu.parallel.erragree import DeadlineHeartbeat

    class FakeClient:
        def __init__(self):
            self.store = {}

        def key_value_set(self, k, v):
            self.store[k] = v

        def key_value_delete(self, k):
            self.store.pop(k, None)

        def key_value_dir_get(self, prefix):
            return [(k, v) for k, v in self.store.items()
                    if k.startswith(prefix)]

    hb = DeadlineHeartbeat(period=0.05, deadline=10.0,
                           client=FakeClient(), nprocs=2, me=0,
                           on_lost=lambda q, age: None)
    hb.start()
    try:
        _time.sleep(0.3)
        ages = hb.peer_ages()
        assert set(ages) == {1}
        assert ages[1] >= 0.0
    finally:
        hb.stop()


# -- the supervisor (tentpole leg 2) -------------------------------------

def test_supervisor_argv_surgery():
    argv = ["gen:poisson2d:16", "--supervise", "--relaunch-budget",
            "2", "--metrics-file", "m.prom", "--ckpt", "ck",
            "--ckpt-every", "8", "--nparts", "8"]
    child = sup.strip_flags(argv, sup.SUPERVISOR_FLAGS)
    assert "--supervise" not in child
    assert "--metrics-file" not in child and "m.prom" not in child
    assert "--ckpt" in child
    assert sup.flag_value(child, "--nparts") == "8"
    re = sup.set_flag(child, "--nparts", 4)
    assert sup.flag_value(re, "--nparts") == "4"
    re = sup.set_flag(re, "--resume", "ck")
    assert sup.flag_value(re, "--resume") == "ck"
    # fault hygiene: device faults are stripped on relaunch, the
    # crossing-safe crash:exit is kept
    a, e = sup._strip_fault(["--fault-inject", "spmv:nan@3"],
                            {"ACG_TPU_FAULT_INJECT": "spmv:nan@3"})
    assert "--fault-inject" not in a and "ACG_TPU_FAULT_INJECT" not in e
    a, e = sup._strip_fault(["--fault-inject", "crash:exit@9"], {})
    assert sup.flag_value(a, "--fault-inject") == "crash:exit@9"


def test_supervisor_reason_classification():
    assert sup._reason(int(ExitCode.CRASH_INJECTED)) == "crash"
    assert sup._reason(int(ExitCode.PEER_LOST)) == "peer-lost"
    assert sup._reason(int(ExitCode.PEER_DEAD_INJECTED)) == "peer-lost"
    assert sup._reason(-9) == "signal"
    assert sup._reason(1) == "failure"
    assert sup._reason(3) == "backend"


def test_chaos_schedules_are_deterministic_and_config_aware():
    class A:
        nparts = 8
        abft = True
        audit_every = 5
        multihost = False
        coordinator = None
        soak = 0
        max_iterations = 300
        num_processes = None

    specs = [sup.chaos_schedule(i, 77, A) for i in range(40)]
    assert specs == [sup.chaos_schedule(i, 77, A) for i in range(40)]
    sites = {s.split(":", 1)[0] for s in specs if s}
    assert "crash" in sites
    # every spec parses back through the fault grammar; sdc flips land
    # on AUDITED iterations ((k+1) % every == 0) -- the ABFT contract
    # protects the checksummed product, an off-cadence flip is the
    # documented negative control, not a campaign schedule
    for s in specs:
        if s is None:
            continue
        spec = faults.parse_fault_spec(s)
        if spec.site == "sdc":
            assert (spec.iteration + 1) % A.audit_every == 0, s
    A.nparts = 0
    A.abft = False
    sites0 = {s.split(":", 1)[0]
              for i in range(40)
              if (s := sup.chaos_schedule(i, 77, A)) is not None}
    assert "halo" not in sites0 and "sdc" not in sites0


def test_verify_solution_detects_wrong_answer(system, tmp_path):
    from acg_tpu.io.mtxfile import vector_mtx, write_mtx

    csr, _ = system
    b = np.ones(csr.shape[0])
    import scipy.sparse.linalg as spla
    x = spla.spsolve(csr.tocsc(), b)
    good = str(tmp_path / "good.mtx")
    write_mtx(good, vector_mtx(x), binary=True)
    ok, rel = sup.verify_solution(csr, b, good, 1e-8)
    assert ok and rel < 1e-8
    bad = str(tmp_path / "bad.mtx")
    xw = x.copy()
    xw[7] *= -1.0                     # the sdc wrong-answer shape
    write_mtx(bad, vector_mtx(xw), binary=True)
    ok, rel = sup.verify_solution(csr, b, bad, 1e-8)
    assert not ok and rel > 1e-4


def test_supervise_cli_validation():
    r = run_cli(["gen:poisson2d:8", "--comm", "none", "--supervise"])
    assert r.returncode != 0 and "--ckpt" in r.stderr
    r = run_cli(["gen:poisson2d:8", "--comm", "none", "--supervise",
                 "--ckpt", "/tmp/x", "--ckpt-every", "8",
                 "--resume", "/tmp/x"])
    assert r.returncode != 0 and "--resume" in r.stderr
    r = run_cli(["gen:poisson2d:8", "--comm", "none", "--chaos",
                 "boom"])
    assert r.returncode != 0


def test_supervisor_crash_relaunch_single_device(tmp_path):
    """crash:exit kills the child (rc 94); the supervisor relaunches
    with --resume and the solve converges -- with the acg_recovery_*
    families on the supervisor's metrics textfile."""
    ck = str(tmp_path / "ck")
    prom = str(tmp_path / "sup.prom")
    r = run_cli(["gen:poisson2d:16", "--comm", "none",
                 "--max-iterations", "300", "--residual-rtol", "1e-8",
                 "--warmup", "0", "--quiet",
                 "--ckpt", ck, "--ckpt-every", "8",
                 "--fault-inject", "crash:exit@20",
                 "--supervise", "--relaunch-backoff", "0",
                 "--metrics-file", prom])
    assert r.returncode == 0, r.stderr
    assert "relaunch 1/3 with --resume" in r.stderr
    assert "recovery:" in r.stderr
    assert "outcome: converged (rc 0)" in r.stderr
    text = open(prom).read()
    assert 'acg_recovery_relaunches_total{reason="crash"} 1' in text
    assert "acg_recovery_mttr_seconds_count 1" in text


@pytest.mark.slow
def test_supervisor_budget_exhaustion(tmp_path):
    """A child that keeps failing (unresolvable config failure after
    the first crash consumed the snapshot) spends the budget and exits
    95."""
    ck = str(tmp_path / "ck")
    # a fault-free child that cannot converge in 1 iteration: rc 1
    # every time; budget 1 -> rc 95 after one relaunch
    r = run_cli(["gen:poisson2d:16", "--comm", "none",
                 "--max-iterations", "1", "--residual-rtol", "1e-12",
                 "--warmup", "0", "--quiet",
                 "--ckpt", ck, "--ckpt-secs", "30",
                 "--supervise", "--relaunch-budget", "1",
                 "--relaunch-backoff", "0"])
    # no snapshot is ever committed in 1 iteration -> not relaunchable
    # via resume; the supervisor passes the failure through
    assert r.returncode in (1, int(ExitCode.RELAUNCH_BUDGET))


def test_supervisor_shrink_elastic_e2e(tmp_path):
    """THE acceptance e2e: crash mid-solve on the 8-part mesh -> the
    supervisor relaunches with --resume --resume-repartition on 4
    parts -> the final true relative residual meets the original
    rtol, and the relaunched child's status document says degraded."""
    ck = str(tmp_path / "ck")
    out = str(tmp_path / "x.mtx")
    status = str(tmp_path / "status.json")
    r = run_cli(["gen:poisson2d:20", "--nparts", "8",
                 "--max-iterations", "400", "--residual-rtol", "1e-8",
                 "--warmup", "0", "--quiet",
                 "--ckpt", ck, "--ckpt-every", "8",
                 "--fault-inject", "crash:exit@20",
                 "--supervise", "--shrink", "any",
                 "--relaunch-backoff", "0",
                 "--status-file", status, "-o", out])
    assert r.returncode == 0, r.stderr
    assert "shrinking 8 -> 4 parts" in r.stderr
    assert "degraded: 8 -> 4 parts (crash)" in r.stderr
    # independent verification: the answer meets the ORIGINAL rtol
    csr = SymCsrMatrix.from_mtx(poisson_mtx(20, dim=2)).to_csr()
    b = np.ones(csr.shape[0])
    ok, rel = sup.verify_solution(csr, b, out, 1e-8)
    assert ok, rel
    assert rel < 1e-7
    doc = json.load(open(status))
    assert doc["degraded"] == {"from": 8, "to": 4, "reason": "crash"}


def test_chaos_campaign_small(tmp_path):
    """A seeded 4-schedule campaign (abft + ckpt armed) ends every
    run converged or agreed-abort, records acg-tpu-chaos/1 ledger
    rows, and exits 0 -- zero wrong-answer-green."""
    hist = str(tmp_path / "hist")
    r = run_cli(["gen:poisson2d:16", "--comm", "none",
                 "--max-iterations", "300", "--residual-rtol", "1e-8",
                 "--warmup", "0", "--quiet",
                 "--ckpt", str(tmp_path / "ck"), "--ckpt-every", "8",
                 "--audit-every", "5", "--abft",
                 "--chaos", "2026:4", "--relaunch-backoff", "0",
                 "--history", hist])
    assert r.returncode == 0, r.stderr
    assert "wrong-answer: 0" in r.stderr
    rows = []
    for name in os.listdir(hist):
        with open(os.path.join(hist, name)) as f:
            for line in f:
                obj = json.loads(line)
                if obj.get("schema") == "acg-tpu-chaos/1":
                    rows.append(obj)
    assert len(rows) == 4
    outcomes = {r_["doc"]["chaos"]["outcome"] for r_ in rows}
    assert outcomes <= {"converged", "agreed-abort"}
    # the schedules are re-runnable: each records its fault spec
    for r_ in rows:
        spec = r_["doc"]["chaos"]["fault"]
        if spec is not None:
            faults.parse_fault_spec(spec)


@pytest.mark.slow
def test_chaos_campaign_acceptance_20_schedules(tmp_path):
    """The full ISSUE-10 acceptance bar: >= 20 seeded schedules on the
    8-part mesh through the supervisor (shrink armed), every run
    converged or agreed-abort, ZERO wrong-answer-green."""
    hist = str(tmp_path / "hist")
    r = run_cli(["gen:poisson2d:20", "--nparts", "8",
                 "--max-iterations", "400", "--residual-rtol", "1e-8",
                 "--warmup", "0", "--quiet",
                 "--ckpt", str(tmp_path / "ck"), "--ckpt-every", "8",
                 "--audit-every", "5", "--abft", "--shrink", "any",
                 "--chaos", "4242:20", "--relaunch-backoff", "0",
                 "--history", hist],
                timeout=3000)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "schedules: 20" in r.stderr
    assert "wrong-answer: 0" in r.stderr
