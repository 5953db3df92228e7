"""CPU rehearsal of the graph-partitioned cell on four virtual devices:
whole untraced and traced runs through the harness, and the faults
that ``correct`` has to catch on this route -- the ``all_to_all`` halo
left out, and the stacked answer handed to the reference in partition
order instead of the file's row order."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.tests import test_rehearsal
from benchmark.tests.test_correct import fails

CELL = "poisson2d-2048-graph4.cg.part4"
N = 32


def route(err: str) -> dict:
    line = next(ln for ln in err.splitlines() if f"cell {CELL} " in ln)
    return json.loads(line.partition(" route ")[2].rpartition(" device ")[0])


@pytest.mark.parametrize("trace", [0, 1])
def test_last_line(run_cell, trace):
    test_rehearsal.test_last_line(run_cell, CELL, N, trace)


def test_route_and_partition_in_the_setup_log(run_cell):
    _, err = run_cell(CELL, N)
    r = route(err)
    assert r["transport"] == "xla" and r["devices"] == [0, 1, 2, 3]
    # the bisection balances the parts to within a few rows
    assert N * N // 4 <= r["rows_max"] <= N * N // 4 + 4
    assert 0 < r["ghosts_max"] < r["rows_max"]
    assert r["halo_entries"] > 0
    assert "bench: partition: 4 parts" in err


def partition_order(problem, stacked):
    """The parts' owned rows one after another, as they lie on the
    chips: a permutation of the file's rows under a graph partition."""
    return np.concatenate([np.asarray(stacked)[p, : s.nowned]
                           for p, s in enumerate(problem.subs)])


@pytest.mark.parametrize("fault", ["none", "halo-left-out",
                                   "partition-order", "control-bf16"])
def test_fault_is_not_correct(run_cell, monkeypatch, fault):
    from acg_tpu.parallel.dist import DistributedProblem

    if fault == "halo-left-out":
        monkeypatch.setattr(jax.lax, "all_to_all",
                            lambda x, *a, **k: jnp.zeros_like(x))
    if fault == "partition-order":
        monkeypatch.setattr(DistributedProblem, "gather", partition_order)
    res, _ = run_cell(CELL, N,
                      dtype="bfloat16" if fault == "control-bf16" else None)
    assert fails(res) is (fault != "none"), res["compared"]
