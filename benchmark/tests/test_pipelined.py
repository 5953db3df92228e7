"""CPU rehearsal of the pipelined cell on one device: whole untraced and
traced runs through the harness, and the faults that ``correct`` has to
catch there -- the residual replacement that never fires, and the
program's bf16 control."""

import jax
import numpy as np
import pytest

from benchmark.tests import test_rehearsal
from benchmark.tests.test_correct import fails

NP1 = "poisson2d-2048-pipelined.np1"
N = 48


@pytest.mark.parametrize("trace", [0, 1])
def test_last_line(run_cell, trace):
    test_rehearsal.test_last_line(run_cell, NP1, N, trace)


@pytest.fixture
def solvers(monkeypatch):
    """The solvers the window ran, recorded as their inputs build them."""
    from benchmark import spec

    made = []
    real = spec.module

    def module(path):
        mod = real(path)
        if path.name == "mtx_pipelined.py":
            build = mod.build

            def recorded(*a, **k):
                solver, rhs = build(*a, **k)
                made.append(solver)
                return solver, rhs

            mod.build = recorded
        return mod

    monkeypatch.setattr(spec, "module", module)
    return made


def test_the_window_runs_the_replacing_pipelined_recurrence(run_cell,
                                                            solvers):
    res, _ = run_cell(NP1, N)
    assert res["correct"] is True
    (solver,) = solvers
    assert solver.pipelined
    assert solver.stats.nreplacements >= 1


def test_a_program_without_the_replacement_stops_in_setup(run_cell,
                                                          monkeypatch):
    """The configuration needs the replacing recurrence: a program
    without ``recurrence.replaces`` (the commit before it) ends its
    set-up at once instead of running to the iteration cap."""
    from acg_tpu import recurrence

    monkeypatch.delattr(recurrence, "replaces")
    with pytest.raises(SystemExit, match="residual replacement"):
        run_cell(NP1, N)


@pytest.fixture
def never_fires(monkeypatch):
    """The replacement criterion never true (a drop factor of 0): the
    pipelined recurrence over f32 vectors without its replacements."""
    from acg_tpu import recurrence

    jax.clear_caches()
    monkeypatch.setattr(recurrence, "REPLACE_DROP",
                        {np.dtype(np.float32): 0.0})
    yield
    jax.clear_caches()


def test_replacement_never_firing_is_not_correct(run_cell, never_fires):
    res, _ = run_cell(NP1, N)
    assert fails(res), res["compared"]


def test_control_bf16_is_not_correct(run_cell):
    res, _ = run_cell(NP1, N, dtype="bfloat16")
    assert fails(res), res["compared"]
