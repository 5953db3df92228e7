"""Unit tests for the benchmark harness's timing logic (bench.py).

The harness times a fixed-iteration solve best-of-N after one warmup,
under a wall-clock budget.  These tests pin that logic with a fake
solver -- no device needed.
"""

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import bench  # noqa: E402


class FakeStats:
    tsolve = 0.0


class FakeSolver:
    """Solver whose per-iteration cost is scripted."""

    def __init__(self, seconds_per_iter):
        self.per_iter = seconds_per_iter
        self.stats = FakeStats()
        self.calls = []

    def solve(self, b, criteria=None, **kw):
        self.calls.append(criteria.maxits)
        self.stats.tsolve += self.per_iter * criteria.maxits


class FakeCriteria:
    def __init__(self, maxits):
        self.maxits = maxits


def test_time_solver_full_trip_count():
    s = FakeSolver(1e-4)
    tsolve, maxits, info = bench._time_solver(s, None, FakeCriteria,
                                              repeats=3)
    assert maxits == bench.MAXITS
    assert tsolve == pytest.approx(1e-4 * bench.MAXITS)
    assert info["budget_exhausted"] is False
    # one compile warmup, then 3 timed runs
    assert s.calls == [bench.WARMUP_ITS] + [bench.MAXITS] * 3


def test_time_solver_slow_config_keeps_full_trip_count():
    """No program-length cap: a slow config still times MAXITS."""
    s = FakeSolver(0.13)
    tsolve, maxits, _ = bench._time_solver(s, None, FakeCriteria, repeats=2)
    assert maxits == bench.MAXITS
    assert maxits / tsolve == pytest.approx(1 / 0.13)


def test_time_solver_wall_clock_budget_stops_repeats():
    """A slow config under a wall-clock budget keeps its first timed run
    and skips the rest (fewer repeats on a slow row beats a dead row)."""
    import time as _time

    class SlowSolver(FakeSolver):
        def solve(self, b, criteria=None, **kw):
            super().solve(b, criteria=criteria)
            _time.sleep(0.05)  # real wall clock, what the budget sees

    s = SlowSolver(1e-4)
    tsolve, maxits, info = bench._time_solver(
        s, None, FakeCriteria, repeats=50, time_budget_s=0.01)
    assert info["budget_exhausted"] is True
    # warmup always runs; then exactly ONE timed run
    assert len(s.calls) == 2
    assert maxits / tsolve == pytest.approx(1e4)


@pytest.mark.parametrize("exhausted", [False, True])
def test_roofline_context_fraction(monkeypatch, exhausted):
    """roofline_frac = achieved bytes/s over the probe's bandwidth; a
    budget-cut timing loop marks its row."""
    monkeypatch.setattr(bench, "bandwidth_probe_gbs", lambda refresh: 800.0)
    row = {"metric": "m", "value": 1000.0, "vs_baseline": 1.0}
    out = bench._roofline_context(dict(row), 0.4e9,
                                  info={"budget_exhausted": exhausted})
    assert out["bw_gbs"] == 800.0
    assert out["roofline_frac"] == pytest.approx(1000.0 * 0.4e9 / 800e9)
    assert out.get("budget_exhausted", False) is exhausted


def test_time_solver_passes_solve_kwargs():
    seen = {}

    class KwSolver(FakeSolver):
        def solve(self, b, criteria=None, **kw):
            seen.update(kw)
            super().solve(b, criteria=criteria)

    s = KwSolver(1e-5)
    bench._time_solver(s, None, FakeCriteria, repeats=1, host_result=False)
    assert seen == {"host_result": False}
