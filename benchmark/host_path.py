"""The solver's host path, read from the program's own host spans.

``JaxCGSolver.solve`` brackets each step of a solve in an ``acg:*``
span (``acg_tpu.telemetry.span``), written into the profiler's capture
on the clock of the device's op events: ``acg:prepare`` (holding
``acg:transfer``), ``acg:dispatch`` and ``acg:wait`` inside
``acg:solve``, then ``acg:fetch``, ``acg:check`` and ``acg:account``.
A program without these spans (one from before them), and a capture
with no device plane (the CPU's), read as None."""

from __future__ import annotations

from benchmark import trace_reduce

# the spans in which the host works for the solve rather than waiting
# on its program
HOST = ("acg:prepare", "acg:transfer", "acg:dispatch", "acg:fetch",
        "acg:check", "acg:account")
WAIT = "acg:wait"


def _spans(trace, names) -> list[tuple[float, float]]:
    return [(s, e) for n, s, e in trace.spans if n in names]


def _readable(run: dict, trace) -> bool:
    return (trace is not None and bool(run["solves"])
            and bool(trace.devices[:run["chips"]])
            and bool(_spans(trace, ("acg:dispatch",))))


def host_ms(run: dict, trace) -> float | None:
    """Host milliseconds per traced solve inside the program's host
    spans (their union, clipped to the window): set-up of the
    arguments, dispatch, the blocking reads of the result, the
    finiteness check and the bookkeeping."""
    if not _readable(run, trace):
        return None
    n = len(run["solves"])
    t0, t1 = trace.window
    host = [(max(s, t0), min(e, t1)) for s, e in _spans(trace, HOST)
            if e > t0 and s < t1]
    return 1e3 * trace_reduce.union_seconds(host) / n


def host_gap_ms(run: dict, trace) -> float | None:
    """Device-idle milliseconds per traced solve while the host is not
    waiting on the solve program (outside every ``acg:wait`` span), on
    the idlest chip: the idle time that the host path between one
    solve's loop and the next puts on the device.  The loop's own
    control gaps fall inside ``acg:wait`` and are left out."""
    if not _readable(run, trace):
        return None
    wait = _spans(trace, (WAIT,))
    gaps = []
    for d in trace.devices[:run["chips"]]:
        idle = trace_reduce.idle_gaps(d, trace.window)
        gaps.append(trace_reduce.union_seconds(idle)
                    - trace_reduce.overlap_seconds(idle, wait))
    return 1e3 * max(gaps) / len(run["solves"])
