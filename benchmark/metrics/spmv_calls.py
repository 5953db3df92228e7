"""spmv_calls (count per iteration, device trace); ``spmv_calls.short``
is the split that moves solve_s.short.  Layer: solver program.

The operator applications one CG iteration costs, on the busiest chip:
the SpMV kernel events of the traced solves -- the ``spmv``-kind ops of
the reduced capture (``trace_reduce.classify`` counts every custom call
as one) -- over the solves' iterations.

A pipelined solve of k iterations without a residual replacement reads
(k + 2) / k: the set-up's two SpMVs (r0 = b - A x0 and w0 = A r0) and
one an iteration.  Each replacement adds its own four (r = b - A x,
w = A r, s = A p, z = A s).  Reads only ``Op.kind``."""

from __future__ import annotations


def read(run: dict, trace) -> float | None:
    its = sum(s["iterations"] for s in run["solves"])
    if trace is None or not its:
        return None
    t0, t1 = trace.window
    calls = [sum(1 for o in d.ops
                 if o.kind == "spmv" and o.start >= t0 and o.end <= t1)
             for d in trace.devices[:run["chips"]]]
    return max(calls) / its if calls and max(calls) else None
