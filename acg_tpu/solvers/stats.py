"""Solver statistics and report formatting.

Rebuilds the always-on counter tier of the reference's profiling (SURVEY.md
section 5): every solver accumulates iteration counts, analytic flop/byte
totals, and per-op-class breakdowns in its struct (``cg.h:88-98``,
``cgcuda.h:107-116``) and reports them in a fixed text block
(``acgsolvercuda_fwrite``, ``cgcuda.c:1927-1975``).  The report format here
is line-compatible so the reference's analysis scripts (which grep
``total solver time``) work unchanged.

One deliberate deviation: under ``jax.jit`` the whole solve is one fused
XLA program, so per-op *times* are not separately observable in-loop.
Per-op counts and analytic bytes are always tracked; op times are filled
by the host reference solver (eager mode) and, for the compiled solvers,
by the replay-based profiling tier (:mod:`acg_tpu.solvers.profile`,
CLI ``--profile-ops``).  Use ``jax.profiler`` traces (``--trace``) for
the fine-grained tier.
"""

from __future__ import annotations

import dataclasses
import io
import sys

from acg_tpu.errors import fexcept_str

OP_CLASSES = ("gemv", "dot", "nrm2", "axpy", "copy", "allreduce", "halo",
              "precond")
# report labels match the reference output block
_OP_LABELS = {"allreduce": "MPI_Allreduce", "halo": "MPI_HaloExchange"}
# op classes the reference block does not know: their row renders only
# when something was counted, so unpreconditioned reports stay
# byte-identical to the reference's (the resilience-lines discipline)
_OPTIONAL_OPS = ("precond",)

# canonical pipeline-phase order for the ``timings:`` section (the
# telemetry tier's always-on phase timer); phases recorded out of order
# -- solvers record transfer/compile/solve, the CLI records the rest --
# still report in this order
# "ckpt" is the survivability tier's snapshot serialisation + atomic
# rename (acg_tpu.checkpoint), billed to its OWN phase so solve (and
# the soak latency histograms) never absorb checkpoint time
PHASE_ORDER = ("ingest", "partition", "transfer", "compile", "solve",
               "ckpt", "writeback")


@dataclasses.dataclass
class StoppingCriteria:
    """Stopping criteria, all four of the reference's (``cg.h:136-149``):

      * maxits - iteration cap
      * residual_atol:  ||b - Ax|| < atol
      * residual_rtol:  ||b - Ax|| / ||b - Ax0|| < rtol
      * diff_atol:      ||alpha p|| < atol   (difference in iterates)
      * diff_rtol:      ||alpha p|| / ||x|| < rtol
    A tolerance of 0 disables that criterion.
    """

    maxits: int = 100
    residual_atol: float = 0.0
    residual_rtol: float = 0.0
    diff_atol: float = 0.0
    diff_rtol: float = 0.0

    @property
    def needs_diff(self) -> bool:
        return self.diff_atol > 0 or self.diff_rtol > 0

    @property
    def unbounded(self) -> bool:
        """True when no tolerance is set: run exactly maxits iterations."""
        return (self.residual_atol == 0 and self.residual_rtol == 0
                and self.diff_atol == 0 and self.diff_rtol == 0)


@dataclasses.dataclass
class OpStats:
    n: int = 0
    t: float = 0.0
    bytes: int = 0

    def add(self, n=1, t=0.0, bytes=0):
        self.n += n
        self.t += t
        self.bytes += bytes


@dataclasses.dataclass
class SolverStats:
    """Accumulated solver state + statistics (the ``acgsolver*`` struct role)."""

    unknowns: int = 0
    nsolves: int = 0
    ntotaliterations: int = 0
    niterations: int = 0
    nflops: float = 0.0
    tsolve: float = 0.0
    bnrm2: float = 0.0
    x0nrm2: float = 0.0
    r0nrm2: float = 0.0
    rnrm2: float = 0.0
    dxnrm2: float = 0.0
    converged: bool = False
    criteria: StoppingCriteria = dataclasses.field(default_factory=StoppingCriteria)
    ops: dict = dataclasses.field(
        default_factory=lambda: {k: OpStats() for k in OP_CLASSES})
    fexcept_arrays: list = dataclasses.field(default_factory=list)
    # resilience tier (solvers.resilience): detected breakdowns,
    # host-policy restarts, and transport/solver fallbacks, with a
    # human-readable event log surfaced in the report
    nbreakdowns: int = 0
    nrestarts: int = 0
    nfallbacks: int = 0
    # survivability tier (acg_tpu.checkpoint): rollbacks to the last
    # on-disk snapshot -- the recovery ladder's new first rung
    nrollbacks: int = 0
    recovery_log: list = dataclasses.field(default_factory=list)
    # telemetry tier (acg_tpu.telemetry): timestamped resilience/fault
    # events for the structured sink, pipeline-phase seconds, and the
    # last solve's convergence trace (a telemetry.ConvergenceTrace)
    events: list = dataclasses.field(default_factory=list)
    timings: dict = dataclasses.field(default_factory=dict)
    trace: object = None
    # perfmodel tier (acg_tpu.perfmodel): the compiler's OWN cost
    # analysis of the compiled solve program (flops / bytes accessed,
    # per-iteration derivation, the static communication ledger) and its
    # memory analysis (argument/output/temp/generated-code HBM bytes).
    # Sections render only when an analysis pass (--explain) recorded
    # them -- the reference-format block stays byte-identical otherwise
    costmodel: dict = dataclasses.field(default_factory=dict)
    memory: dict = dataclasses.field(default_factory=dict)
    # service-metrics tier (acg_tpu.soak): the soak driver's report --
    # latency/iteration percentiles + drift verdict.  Rendered (and
    # exported, stats schema /3) only when a soak run recorded it
    soak: dict = dataclasses.field(default_factory=dict)
    # preconditioning tier (acg_tpu.precond, stats schema /4): the armed
    # preconditioner's kind/parameters, analytic applies, and spectral
    # estimates.  Appends after every existing section, like soak
    precond: dict = dataclasses.field(default_factory=dict)
    # numerical-health tier (acg_tpu.health, stats schema /5): in-loop
    # true-residual audit summary (gap/count/threshold) and the
    # post-hoc Lanczos spectrum estimate.  Appends strictly last
    health: dict = dataclasses.field(default_factory=dict)
    # survivability tier (acg_tpu.checkpoint, stats schema /6): the
    # armed snapshot configuration, snapshots written/resumed, and the
    # last committed iteration.  Appends after health
    ckpt: dict = dataclasses.field(default_factory=dict)
    # timeline-tracing tier (acg_tpu.tracing, stats schema /7): the
    # profiler-capture analysis (measured per-op-class seconds, overlap
    # efficiency, straggler attribution) and the --timeline export
    # summary.  Appends strictly last
    tracing: dict = dataclasses.field(default_factory=dict)
    # live-observatory tier (acg_tpu.observatory, stats schema /8): the
    # declared --slo objectives and their observation/breach/burn
    # verdict.  Appends strictly last
    slo: dict = dataclasses.field(default_factory=dict)
    # batched multi-RHS tier (acg_tpu.solvers.batched, stats schema
    # /9): nrhs, per-RHS iteration/residual/converged columns, and the
    # block-CG iteration totals.  Appends strictly last
    batch: dict = dataclasses.field(default_factory=dict)
    # decision observatory (acg_tpu.planner, stats schema /12): the
    # plan id / decision provenance of a planned solve and its
    # plan-vs-actual row (predicted vs measured s/solve + iterations,
    # misprediction ratio) -- the self-correction feedback the planner
    # consults on replan.  Appends strictly last
    plan: dict = dataclasses.field(default_factory=dict)
    # mesh solvers (parallel.dist.place_operator): how many times the
    # partitioned operator was placed on the mesh -- once per solver,
    # however many solves follow
    operator_placements: int = 0
    # pipelined CG over f32 vectors (recurrence.replaces): the
    # residual replacements of the last solve
    nreplacements: int = 0

    def to_dict(self) -> dict:
        """Machine-readable twin of :meth:`fwrite` -- the ``stats`` key
        of a ``--stats-json`` document (schema versioned there).  Every
        value is plain-JSON-able; the convergence trace's records are
        identical dicts to the ``--convergence-log`` JSONL data lines,
        so the two sinks round-trip."""
        c = self.criteria
        d = {
            "unknowns": self.unknowns,
            "nsolves": self.nsolves,
            "ntotaliterations": self.ntotaliterations,
            "niterations": self.niterations,
            "nflops": self.nflops,
            "tsolve": self.tsolve,
            "bnrm2": self.bnrm2,
            "x0nrm2": self.x0nrm2,
            "r0nrm2": self.r0nrm2,
            "rnrm2": self.rnrm2,
            "dxnrm2": self.dxnrm2,
            "converged": bool(self.converged),
            "criteria": {
                "maxits": c.maxits,
                "residual_atol": c.residual_atol,
                "residual_rtol": c.residual_rtol,
                "diff_atol": c.diff_atol,
                "diff_rtol": c.diff_rtol,
            },
            "ops": {op: {"n": s.n, "t": s.t, "bytes": s.bytes}
                    for op, s in self.ops.items()},
            "fexcept": fexcept_str(*self.fexcept_arrays),
            "resilience": {
                "nbreakdowns": self.nbreakdowns,
                "nrestarts": self.nrestarts,
                "nfallbacks": self.nfallbacks,
                "nrollbacks": self.nrollbacks,
                "log": list(self.recovery_log),
            },
            "events": list(self.events),
            "timings": dict(self.timings),
            "costmodel": dict(self.costmodel),
            "memory": dict(self.memory),
            "soak": dict(self.soak),
            "precond": dict(self.precond),
            "health": dict(self.health),
            "ckpt": dict(self.ckpt),
            "tracing": dict(self.tracing),
            "slo": dict(self.slo),
            "batch": dict(self.batch),
            "plan": dict(self.plan),
            "operator_placements": self.operator_placements,
            "nreplacements": self.nreplacements,
        }
        if self.trace is not None:
            d["trace"] = self.trace.to_dict()
        # JSON has no Inf/NaN literal; dxnrm2 is inf when no diff
        # criterion ran
        import math
        for k in ("bnrm2", "x0nrm2", "r0nrm2", "rnrm2", "dxnrm2",
                  "nflops", "tsolve"):
            if not math.isfinite(d[k]):
                d[k] = repr(d[k])
        return d

    def fwrite(self, f=None, indent: int = 0) -> str:
        """Solver report, line-compatible with ``acgsolvercuda_fwrite``."""
        out = io.StringIO()
        pad = " " * indent
        c = self.criteria

        def p(line):
            out.write(pad + line + "\n")

        tother = self.tsolve - sum(o.t for o in self.ops.values())
        p(f"unknowns: {self.unknowns:,}")
        p(f"solves: {self.nsolves:,}")
        p(f"total iterations: {self.ntotaliterations:,}")
        p(f"total flops: {1.0e-9 * self.nflops:,.3f} Gflop")
        rate = 1.0e-9 * self.nflops / self.tsolve if self.tsolve > 0 else 0.0
        p(f"total flop rate: {rate:,.3f} Gflop/s")
        p(f"total solver time: {self.tsolve:,.6f} seconds")
        p("performance breakdown:")
        for op in OP_CLASSES:
            s = self.ops[op]
            if op in _OPTIONAL_OPS and s.n == 0:
                continue
            gbs = 1.0e-9 * s.bytes / s.t if s.t > 0 else 0.0
            label = _OP_LABELS.get(op, op)
            p(f"  {label}: {s.t:,.6f} seconds {s.n:,} times {s.bytes:,} B {gbs:,.3f} GB/s")
        p(f"  other: {tother:,.6f} seconds")
        p("last solve:")
        p("  stopping criterion:")
        p(f"    maximum iterations: {c.maxits:,}")
        p(f"    tolerance for residual: {c.residual_atol:.15g}")
        p(f"    tolerance for relative residual: {c.residual_rtol:.15g}")
        p(f"    tolerance for difference in solution iterates: {c.diff_atol:.15g}")
        p(f"    tolerance for relative difference in solution iterates: {c.diff_rtol:.15g}")
        p(f"  iterations: {self.niterations:,}")
        p(f"  right-hand side 2-norm: {self.bnrm2:.15g}")
        p(f"  initial guess 2-norm: {self.x0nrm2:.15g}")
        p(f"  initial residual 2-norm: {self.r0nrm2:.15g}")
        p(f"  residual 2-norm: {self.rnrm2:.15g}")
        p(f"  difference in solution iterates 2-norm: {self.dxnrm2:.15g}")
        p(f"  floating-point exceptions: {fexcept_str(*self.fexcept_arrays)}")
        # like the resilience lines: only a solve that replaced shows it
        if self.nreplacements:
            p(f"  residual replacements: {self.nreplacements:,}")
        # resilience lines appear only when something happened, so the
        # report stays byte-identical to the reference's on clean solves
        if (self.nbreakdowns or self.nrestarts or self.nfallbacks
                or self.nrollbacks):
            # the rollback count appends only when rollbacks happened,
            # so pre-survivability report consumers see the exact
            # historical line
            rb = (f", {self.nrollbacks} rollbacks" if self.nrollbacks
                  else "")
            p(f"  resilience: {self.nbreakdowns} breakdowns detected, "
              f"{self.nrestarts} restarts, {self.nfallbacks} fallbacks"
              + rb)
            for ev in self.recovery_log:
                p(f"    {ev}")
        # phase timings appear only when a phase timer ran (the CLI's
        # always-on tier sets them; library solves leave them empty), so
        # library reports stay byte-identical to the reference's
        if self.timings:
            p("timings:")
            seen = []
            for name in PHASE_ORDER:
                if name in self.timings:
                    seen.append(name)
                    p(f"  {name}: {self.timings[name]:,.6f} seconds")
            for name, secs in self.timings.items():
                if name not in seen:
                    p(f"  {name}: {secs:,.6f} seconds")
        # perfmodel sections (compiler-reported cost/memory + the comm
        # ledger) append strictly LAST, like timings: a disarmed run --
        # and every report the reference's scripts grep -- is unchanged
        if self.costmodel:
            p("costmodel:")
            _write_section(p, self.costmodel, 1)
        if self.memory:
            p("memory:")
            _write_section(p, self.memory, 1)
        if self.soak:
            p("soak:")
            _write_section(p, self.soak, 1)
        if self.precond:
            p("precond:")
            _write_section(p, self.precond, 1)
        if self.health:
            p("health:")
            _write_section(p, self.health, 1)
        if self.ckpt:
            p("ckpt:")
            _write_section(p, self.ckpt, 1)
        if self.tracing:
            p("tracing:")
            _write_section(p, self.tracing, 1)
        if self.slo:
            p("slo:")
            _write_section(p, self.slo, 1)
        if self.batch:
            p("batch:")
            _write_section(p, self.batch, 1)
        if self.plan:
            p("plan:")
            _write_section(p, self.plan, 1)
        text = out.getvalue()
        if f is not None:
            f.write(text)
        return text

    def print(self, indent: int = 0):
        self.fwrite(sys.stderr, indent)


def _write_section(p, d: dict, depth: int) -> None:
    """Generic nested renderer for the perfmodel sections: scalars one
    per line, sub-dicts indented, lists summarised by length (their full
    form lives in the --stats-json twin -- a 64-neighbour halo table
    does not belong in the text block)."""
    ind = "  " * depth
    for k, v in d.items():
        if isinstance(v, dict):
            p(f"{ind}{k}:")
            _write_section(p, v, depth + 1)
        elif isinstance(v, (list, tuple)):
            p(f"{ind}{k}: [{len(v)} entries -- see --stats-json]")
        elif isinstance(v, float):
            p(f"{ind}{k}: {v:,.6g}")
        else:
            p(f"{ind}{k}: {v}")


def cg_flops_per_iteration(nnz_full: int, n: int, pipelined: bool = False) -> float:
    """Analytic flop count per CG iteration (reference counts 3 flops per
    stored nonzero per SpMV -- symmetric entries counted twice -- and 2n per
    dot/axpy, ``cgcuda.c:812,901``)."""
    spmv = 3.0 * nnz_full
    if not pipelined:
        # t=Ap; dots: (p,t),(r,r); axpys: x,r,p
        return spmv + 2 * 2.0 * n + 3 * 2.0 * n
    # pipelined: q=Aw; dots (r,r),(w,r); 6 vector updates + scalar recurrences
    return spmv + 2 * 2.0 * n + 6 * 2.0 * n
