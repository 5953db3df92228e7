"""Solve telemetry: convergence traces, structured stats export, phase
timing, and cross-rank aggregation.

The reference's only window into a running solve is the post-hoc stats
block (``acgsolvercuda_fwrite``, SURVEY.md section 5).  Under XLA the
whole CG loop is ONE fused program, so a 10k-iteration solve is a black
box between dispatch and result -- the resilience tier (PR 1) can say
*that* a breakdown happened but not show the residual trajectory that
led there.  Communication-reduced and deep-pipelined CG variants make
per-iteration residual drift and per-rank time imbalance the primary
evidence for choosing a variant (Cornelis & Vanroose, arXiv:1801.04728);
this module makes that evidence machine-readable, per rank, and cheap
enough to leave on.

Four tiers (lowest overhead first):

1. **Always-on counters** -- :class:`~acg_tpu.solvers.stats.SolverStats`
   (unchanged) plus the phase timer (:class:`PhaseTimer`) whose
   ingest/partition/transfer/compile/solve/writeback seconds appear in a
   new ``timings:`` stats section; each phase is also bracketed with a
   ``jax.profiler.TraceAnnotation`` so ``--trace`` Perfetto output is
   navigable.
2. **In-loop convergence telemetry** (``--convergence-log``): the jitted
   classic and pipelined loops carry a fixed-size device-side ring
   buffer recording per-iteration ``(||r||^2, alpha, beta, pAp)``.  The
   buffer rides the loop carry and is fetched ONCE with the result --
   zero additional host transfers per iteration.  Surfaced as JSONL
   (:meth:`ConvergenceTrace.write_jsonl`) and consumed by the recovery
   driver so breakdown/restart events log the trailing residual window.
3. **Progress heartbeat** (``--progress K``): a ``jax.debug.callback``
   fired every K iterations from inside the compiled loop -- the only
   liveness signal a multi-hour pod solve has.
4. **Structured stats sink** (``--stats-json``): a schema-versioned
   machine-readable twin of ``fwrite`` -- run manifest, per-op
   counters, timestamped resilience/fault events, phase timings, the
   convergence trace, and (multi-controller) the cross-rank aggregation
   gathered over the erragree coordination-service KV plumbing.

Everything here is OFF by default and compiles to the byte-identical
pristine programs when disarmed (``trace``/``progress`` are static jit
arguments, the same design as the fault injector's).
"""

from __future__ import annotations

import contextlib
import dataclasses
import io
import json
import math
import sys
import time

import numpy as np

from acg_tpu.solvers.stats import PHASE_ORDER

# /2: the stats twin grew the perfmodel tier's "costmodel" (compiler
# cost analysis + per-iteration derivation + comm ledger) and "memory"
# (compiled HBM footprint) keys -- additive, so /1 consumers keep working
# /3: the service-metrics tier adds a top-level "metrics" key (the
# process-wide registry snapshot, acg_tpu.metrics, present when the
# metrics layer is armed) and a "soak" key inside the stats twin (the
# soak driver's latency/iteration percentiles + drift verdict) --
# additive again, so /1 and /2 consumers keep working
# /4: the preconditioning tier (acg_tpu.precond) adds a "precond" key
# inside the stats twin (kind/applies/spectral estimates), a "precond"
# op-class row under "ops", and a manifest "precond" key that joins the
# bench-diff case key -- additive, so /1../3 consumers keep working
# /5: the numerical-health tier (acg_tpu.health) adds a "health" key
# inside the stats twin (true-residual audit summary + Lanczos spectrum
# estimate) and an optional "gap" column in trace records (the audit
# column; its presence is declared by the trace/meta "fields" list so
# mixed audited/unaudited windows round-trip) -- additive, so /1../4
# consumers keep working
# /6: the survivability tier (acg_tpu.checkpoint) adds a "ckpt" key
# inside the stats twin (armed snapshot configuration, snapshots
# written, resume provenance), an "nrollbacks" counter inside
# "resilience", and an "abft" sub-dict inside "health" (checksum-SpMV
# verification summary) -- additive, so /1../5 consumers keep working
# /7: the timeline-tracing tier (acg_tpu.tracing) adds a "tracing" key
# inside the stats twin (profiler-capture analysis: measured per-op-
# class seconds, overlap efficiency, straggler attribution; plus the
# --timeline export summary) -- additive, so /1../6 consumers keep
# working
# /8: the live-observatory tier (acg_tpu.observatory) adds an "slo" key
# inside the stats twin (declared --slo objectives, per-objective
# observation/breach counts, cumulative burn fractions) and the
# slo-breach event kind -- additive, so /1../7 consumers keep working
# (the run-history ledger wraps whole /N documents, any N, under its
# own acg-tpu-history/1 index lines)
# /9: the batched multi-RHS tier (acg_tpu.solvers.batched) adds a
# "batch" key inside the stats twin (nrhs, per-RHS iteration/residual/
# converged columns, block-CG iteration totals), a "per_rhs" key inside
# "soak" (per-RHS latency/iteration percentiles), and an "nrhs" manifest
# key that joins the bench-diff case key -- additive, so /1../8
# consumers keep working
# /10: the communication observatory (acg_tpu.commbench) adds a
# "calibration" manifest key (the active acg-tpu-commbench/1
# calibration id, or "uncalibrated") that joins the bench-diff case
# key, "segments"/"calibration" keys inside the costmodel: stats
# section (measured SpMV/halo/reduction decomposition), and a
# "calibration" key on the convergence-log meta line -- additive, so
# /1../9 consumers keep working
# /11: the matrix-free operator tier (acg_tpu.ops.operator) adds an
# "operator" manifest key (the operator identity string, e.g.
# "stencil:poisson2d:2048", present only when --operator is armed) that
# joins the bench-diff case key (perfmodel._operator_keyed), and
# "operator"/"matrix_free"/"matrix_bytes_per_spmv" keys inside the comm
# ledger of matrix-free dist solves -- additive, so /1../10 consumers
# keep working
# /12: the decision observatory (acg_tpu.planner) adds a "plan" key
# inside the stats twin (plan id, decision provenance planned/
# flag-forced/fallback, the plan-vs-actual row: predicted vs measured
# s/solve + iterations, misprediction ratio, and the (matrix, mesh,
# calibration) self-correction key) and the calibration-mismatch event
# kind -- additive, so /1../11 consumers keep working
STATS_SCHEMA = "acg-tpu-stats/12"
CONVERGENCE_SCHEMA = "acg-tpu-convergence/1"
# default ring capacity (--telemetry-window): 512 iterations x 4 scalars
# is 8 KiB of f32 carry -- negligible against any solve's vectors, and
# deep enough to show the drift window leading into a breakdown
DEFAULT_WINDOW = 512
TRACE_FIELDS = ("rnrm2", "alpha", "beta", "pAp")
# the optional 5th ring column (the numerical-health tier's in-loop
# true-residual audit, acg_tpu.health): relative gap on audited
# iterations, NaN elsewhere.  Declared through the trace's "fields"
# list so readers never misalign mixed audited/unaudited windows
AUDIT_FIELD = "gap"
# a rank whose solve time exceeds this multiple of the median gets the
# straggler callout in the cross-rank report
STRAGGLER_RATIO = 1.2


# -- device-side ring buffer (inside jit; capacity is static) -----------

def ring_init(capacity: int, dtype, audit: bool = False):
    """The carried ring buffer: ``(capacity, 4)`` slots of
    ``(rnrm2sqr, alpha, beta, pAp)``, NaN-initialised so unwritten
    slots are detectable host-side.  ``audit`` (the numerical-health
    tier) grows a 5th ``gap`` column for the in-loop true-residual
    audit; without it the layout is byte-identical to every pre-/5
    ring."""
    import jax.numpy as jnp

    width = len(TRACE_FIELDS) + (1 if audit else 0)
    return jnp.full((max(int(capacity), 1), width), jnp.nan, dtype=dtype)


def ring_record(buf, k, rnrm2sqr, alpha, beta, pAp, audit=None):
    """Write iteration ``k``'s scalars into slot ``k % capacity``.
    One dynamic_update_slice per iteration -- the documented price of
    telemetry-on (every extra loop-carried array costs; see the
    recurrence.classic_recurrence carry notes); disarmed programs
    compile without any of this.  ``audit`` fills the optional gap column (a ring built
    with ``audit=True`` only)."""
    import jax
    import jax.numpy as jnp

    vals = (rnrm2sqr, alpha, beta, pAp)
    if audit is not None:
        vals = vals + (audit,)
    row = jnp.stack([jnp.asarray(v, buf.dtype).reshape(())
                     for v in vals])[None]
    slot = jnp.asarray(k, jnp.int32) % buf.shape[0]
    return jax.lax.dynamic_update_slice(buf, row, (slot, jnp.int32(0)))


def ring_init_batched(capacity: int, nrhs: int, dtype):
    """The batched tier's carried ring: ``(capacity, nrhs)`` slots of
    per-RHS ``||r_j||^2`` columns, NaN-initialised like the classic
    ring.  Scalars (alpha/beta/pAp) are per-RHS vectors in the batched
    recurrences, so the ring records the one column every consumer
    needs -- the residual fan -- instead of 4*nrhs columns nobody
    reads."""
    import jax.numpy as jnp

    return jnp.full((max(int(capacity), 1), max(int(nrhs), 1)), jnp.nan,
                    dtype=dtype)


def ring_record_batched(buf, k, rnrm2sqr_cols):
    """Write iteration ``k``'s per-RHS squared residuals into slot
    ``k % capacity`` (the batched twin of :func:`ring_record`)."""
    import jax
    import jax.numpy as jnp

    row = jnp.asarray(rnrm2sqr_cols, buf.dtype).reshape(1, -1)
    slot = jnp.asarray(k, jnp.int32) % buf.shape[0]
    return jax.lax.dynamic_update_slice(buf, row, (slot, jnp.int32(0)))


def heartbeat(k, rnrm2sqr, every: int, leader=None, what: str = "cg"):
    """In-loop progress heartbeat: every ``every`` iterations, a host
    callback writes the residual to STDERR (stdout belongs to the
    solution vector).  ``leader`` (a traced bool) gates the emit to one
    shard under shard_map so a mesh prints once, not once per part."""
    if not every:
        return
    import jax
    import jax.numpy as jnp

    def emit(kk, g):
        # the live-observatory tier derives iterations/sec and the ETA
        # from the same samples the status endpoint serves -- one line
        # shape for every tier (observatory.heartbeat_line)
        from acg_tpu import observatory
        sys.stderr.write(observatory.heartbeat_line(
            what, int(kk) + 1,
            math.sqrt(max(float(g), 0.0))) + "\n")
        sys.stderr.flush()

    fire = (jnp.asarray(k, jnp.int32) + 1) % jnp.int32(every) == 0
    if leader is not None:
        fire = fire & leader
    jax.lax.cond(fire,
                 lambda kk, g: jax.debug.callback(emit, kk, g),
                 lambda kk, g: None, k, rnrm2sqr)


# -- host-side trace representation -------------------------------------

@dataclasses.dataclass
class ConvergenceTrace:
    """The host view of one solve attempt's in-loop telemetry.

    ``records`` is ``(m, 4)`` float64 ``(rnrm2, alpha, beta, pAp)`` --
    note rnrm2 is the NORM (the square root is applied here, once,
    instead of per-iteration on device) -- and ``iterations`` the
    0-based iteration index of each row, contiguous and ascending.
    ``wrapped`` marks a ring that overwrote its oldest rows: only the
    trailing ``capacity`` iterations survive (truncation, marked in the
    JSONL meta record).  ``fields`` names the record columns -- rings
    carrying the numerical-health audit column append ``"gap"``
    (relative true-residual gap on audited iterations, NaN elsewhere),
    and the JSONL meta line carries the same list so mixed
    audited/unaudited windows round-trip without misaligned fields."""

    capacity: int
    niterations: int
    records: np.ndarray
    iterations: np.ndarray
    wrapped: bool
    solver: str = "cg"
    fields: tuple = TRACE_FIELDS
    # extra meta-line keys (additive; e.g. the active commbench
    # calibration id the CLI stamps on the JSONL meta record)
    meta_extra: dict = dataclasses.field(default_factory=dict)

    @classmethod
    def from_ring(cls, buf, niterations: int, solver: str = "cg",
                  already_norm: bool = False,
                  offset: int = 0) -> "ConvergenceTrace":
        """Un-rotate a fetched ring buffer: slot ``k % capacity`` holds
        iteration ``k``, so the surviving window is iterations
        ``[max(0, n - capacity), n)``.  The column names come from the
        ring's width (4 = the classic tuple, 5 = + the audit column).
        ``offset`` (the checkpoint chunk drivers) renumbers the window
        to TRAJECTORY iterations: the ring held chunk-local indices,
        and iterations before the chunk are marked truncated exactly
        like a wrapped ring's."""
        buf = np.asarray(buf, dtype=np.float64)
        cap = int(buf.shape[0])
        fields = tuple(TRACE_FIELDS) + (
            (AUDIT_FIELD,) if buf.shape[1] > len(TRACE_FIELDS) else ())
        n = int(niterations)
        off = int(offset)
        m = min(n, cap)
        its = np.arange(n - m, n, dtype=np.int64)
        rows = buf[its % cap] if m else buf[:0]
        rows = np.array(rows, copy=True)
        if m and not already_norm:
            # stored squared (saves the per-iteration device sqrt);
            # NaN/Inf propagate through sqrt unchanged, and a poisoned
            # negative "norm" must stay visibly wrong, not become NaN
            g = rows[:, 0]
            rows[:, 0] = np.where(g >= 0, np.sqrt(np.abs(g)), g)
        return cls(capacity=cap, niterations=n + off, records=rows,
                   iterations=its + off, wrapped=n > cap or off > 0,
                   solver=solver, fields=fields)

    @property
    def first_iteration(self) -> int:
        return int(self.iterations[0]) if self.iterations.size else 0

    def to_dict(self) -> dict:
        """JSON-able form (the ``trace`` key of
        :meth:`SolverStats.to_dict`); record dicts are identical to the
        JSONL data lines, so the two sinks round-trip."""
        return {
            "schema": CONVERGENCE_SCHEMA,
            "solver": self.solver,
            "capacity": self.capacity,
            "niterations": self.niterations,
            "first_iteration": self.first_iteration,
            "wrapped": self.wrapped,
            "fields": list(self.fields),
            **dict(self.meta_extra),
            "records": [self.record_dict(i)
                        for i in range(self.iterations.size)],
        }

    def record_dict(self, i: int) -> dict:
        rec = {"it": int(self.iterations[i])}
        for j, f in enumerate(self.fields):
            rec[f] = _json_float(self.records[i, j])
        return rec

    def write_jsonl(self, f) -> None:
        """One meta line (wrap/truncation marked), then one record per
        surviving iteration."""
        own = isinstance(f, (str, bytes)) or hasattr(f, "__fspath__")
        out = open(f, "w") if own else f
        try:
            meta = self.to_dict()
            records = meta.pop("records")
            meta = {"meta": True, **meta}
            if self.wrapped:
                meta["truncated_before"] = self.first_iteration
            out.write(json.dumps(meta) + "\n")
            for rec in records:
                out.write(json.dumps(rec) + "\n")
        finally:
            if own:
                out.close()

    def tail_summary(self, n: int = 5) -> str:
        """The trailing residual window as one human line -- what the
        recovery driver logs next to a breakdown/restart event.  When
        the audit column is present each audited entry carries its gap
        inline, and the line says so -- a reader of a mixed window must
        never mistake audit gaps for residuals."""
        m = min(int(n), self.iterations.size)
        if not m:
            return "trailing residual window: (empty)"
        audited = AUDIT_FIELD in self.fields
        gi = self.fields.index(AUDIT_FIELD) if audited else None
        parts = []
        for i in range(m):
            row = self.records[-m + i]
            s = f"it {int(self.iterations[-m + i])}: {row[0]:.3e}"
            if audited and math.isfinite(row[gi]):
                s += f" (gap {row[gi]:.3e})"
            parts.append(s)
        line = "trailing residual window: " + ", ".join(parts)
        if audited:
            line += " [audit gap column present]"
        return line


@dataclasses.dataclass
class BatchedConvergenceTrace:
    """Host view of a batched solve's per-RHS residual ring.

    ``records`` is ``(m, nrhs)`` float64 of per-RHS residual NORMS
    (sqrt applied here, once); ``iterations`` the 0-based iteration of
    each row.  The JSONL form declares ``nrhs`` in its meta line and
    each data record carries the full residual column plus the
    worst-RHS value, so :mod:`scripts/plot_convergence` can render the
    residual fan and ascii consumers can fall back to the worst RHS."""

    capacity: int
    niterations: int
    nrhs: int
    records: np.ndarray
    iterations: np.ndarray
    wrapped: bool
    solver: str = "cg-batched"
    # extra meta-line keys (the ConvergenceTrace convention)
    meta_extra: dict = dataclasses.field(default_factory=dict)

    @classmethod
    def from_ring(cls, buf, niterations: int,
                  solver: str = "cg-batched",
                  offset: int = 0) -> "BatchedConvergenceTrace":
        buf = np.asarray(buf, dtype=np.float64)
        cap, nrhs = int(buf.shape[0]), int(buf.shape[1])
        n = int(niterations)
        off = int(offset)
        m = min(n, cap)
        its = np.arange(n - m, n, dtype=np.int64)
        rows = np.array(buf[its % cap] if m else buf[:0], copy=True)
        if m:
            rows = np.where(rows >= 0, np.sqrt(np.abs(rows)), rows)
        return cls(capacity=cap, niterations=n + off, nrhs=nrhs,
                   records=rows, iterations=its + off,
                   wrapped=n > cap or off > 0, solver=solver)

    @property
    def first_iteration(self) -> int:
        return int(self.iterations[0]) if self.iterations.size else 0

    def worst_per_iteration(self) -> np.ndarray:
        """(m,) worst-RHS residual per recorded iteration -- what the
        ascii sparkline and the status-trail consumers fall back to."""
        if not self.records.size:
            return self.records.reshape(0)
        return np.nanmax(self.records, axis=1)

    def to_dict(self) -> dict:
        return {
            "schema": CONVERGENCE_SCHEMA,
            "solver": self.solver,
            "capacity": self.capacity,
            "niterations": self.niterations,
            "first_iteration": self.first_iteration,
            "wrapped": self.wrapped,
            "nrhs": self.nrhs,
            "fields": ["rnrm2"],
            **dict(self.meta_extra),
            "records": [self.record_dict(i)
                        for i in range(self.iterations.size)],
        }

    def record_dict(self, i: int) -> dict:
        cols = [_json_float(v) for v in self.records[i]]
        finite = [v for v in self.records[i] if math.isfinite(v)]
        return {"it": int(self.iterations[i]), "rnrm2": cols,
                "worst": _json_float(max(finite) if finite
                                     else float("nan"))}

    def write_jsonl(self, f) -> None:
        own = isinstance(f, (str, bytes)) or hasattr(f, "__fspath__")
        out = open(f, "w") if own else f
        try:
            meta = self.to_dict()
            records = meta.pop("records")
            meta = {"meta": True, **meta}
            if self.wrapped:
                meta["truncated_before"] = self.first_iteration
            out.write(json.dumps(meta) + "\n")
            for rec in records:
                out.write(json.dumps(rec) + "\n")
        finally:
            if own:
                out.close()

    def tail_summary(self, n: int = 5) -> str:
        worst = self.worst_per_iteration()
        m = min(int(n), self.iterations.size)
        if not m:
            return "trailing residual window: (empty)"
        parts = [f"it {int(self.iterations[-m + i])}: "
                 f"{worst[-m + i]:.3e} (worst of {self.nrhs})"
                 for i in range(m)]
        return "trailing residual window: " + ", ".join(parts)


class EagerTraceRecorder:
    """The eager twin of the device ring for the host solver: same
    capacity/wrap semantics, recorded per iteration in plain Python.
    ``audit=True`` mirrors the health tier's 5-column ring (gap column,
    NaN on unaudited iterations)."""

    def __init__(self, capacity: int, solver: str = "host-cg",
                 audit: bool = False):
        self.capacity = max(int(capacity), 1)
        self.solver = solver
        self.audit = bool(audit)
        self._rows: list = [None] * self.capacity
        self._n = 0

    def record(self, rnrm2: float, alpha: float, beta: float,
               pAp: float, gap: float = math.nan) -> None:
        row = (float(rnrm2), float(alpha), float(beta), float(pAp))
        if self.audit:
            row = row + (float(gap),)
        self._rows[self._n % self.capacity] = row
        self._n += 1

    def finish(self) -> ConvergenceTrace:
        n, cap = self._n, self.capacity
        width = len(TRACE_FIELDS) + (1 if self.audit else 0)
        fields = tuple(TRACE_FIELDS) + ((AUDIT_FIELD,) if self.audit
                                        else ())
        m = min(n, cap)
        its = np.arange(n - m, n, dtype=np.int64)
        rows = np.asarray([self._rows[k % cap] for k in its],
                          dtype=np.float64).reshape(m, width)
        return ConvergenceTrace(capacity=cap, niterations=n, records=rows,
                                iterations=its, wrapped=n > cap,
                                solver=self.solver, fields=fields)


def read_convergence_log(path) -> tuple[dict, list[dict]]:
    """Parse a ``--convergence-log`` JSONL file back into
    ``(meta, records)`` -- the inverse of :meth:`write_jsonl`, shared by
    the tests and ``scripts/plot_convergence.py``.

    A TRUNCATED TRAILING line (a SIGTERM/OOM-kill landing mid-write --
    exactly the runs whose telemetry matters most) yields the parseable
    prefix with ``meta["truncated"] = True`` instead of raising; a
    malformed line with valid JSON after it is still an error (that is
    corruption, not truncation)."""
    meta: dict = {}
    records: list[dict] = []
    with open(path) as f:
        lines = f.read().split("\n")
    for i, line in enumerate(lines):
        line = line.strip()
        if not line:
            continue
        try:
            obj = json.loads(line)
        except ValueError:
            if any(later.strip() for later in lines[i + 1:]):
                raise
            meta["truncated"] = True
            break
        if obj.get("meta"):
            meta = obj
        else:
            records.append(obj)
    return meta, records


def _json_float(v) -> float | str:
    """JSON has no NaN/Inf literal; poisoned telemetry values must
    survive the round trip as strings, not crash the writer."""
    v = float(v)
    if math.isfinite(v):
        return v
    return repr(v)


# -- phase timing + trace annotations -----------------------------------

class PhaseTimer:
    """Wall-clock seconds per pipeline phase (ingest -> partition ->
    transfer -> compile -> solve -> writeback), accumulated across
    retries.  :meth:`phase` also opens a ``jax.profiler.
    TraceAnnotation`` bracket so the same names navigate ``--trace``
    Perfetto output."""

    def __init__(self):
        self.phases: dict[str, float] = {}

    def add(self, name: str, seconds: float) -> None:
        self.phases[name] = self.phases.get(name, 0.0) + float(seconds)
        # service-metrics tier: phase-time histogram (no-op disarmed)
        from acg_tpu import metrics, tracing
        metrics.record_phase(name, seconds)
        # timeline tier: the same phase as a wall-clock span (--timeline;
        # no-op disarmed)
        tracing.record_phase_span(name, seconds)

    @contextlib.contextmanager
    def phase(self, name: str):
        with annotate(name):
            t0 = time.perf_counter()
            try:
                yield
            finally:
                self.add(name, time.perf_counter() - t0)

    def merge_into(self, timings: dict) -> dict:
        """Fold these phases into a stats ``timings`` dict, re-ordered
        so the canonical pipeline order survives whichever side recorded
        first.  CONSUMES the timer's phases (repeated folds -- e.g. a
        late writeback phase after the stats block printed -- accumulate
        instead of double-counting)."""
        merged = dict(timings)
        for k, v in self.phases.items():
            merged[k] = merged.get(k, 0.0) + v
        self.phases.clear()
        ordered = {k: merged[k] for k in PHASE_ORDER if k in merged}
        ordered.update({k: v for k, v in merged.items()
                        if k not in ordered})
        timings.clear()
        timings.update(ordered)
        return timings


def span(name: str):
    """``jax.profiler.TraceAnnotation("acg:<name>")``: a host span on the
    profiler's own clock, beside the device's op events; a cheap no-op
    when no trace is being collected, and tolerant of backends without
    profiler support.  Pipeline phases go through :func:`annotate`; a
    solve's sub-steps (``prepare``, ``dispatch``, ``wait``, ``fetch``,
    ``check``, ``account``) come here directly, so they name the host's
    work in a capture without moving the status document's phase.

    The device side has its own vocabulary: ``jax.named_scope("acg.<
    layer>")`` (``setup``, ``spmv``, ``halo``, ``dot``, ``update``) in
    the solve programs, which lands in each compiled op's ``op_name``."""
    try:
        import jax

        return jax.profiler.TraceAnnotation(f"acg:{name}")
    except Exception:  # noqa: BLE001 -- telemetry must never sink a solve
        return contextlib.nullcontext()


@contextlib.contextmanager
def annotate(name: str):
    """A pipeline phase: :func:`span` ``acg:<name>``, and the
    live-observatory status document's current-phase field (no-op
    disarmed) -- every pipeline phase passes through here."""
    from acg_tpu import observatory
    observatory.note_phase(name)
    with span(name):
        yield


def add_timing(stats, name: str, seconds: float) -> None:
    """Accumulate one phase's seconds onto ``stats.timings``."""
    stats.timings[name] = stats.timings.get(name, 0.0) + float(seconds)
    from acg_tpu import metrics, tracing
    metrics.record_phase(name, seconds)
    tracing.record_phase_span(name, seconds)


def record_event(stats, kind: str, detail: str) -> None:
    """Append one timestamped event (resilience, fault injection) for
    the structured sink; the human-readable ``recovery_log`` is separate
    and unchanged.  Every event also bumps the service-metrics
    by-kind counter (``acg_events_total``; no-op disarmed) and lands as
    an instant on the ``--timeline`` span timeline (no-op disarmed)."""
    stats.events.append({"t": time.time(), "kind": kind,
                         "detail": str(detail)})
    from acg_tpu import metrics, observatory, tracing
    metrics.record_event_kind(kind)
    tracing.record_instant(kind, detail=str(detail))
    # live-observatory tier: the status document serves the last K
    # structured events (no-op disarmed)
    observatory.note_event(kind, str(detail))


# -- structured stats sink ----------------------------------------------

def run_manifest(**extra) -> dict:
    """The run manifest of a ``--stats-json`` document: everything
    needed to interpret the numbers without the launching shell --
    backend, device/mesh shape, jax/jaxlib versions, process layout --
    plus caller-supplied keys (matrix id, solver/kernel/comm choices,
    partition and halo sizes)."""
    man: dict = {"schema": STATS_SCHEMA,
                 "unix_time": time.time()}
    try:
        import jax
        import jaxlib

        man["jax"] = jax.__version__
        man["jaxlib"] = jaxlib.__version__
        man["process_index"] = jax.process_index()
        man["process_count"] = jax.process_count()
        devs = jax.devices()
        man["backend"] = {"platform": devs[0].platform,
                          "device_kind": devs[0].device_kind,
                          "ndevices": len(devs)}
    except Exception as e:  # noqa: BLE001 -- manifest must not sink output
        man["backend"] = f"unavailable ({type(e).__name__})"
    try:
        from acg_tpu import __version__

        man["acg_tpu"] = __version__
    except Exception:  # noqa: BLE001
        pass
    man.update({k: v for k, v in extra.items() if v is not None})
    return man


def stats_document(stats, manifest: dict | None = None,
                   ranks: dict | None = None) -> dict:
    """The full ``--stats-json`` document: schema + manifest + the
    machine-readable twin of ``fwrite`` (+ cross-rank aggregation when
    gathered; + the service-metrics registry snapshot when that layer
    is armed -- the /3 additive key)."""
    doc = {"schema": STATS_SCHEMA,
           "manifest": manifest or run_manifest(),
           "stats": stats.to_dict()}
    if ranks is not None:
        doc["ranks"] = ranks
    from acg_tpu import metrics
    if metrics.armed():
        doc["metrics"] = metrics.snapshot_dict()
    return doc


def write_stats_json(path, stats, manifest: dict | None = None,
                     ranks: dict | None = None,
                     append: bool = False) -> dict:
    """Write (or with ``append``, JSONL-append -- the bench writer) the
    structured stats document.  Returns the document."""
    doc = stats_document(stats, manifest=manifest, ranks=ranks)
    own = isinstance(path, (str, bytes)) or hasattr(path, "__fspath__")
    f = open(path, "a" if append else "w") if own else path
    try:
        json.dump(doc, f, indent=None if append else 2, sort_keys=False,
                  default=str)
        f.write("\n")
    finally:
        if own:
            f.close()
    return doc


# -- cross-rank aggregation ---------------------------------------------

def rank_payload(solver) -> dict:
    """This controller's contribution to the cross-rank report: solve
    time, iteration count, and per-OWNED-part size/imbalance inputs
    (rows, nnz, halo send bytes) where a partitioned problem exists."""
    import jax

    st = solver.stats
    payload = {"process": int(jax.process_index()),
               "tsolve": float(st.tsolve),
               "niterations": int(st.niterations)}
    prob = getattr(solver, "problem", None)
    if prob is not None:
        dbl = int(np.dtype(prob.vdtype).itemsize)
        parts = []
        owned = (range(prob.nparts) if prob.owned_parts is None
                 else prob.owned_parts)
        for p in owned:
            s = prob.subs[p]
            if s is None or getattr(s, "A_local", None) is None:
                continue
            halo = getattr(s, "halo", None)
            parts.append({
                "part": int(p),
                "rows": int(s.nowned),
                "nnz": int(s.A_local.nnz
                           + (s.A_ghost.nnz if s.A_ghost is not None
                              else 0)),
                "halo_send_bytes": int(halo.total_send * dbl
                                       if halo is not None else 0),
            })
        payload["parts"] = parts
    return payload


def gather_rank_stats(payload: dict, timeout: float = 120.0
                      ) -> list[dict] | None:
    """Allgather each controller's payload dict (erragree KV plumbing;
    see :func:`acg_tpu.parallel.erragree.allgather_blobs`).  Every
    controller must call this at the same point.  Returns one dict per
    process, or None when the gather is unavailable."""
    import jax

    if jax.process_count() == 1:
        return [payload]
    from acg_tpu.parallel.erragree import allgather_blobs

    try:
        blobs = allgather_blobs(json.dumps(payload, default=str),
                                tag="telemetry", timeout=timeout)
    except Exception as e:  # noqa: BLE001 -- aggregation is best-effort:
        # a failed gather must not take down a solve that succeeded
        sys.stderr.write(f"acg-tpu: cross-rank stats gather failed "
                         f"({type(e).__name__}); skipping aggregation\n")
        return None
    return [json.loads(b) for b in blobs]


def aggregate_ranks(payloads: list[dict]) -> dict:
    """min/median/max solve time, per-part rows/nnz/halo-bytes imbalance
    (max over mean), and the straggler callout -- the evidence the
    communication-reduced-variant literature asks for, per pod."""
    ts = sorted((float(p.get("tsolve", 0.0)), int(p.get("process", i)))
                for i, p in enumerate(payloads))
    times = [t for t, _ in ts]
    med = float(np.median(times)) if times else 0.0
    agg: dict = {
        "processes": len(payloads),
        "solve_time": {"min": times[0] if times else 0.0,
                       "median": med,
                       "max": times[-1] if times else 0.0},
    }
    parts = [pt for p in payloads for pt in p.get("parts", [])]
    if parts:
        imb = {}
        for key in ("rows", "nnz", "halo_send_bytes"):
            vals = np.asarray([pt.get(key, 0) for pt in parts],
                              dtype=np.float64)
            mean = float(vals.mean()) if vals.size else 0.0
            imb[key] = {"max": float(vals.max(initial=0.0)),
                        "mean": mean,
                        "imbalance": (float(vals.max(initial=0.0) / mean)
                                      if mean > 0 else 1.0)}
        agg["parts"] = {"count": len(parts), "imbalance": imb}
    straggler = None
    if times and med > 0 and times[-1] > STRAGGLER_RATIO * med:
        straggler = {"process": ts[-1][1], "tsolve": times[-1],
                     "ratio_to_median": times[-1] / med}
    agg["straggler"] = straggler
    return agg


def format_rank_report(agg: dict) -> str:
    """One stderr line from the primary summarising the aggregation."""
    st = agg["solve_time"]
    line = (f"cross-rank: {agg['processes']} processes, solve time "
            f"min/median/max {st['min']:.6f}/{st['median']:.6f}/"
            f"{st['max']:.6f} s")
    parts = agg.get("parts")
    if parts:
        imb = parts["imbalance"]
        line += (f"; imbalance (max/mean) rows {imb['rows']['imbalance']:.2f}"
                 f" nnz {imb['nnz']['imbalance']:.2f}"
                 f" halo-bytes {imb['halo_send_bytes']['imbalance']:.2f}")
    s = agg.get("straggler")
    if s:
        line += (f"; straggler: process {s['process']} "
                 f"({s['ratio_to_median']:.2f}x median)")
    return line
