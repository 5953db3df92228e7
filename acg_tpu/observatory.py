"""Live solve observatory: in-flight status, run-history ledger, and
SLO burn tracking -- the observability plane a long-lived service
mounts.

Every observability surface so far is POST-HOC: the stats block,
convergence traces, soak percentiles, and timeline exports all land
after the solve exits.  The reference paper's device-initiated solver
is an opaque persistent loop the host cannot watch mid-flight
(PAPER.md), and global-reduction-pipelined variants (arXiv:1905.06850)
make mid-solve stall attribution harder still -- exactly the blindness
a live status plane exists to remove.  Three legs, all DISARMED by
default (the metrics/tracing ``arm()`` design; disarmed programs stay
byte-identical -- every hook here is host-side bookkeeping, pinned in
tests/test_hlo_structure.py and tests/test_observatory.py):

1. **Live in-flight status** (``--status-port P`` / ``--status-file
   F``): a process-wide :class:`SolveStatus` recorder fed from hooks
   the layers already have -- the ``--progress`` heartbeat, the
   checkpoint chunk drivers' per-chunk carry returns (real
   iteration/residual samples mid-solve), the soak driver's per-solve
   indices, and resilience/health/checkpoint events -- served as a
   JSON document (schema ``acg-tpu-status/1``) over a stdlib
   daemon-thread HTTP endpoint (the ``--metrics-port`` design; the
   status server also answers ``/metrics`` so one port can serve
   both).  The document carries phase, iteration, residual-trail
   sparkline data, iterations/sec, an ETA projected from the
   numerical-health tier's Lanczos kappa CG-bound (falling back to the
   measured residual-decay rate, then the iteration cap), per-part
   imbalance, the last K structured events, and soak progress.
2. **Run-history ledger** (``--history DIR``): every solve appends its
   ``--stats-json`` document to a date-partitioned JSONL ledger, one
   index line per solve (matrix id, tier, precond, dtype, latency,
   iterations, schema version) carrying the full document under its
   ``doc`` key.  ``scripts/history_report.py`` renders per-case trend
   tables and ``perfmodel.check_regression`` /
   ``scripts/bench_diff.py`` accept a ledger directory as the
   baseline, picking the best-known USABLE prior capture and skipping
   ``bench_backend_unavailable`` entries (captures that never reached
   hardware).
3. **SLO tracking** (``--slo latency=S,iters=N,gap=G``): declared
   objectives become ``acg_slo_target`` / ``acg_slo_breaches_total`` /
   ``acg_slo_burn_ratio`` families on the existing Prometheus
   registry, breaches emit structured events into the
   telemetry/timeline stream, and ``--fail-on-slo`` gates the exit
   code (:data:`SLO_EXIT_CODE`) like the soak drift gate.
"""

from __future__ import annotations

import collections
import dataclasses
import json
import math
import os
import sys
import threading
import time

__all__ = [
    "STATUS_SCHEMA", "HISTORY_SCHEMA", "SLO_EXIT_CODE",
    "SolveStatus", "STATUS", "arm", "disarm", "armed", "shutdown",
    "begin_solve", "end_solve", "note_chunk", "note_event",
    "note_imbalance", "note_kappa", "note_soak_solve", "note_solver",
    "progress_sample", "heartbeat_line",
    "serve_status", "set_status_file", "flush_status", "status_document",
    "history_append", "history_scan", "load_history_baseline",
    "SloSpec", "parse_slo", "install_slo", "installed_slo",
    "slo_observe", "slo_report", "slo_breached", "attach_slo",
]

STATUS_SCHEMA = "acg-tpu-status/1"
HISTORY_SCHEMA = "acg-tpu-history/1"
# residual-trail samples the status document serves (sparkline data);
# also the window the measured-rate ETA is fit over
TRAIL_CAPACITY = 64
# last K structured events mirrored into the status document
EVENT_CAPACITY = 16
# minimum seconds between --status-file rewrites: heartbeats can fire
# thousands of times per second on a tiny solve, and the file sink must
# not turn the observability plane into an I/O workload
STATUS_FILE_INTERVAL = 0.2
# CLI exit code for a tripped --fail-on-slo gate (the process-wide
# contract lives in errors.ExitCode; --buildinfo renders the table)
from acg_tpu.errors import ExitCode as _ExitCode

SLO_EXIT_CODE = int(_ExitCode.SLO_BREACH)


def _finite(v) -> float | None:
    try:
        v = float(v)
    except (TypeError, ValueError):
        return None
    return v if math.isfinite(v) else None


class SolveStatus:
    """The process-wide in-flight status recorder.

    Thread-safe (one lock; the HTTP serving thread and the solving
    thread share it); every mutator is a cheap early-return while the
    layer is disarmed, and all recording is host-side bookkeeping --
    arming cannot perturb the compiled solver programs."""

    def __init__(self):
        self._lock = threading.RLock()
        self._reset_locked()

    def _reset_locked(self) -> None:
        self.phase: str | None = None
        self.solve: dict = {}
        self.trail: collections.deque = collections.deque(
            maxlen=TRAIL_CAPACITY)
        self.events: collections.deque = collections.deque(
            maxlen=EVENT_CAPACITY)
        self.imbalance: dict | None = None
        self.soak: dict | None = None
        self.kappa: dict | None = None
        self.degraded: dict | None = None
        self.solves_completed = 0
        self.armed_since: float | None = None

    def reset(self) -> None:
        with self._lock:
            self._reset_locked()

    # -- feeding --------------------------------------------------------

    def begin(self, what: str, maxits: int, rtol: float = 0.0,
              atol: float = 0.0, matrix=None, nparts: int = 1) -> None:
        with self._lock:
            self.trail.clear()
            self.solve = {
                "what": str(what),
                "active": True,
                "iteration": 0,
                "residual": None,
                "maxits": int(maxits),
                "rtol": float(rtol),
                "atol": float(atol),
                "target": None,
                "matrix": (str(matrix) if matrix is not None else None),
                "nparts": int(nparts),
                "started_unix": time.time(),
            }

    def sample(self, what: str, iteration: int, residual) -> None:
        """One in-flight (iteration, residual) observation -- from the
        heartbeat callback or a checkpoint chunk boundary."""
        with self._lock:
            it = int(iteration)
            if self.trail and it < self.trail[-1][1]:
                # iteration went backwards: a new solve (or a rollback)
                # started -- a rate fit across the seam would be
                # nonsense, so the trail restarts
                self.trail.clear()
            self.trail.append((time.time(), it, _finite(residual)))
            if not self.solve:
                self.solve = {"what": str(what), "maxits": 0,
                              "rtol": 0.0, "atol": 0.0, "target": None,
                              "started_unix": time.time()}
            self.solve["active"] = True
            self.solve["iteration"] = it
            self.solve["residual"] = _finite(residual)

    def finish(self, converged: bool, iterations: int,
               seconds: float) -> None:
        with self._lock:
            if self.solve:
                self.solve["active"] = False
                self.solve["converged"] = bool(converged)
                self.solve["iteration"] = int(iterations)
                self.solve["seconds"] = float(seconds)
            self.solves_completed += 1

    def note_target(self, abs_tol) -> None:
        with self._lock:
            if self.solve:
                self.solve["target"] = _finite(abs_tol)

    def note_latency(self, seconds: float) -> None:
        with self._lock:
            if self.solve:
                self.solve["seconds"] = float(seconds)

    def note_batch(self, nrhs: int, residuals, converged) -> None:
        """Per-RHS evidence of a batched solve (acg_tpu.solvers.
        batched): the status document's ``solve.batch`` block names
        the SLOWEST unconverged RHS -- the column the ETA is keyed to,
        since the batched loop runs exactly until it converges."""
        with self._lock:
            if not self.solve:
                self.solve = {"what": "batched", "maxits": 0,
                              "rtol": 0.0, "atol": 0.0, "target": None,
                              "started_unix": time.time()}
            res = [_finite(r) for r in residuals]
            conv = [bool(c) for c in converged]
            unconv = [i for i, c in enumerate(conv) if not c]
            pool = unconv if unconv else list(range(len(res)))
            slowest = max(pool, key=lambda i: (res[i]
                                               if res[i] is not None
                                               else float("inf"))) \
                if pool else 0
            self.solve["batch"] = {
                "nrhs": int(nrhs),
                "unconverged": len(unconv),
                "slowest_rhs": int(slowest),
                "slowest_residual": res[slowest] if res else None,
                "residuals": res,
            }

    def note_phase(self, name: str) -> None:
        with self._lock:
            self.phase = str(name)

    def note_event(self, kind: str, detail: str) -> None:
        with self._lock:
            self.events.append({"t": time.time(), "kind": str(kind),
                                "detail": str(detail)})

    def note_imbalance(self, imbalance: dict) -> None:
        with self._lock:
            self.imbalance = dict(imbalance)

    def note_soak(self, i: int, nsolves: int) -> None:
        with self._lock:
            self.soak = {"solve": int(i), "nsolves": int(nsolves)}

    def note_degraded(self, frm, to, reason: str) -> None:
        """The supervisor relaunched this process on a SHRUNKEN mesh:
        the status document must say so (``degraded: {from, to,
        reason}``) -- a poller watching a degraded solve should not
        mistake it for the full-capacity run."""
        with self._lock:
            self.degraded = {"from": int(frm), "to": int(to),
                             "reason": str(reason)}

    def note_kappa(self, kappa, predicted_total=None) -> None:
        k = _finite(kappa)
        if k is None or k <= 0:
            return
        with self._lock:
            self.kappa = {"kappa": k}
            if predicted_total:
                self.kappa["predicted_iterations"] = int(predicted_total)

    # -- deriving -------------------------------------------------------

    def rates(self) -> tuple[float | None, float | None, str | None]:
        """``(iterations_per_second, eta_seconds, eta_source)`` from
        the current trail.  The remaining-iterations estimate prefers
        the Lanczos kappa CG-bound (the numerical-health tier's
        predicted total), falls back to the measured residual-decay
        rate toward the absolute target, then to the iteration cap."""
        with self._lock:
            trail = list(self.trail)
            solve = dict(self.solve)
            kap = dict(self.kappa) if self.kappa else {}
        ips = None
        if len(trail) >= 2:
            t0, k0, _ = trail[0]
            t1, k1, _ = trail[-1]
            if t1 > t0 and k1 > k0:
                ips = (k1 - k0) / (t1 - t0)
        k = int(solve.get("iteration") or (trail[-1][1] if trail else 0))
        remaining = source = None
        pred = kap.get("predicted_iterations")
        if pred and pred > k:
            remaining, source = pred - k, "kappa-bound"
        if remaining is None:
            remaining, source = self._decay_remaining(trail, solve)
        if remaining is None:
            maxits = int(solve.get("maxits") or 0)
            if maxits > k:
                remaining, source = maxits - k, "iteration-cap"
        eta = (remaining / ips) if (ips and remaining is not None) \
            else None
        return ips, eta, (source if eta is not None else None)

    @staticmethod
    def _decay_remaining(trail, solve):
        """Iterations left to reach the absolute residual target at the
        measured log-residual decay rate over the trail window."""
        target = _finite(solve.get("target"))
        if not target or target <= 0 or len(trail) < 2:
            return None, None
        pts = [(k, r) for _, k, r in trail if r is not None and r > 0]
        if len(pts) < 2:
            return None, None
        (k0, r0), (k1, r1) = pts[0], pts[-1]
        if k1 <= k0 or r1 >= r0:
            return None, None   # not converging over this window
        if r1 <= target:
            return 0, "measured-rate"
        decay = (math.log(r1) - math.log(r0)) / (k1 - k0)   # < 0
        rem = int(math.ceil(math.log(target / r1) / decay))
        return max(rem, 0), "measured-rate"

    def document(self) -> dict:
        """The ``acg-tpu-status/1`` JSON document served to pollers."""
        ips, eta, source = self.rates()
        with self._lock:
            solve = dict(self.solve)
            doc: dict = {
                "schema": STATUS_SCHEMA,
                "unix_time": time.time(),
                "pid": os.getpid(),
                "armed_since": self.armed_since,
                "phase": self.phase,
                "solves_completed": self.solves_completed,
                "residual_trail": [[k, r] for _, k, r in self.trail],
            }
            if solve:
                solve["iterations_per_second"] = ips
                solve["eta_seconds"] = eta
                solve["eta_source"] = source
                if solve.get("started_unix"):
                    solve["elapsed_seconds"] = (time.time()
                                                - solve["started_unix"])
                doc["solve"] = solve
            if self.kappa:
                doc["kappa"] = dict(self.kappa)
            if self.imbalance:
                doc["imbalance"] = dict(self.imbalance)
            if self.soak:
                doc["soak"] = dict(self.soak)
            if self.degraded:
                doc["degraded"] = dict(self.degraded)
            if self.events:
                doc["events"] = list(self.events)
        rep = slo_report()
        if rep:
            doc["slo"] = rep
        peers = _peers_block()
        if peers is not None:
            doc["peers"] = peers
        return doc


STATUS = SolveStatus()

# the erragree DeadlineHeartbeat this run started (--heartbeat with a
# status plane armed): the status document's peers: block reads its
# per-peer beat ages.  Duck-typed -- anything with peer_ages() and a
# deadline attribute serves (tests use a stub).
_heartbeat = None

# the supervisor tells a relaunched child it runs on a shrunken mesh
# through this env var ("FROM:TO:REASON"); arm() folds it into the
# status document's degraded key
DEGRADED_ENV = "ACG_TPU_DEGRADED"


def set_heartbeat(hb) -> None:
    """Attach the run's dead-peer heartbeat so the status document can
    expose per-peer liveness (``peers:``)."""
    global _heartbeat
    _heartbeat = hb


def _peers_block() -> dict | None:
    hb = _heartbeat
    if hb is None:
        return None
    try:
        ages = hb.peer_ages()
    except Exception:  # noqa: BLE001 -- a torn-down heartbeat must
        return None    # never break a status scrape
    return {
        "deadline_seconds": float(getattr(hb, "deadline", 0.0)),
        "last_beat_age_seconds": {str(q): round(float(a), 3)
                                  for q, a in sorted(ages.items())},
    }


_armed = False
_status_file: str | None = None
_last_flush = 0.0
# one writer at a time: the heartbeat callback thread and the solving
# thread both reach _maybe_flush, and two writers sharing the per-pid
# temp name would interleave INSIDE it -- renaming torn JSON into place
_flush_lock = threading.Lock()


def arm() -> None:
    """Arm the process-wide status recorder.  All recording is
    host-side bookkeeping, so arming cannot perturb the compiled
    programs (the metrics/tracing arm() contract)."""
    global _armed
    _armed = True
    if STATUS.armed_since is None:
        STATUS.armed_since = time.time()
    env = os.environ.get(DEGRADED_ENV)
    if env:
        # a supervisor relaunch on a shrunken mesh announces itself
        try:
            frm, to, reason = env.split(":", 2)
            STATUS.note_degraded(int(frm), int(to), reason)
        except ValueError:
            sys.stderr.write(f"acg-tpu: {DEGRADED_ENV}={env!r} is not "
                             f"FROM:TO:REASON; ignored\n")


def disarm() -> None:
    global _armed
    _armed = False


def armed() -> bool:
    return _armed


def shutdown() -> None:
    """End-of-invocation cleanup (the CLI's finally): a final status
    flush with the solve marked over, then disarm and clear -- an
    in-process caller (tests, library use) must never observe a stale
    run's status or SLO state."""
    global _status_file
    if _armed and _status_file:
        try:
            STATUS.note_phase("exited")
            flush_status(force=True)
        except OSError as e:
            sys.stderr.write(f"acg-tpu: --status-file {_status_file}: "
                             f"{e}\n")
    disarm()
    _status_file = None
    set_heartbeat(None)
    STATUS.reset()
    _clear_slo()


# -- feeding hooks (cheap early-returns while disarmed) -------------------

def begin_solve(what: str, maxits: int, rtol: float = 0.0,
                atol: float = 0.0, matrix=None, nparts: int = 1) -> None:
    """The run header.  Unconditional (the progress_sample contract):
    pure host bookkeeping, and the ``--progress`` heartbeat's ETA
    needs the iteration cap even when no status sink is armed."""
    STATUS.begin(what, maxits, rtol=rtol, atol=atol, matrix=matrix,
                 nparts=nparts)
    _maybe_flush()


def end_solve(converged: bool, iterations: int, seconds: float) -> None:
    """Solve close-out (every solver tail via metrics.record_solve);
    unconditional like begin_solve, so the recorder's active flag and
    solve counter stay truthful whether or not a sink is armed."""
    STATUS.finish(converged, iterations, seconds)
    _maybe_flush()


def note_chunk(what: str, iteration: int, residual, abs_tol=None,
               trace=None, rtol: float = 0.0) -> None:
    """One checkpoint-chunk boundary (the chunk drivers' per-dispatch
    carry return): a REAL mid-solve iteration/residual sample, plus --
    when the telemetry ring rode the chunk -- a Lanczos kappa estimate
    refresh so the ETA can ride the CG bound."""
    if not _armed:
        return
    STATUS.sample(what, iteration, residual)
    if abs_tol is not None:
        STATUS.note_target(abs_tol)
    if trace is not None:
        _kappa_from_trace(trace, rtol or STATUS.solve.get("rtol", 0.0))
    _maybe_flush()


def _kappa_from_trace(trace, rtol) -> None:
    """Refresh the kappa/predicted-iterations estimate from an in-loop
    convergence trace (host-side, a tridiagonal eig of at most the ring
    window -- cheap at chunk cadence; never sinks a solve)."""
    try:
        from acg_tpu.health import predicted_iterations, spectrum_estimate
        est = spectrum_estimate(trace)
        kappa = (est or {}).get("kappa")
        if not kappa:
            return
        STATUS.note_kappa(kappa, predicted_iterations(kappa, rtol))
    except Exception:  # noqa: BLE001 -- observability must never sink
        pass           # the solve it watches


def note_event(kind: str, detail: str) -> None:
    if not _armed:
        return
    STATUS.note_event(kind, detail)
    _maybe_flush()


def note_phase(name: str) -> None:
    if not _armed:
        return
    STATUS.note_phase(name)


def note_imbalance(imbalance: dict) -> None:
    if not _armed:
        return
    STATUS.note_imbalance(imbalance)


def note_kappa(kappa, predicted_total=None) -> None:
    if not _armed:
        return
    STATUS.note_kappa(kappa, predicted_total)


def note_soak_solve(i: int, nsolves: int, latency: float) -> None:
    """One completed soak solve (the soak driver's per-solve tail).
    Only the queue-progress note plus the driver's own timed latency
    (dispatch included -- what a serving fleet experiences): iteration
    counts were already closed out by the solver tail's
    ``metrics.record_solve`` hook."""
    if not _armed:
        return
    STATUS.note_soak(i + 1, nsolves)
    STATUS.note_latency(latency)
    _maybe_flush()


def note_batch(nrhs: int, residuals, converged) -> None:
    """Per-RHS residual/convergence columns of a batched solve (the
    status document's ``solve.batch`` block; the ETA keys to the
    slowest unconverged RHS).  No-op disarmed."""
    if not _armed:
        return
    STATUS.note_batch(nrhs, residuals, converged)
    _maybe_flush()


def note_solver(solver) -> None:
    """Per-part size/imbalance from the telemetry tier's rank payload
    (the PR-2 aggregation), recorded once a partitioned solver exists."""
    if not _armed:
        return
    try:
        from acg_tpu import telemetry
        inner = solver
        while hasattr(inner, "inner"):
            inner = inner.inner
        payload = telemetry.rank_payload(inner)
        agg = telemetry.aggregate_ranks([payload])
        parts = agg.get("parts")
        if parts:
            STATUS.note_imbalance(parts)
    except Exception:  # noqa: BLE001 -- observability must never sink
        pass           # the solve it watches


# -- the heartbeat's numbers ---------------------------------------------

def progress_sample(what: str, iteration: int, residual
                    ) -> tuple[float | None, float | None]:
    """Feed one ``--progress`` heartbeat observation and return
    ``(iterations_per_second, eta_seconds)`` -- the same numbers the
    status endpoint serves.  Records unconditionally (the heartbeat
    only fires when --progress armed it; its rate bookkeeping is what
    makes the line's numbers possible even without a status sink)."""
    STATUS.sample(what, iteration, residual)
    if _armed:
        _maybe_flush()
    ips, eta, _source = STATUS.rates()
    return ips, eta


def _fmt_eta(seconds: float) -> str:
    if seconds >= 3600:
        return f"{seconds / 3600:.1f}h"
    if seconds >= 60:
        return f"{seconds / 60:.1f}m"
    return f"{seconds:.1f}s"


def heartbeat_line(what: str, iteration: int, rnrm2: float) -> str:
    """The ``--progress`` heartbeat line, shared by the compiled loops'
    callback and the host oracle so every tier prints the same shape:
    iteration, residual, and -- once two samples exist -- the measured
    iterations/sec and ETA."""
    ips, eta = progress_sample(what, iteration, rnrm2)
    line = (f"acg-tpu: {what}: iteration {int(iteration)}: "
            f"residual 2-norm {float(rnrm2):.6e}")
    if ips is not None:
        line += f", {ips:,.1f} it/s"
        if eta is not None:
            line += f", ETA {_fmt_eta(eta)}"
    return line


# -- sinks ----------------------------------------------------------------

def status_document() -> dict:
    return STATUS.document()


def set_status_file(path) -> None:
    global _status_file
    _status_file = os.fspath(path)


def flush_status(force: bool = False) -> None:
    """Write the status document to ``--status-file`` with atomic
    rename (a poller never reads torn JSON -- the metrics-textfile
    contract), throttled to :data:`STATUS_FILE_INTERVAL`.  Serialised
    under one lock: the throttle check and the temp-file write must be
    one unit, or two threads passing the check together would
    interleave writes into the shared temp name."""
    global _last_flush
    if _status_file is None:
        return
    with _flush_lock:
        if _status_file is None:
            return
        now = time.monotonic()
        if not force and now - _last_flush < STATUS_FILE_INTERVAL:
            return
        _last_flush = now
        tmp = f"{_status_file}.tmp.{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump(status_document(), f)
            f.write("\n")
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, _status_file)


def _maybe_flush() -> None:
    if _status_file is None:
        return
    try:
        flush_status()
    except OSError:
        pass  # a full disk must not sink the solve it watches


def serve_status(port: int):
    """Serve ``GET /status`` (the acg-tpu-status/1 JSON document) on a
    daemon thread -- the ``--metrics-port`` design.  The handler also
    answers ``/metrics`` with the Prometheus exposition, so one port
    can serve both planes (``--status-port`` == ``--metrics-port`` is
    explicitly supported).  Returns the live server
    (``.server_address[1]`` is the real port; pass 0 to let the OS
    pick, the test hook)."""
    from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

    class _Handler(BaseHTTPRequestHandler):
        def do_GET(self):  # noqa: N802 -- stdlib handler contract
            path = self.path.split("?")[0]
            if path in ("/status", "/"):
                body = json.dumps(status_document()).encode()
                ctype = "application/json"
            elif path == "/metrics":
                from acg_tpu import metrics
                body = metrics.expose().encode()
                ctype = "text/plain; version=0.0.4; charset=utf-8"
            else:
                self.send_error(404)
                return
            self.send_response(200)
            self.send_header("Content-Type", ctype)
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

        def log_message(self, *a):  # pollers must not spam stderr
            pass

    server = ThreadingHTTPServer(("", int(port)), _Handler)
    t = threading.Thread(target=server.serve_forever,
                         name="acg-status", daemon=True)
    t.start()
    return server


# -- SLO tracking ---------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class SloSpec:
    """Declared per-solve objectives (``--slo latency=S,iters=N,gap=G``,
    any subset): solve latency in seconds, iterations-to-converge, and
    the numerical-health audit gap."""

    latency_s: float | None = None
    iters: int | None = None
    gap: float | None = None

    def targets(self) -> dict:
        out = {}
        if self.latency_s is not None:
            out["latency"] = float(self.latency_s)
        if self.iters is not None:
            out["iters"] = float(self.iters)
        if self.gap is not None:
            out["gap"] = float(self.gap)
        return out

    def __str__(self) -> str:
        bits = []
        if self.latency_s is not None:
            bits.append(f"latency={self.latency_s:g}")
        if self.iters is not None:
            bits.append(f"iters={self.iters}")
        if self.gap is not None:
            bits.append(f"gap={self.gap:g}")
        return ",".join(bits)


def parse_slo(spec: str) -> SloSpec:
    """Parse ``latency=S,iters=N,gap=G`` (any non-empty subset, any
    order); every target must be positive."""
    kw: dict = {}
    for item in str(spec).split(","):
        item = item.strip()
        if not item:
            continue
        key, sep, val = item.partition("=")
        key = key.strip()
        if not sep or key not in ("latency", "iters", "gap"):
            raise ValueError(
                f"invalid --slo objective {item!r}: expected "
                f"latency=SECONDS, iters=N and/or gap=G")
        try:
            v = int(val) if key == "iters" else float(val)
        except ValueError:
            raise ValueError(f"invalid --slo value {val!r} for {key}")
        if v <= 0:
            raise ValueError(f"--slo {key} must be positive, got {val}")
        kw["latency_s" if key == "latency" else key] = v
    if not kw:
        raise ValueError("empty --slo spec: declare at least one of "
                         "latency=S, iters=N, gap=G")
    return SloSpec(**kw)


_slo: SloSpec | None = None
_slo_lock = threading.Lock()
_slo_observed: dict = {}
_slo_breaches: dict = {}
_slo_last: dict = {}


def install_slo(spec: SloSpec) -> None:
    """Arm the declared objectives: target gauges land on the metrics
    registry immediately (a scrape shows what the run promised even
    before the first solve)."""
    global _slo
    from acg_tpu import metrics
    _clear_slo()
    _slo = spec
    for objective, target in spec.targets().items():
        metrics.record_slo_target(objective, target)


def installed_slo() -> SloSpec | None:
    return _slo


def _clear_slo() -> None:
    global _slo
    with _slo_lock:
        _slo = None
        _slo_observed.clear()
        _slo_breaches.clear()
        _slo_last.clear()


def slo_observe(stats=None, latency=None, iterations=None,
                gap=None) -> bool:
    """Judge one completed solve against the declared objectives.
    Returns True when any objective breached; every breach bumps
    ``acg_slo_breaches_total``, refreshes ``acg_slo_burn_ratio`` (the
    cumulative fraction of observed solves breaching -- the error
    budget burned so far), and emits a structured ``slo-breach`` event
    into the telemetry/timeline stream when ``stats`` is given."""
    spec = _slo
    if spec is None:
        return False
    from acg_tpu import metrics
    observed = {}
    if spec.latency_s is not None and latency is not None:
        observed["latency"] = (float(latency), spec.latency_s, "s")
    if spec.iters is not None and iterations is not None:
        observed["iters"] = (float(iterations), float(spec.iters), "")
    if spec.gap is not None and gap is not None \
            and _finite(gap) is not None:
        observed["gap"] = (float(gap), spec.gap, "")
    any_breach = False
    for objective, (value, target, unit) in observed.items():
        breached = value > target
        with _slo_lock:
            _slo_observed[objective] = _slo_observed.get(objective,
                                                         0) + 1
            if breached:
                _slo_breaches[objective] = _slo_breaches.get(objective,
                                                             0) + 1
            _slo_last[objective] = value
            burn = (_slo_breaches.get(objective, 0)
                    / _slo_observed[objective])
        metrics.record_slo(objective, breached, burn)
        if breached:
            any_breach = True
            msg = (f"SLO breach: {objective} {value:g}{unit} > target "
                   f"{target:g}{unit} (burn "
                   f"{burn * 100.0:.0f}% of observed solves)")
            if stats is not None:
                from acg_tpu import telemetry
                telemetry.record_event(stats, "slo-breach", msg)
            else:
                note_event("slo-breach", msg)
            sys.stderr.write(f"acg-tpu: {msg}\n")
    return any_breach


def slo_report() -> dict:
    """The JSON-able ``slo`` section (the stats twin's /8 additive key
    and the status document's ``slo`` entry)."""
    spec = _slo
    if spec is None:
        return {}
    with _slo_lock:
        rep: dict = {"targets": spec.targets(),
                     "observed": dict(_slo_observed),
                     "breaches": dict(_slo_breaches),
                     "last": dict(_slo_last)}
        rep["burn"] = {
            obj: (_slo_breaches.get(obj, 0) / n if n else 0.0)
            for obj, n in _slo_observed.items()}
        rep["breached"] = any(_slo_breaches.values())
    return rep


def slo_breached() -> bool:
    with _slo_lock:
        return any(_slo_breaches.values())


def attach_slo(stats) -> None:
    """Record the SLO verdict onto ``stats.slo`` (the ``slo:`` stats
    section and its --stats-json twin; no-op without declared
    objectives)."""
    rep = slo_report()
    if rep:
        stats.slo = rep


def slo_exit_code(fail_on_slo: bool) -> int:
    """The ``--fail-on-slo`` verdict: 0, or :data:`SLO_EXIT_CODE` when
    the gate is set and any objective breached."""
    return SLO_EXIT_CODE if (fail_on_slo and slo_breached()) else 0


# -- run-history ledger ---------------------------------------------------

def _index_of(doc: dict) -> dict:
    """The ledger index fields for one --stats-json document: enough to
    scan trends without parsing the full document."""
    man = doc.get("manifest") or {}
    st = doc.get("stats") or {}
    soak = st.get("soak") or {}
    lat = (soak.get("latency") or {}).get("p50")
    if lat is None:
        lat = st.get("tsolve")
    case = value = None
    try:
        from acg_tpu.perfmodel import _doc_case
        c = _doc_case(doc)
        if c is not None:
            case, value = c
    except Exception:  # noqa: BLE001 -- an unparseable case still gets
        pass           # a ledger row; it just never baselines
    return {
        "ledger": HISTORY_SCHEMA,
        "unix_time": float(man.get("unix_time") or time.time()),
        "schema": doc.get("schema"),
        "matrix": man.get("matrix"),
        "solver": man.get("solver"),
        "nparts": man.get("nparts"),
        "precond": man.get("precond"),
        "dtype": man.get("dtype"),
        "converged": st.get("converged"),
        "iterations": st.get("niterations"),
        "latency_s": _finite(lat),
        "case": case,
        "value": value,
    }


def history_append(dirpath, doc: dict) -> str:
    """Append one solve's stats document to the date-partitioned
    ledger: ``DIR/YYYY-MM-DD.jsonl``, one index line per solve carrying
    the full document under ``doc``.  Returns the ledger file path."""
    dirpath = os.fspath(dirpath)
    os.makedirs(dirpath, exist_ok=True)
    idx = _index_of(doc)
    day = time.strftime("%Y-%m-%d", time.gmtime(idx["unix_time"]))
    path = os.path.join(dirpath, f"{day}.jsonl")
    line = json.dumps({**idx, "doc": doc}, default=str)
    with open(path, "a") as f:
        f.write(line + "\n")
    return path


def history_scan(dirpath) -> list[dict]:
    """Every ledger entry under ``DIR`` (all ``*.jsonl`` partitions),
    sorted by capture time.  Malformed lines are skipped (a killed run
    may have torn its last append; the usable prefix is the ledger)."""
    dirpath = os.fspath(dirpath)
    entries: list[dict] = []
    try:
        names = sorted(os.listdir(dirpath))
    except OSError:
        return []
    for name in names:
        if not name.endswith(".jsonl"):
            continue
        try:
            with open(os.path.join(dirpath, name)) as f:
                for raw in f:
                    raw = raw.strip()
                    if not raw:
                        continue
                    try:
                        obj = json.loads(raw)
                    except ValueError:
                        continue
                    if isinstance(obj, dict) and str(
                            obj.get("ledger", "")).startswith(
                            "acg-tpu-history"):
                        entries.append(obj)
        except OSError:
            continue
    entries.sort(key=lambda e: e.get("unix_time") or 0.0)
    return entries


def load_history_baseline(dirpath) -> tuple[dict, bool, int]:
    """The ``--baseline-from-history`` selection: the best-known USABLE
    value per case across every ledger entry.  Entries recording only
    the ``bench_backend_unavailable`` sentinel (captures that never
    reached hardware) are skipped; returns ``(cases,
    all_unavailable, nentries)`` where ``all_unavailable`` is True when
    entries exist but none was usable."""
    from acg_tpu.perfmodel import UNAVAILABLE_METRIC
    entries = history_scan(dirpath)
    cases: dict = {}
    nsentinel = nother = 0
    for e in entries:
        case, value = e.get("case"), e.get("value")
        if (case == UNAVAILABLE_METRIC
                or str(case).startswith(UNAVAILABLE_METRIC + "|")):
            nsentinel += 1
            continue
        if (not case or not isinstance(value, (int, float))
                or value <= 0):
            # unusable for some OTHER reason (a failed run the ledger
            # deliberately records, an uncased entry): must NOT trigger
            # the backend-was-down diagnosis below
            nother += 1
            continue
        cases[case] = max(cases.get(case, float("-inf")), float(value))
    # the re-baseline refusal claims the backend never came up: only
    # say so when EVERY unusable entry is the sentinel
    all_unavailable = (bool(entries) and not cases
                       and nsentinel > 0 and nother == 0)
    return cases, all_unavailable, len(entries)
