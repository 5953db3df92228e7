"""ICI communication observatory: mesh collective microbenchmarks,
a measured alpha-beta (latency / inverse-bandwidth) calibration, and
measured segment decomposition of the iteration time.

The reference's whole comm-strategy argument (CPU- vs GPU-initiated,
NCCL vs NVSHMEM, SURVEY.md section 2) is justified by MEASURED transfer
latencies; our ``--explain`` roofline and the fused tier's overlap
pricing have so far run on estimates -- ring-hop counts from the mesh
shape and one host triad probe standing in for ICI bandwidth
(perfmodel.ICI_GBS, explicitly a stand-in).  This module is the
calibration step the s-step/pipelining literature assumes before any
latency-hiding claim (Ghysels-Vanroose; PAPERS.md arXiv 2501.03743):

* **Collective microbenchmarks** run over the solver's own mesh --
  psum/all_reduce scalar latency, ``all_to_all`` and
  ``collective_permute`` bandwidth sweeps across message sizes, and the
  one-sided ``halo_dma`` systolic exchange including PER-EDGE put/wait
  timing by ring distance (a globally-uniform count gate per rotation
  round, so the interpret-mode emulation's op pairing holds) -- each
  kind fitted to ``t = alpha + beta * bytes``.
* **Measured segment decomposition**: SpMV-only / halo-only /
  reduction-only probe programs built from the SAME TierOps composition
  the recurrence builder dispatches (``recurrence.build_*_segment_
  probes`` -- the ``lower_solve`` discipline: same SpMV selection, same
  psum ladder), each run for K chained repetitions inside one dispatch,
  so a measured s/iter splits into measured segments instead of
  replayed op estimates.
* **The calibration document**: an ``acg-tpu-commbench/1`` JSON doc
  (``--commbench FILE``) with a content-hashed ``calibration_id``,
  validated by :func:`validate_commbench` and consumed by
  ``--explain --calibration FILE`` (perfmodel prices comm from the
  fitted alpha-beta instead of ring-hop guesses), by the fused tier's
  exposed-halo overlap pricing, and -- as a provenance key -- by the
  stats-json manifest / convergence-log meta line / bench_diff case
  keying, so differently-calibrated captures never diff silently.

Everything here is an analysis pass: nothing mutates solver state, and
with the observatory disarmed every dispatched solver program stays
byte-identical (pinned in tests/test_commbench.py alongside the
perfmodel/metrics/tracing pins).  jax imports stay inside functions --
the validator and the bench_diff keying must answer without
initialising a backend.
"""

from __future__ import annotations

import hashlib
import json
import os
import sys
import time

import numpy as np

COMMBENCH_SCHEMA = "acg-tpu-commbench/1"

# the provenance value a run without a calibration records in its
# stats-json manifest / convergence-log meta line; bench_diff keys only
# on REAL ids, so uncalibrated captures keep diffing against old ones
UNCALIBRATED = "uncalibrated"

# collective kinds the suite benchmarks -- the SAME kind names
# tracing.analyze_trace's per-kind breakdown reports, so a fit can be
# confronted with a capture kind by kind
KINDS = ("all_reduce", "all_to_all", "collective_permute", "dma")

# message-size sweeps (payload bytes per shard).  The CPU sweep keeps
# the 8-part interpret-mode CI smoke under seconds; the TPU sweep
# reaches into the bandwidth-dominated regime where beta is resolvable
CPU_SWEEP = (256, 8192, 131072)
TPU_SWEEP = (256, 4096, 65536, 1048576, 8388608)

# chained collective rounds per timed dispatch (amortises dispatch
# latency out of the per-round figure) and timing repeats (min-of)
DEFAULT_REPS = 24
TIMED_REPEATS = 3
SEGMENT_REPS = 16


# -- the alpha-beta fit ---------------------------------------------------

def fit_alpha_beta(points) -> dict | None:
    """Least-squares fit of ``t = alpha + beta * bytes`` over
    ``[(bytes, seconds), ...]`` with both coefficients clamped
    nonnegative (a negative latency or inverse bandwidth is a
    measurement artifact; the clamped refit keeps the other coefficient
    honest).  Returns ``{"alpha_s", "beta_s_per_byte", "npoints",
    "r2"}`` or None when nothing usable was measured."""
    pts = [(float(b), float(s)) for b, s in points
           if s > 0 and b >= 0 and np.isfinite(s) and np.isfinite(b)]
    if not pts:
        return None
    x = np.asarray([p[0] for p in pts], dtype=np.float64)
    y = np.asarray([p[1] for p in pts], dtype=np.float64)
    if len(pts) == 1 or np.ptp(x) == 0:
        b0, s0 = float(x[0]), float(np.min(y))
        return {"alpha_s": s0 if b0 == 0 else 0.0,
                "beta_s_per_byte": (s0 / b0) if b0 > 0 else 0.0,
                "npoints": len(pts), "r2": None}
    A = np.stack([np.ones_like(x), x], axis=1)
    coef, *_ = np.linalg.lstsq(A, y, rcond=None)
    alpha, beta = float(coef[0]), float(coef[1])
    if beta < 0.0:
        # bandwidth buried in noise: pure-latency fit
        alpha, beta = float(np.mean(y)), 0.0
    elif alpha < 0.0:
        # latency buried in noise: pure-bandwidth fit through origin
        alpha, beta = 0.0, float((x @ y) / (x @ x))
    pred = alpha + beta * x
    ss_res = float(np.sum((y - pred) ** 2))
    ss_tot = float(np.sum((y - np.mean(y)) ** 2))
    r2 = (1.0 - ss_res / ss_tot) if ss_tot > 0 else None
    return {"alpha_s": alpha, "beta_s_per_byte": beta,
            "npoints": len(pts),
            "r2": (round(r2, 6) if r2 is not None else None)}


def predict_seconds(fit, nbytes) -> float | None:
    """``alpha + beta * bytes`` for one fitted kind; None when the fit
    is absent/unusable."""
    if not isinstance(fit, dict) or "alpha_s" not in fit:
        return None
    return (float(fit["alpha_s"])
            + float(fit.get("beta_s_per_byte", 0.0))
            * max(float(nbytes), 0.0))


# -- timing ---------------------------------------------------------------

def _time_dispatch(runner, repeats: int = TIMED_REPEATS) -> float:
    """Min-of-``repeats`` wall seconds of one synced dispatch of
    ``runner`` (the runner must return a device value to block on).
    The first (untimed) call absorbs the compile."""
    import jax

    jax.block_until_ready(runner())
    ts = []
    for _ in range(max(int(repeats), 1)):
        t0 = time.perf_counter()
        jax.block_until_ready(runner())
        ts.append(time.perf_counter() - t0)
    return min(ts)


# -- collective microbenchmark programs -----------------------------------

def _collective_program(mesh, kind: str, nbytes: int, reps: int):
    """One benchmark program: ``reps`` CHAINED rounds of one collective
    over the mesh's parts axis inside a single jitted shard_map dispatch
    (each round's input is the previous round's output, so XLA can
    neither elide nor reorder rounds).  Returns ``(runner,
    bytes_per_shard)`` -- the realised per-shard payload, which is what
    the alpha-beta fit is over."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from acg_tpu.parallel.mesh import PARTS_AXIS

    nparts = int(mesh.shape[PARTS_AXIS])
    item = 4  # f32 payloads throughout -- the solve vectors' dtype class
    if kind == "all_reduce":
        m = max(int(nbytes) // item, 1)
        shape = (m,)
        scale = jnp.float32(1.0 / nparts)

        def round_(v):
            # psum of identical shards = nparts * v; the rescale keeps
            # the chained value exactly 1.0 (1/8 etc. are exact in f32)
            return lax.psum(v, PARTS_AXIS) * scale
        payload = m * item
    elif kind == "all_to_all":
        m = max(int(nbytes) // (item * nparts), 1)
        shape = (nparts, m)

        def round_(v):
            return lax.all_to_all(v, PARTS_AXIS, 0, 0)
        payload = nparts * m * item
    elif kind == "collective_permute":
        m = max(int(nbytes) // item, 1)
        shape = (m,)
        perm = [(i, (i + 1) % nparts) for i in range(nparts)]

        def round_(v):
            return lax.ppermute(v, PARTS_AXIS, perm)
        payload = m * item
    else:
        raise ValueError(f"unknown collective kind {kind!r}")

    def body(vs):
        v = vs[0]
        v = lax.fori_loop(0, int(reps), lambda i, v: round_(v), v)
        return v[None]

    prog = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=P(PARTS_AXIS),
                                 out_specs=P(PARTS_AXIS), check_vma=False))
    x = jax.device_put(np.ones((nparts,) + shape, np.float32),
                       NamedSharding(mesh, P(PARTS_AXIS)))
    return (lambda: prog(x)), payload


def bench_collectives(mesh, sizes_bytes, reps: int = DEFAULT_REPS,
                      repeats: int = TIMED_REPEATS) -> dict:
    """Sweep the XLA collective kinds across message sizes on the mesh;
    one ``{"alpha_s", "beta_s_per_byte", ..., "points": [...]}`` entry
    per kind."""
    out: dict = {}
    for kind in ("all_reduce", "all_to_all", "collective_permute"):
        points = []
        for nbytes in sizes_bytes:
            runner, payload = _collective_program(mesh, kind,
                                                  int(nbytes), reps)
            secs = _time_dispatch(runner, repeats) / reps
            points.append({"bytes": int(payload),
                           "seconds": float(secs)})
        fit = fit_alpha_beta([(p["bytes"], p["seconds"])
                              for p in points]) or {}
        out[kind] = {**fit, "points": points}
    return out


def _dma_counts(nparts: int, maxcnt: int,
                distance: int | None) -> np.ndarray:
    """The per-neighbour count matrix of a benchmark exchange:
    ``distance=None`` is the dense systolic exchange; a ring distance d
    gates the puts to distance-d pairs only -- a gate that is globally
    UNIFORM per rotation round, which is exactly the pattern the
    interpret-mode DMA emulation supports (halo_dma module docs)."""
    cnt = np.zeros((nparts, nparts), np.int32)
    for p in range(nparts):
        for q in range(nparts):
            if p == q:
                continue
            d = min((q - p) % nparts, (p - q) % nparts)
            if distance is None or d == int(distance):
                cnt[p, q] = maxcnt
    return cnt


def _dma_program(mesh, maxcnt: int, reps: int, interpret: bool,
                 distance: int | None = None):
    """``reps`` chained one-sided halo_dma exchanges of a
    ``(nparts, maxcnt)`` f32 window plane (the put-with-signal systolic
    schedule itself, no pack/unpack).  Returns ``(runner,
    bytes_per_shard, peers_per_shard)``."""
    import jax
    from jax import lax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from acg_tpu.parallel.halo_dma import dma_exchange
    from acg_tpu.parallel.mesh import PARTS_AXIS

    nparts = int(mesh.shape[PARTS_AXIS])
    cnt = _dma_counts(nparts, int(maxcnt), distance)
    peers = int((cnt[0] > 0).sum())
    gated = distance is not None

    def body(sb, sc, rc):
        sb, sc, rc = sb[0], sc[0], rc[0]

        def round_(i, buf):
            return dma_exchange(buf, sc, rc, axis=PARTS_AXIS,
                                interpret=interpret,
                                gate_by_counts=True if gated else None)
        out = lax.fori_loop(0, int(reps), round_, sb)
        return out[None]

    pspec = P(PARTS_AXIS)
    prog = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=(pspec,) * 3,
                                 out_specs=pspec, check_vma=False))
    sh = NamedSharding(mesh, pspec)
    sb = jax.device_put(np.ones((nparts, nparts, maxcnt), np.float32),
                        sh)
    # row p of the stacked count arrays is shard p's per-neighbour
    # view: what it sends to each q, and what it receives from each q
    sc = jax.device_put(np.ascontiguousarray(cnt), sh)
    rc = jax.device_put(np.ascontiguousarray(cnt.T), sh)
    return (lambda: prog(sb, sc, rc)), peers * maxcnt * 4, peers


def bench_dma(mesh, sizes_bytes, reps: int = DEFAULT_REPS,
              repeats: int = TIMED_REPEATS,
              interpret: bool | None = None) -> dict:
    """The one-sided transport's bandwidth sweep: dense systolic
    exchanges across window sizes, fitted alpha-beta over the per-shard
    outgoing bytes."""
    import jax

    if interpret is None:
        interpret = jax.devices()[0].platform != "tpu"
    from acg_tpu.parallel.mesh import PARTS_AXIS
    nparts = int(mesh.shape[PARTS_AXIS])
    points = []
    for nbytes in sizes_bytes:
        maxcnt = max(int(nbytes) // (4 * max(nparts - 1, 1)), 1)
        runner, payload, _ = _dma_program(mesh, maxcnt, reps, interpret)
        secs = _time_dispatch(runner, repeats) / reps
        points.append({"bytes": int(payload), "seconds": float(secs)})
    fit = fit_alpha_beta([(p["bytes"], p["seconds"])
                          for p in points]) or {}
    return {**fit, "points": points,
            "interpret": bool(interpret)}


def bench_dma_edges(mesh, window_bytes: int,
                    reps: int = DEFAULT_REPS,
                    repeats: int = TIMED_REPEATS,
                    interpret: bool | None = None) -> list[dict]:
    """PER-EDGE one-sided put/wait timing by ring distance: one gated
    exchange per distance d (every shard puts one window_bytes window
    to its distance-d peer(s) and waits the matching receives) -- the
    on-silicon transport validation row PR 13 left open, measured here
    wherever the transport runs (interpret mode on CPU meshes, compiled
    puts on TPU)."""
    import jax

    if interpret is None:
        interpret = jax.devices()[0].platform != "tpu"
    from acg_tpu.parallel.mesh import PARTS_AXIS
    nparts = int(mesh.shape[PARTS_AXIS])
    maxcnt = max(int(window_bytes) // 4, 1)
    rows = []
    for d in range(1, nparts // 2 + 1):
        runner, payload, peers = _dma_program(mesh, maxcnt, reps,
                                              interpret, distance=d)
        secs = _time_dispatch(runner, repeats) / reps
        rows.append({"distance": int(d),
                     "window_bytes": int(maxcnt * 4),
                     "peers_per_shard": int(peers),
                     "put_wait_seconds": float(secs)})
    return rows


# -- measured segment decomposition ---------------------------------------

def segment_decomposition(solver, b, reps: int = SEGMENT_REPS,
                          repeats: int = TIMED_REPEATS) -> dict:
    """Measured SpMV-only / halo-only / reduction-only segments of the
    solver's iteration: probe programs built from the SAME TierOps
    composition the recurrence builder dispatches (recurrence.build_*_
    segment_probes), each run ``reps`` chained times inside one
    dispatch.  The halo segment is CONTAINED in the SpMV segment (the
    dispatched SpMV embeds the exchange), so the explained s/iter is
    ``spmv + reduction``; whatever the measured s/iter holds beyond
    that is the axpy/control remainder.  Degrades to ``{"available":
    False, "why": ...}`` -- a probe failure must never sink an explain
    pass."""
    from acg_tpu import recurrence

    try:
        if getattr(solver, "problem", None) is not None:
            probes = recurrence.build_dist_segment_probes(solver, b,
                                                          reps)
        else:
            probes = recurrence.build_single_segment_probes(solver, b,
                                                            reps)
    except Exception as e:  # noqa: BLE001 -- observability degrades
        return {"available": False,
                "why": f"{type(e).__name__}: {e}"}
    segs: dict = {}
    try:
        for name, runner, calls in probes:
            secs = _time_dispatch(runner, repeats) / reps
            segs[name] = {"s_per_call": float(secs),
                          "calls_per_iteration": float(calls),
                          "s_per_iteration": float(secs) * float(calls)}
    except Exception as e:  # noqa: BLE001
        return {"available": False,
                "why": f"{type(e).__name__}: {e}"}
    explained = sum(v["s_per_iteration"] for k, v in segs.items()
                    if k != "halo")
    return {"available": True, "reps": int(reps),
            "segments": segs,
            "explained_s_per_iteration": float(explained),
            "note": "halo is contained in the spmv segment; "
                    "explained = spmv + reduction"}


# -- the calibration document ---------------------------------------------

def calibration_id(doc: dict) -> str:
    """Content-hashed id: any edit to the measurements produces a
    different id, so two captures keyed by it can never silently claim
    the same calibration."""
    payload = {k: v for k, v in doc.items() if k != "calibration_id"}
    h = hashlib.sha256(json.dumps(payload, sort_keys=True,
                                  default=str).encode()).hexdigest()
    backend = "x"
    man = doc.get("manifest")
    if isinstance(man, dict) and isinstance(man.get("backend"), dict):
        backend = str(man["backend"].get("platform", "x"))
    return f"cb-{backend}-{int(doc.get('nparts', 0))}p-{h[:10]}"


def _num(v) -> float | None:
    """Coerce a JSON value to a finite float, or None -- the validator
    must REPORT a malformed value, never raise on one."""
    if isinstance(v, bool) or not isinstance(v, (int, float)):
        return None
    v = float(v)
    return v if np.isfinite(v) else None


def validate_commbench(doc) -> list[str]:
    """Problems with a commbench document (empty list = valid): schema,
    id integrity (content hash must match -- a hand-edited doc must not
    pass as the measurement it no longer is), and per-kind fit/point
    sanity.  Every check is type-defensive: a malformed value becomes a
    named problem, never an exception (rejecting such docs gracefully
    is this function's whole job)."""
    problems: list[str] = []
    if not isinstance(doc, dict):
        return ["not a JSON object"]
    if doc.get("schema") != COMMBENCH_SCHEMA:
        problems.append(f"schema is {doc.get('schema')!r}, expected "
                        f"{COMMBENCH_SCHEMA!r}")
        return problems
    cid = doc.get("calibration_id")
    if not isinstance(cid, str) or not cid:
        problems.append("missing calibration_id")
    elif cid != calibration_id(doc):
        problems.append("calibration_id does not match the document "
                        "content (edited after capture?)")
    nparts = doc.get("nparts")
    if not isinstance(nparts, int) or isinstance(nparts, bool) \
            or nparts < 1:
        problems.append(f"nparts must be a positive int "
                        f"(got {nparts!r})")
    colls = doc.get("collectives")
    if not isinstance(colls, dict) or not colls:
        problems.append("missing collectives section")
        return problems
    fitted = 0
    for kind, entry in colls.items():
        if kind not in KINDS:
            problems.append(f"unknown collective kind {kind!r}")
            continue
        if not isinstance(entry, dict):
            problems.append(f"{kind}: not an object")
            continue
        if "alpha_s" not in entry:
            continue  # an unfitted kind is allowed (e.g. dma skipped)
        alpha = _num(entry["alpha_s"])
        beta = _num(entry.get("beta_s_per_byte", 0.0))
        if alpha is None or beta is None or alpha < 0 or beta < 0:
            problems.append(f"{kind}: alpha/beta not nonnegative "
                            f"numbers")
        pts = entry.get("points")
        if not isinstance(pts, list) or not pts:
            problems.append(f"{kind}: fitted without points")
        else:
            for p in pts:
                nb = _num(p.get("bytes")) if isinstance(p, dict) \
                    else None
                sec = _num(p.get("seconds")) if isinstance(p, dict) \
                    else None
                if nb is None or sec is None or nb < 0 or sec <= 0:
                    problems.append(f"{kind}: bad point {p!r}")
                    break
        fitted += 1
    if not fitted:
        problems.append("no fitted collective kinds")
    edges = doc.get("edges") or []
    if not isinstance(edges, list):
        problems.append("edges is not a list")
        edges = []
    for row in edges:
        d = _num(row.get("distance")) if isinstance(row, dict) else None
        sec = (_num(row.get("put_wait_seconds"))
               if isinstance(row, dict) else None)
        if d is None or sec is None or d < 1 or sec <= 0:
            problems.append(f"bad edge row {row!r}")
            break
    return problems


def load_calibration(path) -> dict:
    """Read + validate a saved commbench document; raises ValueError
    with every problem named (the --calibration refusal text)."""
    with open(path) as f:
        try:
            doc = json.load(f)
        except ValueError as e:
            raise ValueError(f"not JSON ({e})")
    problems = validate_commbench(doc)
    if problems:
        raise ValueError("not a valid acg-tpu-commbench/1 document: "
                         + "; ".join(problems))
    return doc


def write_document(doc: dict, dest) -> None:
    """Write the doc to a path (``"-"`` = stdout)."""
    if dest in (None, "-"):
        json.dump(doc, sys.stdout, indent=2)
        sys.stdout.write("\n")
        return
    with open(dest, "w") as f:
        json.dump(doc, f, indent=2)
        f.write("\n")


# -- calibrated comm pricing ----------------------------------------------

def _halo_fit(cal: dict, led: dict) -> tuple[dict | None, str]:
    """``(fit, kind_used)`` for the ledger's halo transport: the dma
    fit when the one-sided transport is armed AND was benchmarked,
    falling back to the all_to_all fit -- ``kind_used`` names the fit
    actually applied, so provenance never claims a fit that was not
    there."""
    kinds = cal.get("collectives", {})
    kind = "dma" if led.get("transport") == "dma" else "all_to_all"
    fit = kinds.get(kind)
    if not isinstance(fit, dict) or "alpha_s" not in fit:
        kind, fit = "all_to_all", kinds.get("all_to_all")
    if not isinstance(fit, dict) or "alpha_s" not in fit:
        return None, kind
    return fit, kind


def halo_exchange_seconds(cal: dict, led: dict) -> float | None:
    """Seconds of ONE halo exchange priced from the calibration's
    fitted transport kind (``dma`` when the ledger armed the one-sided
    transport and the dma kind was benchmarked, else ``all_to_all``),
    over the PADDED per-shard plane the transport actually moves
    (``halo_plane_bytes_per_exchange``; the unpadded per-edge totals
    are a lower bound the wire never sees)."""
    if not led.get("halo_bytes_per_iteration"):
        return 0.0
    fit, _kind = _halo_fit(cal, led)
    nb = led.get("halo_plane_bytes_per_exchange")
    if nb is None:
        nb = (led.get("halo_bytes_per_iteration", 0)
              / max(int(led.get("nparts", 1)), 1))
    return predict_seconds(fit, nb)


def comm_seconds(cal: dict, led: dict) -> dict | None:
    """Per-iteration communication seconds priced from the fitted
    alpha-beta model -- the calibrated replacement for the
    bytes-over-ICI_GBS ring-hop guess.  None when the ledger or the
    needed fits are unusable."""
    if not isinstance(led, dict) or "error" in led:
        return None
    kinds = cal.get("collectives", {})
    nred = float(led.get("allreduce_per_iteration", 0) or 0)
    ar_bytes = float(led.get("allreduce_bytes_per_iteration", 0) or 0)
    ar_s = 0.0
    if nred > 0:
        per_red = ar_bytes / nred
        p = predict_seconds(kinds.get("all_reduce"), per_red)
        if p is None:
            return None
        ar_s = nred * p
    halo_one = halo_exchange_seconds(cal, led)
    if halo_one is None:
        return None
    nex = float(led.get("halo_exchanges_per_iteration", 1) or 1)
    halo_s = (halo_one * nex
              if led.get("halo_bytes_per_iteration") else 0.0)
    _fit, kind = _halo_fit(cal, led)
    return {"allreduce_s": float(ar_s), "halo_s": float(halo_s),
            "total_s": float(ar_s + halo_s),
            "halo_kind": kind,
            "calibration_id": str(cal.get("calibration_id", ""))}


# -- the --commbench CLI mode ---------------------------------------------

def _fmt_gbs(beta: float) -> str:
    if beta <= 0:
        return "inf GB/s"
    return f"{1.0 / beta / 1e9:,.2f} GB/s"


def collect_document(args, dtype, vec_dtype, err) -> dict:
    """Run the whole observatory over the configured case and mesh and
    return the commbench document (also printing the human summary to
    ``err``)."""
    import jax
    import jax.numpy as jnp

    from acg_tpu import perfmodel, telemetry
    from acg_tpu.parallel.mesh import PARTS_AXIS, solve_mesh

    csr = perfmodel._explain_matrix(args)
    on_tpu = jax.default_backend() == "tpu"
    # standalone default: up to 8 parts; under a live --explain
    # --commbench run, match run_explain's dist-tier default so the
    # calibration describes the very mesh the verdict prices
    nparts = args.nparts or min(len(jax.devices()),
                                4 if getattr(args, "explain", False)
                                else 8)
    if nparts < 2:
        raise SystemExit("acg-tpu: --commbench benchmarks the mesh "
                         "collectives; need --nparts >= 2 (or more "
                         "than one visible device)")
    mesh = solve_mesh(nparts)
    interpret = not on_tpu
    sweep = TPU_SWEEP if on_tpu else CPU_SWEEP
    reps = DEFAULT_REPS
    err.write(f"== commbench: {nparts}-part mesh "
              f"({'compiled ICI' if on_tpu else 'interpret/CPU'}), "
              f"{len(sweep)}-size sweep x {reps} chained rounds ==\n")

    colls = bench_collectives(mesh, sweep, reps=reps)
    dma_entry = None
    edges: list[dict] = []
    try:
        dma_entry = bench_dma(mesh, sweep, reps=reps,
                              interpret=interpret)
        edges = bench_dma_edges(mesh, max(sweep), reps=reps,
                                interpret=interpret)
    except Exception as e:  # noqa: BLE001 -- the one-sided transport
        # may be unavailable (e.g. unvalidated multi-chip ICI); the
        # XLA kinds still calibrate
        dma_entry = {"unavailable": f"{type(e).__name__}: {e}"}
        err.write(f"  dma transport bench unavailable: "
                  f"{type(e).__name__}: {e}\n")
    colls["dma"] = dma_entry
    for kind in KINDS:
        entry = colls.get(kind)
        if not isinstance(entry, dict) or "alpha_s" not in entry:
            why = (entry or {}).get("unavailable", "not benchmarked")
            err.write(f"  {kind:<19}: ({why})\n")
            continue
        err.write(f"  {kind:<19}: alpha {entry['alpha_s']:.3e} s, "
                  f"beta {entry['beta_s_per_byte']:.3e} s/B "
                  f"({_fmt_gbs(entry['beta_s_per_byte'])}), "
                  f"{entry['npoints']} point(s)"
                  + (f", r2 {entry['r2']:.3f}"
                     if entry.get("r2") is not None else "") + "\n")
    for row in edges:
        err.write(f"  dma edge d={row['distance']}: "
                  f"{row['window_bytes']:,} B window put+wait "
                  f"{row['put_wait_seconds']:.3e} s "
                  f"({row['peers_per_shard']} peer(s)/shard)\n")
    scalar_lat = None
    ar_pts = (colls.get("all_reduce") or {}).get("points") or []
    if ar_pts:
        scalar_lat = min(p["seconds"] for p in ar_pts)
        err.write(f"  scalar all_reduce latency: {scalar_lat:.3e} s\n")

    # the case's measured segment decomposition, through the same
    # dist-tier construction --explain uses
    segs: dict = {"available": False, "why": "dist tier construction "
                                             "failed"}
    case: dict = {"matrix": str(args.A), "n": int(csr.shape[0]),
                  "nnz": int(csr.nnz)}
    try:
        from acg_tpu.solvers.stats import StoppingCriteria

        # the SAME dist-tier construction run_explain analyses (one
        # copy -- the calibration must describe the very mesh the
        # explain verdict prices)
        solver = perfmodel.build_explain_dist_solver(
            args, csr, nparts, dtype, vec_dtype)
        b = np.ones(csr.shape[0])
        segs = segment_decomposition(solver, b)
        K = max(8, min(args.max_iterations, 60))
        solver.stats.tsolve = 0.0
        solver.solve(b, criteria=StoppingCriteria(maxits=K), warmup=1,
                     host_result=False, raise_on_divergence=False)
        case["measured_s_per_iteration"] = solver.stats.tsolve / K
        case["timed_iterations"] = K
        case["transport"] = solver.comm
    except Exception as e:  # noqa: BLE001
        err.write(f"acg-tpu: commbench segment pass failed: "
                  f"{type(e).__name__}: {e}\n")
        segs = {"available": False, "why": f"{type(e).__name__}: {e}"}
    if segs.get("available"):
        parts_txt = ", ".join(
            f"{k} {v['s_per_iteration']:.3e} s/iter"
            for k, v in segs["segments"].items())
        err.write(f"  segments: {parts_txt}\n")
        meas = case.get("measured_s_per_iteration")
        if meas:
            err.write(f"  explained {segs['explained_s_per_iteration']:.3e}"
                      f" of measured {meas:.3e} s/iter "
                      f"({segs['explained_s_per_iteration'] / meas:.0%}; "
                      f"remainder = axpy/control)\n")

    man = telemetry.run_manifest(metric="commbench",
                                 matrix=str(args.A), dtype=args.dtype)
    doc = {
        "schema": COMMBENCH_SCHEMA,
        "manifest": man,
        "nparts": int(nparts),
        "mesh_shape": {PARTS_AXIS: int(nparts)},
        "interpret": bool(interpret),
        "reps": int(reps),
        "sweep_bytes": [int(s) for s in sweep],
        "collectives": colls,
        "scalar_allreduce_latency_s": scalar_lat,
        "edges": edges,
        "segments": segs,
        "case": case,
    }
    doc["calibration_id"] = calibration_id(doc)
    err.write(f"  calibration id: {doc['calibration_id']}\n\n")
    from acg_tpu import metrics
    metrics.record_commbench(doc)
    return doc


def run_commbench(args, dtype, vec_dtype) -> int:
    """The CLI ``--commbench`` driver (standalone mode): run the suite,
    validate the document against our own validator (a doc we cannot
    re-read is a bug, not a capture), and write it."""
    err = sys.stderr
    doc = collect_document(args, dtype, vec_dtype, err)
    problems = validate_commbench(doc)
    if problems:
        err.write("acg-tpu: commbench produced an invalid document: "
                  + "; ".join(problems) + "\n")
        return 1
    try:
        write_document(doc, args.commbench)
    except OSError as e:
        err.write(f"acg-tpu: --commbench {args.commbench}: {e}\n")
        return 1
    if args.commbench not in (None, "-"):
        err.write(f"acg-tpu: commbench document written to "
                  f"{args.commbench}\n")
    return 0
