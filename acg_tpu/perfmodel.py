"""Compiled-program performance observability: XLA cost/memory
introspection, the static communication ledger, the roofline ``--explain``
tier, and the bench regression gate.

The reference's credibility rests on accounting for every byte and flop:
its stats block reports per-op GB/s against a hardware roofline
(``cgcuda.c:1942-1957``), and the SC'25 paper's core claims are
communication-volume arguments (halo bytes vs. allreduce latency).  Our
always-on counters (:func:`acg_tpu.solvers.stats.cg_flops_per_iteration`,
``bench._our_bytes_per_iter``) are ANALYTIC -- hand-derived models that
XLA can silently invalidate through fusion, recomputation, or layout
padding.  This module closes that gap with ground truth from the compiler
itself:

* :func:`analyze_solver` lowers + compiles the EXACT whole-solve program a
  solver dispatches (the ``lower_solve`` hook on :class:`~acg_tpu.solvers.
  jax_cg.JaxCGSolver`, :class:`~acg_tpu.parallel.dist.DistCGSolver` and
  the sharded tiers) and extracts ``compiled.cost_analysis()`` (flops,
  bytes accessed) and ``compiled.memory_analysis()`` (argument / output /
  temp / generated-code HBM bytes).
* :func:`per_iteration_cost` separates the loop body's cost from the
  setup's: HloCostAnalysis counts a while/fori body ONCE, so
  per-iteration = cost(whole program) - cost(setup probe), the probe
  lowered from the solver's own SpMV/dot selection.
* :func:`comm_ledger` asks the solver for its static communication
  ledger (per-neighbour halo payload bytes, psum counts and bytes,
  ring-hop estimates from the mesh shape) -- the ``comm_profile`` hooks
  on the distributed tiers.
* :func:`run_explain` (CLI ``--explain``) fuses all of it into a per-tier
  roofline verdict: predicted iteration time from the modelled HBM,
  comm and dispatch components against the probed bandwidth, measured
  time, attained fraction of the HBM roofline, and the top residual
  (HBM- / comm- / dispatch-bound; the unexplained remainder is
  attributed to compute -- no flops/peak time model is claimed).
* :func:`load_cases` / :func:`compare_cases` / :func:`check_regression`
  diff two ``--stats-json`` captures (or bench row files) case-by-case
  -- ``scripts/bench_diff.py`` and ``bench.py --baseline FILE
  --fail-on-regress PCT`` -- turning the ``BENCH_*.json`` trajectory
  into an enforced gate instead of an eyeballed one.

Everything degrades gracefully: where ``cost_analysis`` /
``memory_analysis`` are unsupported on the running jax version/backend
the report says so and the analytic counters stand alone.  Nothing here
mutates solver state or the compiled programs -- disarmed perfmodel
leaves every solve program byte-identical (asserted at the StableHLO
level in ``tests/test_hlo_structure.py``), and the ``costmodel:`` /
``memory:`` stats sections append strictly after the reference-format
block, like ``timings:``.

jax imports stay inside functions: the bench-diff path (and
``scripts/bench_diff.py --help``) must answer without initialising a
backend.
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

# Order-of-magnitude per-link inter-chip (ICI) bandwidth for v5e-class
# parts, used only to price the comm ledger's bytes in the --explain
# verdict on TPU backends (off-TPU the "interconnect" is host memory and
# the HBM probe is reused).  A stand-in until a measured ppermute probe
# exists -- the verdict prints the number it used, so a reader can
# re-price.
ICI_GBS = 45.0

UNAVAILABLE = ("analysis unavailable on this jax version/backend")


# -- compiled-program introspection --------------------------------------

def cost_analysis(compiled) -> dict | None:
    """Normalise ``compiled.cost_analysis()`` across jax versions (a
    dict, or one dict per device in older releases) to
    ``{"flops", "bytes_accessed", "output_bytes", "transcendentals"}``.
    None when the backend/version exposes nothing usable.  NOTE: for
    multi-device programs the values are PER DEVICE (XLA analyses the
    per-device module)."""
    try:
        ca = compiled.cost_analysis()
    except Exception:  # noqa: BLE001 -- unsupported backends raise freely
        return None
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else None
    if not isinstance(ca, dict) or not ca:
        return None
    out: dict = {}
    for key, name in (("flops", "flops"),
                      ("bytes accessed", "bytes_accessed"),
                      ("bytes accessedout{}", "output_bytes"),
                      ("transcendentals", "transcendentals")):
        v = ca.get(key)
        if v is not None:
            v = float(v)
            if v == v:  # drop NaN placeholders
                out[name] = v
    return out or None


def memory_analysis(compiled) -> dict | None:
    """Normalise ``compiled.memory_analysis()`` (CompiledMemoryStats) to
    plain ints: the program's HBM footprint split into argument / output
    / temp / generated-code bytes, plus the aliased-buffer discount and
    the total."""
    try:
        ma = compiled.memory_analysis()
    except Exception:  # noqa: BLE001
        return None
    if ma is None:
        return None
    out: dict = {}
    for attr, name in (("argument_size_in_bytes", "argument_bytes"),
                       ("output_size_in_bytes", "output_bytes"),
                       ("temp_size_in_bytes", "temp_bytes"),
                       ("alias_size_in_bytes", "alias_bytes"),
                       ("generated_code_size_in_bytes",
                        "generated_code_bytes")):
        v = getattr(ma, attr, None)
        if v is not None:
            out[name] = int(v)
    if not out:
        return None
    out["total_hbm_bytes"] = (out.get("argument_bytes", 0)
                              + out.get("output_bytes", 0)
                              + out.get("temp_bytes", 0)
                              + out.get("generated_code_bytes", 0)
                              - out.get("alias_bytes", 0))
    return out


def analyze_solver(solver, b, x0=None, criteria=None) -> dict:
    """Lower + compile the solver's exact solve program for ``(b, x0,
    criteria)`` and extract the compiler's own cost/memory analysis.

    Returns ``{"available": True, "cost": {...}, "memory": {...}}`` or
    ``{"available": False, "why": "..."}`` -- observability must degrade,
    never raise into a solve path.  Never mutates solver state (the
    ``lower_solve`` hooks re-dispatch the same static configuration a
    real solve uses)."""
    try:
        compiled = solver.lower_solve(b, x0=x0, criteria=criteria).compile()
    except Exception as e:  # noqa: BLE001
        return {"available": False,
                "why": f"lower/compile failed: {type(e).__name__}: {e}"}
    c = cost_analysis(compiled)
    m = memory_analysis(compiled)
    if c is None and m is None:
        return {"available": False, "why": UNAVAILABLE}
    doc: dict = {"available": True}
    if c is not None:
        doc["cost"] = c
    if m is not None:
        doc["memory"] = m
    return doc


def _setup_probe_costs(solver, b, x0) -> dict | None:
    """Cost of the solve program's SETUP phase, compiled standalone from
    the solver's own SpMV/dot selection -- the subtrahend of the
    per-iteration derivation.  Mirrors ``_cg_program`` /
    ``_cg_pipelined_program`` setup (norms, initial residual, and for
    the pipelined variant ``w = A r`` plus the epilogue's fresh ``(r,
    r)``); the leftover ``maximum``/``sqrt`` scalars are noise at vector
    sizes.  Only the direct classic/pipelined single-chip tiers have a
    probe: the replacement/fused tiers restructure the loop, and the
    shard_map program's setup has no standalone form."""
    import jax
    import jax.numpy as jnp

    from acg_tpu.solvers.jax_cg import _scalar_setup, _spmv_fn

    if getattr(solver, "replace_every", 0):
        return None
    if getattr(solver, "problem", None) is not None:
        return None
    kern = solver.kernels
    if isinstance(kern, str) and kern.startswith("fused"):
        return None
    spmv_ = _spmv_fn(kern)
    dot, _sdt = _scalar_setup(b.dtype, solver.precise_dots)
    pipelined = solver.pipelined

    @jax.jit
    def probe(A, b, x0):
        bn = jnp.sqrt(dot(b, b))
        xn = jnp.sqrt(dot(x0, x0))
        r = b - spmv_(A, x0)
        g = dot(r, r)
        out = (bn, xn, jnp.sqrt(g), r)
        if pipelined:
            w = spmv_(A, r)
            out = out + (w, dot(r, r))
        return out

    try:
        compiled = probe.lower(solver._A_program, b, x0).compile()
    except Exception:  # noqa: BLE001
        return None
    return cost_analysis(compiled)


def per_iteration_cost(solver, b, x0=None, criteria=None,
                       whole: dict | None = None) -> dict | None:
    """Compiler-derived per-iteration flops/bytes for the direct
    single-chip tiers: HloCostAnalysis counts a while/fori body ONCE, so
    per-iteration = cost(whole program) - cost(setup probe).  None
    where either half is unavailable.

    Counting conventions differ from the analytic counters BY DESIGN --
    know them before comparing: XLA bills 2 flops per multiply-add over
    PADDED DIA/ELL plane elements where the analytic model bills 3 per
    stored nonzero (the reference's convention, symmetric entries twice,
    ``cgcuda.c:812``), and ``bytes_accessed`` is fusion-aware where the
    analytic model is a fixed pass count.  The cross-check test
    (tests/test_perfmodel.py) pins a small-factor agreement band --
    tight enough to catch silent drift (wrong pass counts, dropped
    terms, double billing), loose enough not to chase convention gaps.
    """
    import jax.numpy as jnp

    if getattr(solver, "problem", None) is not None:
        # the shard_map program's setup has no standalone probe form
        return None
    dtype = solver._solve_dtype()
    b = jnp.asarray(b, dtype=dtype)
    x0 = jnp.zeros_like(b) if x0 is None else jnp.asarray(x0, dtype=dtype)
    if whole is None:
        whole = analyze_solver(solver, b, x0=x0, criteria=criteria)
    if not whole.get("available") or "cost" not in whole:
        return None
    setup = _setup_probe_costs(solver, b, x0)
    if setup is None:
        return None
    out: dict = {}
    for k in ("flops", "bytes_accessed", "transcendentals"):
        w, s = whole["cost"].get(k), setup.get(k)
        if w is not None and s is not None:
            out[k] = max(w - s, 0.0)
    return out or None


# -- communication ledger -------------------------------------------------

def comm_ledger(solver) -> dict | None:
    """The solver's static per-iteration communication ledger (the
    ``comm_profile`` hook on the distributed tiers: per-neighbour halo
    bytes from the halo plans, psum counts/bytes, ICI-hop estimates from
    the mesh shape).  None for single-device solvers; pure host
    arithmetic -- building it cannot perturb the compiled programs."""
    prof = getattr(solver, "comm_profile", None)
    if prof is None:
        return None
    try:
        return prof()
    except Exception as e:  # noqa: BLE001 -- a ledger bug must not sink a solve
        return {"error": f"{type(e).__name__}: {e}"}


def attach(stats, analysis: dict | None, ledger: dict | None = None,
           per_iteration: dict | None = None) -> None:
    """Record an analysis onto ``stats`` -- fills the ``costmodel:`` /
    ``memory:`` sections of the stats block and its ``--stats-json``
    twin.  Append-only by construction: the reference-format block and
    every existing section are untouched (asserted in
    tests/test_hlo_structure.py)."""
    cm: dict = {}
    if analysis is not None:
        if analysis.get("available"):
            cm.update(analysis.get("cost", {}))
        else:
            cm["unavailable"] = analysis.get("why", UNAVAILABLE)
    if per_iteration:
        cm["per_iteration"] = dict(per_iteration)
    if ledger is not None:
        cm["comm"] = ledger
    if cm:
        stats.costmodel.update(cm)
    if analysis is not None and analysis.get("available"):
        mem = analysis.get("memory")
        if mem:
            stats.memory.update(mem)


# -- analytic traffic model (shared with bench.py) ------------------------

def analytic_bytes_per_iteration(nnz: int, n: int, idx_bytes: float,
                                 mat_itemsize: int, vec_itemsize: int,
                                 pipelined: bool) -> float:
    """OUR analytic HBM traffic per CG iteration: matrix reads in the
    matrix storage dtype (+ per-nonzero index bytes) plus the vector
    passes of the loop (15 classic / 21 pipelined -- the pass count
    implied by the measured 335 MB/iter f32 flagship, BASELINE.md) in
    the vector storage dtype.  ``bench._our_bytes_per_iter`` delegates
    here so the harness and the explain tier cannot drift apart."""
    passes = 21 if pipelined else 15
    return nnz * (mat_itemsize + idx_bytes) + passes * n * vec_itemsize


def triad_probe_gbs(nelems: int = 1 << 26, reps: int = 3,
                    attempts: int = 4, lo: float = 20.0,
                    hi: float = 4000.0) -> float:
    """Two-point chained saxpy-triad HBM bandwidth estimate -- the
    estimator ``bench.bandwidth_probe_gbs`` has always used, hoisted
    here so the --explain tier and the bench harness share ONE
    implementation.  ``a = c + s*a``: 2 reads + 1 write per step; the
    16-vs-4-step chained difference cancels per-dispatch latency.
    ``lo``/``hi`` bound plausibility (the bench defaults suit
    accelerator HBM; --explain lowers ``lo`` for small host-CPU
    probes).  Raises RuntimeError when contention keeps the estimate
    implausible for ``attempts`` tries."""
    import functools

    import jax
    import jax.numpy as jnp

    n = int(nelems)
    c = jnp.full((n,), 0.5, jnp.float32)
    a = jnp.ones((n,), jnp.float32)

    @functools.partial(jax.jit, static_argnames="k")
    def chain(a, c, k):
        return jax.lax.fori_loop(
            0, k, lambda _, v: c + jnp.float32(1.0000001) * v, a)

    def best(k):
        jax.block_until_ready(chain(a, c, k))
        ts = []
        for _ in range(reps):
            t0 = time.perf_counter()
            jax.block_until_ready(chain(a, c, k))
            ts.append(time.perf_counter() - t0)
        return min(ts)

    for _ in range(attempts):
        dt = best(16) - best(4)
        if dt > 0:
            bw = 3.0 * n * 4.0 * 12 / dt / 1e9
            if lo <= bw <= hi:
                return bw
        # contention burst corrupted the estimate; retry
    raise RuntimeError("bandwidth probe unstable (two-point estimate "
                       f"implausible after {attempts} attempts)")


def _probe_cache_path() -> str:
    """The on-disk triad-probe sidecar (``ACG_TPU_PROBE_CACHE``
    overrides; default under the XDG cache dir)."""
    p = os.environ.get("ACG_TPU_PROBE_CACHE")
    if p:
        return p
    base = (os.environ.get("XDG_CACHE_HOME")
            or os.path.join(os.path.expanduser("~"), ".cache"))
    return os.path.join(base, "acg-tpu", "probe_cache.json")


def cached_triad_probe_gbs(nelems: int = 1 << 26, use_cache: bool = True,
                           refresh: bool = False, **kw) -> float:
    """:func:`triad_probe_gbs` behind an on-disk, backend-keyed sidecar
    so repeated ``--explain``/bench runs skip the ~1 s re-probe
    (``--no-probe-cache`` forces a fresh measurement).  Keyed by
    ``platform:device_kind:nelems`` -- a CPU figure can never stand in
    for a TPU one, and the small --explain host probe never collides
    with the full-size bench probe.  ``refresh`` re-measures but still
    updates the sidecar (a fresh probe is the best cache entry); cache
    I/O failures degrade to a plain probe."""
    import jax

    dev = jax.devices()[0]
    key = f"{dev.platform}:{dev.device_kind}:n{int(nelems)}"
    path = _probe_cache_path()
    if use_cache and not refresh:
        try:
            with open(path) as f:
                entry = (json.load(f) or {}).get(key)
            if isinstance(entry, dict) and float(entry.get("gbs", 0)) > 0:
                return float(entry["gbs"])
        except (OSError, ValueError, TypeError):
            pass
    bw = triad_probe_gbs(nelems, **kw)
    if use_cache:
        try:
            os.makedirs(os.path.dirname(path), exist_ok=True)
            try:
                with open(path) as f:
                    cache = json.load(f)
            except (OSError, ValueError):
                cache = {}
            if not isinstance(cache, dict):
                cache = {}
            cache[key] = {"gbs": float(bw), "unix_time": time.time()}
            tmp = f"{path}.tmp.{os.getpid()}"
            with open(tmp, "w") as f:
                json.dump(cache, f, indent=2)
            os.replace(tmp, path)
        except OSError:
            pass
    return bw


def _dispatch_seconds(reps: int = 5, dtype=None) -> float:
    """Per-program dispatch latency (a synced noop): the fixed cost a
    whole-solve program pays ONCE, amortised over its iterations in the
    roofline verdict -- it legitimately dominates short solves
    (dispatch-bound).  ``dtype``
    follows the solve's VECTOR dtype, the same rule the --profile-ops
    dispatch probe applies (solvers/profile.py): an f32 noop under an
    x64/bf16 config would measure a different-dtype program than the
    solve dispatches."""
    import jax
    import jax.numpy as jnp

    dt = jnp.dtype(dtype) if dtype is not None else jnp.float32
    noop = jax.jit(lambda v: v + jnp.asarray(1, v.dtype))
    x = jnp.zeros((8,), dt)
    jax.block_until_ready(noop(x))
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(noop(x))
        ts.append(time.perf_counter() - t0)
    return min(ts)


def predicted_overlap_seconds(led: dict, bw_gbs: float | None,
                              ici_gbs: float | None,
                              halo_s: float | None = None) -> dict | None:
    """The fused tier's overlap verdict from its static ledger: price
    the halo payload against the interconnect and the interior-SpMV
    traffic against HBM, then ``exposed = max(0, halo - interior)`` --
    halo latency is only *felt* where the interior rows' work runs out
    before the puts land (the reference's stream-overlap argument,
    restated in ledger terms).  ``hidden_frac`` is directly comparable
    to the measured solve-windowed overlap-efficiency score a --trace
    capture yields.  ``halo_s`` (the commbench calibration's measured
    per-exchange halo seconds) replaces the bytes-over-ici guess when
    given.  None when a needed bandwidth is unknown."""
    ov = led.get("overlap") or {}
    if not bw_gbs or (halo_s is None and not ici_gbs):
        return None
    t_halo = (halo_s if halo_s is not None else
              led.get("halo_bytes_per_iteration", 0) / (ici_gbs * 1e9))
    t_int = ov.get("interior_matrix_bytes", 0) / (bw_gbs * 1e9)
    exposed = max(0.0, t_halo - t_int)
    out = {"halo_s": t_halo, "interior_spmv_s": t_int,
           "exposed_halo_s": exposed,
           "hidden_frac": (1.0 - exposed / t_halo) if t_halo > 0
           else None}
    if halo_s is not None:
        out["halo_source"] = "commbench calibration"
    return out


def classify_bound(measured_s: float, hbm_s: float, comm_s: float,
                   dispatch_s: float) -> tuple[str, dict]:
    """``(verdict, components)``: attribute a measured iteration time to
    its largest modelled component; whatever the byte/comm/dispatch
    model cannot explain is attributed to compute (or an unmodelled
    term -- the verdict is a pointer, not a proof)."""
    comp = {"HBM-bound": max(hbm_s, 0.0),
            "comm-bound": max(comm_s, 0.0),
            "dispatch-bound": max(dispatch_s, 0.0)}
    comp["compute-bound"] = max(measured_s - sum(comp.values()), 0.0)
    verdict = max(comp, key=lambda k: comp[k])
    return verdict, comp


# -- the CLI --explain tier ----------------------------------------------

def _explain_matrix(args):
    """Host CSR for the explain pass: gen: specs synthesized in-process,
    files read via mtxfile.  Explain is an analysis pass over all three
    solver tiers, so it needs the host matrix -- refuse sizes that only
    the direct on-device assembly path could hold."""
    from acg_tpu.errors import AcgError
    from acg_tpu.matrix import SymCsrMatrix

    if args.A.startswith("gen:"):
        from acg_tpu.cli import _gen_direct_min, _parse_gen_spec
        from acg_tpu.io.generators import (irregular_spd_coo, poisson2d_coo,
                                           poisson3d_coo)

        kind, dim, n, N, avg = _parse_gen_spec(args.A)
        if N > _gen_direct_min():
            raise SystemExit(
                f"acg-tpu: --explain analyses host-assembled tiers "
                f"(N={N:,} rows needs the direct on-device path); use a "
                f"smaller gen: spec")
        if kind == "poisson" and getattr(args, "aniso", None) is not None:
            from acg_tpu.io.generators import aniso_poisson2d_coo
            r, c, v, N = aniso_poisson2d_coo(n, args.aniso)
        elif kind == "poisson":
            gen = poisson2d_coo if dim == 2 else poisson3d_coo
            r, c, v, N = gen(n)
        else:
            r, c, v, N = irregular_spd_coo(n, avg_degree=avg,
                                           seed=args.seed)
        A = SymCsrMatrix.from_coo(N, r, c, v)
    else:
        from acg_tpu.io.mtxfile import read_mtx

        try:
            A = SymCsrMatrix.from_mtx(read_mtx(args.A, binary=args.binary))
        except AcgError as e:
            raise SystemExit(f"acg-tpu: {args.A}: {e}")
    return A.to_csr(epsilon=args.epsilon)


def _fmt_bytes(n: float) -> str:
    return f"{n:,.0f} B" if n < 1 << 20 else f"{n / 2**20:,.1f} MiB"


def _explain_tier(name, solver, b, csr, K, bw_gbs, dispatch_s, on_tpu,
                  err, cal: dict | None = None) -> dict | None:
    """Analyze + time one tier and print its explain block.  Returns the
    verdict row (for the optional --stats-json sink), or None when the
    tier failed entirely.

    With a commbench calibration (``cal``, --calibration FILE or a live
    --commbench run) the comm component is priced from the fitted
    alpha-beta model instead of the ring-hop/ICI_GBS guess, the fused
    overlap verdict prices the MEASURED per-exchange halo seconds, and
    the tier's own measured segment decomposition (SpMV-only /
    reduction-only probes from the dispatched TierOps composition)
    replaces the analytic-bytes prediction -- both the calibrated and
    the uncalibrated predicted s/iter are reported so the calibration's
    effect is auditable."""
    from acg_tpu.ops.spmv import matrix_index_bytes, matrix_dtype
    from acg_tpu.solvers.stats import (StoppingCriteria,
                                       cg_flops_per_iteration)

    an = analyze_solver(solver, b)
    per = per_iteration_cost(solver, b, whole=an)
    led = comm_ledger(solver)

    # timed short solve: warmup absorbs the compile, K iterations
    # unbounded (the benchmark protocol's fixed-trip shape)
    solver.stats.tsolve = 0.0
    solver.solve(b, criteria=StoppingCriteria(maxits=K), warmup=1,
                 host_result=False, raise_on_divergence=False)
    t_iter = solver.stats.tsolve / K

    attach(solver.stats, an, ledger=led, per_iteration=per)

    # analytic fallbacks when the compiler analysis is unavailable
    prob = getattr(solver, "problem", None)
    matfree_tables = None
    if prob is not None:
        nnz, n = int(prob.nnz_total), int(prob.n)
        mat_b = int(np.dtype(prob.dtype).itemsize)
        vec_b = int(np.dtype(prob.vdtype).itemsize)
        idx_b = 0.0 if prob.local.format in ("dia", "matfree") else 4.0
        if getattr(prob, "operator", None) is not None:
            matfree_tables = int(prob.operator.table_bytes())
    else:
        A = solver.A
        nnz, n = int(csr.nnz), int(csr.shape[0])
        mat_b = int(np.dtype(matrix_dtype(A)).itemsize)
        vec_b = int(np.dtype(solver._solve_dtype()).itemsize)
        idx_b = matrix_index_bytes(A)
        if hasattr(A, "matfree_apply"):
            matfree_tables = int(A.table_bytes())
    flops_it_analytic = cg_flops_per_iteration(nnz, n, solver.pipelined)
    if matfree_tables is not None:
        # matrix-free operator tier: the roofline's matrix-bytes term
        # goes to (nearly) zero -- the apply reads the O(grid-side)
        # coefficient tables, not nnz * itemsize of planes.  Flops are
        # unchanged (the multiply-adds still happen)
        bytes_it_analytic = (analytic_bytes_per_iteration(
            0, n, 0.0, 0, vec_b, solver.pipelined) + matfree_tables)
    else:
        bytes_it_analytic = analytic_bytes_per_iteration(
            nnz, n, idx_b, mat_b, vec_b, solver.pipelined)
    spec = getattr(solver, "precond_spec", None)
    if spec is not None:
        # reclassify the roofline for PCG: one M^-1 apply per iteration
        # joins both analytic models (the compiler-derived numbers see
        # it automatically -- the apply is IN the program)
        from acg_tpu.precond import (bytes_per_apply, flops_per_apply,
                                     state_bytes)
        mst = getattr(solver, "_mstate", None)
        sb = state_bytes(mst) if mst is not None else 0
        flops_it_analytic += flops_per_apply(spec, n, 3.0 * nnz)
        bytes_it_analytic += bytes_per_apply(
            spec, n, vec_b, nnz * (mat_b + idx_b) + 2 * n * vec_b, sb)
    bytes_it = per.get("bytes_accessed", bytes_it_analytic) if per \
        else bytes_it_analytic

    comm_bytes = 0
    if led and "error" not in led:
        comm_bytes = (led.get("halo_bytes_per_iteration", 0)
                      + led.get("allreduce_bytes_per_iteration", 0))
    ici = ICI_GBS if on_tpu else bw_gbs
    t_hbm = bytes_it / (bw_gbs * 1e9) if bw_gbs else 0.0
    t_comm = comm_bytes / (ici * 1e9) if (comm_bytes and ici) else 0.0
    t_disp = dispatch_s / max(K, 1)
    # the fused tier's overlap model: its ledger declares how much
    # interior-SpMV work is available to hide the halo behind, so the
    # comm verdict prices the EXPOSED halo seconds -- max(0, halo -
    # interior SpMV) -- instead of the full serialised halo time
    overlap = None
    if led and "error" not in led and led.get("overlap"):
        overlap = predicted_overlap_seconds(led, bw_gbs, ici)
        if overlap is not None and ici:
            t_comm = (overlap["exposed_halo_s"]
                      + led.get("allreduce_bytes_per_iteration", 0)
                      / (ici * 1e9))
    t_comm_uncal = t_comm
    predicted_uncal = t_hbm + t_comm_uncal + t_disp

    # -- the calibrated verdict (acg_tpu.commbench) ---------------------
    cal_comm = segs = None
    cal_id = None
    if cal is not None:
        from acg_tpu import commbench
        cal_id = str(cal.get("calibration_id", ""))
        if led and "error" not in led:
            cal_comm = commbench.comm_seconds(cal, led)
            if led.get("overlap"):
                halo_meas = commbench.halo_exchange_seconds(cal, led)
                ov_cal = predicted_overlap_seconds(led, bw_gbs, ici,
                                                   halo_s=halo_meas)
                if ov_cal is not None:
                    overlap = ov_cal
        segs = commbench.segment_decomposition(solver, b)
        if cal_comm is not None:
            # fitted alpha-beta replaces the ring-hop/ICI_GBS guess;
            # the fused ledger still discounts the hidden halo share
            t_comm = (cal_comm["allreduce_s"]
                      + (overlap["exposed_halo_s"]
                         if overlap is not None
                         else cal_comm["halo_s"]))
    verdict, comp = classify_bound(t_iter, t_hbm, t_comm, t_disp)
    predicted = t_hbm + t_comm + t_disp
    if cal is not None and segs and segs.get("available"):
        # measured segments replace the analytic-HBM stand-in: the
        # SpMV segment (exchange included, as dispatched) plus the
        # reduction component (fitted alpha-beta where a mesh ledger
        # exists, the measured psum-ladder probe otherwise) plus the
        # amortised dispatch
        sseg = segs["segments"]
        spmv_seg = sseg.get("spmv", {}).get("s_per_iteration", 0.0)
        red_seg = (cal_comm["allreduce_s"] if cal_comm is not None
                   else sseg.get("reduction", {})
                   .get("s_per_iteration", 0.0))
        predicted = spmv_seg + red_seg + t_disp
    attained = (t_hbm / t_iter) if t_iter > 0 else 0.0

    err.write(f"== explain: {name} ==\n")
    solver.stats.fwrite(err, indent=2)
    if an.get("available") and "cost" in an:
        c = an["cost"]
        err.write(f"  compiler: flops {c.get('flops', 0):,.4g}, bytes "
                  f"accessed {c.get('bytes_accessed', 0):,.4g} per program"
                  f" (loop body counted once by HloCostAnalysis"
                  f"{'; per device' if prob is not None else ''})\n")
    else:
        err.write(f"  compiler: cost {an.get('why', UNAVAILABLE)}\n")
    if per:
        err.write(f"  per-iteration (compiler-derived): flops "
                  f"{per.get('flops', 0):,.4g}, bytes "
                  f"{per.get('bytes_accessed', 0):,.4g}; analytic: flops "
                  f"{flops_it_analytic:,.4g}, bytes "
                  f"{bytes_it_analytic:,.4g}\n")
    else:
        err.write(f"  per-iteration (analytic): flops "
                  f"{flops_it_analytic:,.4g}, bytes "
                  f"{bytes_it_analytic:,.4g}\n")
    if matfree_tables is not None:
        err.write(f"  matrix-free operator: matrix bytes/SpMV "
                  f"{matfree_tables:,} (generated planes read only the "
                  f"coefficient tables; the assembled twin reads "
                  f"{nnz * (mat_b + idx_b):,.0f})\n")
    mem = an.get("memory") if an.get("available") else None
    if mem:
        err.write(f"  memory (HBM footprint): arguments "
                  f"{_fmt_bytes(mem.get('argument_bytes', 0))} + output "
                  f"{_fmt_bytes(mem.get('output_bytes', 0))} + temp "
                  f"{_fmt_bytes(mem.get('temp_bytes', 0))} = "
                  f"{_fmt_bytes(mem.get('total_hbm_bytes', 0))}\n")
    if led and "error" not in led:
        err.write(f"  comm ledger: halo "
                  f"{led.get('halo_bytes_per_iteration', 0):,} B/iter, "
                  f"allreduce {led.get('allreduce_per_iteration', 0)} x "
                  f"{led.get('allreduce_scalars', 0)} scalars "
                  f"({led.get('allreduce_bytes_per_iteration', 0)} B/iter),"
                  f" max {led.get('max_hops', 0)} hop(s) "
                  f"[{led.get('transport', '?')}]\n")
    if overlap is not None:
        ov = led["overlap"]
        hid = overlap.get("hidden_frac")
        err.write(f"  overlap model (interior|border split, "
                  f"{ov.get('interior_rows', 0):,} interior / "
                  f"{ov.get('border_rows', 0):,} border rows): halo "
                  f"{overlap['halo_s']:.3e} s vs interior SpMV "
                  f"{overlap['interior_spmv_s']:.3e} s -> predicted "
                  f"exposed {overlap['exposed_halo_s']:.3e} s/iter"
                  + (f" ({hid:.0%} hidden)" if hid is not None else "")
                  + "\n")
    if segs is not None:
        if segs.get("available"):
            sseg = segs["segments"]
            parts_txt = " + ".join(
                f"{k} {v['s_per_iteration']:.3e}"
                for k, v in sseg.items() if k != "halo")
            halo_txt = (f" (halo {sseg['halo']['s_per_iteration']:.3e}"
                        f" inside spmv)" if "halo" in sseg else "")
            err.write(f"  segments (measured, {segs['reps']} chained "
                      f"reps/probe): {parts_txt} ="
                      f" {segs['explained_s_per_iteration']:.3e} "
                      f"s/iter explained{halo_txt}\n")
        else:
            err.write(f"  segments: unavailable "
                      f"({segs.get('why', '?')})\n")
    if cal is not None:
        if cal_comm is not None:
            err.write(f"  calibrated comm (alpha-beta, {cal_id}): "
                      f"allreduce {cal_comm['allreduce_s']:.3e} + "
                      f"halo[{cal_comm['halo_kind']}] "
                      f"{cal_comm['halo_s']:.3e} s/iter (replaces the "
                      f"ring-hop/ICI stand-in)\n")
        elif led is None:
            err.write(f"  calibrated comm ({cal_id}): no comm ledger "
                      f"on this tier (single device) -- segments "
                      f"carry the calibration\n")
    bw_txt = f"{bw_gbs:,.1f} GB/s" if bw_gbs else "unavailable"
    err.write(f"  roofline: probe {bw_txt}"
              + (f", ici {ici:,.0f} GB/s (stand-in)" if comm_bytes and
                 on_tpu and cal_comm is None else "")
              + f"; predicted {predicted:.3e} s/iter"
              + (f" (measured segments + fitted comm + dispatch; "
                 f"uncalibrated model {predicted_uncal:.3e})"
                 if cal is not None and predicted != predicted_uncal
                 else f" (hbm {t_hbm:.3e} + comm {t_comm:.3e} + "
                      f"dispatch {t_disp:.3e})") + "\n")
    ratio = (predicted / t_iter) if t_iter > 0 else 0.0
    ratio_uncal = (predicted_uncal / t_iter) if t_iter > 0 else 0.0
    err.write(f"  measured {t_iter:.3e} s/iter over {K} iterations; "
              f"attained {attained:.2f}x of HBM roofline; "
              f"predicted/measured {ratio:.2f}x"
              + (f" (uncalibrated {ratio_uncal:.2f}x; calibration "
                 f"{cal_id})" if cal is not None else "")
              + f"; verdict: {verdict}\n\n")

    row = {"tier": name, "measured_s_per_iter": t_iter,
           "predicted_s_per_iter": predicted,
           "attained_roofline_frac": attained, "bound": verdict,
           "components_s": comp}
    if matfree_tables is not None:
        row["matrix_free"] = True
        row["matrix_bytes_per_spmv"] = matfree_tables
    if overlap is not None:
        row["overlap_model"] = overlap
    if cal is not None:
        row["calibration"] = cal_id
        row["uncalibrated_predicted_s_per_iter"] = predicted_uncal
        solver.stats.costmodel["calibration"] = cal_id
        if cal_comm is not None:
            row["calibrated_comm_s"] = cal_comm
    if segs is not None and segs.get("available"):
        row["segments"] = segs
        solver.stats.costmodel["segments"] = segs
    return row


def build_explain_dist_solver(args, csr, nparts, dtype, vec_dtype,
                              operator=None, **solver_kw):
    """The dist analysis tier's construction, shared by
    :func:`run_explain` and the commbench observatory (ONE copy: same
    partition method/seed, same transport resolution -- a commbench
    calibration must describe the very mesh the explain verdict
    prices).  ``operator`` (a matrix-free stencil) forces the band
    partition it requires and arms the matfree local block."""
    from acg_tpu.ops.spmv import prefers_dia
    from acg_tpu.parallel.dist import (DistCGSolver, DistributedProblem,
                                       arm_matfree, resolve_comm)
    from acg_tpu.partition import partition_rows

    method = "band" if operator is not None or prefers_dia(csr) \
        else "graph"
    part = partition_rows(csr, nparts, seed=args.seed, method=method)
    prob = DistributedProblem.build(csr, part, nparts, dtype=dtype,
                                    vector_dtype=vec_dtype)
    if operator is not None:
        arm_matfree(prob, operator)
    return DistCGSolver(prob, pipelined=False,
                        comm=resolve_comm(args.comm),
                        precise_dots=args.precise_dots,
                        kernels=args.kernels, **solver_kw)


def run_explain(args, dtype, vec_dtype) -> int:
    """The CLI ``--explain`` driver: build the system once, then for the
    classic, pipelined and distributed tiers lower + compile the exact
    solve programs, extract compiler cost/memory, build the comm ledger,
    time a short solve, and print the roofline verdict per tier.
    Single-controller analysis pass; exits 0 when at least one tier
    reported."""
    import jax

    err = sys.stderr
    csr = _explain_matrix(args)
    n = csr.shape[0]
    b = np.ones(n)
    K = max(8, min(args.max_iterations, 60))
    on_tpu = jax.default_backend() == "tpu"
    nparts = args.nparts or min(len(jax.devices()), 4)
    # the communication observatory's calibration (a saved --calibration
    # doc or a live --commbench run, loaded/collected by the CLI): the
    # comm components below are then priced from its fitted alpha-beta
    # model and the tiers run measured segment decompositions
    cal = getattr(args, "_calibration", None)
    cal_mismatch_event = None
    if cal is not None:
        from acg_tpu.commbench import KINDS
        src = getattr(args, "_calibration_source", None) \
            or "live --commbench run"
        fitted = [k for k in KINDS
                  if isinstance(cal.get("collectives", {}).get(k), dict)
                  and "alpha_s" in cal["collectives"][k]]
        err.write(f"== explain: calibration ==\n"
                  f"  id {cal.get('calibration_id')} ({src}); fitted "
                  f"kinds: {', '.join(fitted) or 'none'}; benchmarked "
                  f"on a {cal.get('nparts')}-part mesh\n")
        mismatches = []
        if int(cal.get("nparts", 0)) != int(nparts):
            mismatches.append(f"mesh {cal.get('nparts')} parts vs this "
                              f"run's {nparts}")
        cal_backend = cal.get("backend")
        if cal_backend and str(cal_backend) != jax.default_backend():
            mismatches.append(f"backend {cal_backend} vs this run's "
                              f"{jax.default_backend()}")
        if mismatches:
            detail = (f"calibration {cal.get('calibration_id')}: "
                      + "; ".join(mismatches)
                      + " -- fitted latencies may not transfer")
            err.write(f"  WARNING: {detail}\n")
            # the structured twin of the warning (the decision
            # observatory's audit trail): an event the stats-json /
            # history consumers and the metrics textfile can gate on,
            # not just a stderr line
            cal_mismatch_event = {"t": time.time(),
                                  "kind": "calibration-mismatch",
                                  "detail": detail}
            from acg_tpu import metrics, observatory
            metrics.record_event_kind("calibration-mismatch")
            observatory.note_event("calibration-mismatch", detail)
        err.write("\n")
    bw = None
    use_cache = not getattr(args, "no_probe_cache", False)
    try:
        # full-size probe on real HBM; a small (16 MiB/vector) variant
        # elsewhere -- host CPUs move the small triad fast enough, and
        # --explain must stay cheap in CPU test runs.  Behind the
        # backend-keyed sidecar so repeated explain runs skip the
        # re-probe (--no-probe-cache forces one)
        bw = (cached_triad_probe_gbs(use_cache=use_cache) if on_tpu
              else cached_triad_probe_gbs(1 << 22, use_cache=use_cache,
                                          lo=0.5))
    except Exception as e:  # noqa: BLE001
        err.write(f"acg-tpu: bandwidth probe failed ({e}); roofline "
                  f"fractions unavailable\n")
    disp = _dispatch_seconds(dtype=vec_dtype)

    import jax.numpy as jnp

    from acg_tpu.ops.spmv import device_matrix_from_csr
    from acg_tpu.solvers.jax_cg import JaxCGSolver

    # the matrix-free operator tier (--operator): the single tiers run
    # over the operator itself, the dist tier arms the matfree local
    # block -- the roofline's matrix-bytes term then goes to ~0
    op = None
    if getattr(args, "_operator_spec", None) is not None:
        # ONE construction path with the CLI solve (validation against
        # the gen: matrix, SystemExit wrapping, manifest identity
        # recording) -- a duplicate here would let the two drift
        from acg_tpu.cli import _build_cli_operator
        op = _build_cli_operator(args, n, dtype)
    op_id = op.identity() if op is not None else None
    op_tag = f", operator {op_id}" if op_id else ""

    rows = []
    # under --trace the WHOLE tier sweep runs inside one profiler
    # capture (acg_tpu.tracing): the measured section below then
    # confronts the static ledger with per-op-class device time from
    # the same programs the verdicts describe
    from acg_tpu.tracing import profiler_trace
    with profiler_trace(args.trace):
        # ONE device assembly serves both single-chip tiers (A is immutable;
        # rebuilding it per tier would re-upload every plane)
        A = op if op is not None else device_matrix_from_csr(
            csr, dtype=dtype, format=args.spmv_format)
        for name, pipelined in (("cg", False), ("cg-pipelined", True)):
            try:
                # the session's recovery policy rides along (--recover):
                # lower_solve arms detect exactly like solve(), so the
                # analyzed/timed programs are the configured ones
                solver = JaxCGSolver(A, pipelined=pipelined,
                                     precise_dots=args.precise_dots,
                                     kernels=args.kernels,
                                     vector_dtype=vec_dtype,
                                     recovery=getattr(args, "_recovery",
                                                      None),
                                     precond=getattr(args, "_precond", None))
                pc = getattr(args, "_precond", None)
                row = _explain_tier(
                    f"{name} ({solver.kernels} kernels, {args.dtype}"
                    + (f", precond {pc}" if pc is not None else "")
                    + op_tag + ")",
                    solver, jnp.asarray(b, solver._solve_dtype()), csr, K, bw,
                    disp, on_tpu, err, cal=cal)
                if row:
                    rows.append((row, solver))
            except Exception as e:  # noqa: BLE001 -- one tier must not sink the rest
                err.write(f"acg-tpu: explain tier {name} failed: "
                          f"{type(e).__name__}: {e}\n")

        # one distributed tier: the halo'd multi-part program over however
        # many devices this host exposes (capped -- the ledger and verdict,
        # not scaling, are the point here)
        try:
            solver = build_explain_dist_solver(
                args, csr, nparts, dtype, vec_dtype, operator=op,
                recovery=getattr(args, "_recovery", None),
                precond=getattr(args, "_precond", None))
            pc = getattr(args, "_precond", None)
            row = _explain_tier(f"dist-cg (nparts={nparts}, {solver.kernels} "
                                f"kernels, {args.dtype}"
                                + (f", precond {pc}" if pc is not None
                                   else "") + op_tag + ")", solver, b,
                                csr, K, bw, disp, on_tpu, err, cal=cal)
            if row:
                rows.append((row, solver))
        except Exception as e:  # noqa: BLE001
            err.write(f"acg-tpu: explain tier dist-cg failed: "
                      f"{type(e).__name__}: {e}\n")

    # with a capture: confront the ledgers above with MEASURED device
    # time from the very programs the verdicts describe (acg_tpu.
    # tracing) -- per-op-class seconds, overlap efficiency, and the
    # measured-vs-predicted comm line.  Without --trace this section is
    # absent and the static verdict stands unchanged
    # the mismatch event rides every tier's stats twin, so --stats-json
    # consumers see it next to the comm components it taints
    if cal_mismatch_event is not None:
        for _row, solver in rows:
            solver.stats.events.append(dict(cal_mismatch_event))

    if args.trace:
        _explain_measured(args, rows, K, err)

    # the numerical-health tier's convergence verdict: kappa from the
    # Lanczos tridiagonal of a traced host-oracle solve, the CG-bound
    # predicted iteration count against the measured one, and (when a
    # preconditioner is armed) the kappa(A)/kappa(M^-1 A) effectiveness
    # score -- one tier-independent section (kappa is a property of the
    # operator + preconditioner, not of the execution tier)
    _explain_convergence(args, csr, rows, err)

    if args.stats_json:
        from acg_tpu import telemetry

        try:
            from acg_tpu.commbench import UNCALIBRATED
            for row, solver in rows:
                man = telemetry.run_manifest(
                    metric=f"explain:{row['tier']}", matrix=str(args.A),
                    dtype=args.dtype, explain=row, operator=op_id,
                    calibration=(cal.get("calibration_id")
                                 if cal is not None else UNCALIBRATED))
                telemetry.write_stats_json(args.stats_json, solver.stats,
                                           manifest=man, append=True)
        except OSError as e:
            err.write(f"acg-tpu: {args.stats_json}: {e}\n")
    return 0 if rows else 1


def _explain_measured(args, rows, K: int, err) -> dict | None:
    """The ``--explain`` measured section: parse the capture the tier
    sweep just wrote, print per-op-class device seconds + the
    overlap-efficiency score, and confront the static ledger's
    predicted collective seconds (each tier's comm component x its K
    timed iterations) with the measured ones.  Degrades to a one-line
    why when the capture is unusable (xplane-only schema, failed
    profiler start) -- the static verdict above stands either way."""
    from acg_tpu import tracing

    analysis = tracing.analyze_trace(args.trace)
    err.write("== explain: measured (profiler trace) ==\n")
    for line in tracing.format_analysis(analysis):
        err.write(line + "\n")
    if analysis.get("available"):
        predicted = sum(
            row["components_s"].get("comm-bound", 0.0) * K
            for row, _ in rows)
        err.write(tracing.measured_comm_line(
            analysis, predicted,
            label=f"comm ledger x {K} iters/tier") + "\n")
        # per-KIND confrontation: the commbench alpha-beta fit priced
        # allreduce and halo separately, and the capture now breaks
        # collective seconds out by kind -- confront them kind by kind
        kinds = (analysis.get("collective_kind_seconds_in_solve")
                 or analysis.get("collective_kind_seconds") or {})
        cal_rows = [row for row, _ in rows
                    if row.get("calibrated_comm_s")]
        if kinds and cal_rows:
            pred_ar = sum(r["calibrated_comm_s"]["allreduce_s"] * K
                          for r in cal_rows)
            pred_halo = sum(r["calibrated_comm_s"]["halo_s"] * K
                            for r in cal_rows)
            meas_ar = kinds.get("all_reduce", 0.0)
            meas_halo = sum(v for k, v in kinds.items()
                            if k != "all_reduce")
            err.write(f"  per-kind (commbench fit x {K} iters/"
                      f"calibrated tier): allreduce predicted "
                      f"{pred_ar:.3e} s vs measured {meas_ar:.3e} s; "
                      f"halo predicted {pred_halo:.3e} s vs measured "
                      f"{meas_halo:.3e} s\n")
        # the fused tier's overlap verdict, confronted: the static
        # ledger's predicted hidden fraction vs the capture's measured
        # solve-windowed overlap-efficiency score (same quantity, one
        # modelled, one observed)
        eff = analysis.get("overlap_efficiency")
        for row, _ in rows:
            ov = row.get("overlap_model")
            if ov is None or ov.get("hidden_frac") is None:
                continue
            err.write(f"  overlap verdict [{row['tier']}]: ledger "
                      f"predicts {ov['hidden_frac']:.0%} of halo "
                      f"latency hidden"
                      + (f"; measured solve-windowed "
                         f"overlap-efficiency {eff:.2%}"
                         if eff is not None else
                         "; no measured overlap in this capture")
                      + "\n")
        # the tracing: stats section rides every tier's --stats-json
        # document (one capture covers the whole sweep, so no per-tier
        # op attribution is claimed -- ops rows stay as analyzed)
        # None values (no straggler, overlap n/a) are suppressed, the
        # way tracing.attach builds the section
        compact = {k: analysis[k] for k in
                   ("available", "nfiles", "op_seconds",
                    "collective_seconds", "collective_kind_seconds",
                    "exposed_collective_seconds",
                    "overlap_efficiency", "straggler")
                   if analysis.get(k) is not None}
        for _, solver in rows:
            solver.stats.tracing.update(compact)
    err.write("\n")
    return analysis


def _explain_convergence(args, csr, rows, err) -> dict | None:
    """The ``--explain`` "convergence" section (acg_tpu.health): run
    the eager f64 host oracle traced (cheap at explain sizes), rebuild
    the Lanczos tridiagonal from its (alpha, beta) window, and print
    the kappa estimate + predicted-vs-measured verdict.  The report
    also lands on every tier's ``health:`` stats section so the
    --stats-json twin carries it."""
    import numpy as np

    from acg_tpu import health as health_mod
    from acg_tpu.solvers.host_cg import HostCGSolver
    from acg_tpu.solvers.stats import StoppingCriteria

    # the oracle is an eager single-threaded f64 loop: bound it by
    # matrix size the way _explain_tier bounds its timed solves by K --
    # --explain is documented as a cheap introspection pass, and a
    # multi-million-nnz oracle solve (x2 under --precond) is not
    if csr.shape[0] > 200_000 or csr.nnz > 2_000_000:
        err.write("== explain: convergence ==\n  (skipped: matrix too "
                  "large for the host-oracle Lanczos estimate; run a "
                  "normal solve with --audit-every + --convergence-log "
                  "for the device-side spectrum report)\n\n")
        return None
    rtol = (args.residual_rtol
            if 0 < args.residual_rtol < 1 else 1e-9)
    crit = StoppingCriteria(maxits=min(max(args.max_iterations, 200),
                                       2000),
                            residual_rtol=rtol)
    b = np.ones(csr.shape[0])
    pc = getattr(args, "_precond", None)
    try:
        kappa_ref = None
        if pc is not None:
            # the effectiveness baseline: kappa(A) from an
            # unpreconditioned oracle run of the same system
            plain = HostCGSolver(csr, trace=4096)
            plain.solve(b, criteria=crit, raise_on_divergence=False)
            ref = health_mod.spectrum_estimate(plain.last_trace)
            kappa_ref = (ref or {}).get("kappa")
        hs = HostCGSolver(csr, trace=4096, precond=pc)
        hs.solve(b, criteria=crit, raise_on_divergence=False)
        rep = health_mod.convergence_report(
            hs.last_trace, hs.stats.niterations, rtol,
            precond=str(pc) if pc is not None else None,
            kappa_ref=kappa_ref)
    except Exception as e:  # noqa: BLE001 -- the verdict must not sink
        err.write(f"acg-tpu: explain convergence verdict failed: "
                  f"{type(e).__name__}: {e}\n")
        return None
    if rep is None:
        err.write("== explain: convergence ==\n  (window too short "
                  "for a Lanczos estimate)\n\n")
        return None
    err.write("== explain: convergence (host-oracle Lanczos "
              "estimate) ==\n")
    err.write(f"  operator {rep['operator']}: lambda "
              f"{rep['lambda_min']:.4g} .. {rep['lambda_max']:.4g}"
              + (f", kappa {rep['kappa']:.4g}" if rep.get("kappa")
                 else ", kappa unavailable (non-positive Ritz value)")
              + f" (m={rep['m']})\n")
    if rep.get("precond_effectiveness") is not None:
        err.write(f"  preconditioner effectiveness: kappa(A) "
                  f"{rep['kappa_unpreconditioned']:.4g} / "
                  f"kappa(M^-1 A) {rep['kappa']:.4g} = "
                  f"{rep['precond_effectiveness']:.2f}x spectrum "
                  f"compression\n")
    pred = rep.get("predicted_iterations")
    if pred is not None:
        meas = rep["measured_iterations"]
        verdict = ("within-bound" if meas <= pred
                   else "OVER-bound (measured exceeds the worst-case "
                        "CG bound: suspect the estimate window or "
                        "numerical trouble)")
        err.write(f"  CG bound at rtol {rep['rtol']:g}: predicted "
                  f"<= {pred} iterations; measured {meas} "
                  f"({meas / pred:.2f}x); verdict: {verdict}\n")
    err.write("\n")
    for _row, solver in rows:
        solver.stats.health.setdefault("spectrum", rep)
    return rep


# -- bench regression gate ------------------------------------------------

# the sentinel row of a capture whose backend never came up (older
# bench.py runs emitted it): value 0 iters/s, not a performance case.
# A capture consisting of it alone describes a run that never reached hardware -- comparing
# against it can only mislead (ROADMAP Recent notes r05)
UNAVAILABLE_METRIC = "bench_backend_unavailable"


def split_unavailable(cases: dict) -> tuple[dict, bool]:
    """Drop the backend-unavailable sentinel from a case dict; returns
    ``(real_cases, sentinel_was_present)``.  A capture that is ONLY the
    sentinel must exit 2 with a re-baseline message, never enter a
    comparison."""
    had = any(k == UNAVAILABLE_METRIC or
              k.startswith(UNAVAILABLE_METRIC + "|") for k in cases)
    return {k: v for k, v in cases.items()
            if not (k == UNAVAILABLE_METRIC
                    or k.startswith(UNAVAILABLE_METRIC + "|"))}, had


def refuse_unavailable(old: dict, new: dict, old_name: str,
                       new_name: str) -> tuple[dict, dict, bool]:
    """The shared regression-gate guard (check_regression and
    scripts/bench_diff.py): strip the backend-unavailable sentinel from
    both captures and, when either side carried ONLY the sentinel,
    print the re-baseline refusal and flag exit 2.  Returns
    ``(old_cases, new_cases, refused)``."""
    old, old_unavail = split_unavailable(old)
    new, new_unavail = split_unavailable(new)
    refused = (old_unavail and not old) or (new_unavail and not new)
    if refused:
        which = old_name if old_unavail and not old else new_name
        print(f"bench-diff: {which} records {UNAVAILABLE_METRIC} (the "
              f"backend never came up): no comparable cases -- "
              f"re-baseline before trusting --fail-on-regress",
              file=sys.stderr)
    return old, new, refused


def _doc_case(doc: dict):
    """``(key, value)`` for one --stats-json document: the case key is
    the manifest metric (bench rows) or solver:matrix (CLI solves), the
    value iterations/second from the stats twin.

    A ``/3`` SOAK capture (``stats.soak`` present) is valued at its
    median instead -- p50 iterations over p50 latency -- so two soak
    runs of the same case diff on the steady-state figure, not on a
    cumulative ``tsolve`` whose meaning shifts with the solve count."""
    man = doc.get("manifest") or {}
    st = doc.get("stats") or {}
    metric = man.get("metric")
    if metric is None:
        metric = f"{man.get('solver', 'solve')}:{man.get('matrix', '?')}"
    metric = _precond_keyed(metric, man.get("precond"))
    metric = _batch_keyed(metric, man.get("nrhs"), man.get("block_cg"))
    metric = _operator_keyed(metric, man.get("operator"))
    metric = _calibration_keyed(metric, man.get("calibration"))
    soak = st.get("soak") or {}
    if soak:
        try:
            lat = float((soak.get("latency") or {}).get("p50") or 0.0)
            its = float((soak.get("iterations") or {}).get("p50") or 0.0)
        except (TypeError, ValueError):
            return None
        if lat <= 0 or its <= 0:
            return None
        return str(metric), its / lat
    try:
        tsolve = float(st.get("tsolve", 0.0))
        niter = float(st.get("niterations", 0))
    except (TypeError, ValueError):
        return None
    if tsolve <= 0 or niter <= 0:
        return None
    return str(metric), niter / tsolve


def _precond_keyed(metric, precond) -> str:
    """Fold the precond selection into the case key: a preconditioned
    capture must NEVER silently diff against a plain one -- their
    iterations/second measure different algorithms."""
    metric = str(metric)
    if precond and str(precond) != "none":
        return f"{metric}|precond={precond}"
    return metric


def _batch_keyed(metric, nrhs, block=None) -> str:
    """Fold the batch selection into the case key (the _precond_keyed
    pattern): a B-wide batched (or block-CG) capture measures a
    different program than a single-RHS one and must never silently
    diff against it."""
    metric = str(metric)
    try:
        b = int(nrhs or 0)
    except (TypeError, ValueError):
        b = 0
    if b > 1:
        metric = f"{metric}|nrhs={b}"
        if block:
            metric = f"{metric}|block"
    return metric


def _operator_keyed(metric, operator) -> str:
    """Fold the operator selection into the case key (the
    _precond_keyed pattern): a matrix-free capture runs a different
    program -- zero matrix HBM traffic -- than an assembled one of the
    same system and must never silently diff against it.  Absent keys
    (every assembled capture, and all pre-/11 captures) add nothing, so
    old baselines keep comparing."""
    metric = str(metric)
    op = str(operator or "")
    if op and op != "none":
        return f"{metric}|operator={op}"
    return metric


def _calibration_keyed(metric, calibration) -> str:
    """Fold a commbench calibration id into the case key (the
    _precond_keyed pattern): two captures explained/priced under
    DIFFERENT calibrations measure against different models and must
    never diff silently -- they become distinct, reported-not-gated
    cases.  The ``"uncalibrated"`` sentinel (and absent keys, every
    pre-/10 capture) adds nothing, so old baselines keep comparing."""
    metric = str(metric)
    cal = str(calibration or "")
    if cal and cal != "uncalibrated":
        return f"{metric}|cal={cal}"
    return metric


def _row_case(row: dict):
    """``(key, value)`` for one bench summary row (the JSON lines bench
    prints / BENCH_*.json records)."""
    metric, value = row.get("metric"), row.get("value")
    if metric is None or not isinstance(value, (int, float)):
        return None
    key = _precond_keyed(metric, row.get("precond"))
    key = _batch_keyed(key, row.get("nrhs"), row.get("block"))
    key = _operator_keyed(key, row.get("operator"))
    key = _calibration_keyed(key, row.get("calibration"))
    return key, float(value)


def rows_to_cases(rows) -> dict:
    """Best value per metric over a list of bench row dicts."""
    cases: dict = {}
    for row in rows:
        c = _row_case(row)
        if c is not None:
            cases[c[0]] = max(cases.get(c[0], float("-inf")), c[1])
    return cases


def load_cases(path) -> dict:
    """Parse a capture file into ``{metric: best_value}``.  Accepts
    either format on either side of a diff: ``--stats-json`` documents
    (one indented document, or JSONL-appended as bench writes them) or
    bench summary-row JSONL (BENCH_*.json); non-JSON lines (the ``#``
    commentary bench interleaves) are skipped."""
    with open(path) as f:
        text = f.read()
    objs = []
    try:
        whole = json.loads(text)
        objs = whole if isinstance(whole, list) else [whole]
    except ValueError:
        for line in text.splitlines():
            line = line.strip()
            if not line or line.startswith("#"):
                continue
            try:
                objs.append(json.loads(line))
            except ValueError:
                continue
    cases: dict = {}
    for obj in objs:
        if not isinstance(obj, dict):
            continue
        if isinstance(obj.get("parsed"), dict):
            # the growth driver's BENCH_r0N.json wrapper: the row it
            # parsed from the run's stdout rides under "parsed"
            obj = obj["parsed"]
        if isinstance(obj.get("doc"), dict) and "ledger" in obj:
            # a run-history ledger line (acg_tpu.observatory): the
            # stats document rides under "doc"
            obj = obj["doc"]
        c = _doc_case(obj) if "stats" in obj else _row_case(obj)
        if c is not None:
            cases[c[0]] = max(cases.get(c[0], float("-inf")), c[1])
    return cases


def compare_cases(old: dict, new: dict, pct: float
                  ) -> tuple[list[str], int, int]:
    """``(report_lines, nregressed, ncompared)``: case-by-case diff of
    two capture dicts.  A case regresses when its new value falls more
    than ``pct`` percent below the baseline; cases present on only one
    side are reported but never gate (a renamed row must not silently
    pass OR fail -- the no-common-cases outcome is its own exit code)."""
    lines: list[str] = []
    nreg = ncmp = 0
    for key in sorted(set(old) | set(new)):
        if key not in old:
            lines.append(f"bench-diff: {key}: (new case) {new[key]:,.2f}")
            continue
        if key not in new:
            lines.append(f"bench-diff: {key}: baseline-only "
                         f"({old[key]:,.2f}); not gated")
            continue
        ncmp += 1
        o, v = old[key], new[key]
        delta = (v - o) / o * 100.0 if o else 0.0
        if o > 0 and v < o * (1.0 - pct / 100.0):
            nreg += 1
            lines.append(f"bench-diff: {key}: {o:,.2f} -> {v:,.2f} "
                         f"({delta:+.1f}% REGRESSION, threshold "
                         f"-{pct:g}%)")
        else:
            lines.append(f"bench-diff: {key}: {o:,.2f} -> {v:,.2f} "
                         f"({delta:+.1f}%)")
    return lines, nreg, ncmp


def load_baseline_cases(baseline_path) -> dict | None:
    """Baseline cases for the regression gate.  A DIRECTORY is a
    run-history ledger (acg_tpu.observatory, ``--history``): the
    best-known USABLE value per case across every entry, with
    ``bench_backend_unavailable`` captures skipped automatically (they
    never reached hardware).  Prints the refusal and returns
    None (exit 2) when the ledger is empty or ALL its entries are
    unusable -- an all-unavailable history must force a re-baseline,
    never silently pass."""
    if not os.path.isdir(baseline_path):
        return load_cases(baseline_path)
    from acg_tpu.observatory import load_history_baseline
    cases, all_unavailable, nentries = \
        load_history_baseline(baseline_path)
    if all_unavailable:
        print(f"bench-diff: every capture in {baseline_path} records "
              f"{UNAVAILABLE_METRIC} (the backend never came up "
              f"for all {nentries} entr{'y' if nentries == 1 else 'ies'}"
              f"): no usable baseline -- re-baseline before trusting "
              f"--fail-on-regress", file=sys.stderr)
        return None
    if not cases:
        print(f"bench-diff: {baseline_path}: no usable ledger entries "
              f"(empty history directory?)", file=sys.stderr)
        return None
    return cases


def check_regression(rows, baseline_path, pct: float) -> int:
    """The ``bench.py --baseline FILE --fail-on-regress PCT`` gate:
    compare this run's emitted rows against the baseline capture --
    a file, or a ``--history`` ledger DIRECTORY (the best usable prior
    capture per case; see :func:`load_baseline_cases`).
    Exit-code contract (shared with scripts/bench_diff.py): 0 = no
    regression, 1 = regression past the threshold, 2 = nothing
    comparable (unreadable baseline / no common cases / an
    all-unavailable history) -- 2 is a failure too, so a renamed
    metric cannot silently green the gate."""
    try:
        old = load_baseline_cases(baseline_path)
    except OSError as e:
        print(f"bench-diff: {baseline_path}: {e}", file=sys.stderr)
        return 2
    if old is None:
        return 2
    old, new, refused = refuse_unavailable(old, rows_to_cases(rows),
                                           str(baseline_path),
                                           "this run")
    if refused:
        return 2
    lines, nreg, ncmp = compare_cases(old, new, pct)
    for ln in lines:
        print(ln, file=sys.stderr)
    if ncmp == 0:
        print("bench-diff: no comparable cases between this run and "
              f"{baseline_path}", file=sys.stderr)
        return 2
    return 1 if nreg else 0
