"""Benchmark harness: CG iterations/second on the reference workloads.

Protocol (BASELINE.md, from the reference's scripts): Poisson stencil
matrices, fixed 1000-iteration CG solves, warmup before timing, metric =
iterations/second ("total solver time" for a fixed iteration count).
Runs on whatever accelerator JAX exposes (one TPU chip under the driver).

Default mode prints ONE JSON line for the flagship config (2D Poisson
n=2048, N=4,194,304, classic CG), the best of {f32, bf16} x {pallas,
xla}:
  {"metric": ..., "value": N, "unit": "iters/s", "vs_baseline": N,
   "dtype": ..., "kernels": ..., "bw_gbs": N, "roofline_frac": N}
``bw_gbs`` is a ~1 s triad bandwidth probe and ``roofline_frac`` the
fraction of that bandwidth the solve achieved.  A
bf16 winner also reports its measured accuracy cost
(``rel_residual_1000it``; recovery via --refine is documented in
BASELINE.md).

``--full`` runs the BASELINE ladder (classic + pipelined x 2D n=2048 /
3D 128^3 / 3D 256^3, plus the distributed program at nparts=1 to bound
sharding overhead) and prints one JSON line per row.

``--sweep-np`` runs the multi-chip CPU-mesh correctness sweep
(np=1,2,4,8, the reference's single-node scaling protocol,
``scripts/nccl_combined.sh:41-176``): iterations-to-rtol must stay
nearly flat across mesh sizes.  Re-executes itself on a provisioned
virtual CPU mesh, so it works from any platform.

``vs_baseline`` divides by an analytic roofline for one H100 running the
reference's CUDA solver on the same workload (HBM-bound: ~587 MB of f64
traffic per iteration at 3.35 TB/s with ~80% efficiency -> ~4500 iters/s
for the flagship; other configs scale by the reference's own f64
bytes/iter, not ours).  The reference repo publishes no measured numbers
(BASELINE.md); this analytic stand-in is documented there and replaced
when measured numbers exist.  Timed solves repeat ``TIMED_REPEATS``
times and report the best.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

MAXITS = 1000
WARMUP_ITS = 50

# Analytic H100 baseline, flagship config (see module docstring/BASELINE.md)
H100_BASELINE_ITERS_PER_SEC = 4500.0
# The reference's bytes/iteration on the flagship config, in ITS dtype
# (strictly f64 values + int32 column indices, ``comm.h:180-183``):
# nnz*(8+4) + 10 vector passes * 8 B = ~587 MB for 2D n=2048.  The
# stand-in for other configs scales 4500 iters/s by the reference's own
# traffic ratio -- NOT by our f32 traffic, which would wrongly credit
# the H100 with our halved-precision bandwidth advantage.
_FLAGSHIP_REF_BYTES_PER_ITER = (5 * 2048**2 - 4 * 2048) * 12.0 + 80.0 * 2048**2
# timed repeats per row; the row reports the best
TIMED_REPEATS = 5


# --stats-json sink: the telemetry tier's structured writer
# (acg_tpu.telemetry.write_stats_json, JSONL-appended one document per
# measured case) -- the same schema-versioned twin of the fwrite block
# the CLI writes, so bench captures and CLI solves feed one consumer
_STATS_SINK: str | None = None


_CALIBRATION_ID: str | None = None


def _sink_stats(row: dict, solver) -> None:
    """Append the timed solver's full stats document for this row."""
    if _STATS_SINK is None or solver is None:
        return
    try:
        from acg_tpu import telemetry

        man = telemetry.run_manifest(
            metric=row.get("metric"), dtype=row.get("dtype"),
            kernels=row.get("kernels"), format=row.get("format"),
            # rides into the bench-diff case key (perfmodel._doc_case):
            # preconditioned captures never diff against plain ones,
            # and differently-calibrated captures key apart too
            precond=row.get("precond"),
            calibration=_CALIBRATION_ID)
        telemetry.write_stats_json(_STATS_SINK, solver.stats,
                                   manifest=man, append=True)
    except Exception as e:  # noqa: BLE001 -- the sink must never sink a row
        print(f"# stats-json sink failed: {type(e).__name__}: {e}",
              file=sys.stderr)


def _ref_bytes_per_iter(csr) -> float:
    """The reference's analytic HBM traffic per classic-CG iteration
    (f64 values, int32 indices -- same accounting as its GB/s printout,
    ``cgcuda.c:1942-1957``)."""
    return csr.nnz * 12.0 + 80.0 * csr.shape[0]


def _our_bytes_per_iter(nnz: int, n: int, idx_bytes: float,
                        mat_itemsize: int, vec_itemsize: int,
                        pipelined: bool) -> float:
    """OUR analytic HBM traffic per CG iteration: matrix reads in the
    matrix storage dtype (+``idx_bytes`` index bytes per nonzero --
    ops.spmv.matrix_index_bytes) plus the vector passes of the loop
    (15 classic / 21 pipelined, the pass count implied by the measured
    335 MB/iter f32 flagship -- BASELINE.md) in the vector storage
    dtype (they differ under --dtype mixed).  Delegates to the perfmodel
    tier's shared model, which the --explain roofline and the
    cost_analysis cross-check test also consume -- one model, no drift."""
    from acg_tpu.perfmodel import analytic_bytes_per_iteration

    return analytic_bytes_per_iteration(nnz, n, idx_bytes, mat_itemsize,
                                        vec_itemsize, pipelined)


# storage tiers: (matrix dtype, vector dtype) by bench dtype name;
# "mixed" = bf16 matrix + f32 vectors (lossless for Poisson stencil
# values -> arithmetic-identical to f32 at half the matrix traffic);
# "bf16" = half traffic everywhere but kappa-limited (~500) vector
# storage -- diverges at flagship conditioning, measured and reported;
# "bf16rr" = bf16 with periodic f32 residual replacement every
# REPLACE_EVERY iterations (solvers.jax_cg._cg_replaced_program): the
# SOUND half-traffic tier -- f32-class residuals at flagship
# conditioning for ~2% replacement overhead (round 4)
REPLACE_EVERY = 50


def _dtypes_of(dtype_name: str):
    import jax.numpy as jnp

    return {"f32": (jnp.float32, jnp.float32),
            "mixed": (jnp.bfloat16, jnp.float32),
            "bf16": (jnp.bfloat16, jnp.bfloat16),
            "bf16rr": (jnp.bfloat16, jnp.bfloat16)}[dtype_name]


_probe_cache: float | None = None
_USE_PROBE_CACHE = True


def bandwidth_probe_gbs(refresh: bool = False) -> float:
    """~1 s saxpy-triad HBM bandwidth probe on the current device.

    Reported as ``bw_gbs`` in every JSON row.  Uses the two-point
    chained-program estimator (solvers/profile.py rationale) so the
    per-dispatch latency cancels.
    """
    global _probe_cache
    if _probe_cache is not None and not refresh:
        return _probe_cache
    # the chained two-point estimator (dispatch latency
    # cancelled, 20-4000 GB/s plausibility bounds) lives in the
    # perfmodel tier now, shared with the --explain roofline verdict;
    # raises RuntimeError("bandwidth probe unstable ...") as before.
    # Behind the backend-keyed on-disk sidecar so repeated bench runs
    # skip the ~1 s re-probe; refresh (the per-row call
    # sites) re-measures but still refreshes the sidecar, and
    # --no-probe-cache bypasses the disk entirely
    from acg_tpu.perfmodel import cached_triad_probe_gbs

    _probe_cache = cached_triad_probe_gbs(
        1 << 26, use_cache=_USE_PROBE_CACHE,
        refresh=refresh)  # 256 MB per f32 vector
    return _probe_cache


def _h100_standin(ref_bytes_per_iter: float) -> float:
    """HBM-roofline iters/s estimate for the reference on one H100."""
    return (H100_BASELINE_ITERS_PER_SEC
            * _FLAGSHIP_REF_BYTES_PER_ITER / ref_bytes_per_iter)


def _build(side: int, dim: int):
    """dim 2/3 = Poisson stencils; dim 0 = irregular power-law SPD with
    ``side`` rows (the SuiteSparse-workload stand-in, configs 4-5)."""
    from acg_tpu.io.generators import (irregular_spd_coo, poisson2d_coo,
                                       poisson3d_coo)
    from acg_tpu.matrix import SymCsrMatrix

    gen = {2: poisson2d_coo, 3: poisson3d_coo,
           0: lambda n: irregular_spd_coo(n, avg_degree=16.0, seed=0)}[dim]
    r, c, v, N = gen(side)
    return SymCsrMatrix.from_coo(N, r, c, v).to_csr()


# wall-clock cap for one row's TIMING loop: fewer repeats on a slow
# config beats a row that outlives its caller's time limit
ROW_TIME_BUDGET = 420.0


def _time_solver(solver, b, criteria_cls, repeats: int = TIMED_REPEATS,
                 time_budget_s: float | None = None, **solve_kwargs):
    """Best-of-``repeats`` time of a ``MAXITS``-iteration solve, as
    ``(tsolve, maxits, info)``, after one compile-and-warm solve of
    ``WARMUP_ITS`` iterations.  ``info["budget_exhausted"]`` says
    whether ``time_budget_s`` (a cap on the WALL CLOCK of the whole
    timing loop) cut the repeats short; the first timed run always
    happens."""
    t_start = time.monotonic()

    def over_budget() -> bool:
        return (time_budget_s is not None
                and time.monotonic() - t_start > time_budget_s)

    def timed(its: int) -> float:
        solver.stats.tsolve = 0.0
        solver.solve(b, criteria=criteria_cls(maxits=its), **solve_kwargs)
        return solver.stats.tsolve

    timed(WARMUP_ITS)  # compile + warm
    times = [timed(MAXITS)]
    for _ in range(repeats - 1):
        if over_budget():
            break
        times.append(timed(MAXITS))
    if max(times) > 1.5 * min(times):
        print(f"# spread: solve times ranged "
              f"{min(times):.3f}-{max(times):.3f}s over {len(times)} runs",
              file=sys.stderr)
    return min(times), MAXITS, {"budget_exhausted": over_budget()}


def _roofline_context(row: dict, bytes_per_iter: float,
                      info: dict | None = None) -> dict:
    """Attach ``bw_gbs`` (a fresh triad bandwidth probe) and
    ``roofline_frac`` (achieved traffic over probe bandwidth).
    ``roofline_frac`` can legitimately exceed 1.0 for configs whose
    working set is partly on-chip-resident (v5e VMEM is 128 MiB)."""
    try:
        bw = bandwidth_probe_gbs(refresh=True)
    except Exception as e:  # noqa: BLE001 -- the probe must not sink rows
        print(f"# bandwidth probe failed: {e}", file=sys.stderr)
        return row
    row["bw_gbs"] = round(bw, 1)
    row["roofline_frac"] = round(
        row["value"] * bytes_per_iter / (bw * 1e9), 3)
    if info is not None and info.get("budget_exhausted"):
        row["budget_exhausted"] = True
    return row


def run_case(csr, name: str, pipelined: bool, dist: bool = False,
             kernels: str = "xla", dtype_name: str = "f32",
             spmv_format: str = "auto") -> dict:
    import jax.numpy as jnp
    import numpy as np

    from acg_tpu.solvers.stats import StoppingCriteria

    mat_dtype, vec_dtype = _dtypes_of(dtype_name)
    b = np.ones(csr.shape[0], dtype=np.float32)
    if dist:
        from acg_tpu.parallel.dist import DistCGSolver, DistributedProblem
        from acg_tpu.partition import partition_rows

        part = partition_rows(csr, 1, seed=0)
        prob = DistributedProblem.build(csr, part, 1, dtype=mat_dtype,
                                        vector_dtype=vec_dtype)
        solver = DistCGSolver(prob, pipelined=pipelined)
        fmt = prob.local.format
        idx_bytes = 0.0 if fmt == "dia" else 4.0
    else:
        from acg_tpu.ops.spmv import device_matrix_from_csr
        from acg_tpu.solvers.jax_cg import JaxCGSolver

        from acg_tpu.ops.spmv import matrix_index_bytes

        A = device_matrix_from_csr(csr, dtype=mat_dtype, format=spmv_format)
        solver = JaxCGSolver(
            A, pipelined=pipelined, kernels=kernels,
            vector_dtype=vec_dtype,
            replace_every=REPLACE_EVERY if dtype_name == "bf16rr" else 0)
        fmt = type(A).__name__.replace("Matrix", "").lower()
        idx_bytes = matrix_index_bytes(A)
    tsolve, maxits, info = _time_solver(solver, b, StoppingCriteria,
                                        time_budget_s=ROW_TIME_BUDGET)
    iters_per_sec = maxits / tsolve
    standin = _h100_standin(_ref_bytes_per_iter(csr))
    print(f"# {name}: total solver time: {tsolve:.6f} seconds "
          f"({solver.stats.nflops * 1e-9 / tsolve:.1f} Gflop/s)",
          file=sys.stderr)
    row = {
        "metric": name,
        "value": round(iters_per_sec, 2),
        "unit": "iters/s",
        "vs_baseline": round(iters_per_sec / standin, 4),
        "dtype": dtype_name,
        "format": fmt,
    }
    if hasattr(solver, "kernels"):
        # record the *resolved* tier so an off-TPU run of the pallas-named
        # case cannot masquerade as a Pallas measurement
        row["kernels"] = solver.kernels
    mvb = np.dtype(mat_dtype).itemsize
    vvb = np.dtype(vec_dtype).itemsize
    row = _roofline_context(
        row, _our_bytes_per_iter(csr.nnz, csr.shape[0], idx_bytes, mvb,
                                 vvb, pipelined),
        info=info)
    _sink_stats(row, solver)
    return row


def run_host_baseline(csr, name: str, kind: str) -> dict:
    """Host/external baseline row (f64 on the host CPU): ``petsc`` =
    the scipy-CG external oracle, ``native`` = the C++ core solver."""
    import numpy as np

    from acg_tpu.solvers.stats import StoppingCriteria

    if kind == "petsc":
        from acg_tpu.solvers.petsc_cg import PetscBaselineSolver
        solver = PetscBaselineSolver(csr)
    else:
        from acg_tpu.solvers.host_cg import NativeHostCGSolver
        solver = NativeHostCGSolver(csr)
    b = np.ones(csr.shape[0])
    tsolve, maxits, _ = _time_solver(solver, b, StoppingCriteria, repeats=2,
                                     time_budget_s=ROW_TIME_BUDGET)
    iters_per_sec = maxits / tsolve
    standin = _h100_standin(_ref_bytes_per_iter(csr))
    print(f"# {name}: total solver time: {tsolve:.6f} seconds",
          file=sys.stderr)
    row = {"metric": name, "value": round(iters_per_sec, 2),
           "unit": "iters/s",
           "vs_baseline": round(iters_per_sec / standin, 4),
           "dtype": "f64", "host": True}
    _sink_stats(row, solver)
    return row


def _enable_compile_cache():
    from acg_tpu._platform import enable_compile_cache

    enable_compile_cache()


# headline-eligibility threshold for the bf16-family tiers: the true
# relative residual after the protocol's 1000 iterations under the
# manufactured-solution setup must be f32-class.  Measured flagship
# values: f32 8.0e-7, bf16rr 1.0e-6, plain bf16 2.2e-1 (stall), so the
# gate cleanly separates sound from stalled tiers with margin
SOUND_REL_RESIDUAL = 1e-4


def _accuracy_context(csr, row: dict, dtype_name: str) -> dict:
    """Measure a bf16-family tier's accuracy next to its speed: the TRUE
    f64 relative residual after the protocol's fixed iteration count,
    under the reference's own verification setup (random unit-norm
    manufactured xsol, b = A xsol -- ``cuda/acg-cuda.c:1969-1984``; the
    benchmark scripts always run with --manufactured-solution,
    ``scripts/nccl_combined.sh:55-60``).  b = ones is NOT used here: at
    flagship conditioning its solution norm is ~1e8, putting even exact
    f32 arithmetic at an O(10) relative-residual floor -- a scale
    artifact that would mask the actual soundness difference between
    tiers (plain bf16 stalls at 2e-1, replacement reaches 1e-6)."""
    import jax.numpy as jnp
    import numpy as np

    from acg_tpu.ops.spmv import device_matrix_from_csr
    from acg_tpu.solvers.jax_cg import JaxCGSolver
    from acg_tpu.solvers.stats import StoppingCriteria

    try:
        rng = np.random.default_rng(0)
        xsol = rng.standard_normal(csr.shape[0])
        xsol /= np.linalg.norm(xsol)
        b = (csr @ xsol).astype(np.float32)
        A = device_matrix_from_csr(csr, dtype=jnp.bfloat16)
        s = JaxCGSolver(
            A, kernels="xla",
            replace_every=REPLACE_EVERY if dtype_name == "bf16rr" else 0)
        x = np.asarray(s.solve(b, criteria=StoppingCriteria(maxits=MAXITS),
                               raise_on_divergence=False), dtype=np.float64)
        rel = float(np.linalg.norm(b - csr @ x) / np.linalg.norm(b))
        row["rel_residual_1000it"] = float(f"{rel:.3g}")
        row["error_2norm_1000it"] = float(
            f"{np.linalg.norm(x - xsol):.3g}")
    except Exception as e:  # noqa: BLE001 -- context must not sink the row
        print(f"# accuracy context failed: {e}", file=sys.stderr)
    return row


def _accuracy_context_dia(A, row: dict, replace_every: int) -> dict:
    """Soundness gate for the bf16-family tiers at DIRECT-DIA sizes,
    fully device-resident (no host CSR exists at 512^3): manufactured
    f32 unit-norm xsol, ``b = A xsol`` in f32 arithmetic (lossless for
    bf16-exact stencil values), then the tier's own solve for the
    protocol's ``MAXITS`` iterations, and the TRUE df64 relative
    residual -- ``dia_mv_roll_df`` carries ~48 mantissa bits, so the
    reported residual is not capped by f32 roundoff."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from acg_tpu.parallel.sharded_dia import dia_mv_roll_df
    from acg_tpu.ops.spmv import dia_mv_roll
    from acg_tpu.solvers.jax_cg import JaxCGSolver
    from acg_tpu.solvers.stats import StoppingCriteria

    assert replace_every
    N, offsets = A.nrows, A.offsets

    try:
        @jax.jit
        def build(key, planes):
            x = jax.random.normal(key, (N,), jnp.float32)
            x = x / jnp.linalg.norm(x)
            return x, dia_mv_roll(planes, offsets, x)

        xsol, b = build(jax.random.key(0), A.data)
        s = JaxCGSolver(A, kernels="auto", vector_dtype=jnp.bfloat16,
                        replace_every=replace_every)
        x = s.solve(b, criteria=StoppingCriteria(maxits=MAXITS),
                    raise_on_divergence=False, host_result=False)

        @jax.jit
        def norms(planes, b, x, xsol):
            ah, al = dia_mv_roll_df(planes, offsets, x,
                                    jnp.zeros_like(x))
            r = (b - ah) - al
            return (jnp.linalg.norm(r), jnp.linalg.norm(b),
                    jnp.linalg.norm(x - xsol))

        rn, bn, en = norms(A.data, b, x, xsol)
        row["rel_residual_1000it"] = float(f"{float(rn) / float(bn):.3g}")
        row["error_2norm_1000it"] = float(f"{float(en):.3g}")
    except Exception as e:  # noqa: BLE001 -- context must not sink the row
        print(f"# accuracy context failed: {e}", file=sys.stderr)
    return row


def run_case_dia(side: int, dim: int, name: str,
                 dtype_name: str = "f32") -> dict:
    """Stencil configs assembled DIRECTLY as DIA planes (no COO/CSR/sort
    preprocessing) -- the only practical route to the north-star 512^3
    problem (N=134M, ~0.9G nnz) on one chip: ~4 GB of f32 planes built
    in seconds instead of tens of GB of COO intermediates.

    ``bf16rr`` runs the sound half-traffic tier (periodic f32 residual
    replacement, solvers.jax_cg._cg_replaced_program) and measures its
    soundness at 3D conditioning next to the speed (round-4 verdict
    item 1: the tier that makes the 2D flagship green must run -- and
    be accuracy-gated -- at the problem size the project is named for)."""
    import jax.numpy as jnp
    import numpy as np

    _enable_compile_cache()

    from acg_tpu.io.generators import poisson_dia_device
    from acg_tpu.ops.spmv import DiaMatrix
    from acg_tpu.solvers.jax_cg import JaxCGSolver
    from acg_tpu.solvers.stats import StoppingCriteria

    mat_dtype, vec_dtype = _dtypes_of(dtype_name)
    replace_every = REPLACE_EVERY if dtype_name == "bf16rr" else 0
    planes, offsets, N = poisson_dia_device(side, dim, dtype=mat_dtype)
    A = DiaMatrix(data=tuple(planes), offsets=offsets,
                  nrows=N, ncols_padded=N)
    n_axis = N // side
    nnz = N + 2 * dim * (N - n_axis)  # full-storage stencil nonzeros
    solver = JaxCGSolver(A, kernels="auto", vector_dtype=vec_dtype,
                         replace_every=replace_every)
    # b lives on device from birth, and results stay device-resident
    # (host_result=False): no 537 MB host<->device copy is part of the
    # measured solve; 2 repeats keep the row inside a bench budget
    b = jnp.ones(N, dtype=jnp.float32 if replace_every else vec_dtype)
    tsolve, maxits, info = _time_solver(solver, b, StoppingCriteria,
                                        repeats=2, host_result=False,
                                        time_budget_s=ROW_TIME_BUDGET)
    iters_per_sec = maxits / tsolve
    standin = _h100_standin(nnz * 12.0 + 80.0 * N)
    print(f"# {name}: total solver time: {tsolve:.6f} seconds",
          file=sys.stderr)
    # report what actually RAN: the pallas tier routes wide-band DIA
    # (512^3's +-n^2 diagonals) back to XLA's shifted-views SpMV
    kernels = solver.kernels
    if kernels.startswith("pallas"):
        from acg_tpu.ops.pallas_kernels import dia_spmv_route

        if dia_spmv_route(offsets, N, vec_dtype)[0] == "xla":
            kernels = "xla"
    row = {"metric": name, "value": round(iters_per_sec, 2),
           "unit": "iters/s",
           "vs_baseline": round(iters_per_sec / standin, 4),
           "dtype": dtype_name, "kernels": kernels}
    if replace_every:
        row = _accuracy_context_dia(A, row, replace_every)
        if row.get("rel_residual_1000it",
                   float("inf")) >= SOUND_REL_RESIDUAL:
            row["sound"] = False  # speed without the accuracy contract
    mvb = np.dtype(mat_dtype).itemsize
    vvb = 2 if replace_every else np.dtype(vec_dtype).itemsize
    row = _roofline_context(
        row, _our_bytes_per_iter(nnz, N, 0.0, mvb, vvb, False),
        info=info)
    _sink_stats(row, solver)
    return row


def sweep_np(out=sys.stdout) -> int:
    """Multi-chip correctness sweep on the virtual CPU mesh: iterations to
    residual_rtol=1e-6 at np=1,2,4,8 (should be nearly flat -- CG
    iteration count is partition-invariant up to rounding)."""
    from acg_tpu._platform import provision_host_mesh

    jax = provision_host_mesh(8)
    jax.config.update("jax_enable_x64", True)
    if len(jax.devices()) < 8:
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
        import subprocess
        return subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--sweep-np"],
            env=env).returncode

    import jax.numpy as jnp
    import numpy as np

    from acg_tpu.parallel.dist import DistCGSolver, DistributedProblem
    from acg_tpu.partition import partition_rows
    from acg_tpu.solvers.stats import StoppingCriteria

    csr = _build(256, 2)  # N=65,536: big enough to partition meaningfully
    rng = np.random.default_rng(0)
    xsol = rng.standard_normal(csr.shape[0])
    xsol /= np.linalg.norm(xsol)
    b = csr @ xsol
    rows = []
    for nparts in (1, 2, 4, 8):
        part = partition_rows(csr, nparts, seed=0, method="band")
        prob = DistributedProblem.build(csr, part, nparts, dtype=jnp.float64)
        solver = DistCGSolver(prob, pipelined=False)
        x = solver.solve(b, criteria=StoppingCriteria(
            maxits=5000, residual_rtol=1e-6))
        err = float(np.linalg.norm(x - xsol))
        rows.append({"np": nparts, "iterations": solver.stats.niterations,
                     "error_2norm": err, "local_format": prob.local.format})
        print(f"# np={nparts}: {solver.stats.niterations} iterations, "
              f"error {err:.3e} ({prob.local.format})", file=sys.stderr)
    iters = [r["iterations"] for r in rows]
    flat = max(iters) - min(iters) <= max(2, int(0.02 * max(iters)))
    print(json.dumps({"metric": "dist_cg_iters_to_rtol1e-6_np_sweep",
                      "rows": rows, "flat": flat}), file=out)

    # the DIRECT-ASSEMBLY route (sharded on-device planes + derived
    # halo, parallel/sharded_dia -- the north-star path) swept the same
    # way: manufactured solution, iterations to rtol must stay flat
    from acg_tpu.parallel.sharded_dia import build_sharded_poisson_solver

    n3 = 32
    rows2 = []
    for nparts in (1, 2, 4, 8):
        s = build_sharded_poisson_solver(n3, 3, nparts=nparts)
        xsol, b = s.manufactured(seed=0)
        x = s.solve(b, criteria=StoppingCriteria(maxits=5000,
                                                 residual_rtol=1e-6),
                    host_result=False)
        err = float(np.linalg.norm(np.asarray(x, np.float64)
                                   - np.asarray(xsol, np.float64)))
        rows2.append({"np": nparts, "iterations": s.stats.niterations,
                      "error_2norm": err})
        print(f"# direct np={nparts}: {s.stats.niterations} iterations, "
              f"error {err:.3e}", file=sys.stderr)
    iters2 = [r["iterations"] for r in rows2]
    flat2 = max(iters2) - min(iters2) <= max(2, int(0.02 * max(iters2)))
    print(json.dumps({"metric": "direct_dia_iters_to_rtol1e-6_np_sweep",
                      "rows": rows2, "flat": flat2}), file=out)

    # IRREGULAR workload over the mesh (VERDICT r2 item 6): graph
    # partition -> ELL local blocks; iterations to rtol must stay flat
    csr_i = _build(20_000, 0)
    xsol_i = rng.standard_normal(csr_i.shape[0])
    xsol_i /= np.linalg.norm(xsol_i)
    b_i = csr_i @ xsol_i
    rows3 = []
    for nparts in (1, 2, 4, 8):
        part = partition_rows(csr_i, nparts, seed=0, method="graph")
        prob = DistributedProblem.build(csr_i, part, nparts,
                                        dtype=jnp.float64)
        solver = DistCGSolver(prob)
        x = solver.solve(b_i, criteria=StoppingCriteria(
            maxits=5000, residual_rtol=1e-6))
        err = float(np.linalg.norm(x - xsol_i))
        rows3.append({"np": nparts, "iterations": solver.stats.niterations,
                      "error_2norm": err,
                      "local_format": prob.local.format})
        print(f"# irregular np={nparts}: {solver.stats.niterations} "
              f"iterations, error {err:.3e} ({prob.local.format})",
              file=sys.stderr)
    iters3 = [r["iterations"] for r in rows3]
    flat3 = max(iters3) - min(iters3) <= max(2, int(0.02 * max(iters3)))
    print(json.dumps({"metric": "irregular_iters_to_rtol1e-6_np_sweep",
                      "rows": rows3, "flat": flat3}), file=out)
    return 0 if (flat and flat2 and flat3) else 1


def run_soak_mode(args) -> int:
    """``bench.py --soak N``: the service-soak harness over one Poisson
    matrix (``--soak-side``/``--soak-dim``) -- N repeated fixed-work
    solves through :func:`acg_tpu.soak.run_soak`, one JSON summary row
    (p50/p95/p99 latency, drift verdict) on stdout, the full
    ``acg-tpu-stats/3`` document on ``--stats-json``, a Prometheus
    textfile on ``--metrics-file``, and the ``--fail-on-drift`` exit
    gate (exit 7) shared with the CLI."""
    import numpy as np

    from acg_tpu import metrics, soak
    from acg_tpu.ops.spmv import device_matrix_from_csr
    from acg_tpu.solvers.jax_cg import JaxCGSolver
    from acg_tpu.solvers.stats import StoppingCriteria

    metrics.arm()
    if args.metrics_file:
        metrics.install_flush_handlers(args.metrics_file)
    from acg_tpu.precond import parse_precond
    pc = parse_precond(args.precond)
    name = (f"soak_poisson{args.soak_dim}d_n{args.soak_side}"
            f"_{args.soak_dtype}_x{args.soak}")
    csr = _build(args.soak_side, args.soak_dim)
    mat_dtype, vec_dtype = _dtypes_of(args.soak_dtype)
    A = device_matrix_from_csr(csr, dtype=mat_dtype)
    solver = JaxCGSolver(A, kernels="auto", vector_dtype=vec_dtype,
                         precond=pc)
    b = np.ones(csr.shape[0], dtype=np.float32)
    # fixed-iteration protocol (the bench convention): every solve does
    # identical work, so the latency distribution measures the SYSTEM,
    # not the convergence path
    crit = StoppingCriteria(maxits=args.soak_its)
    t0 = time.perf_counter()
    x, report = soak.run_soak(
        solver, b, nsolves=args.soak, criteria=crit,
        fail_on_drift=args.fail_on_drift,
        first_solve_kwargs={"warmup": 1},
        solve_kwargs={"raise_on_divergence": False,
                      "host_result": False},
        progress_every=max(1, args.soak // 10), what="bench-soak")
    print(f"# {name}: {args.soak} solves in "
          f"{time.perf_counter() - t0:.1f}s", file=sys.stderr)
    lat, its = report["latency"], report["iterations"]
    row = {
        "metric": name,
        # iters/s at the medians: the longitudinally comparable figure
        "value": (round(its["p50"] / lat["p50"], 2)
                  if lat.get("p50") and its.get("p50") else 0.0),
        "unit": "iters/s",
        "dtype": args.soak_dtype,
        "kernels": getattr(solver, "kernels", "auto"),
        "latency_p50_s": lat["p50"], "latency_p95_s": lat["p95"],
        "latency_p99_s": lat["p99"],
        "drift_ratio": report["drift"]["ratio"],
        "drift_tripped": report["drift"]["tripped"],
        "nsolves": args.soak,
    }
    if pc is not None:
        # folded into the diff case key by perfmodel._row_case
        row["precond"] = str(pc)
    print(json.dumps(row))
    _sink_stats(row, solver)
    if args.metrics_file:
        metrics.write_textfile(args.metrics_file)
    rc = _finish(args, [row], 0)
    return rc or soak.gate_exit_code(report, args.fail_on_drift)


def run_batched_mode(args) -> int:
    """``bench.py --batched``: the batched-vs-sequential throughput
    case (ISSUE 11 acceptance) -- solves/second at B in {1, 4, 8} for
    one Poisson matrix, each B measured as ONE batched multi-RHS solve
    against a sequential loop of B single-RHS solves of the SAME
    columns (fixed-iteration protocol, so every row does identical
    numerical work), plus the block-CG iteration-count case on the
    --aniso family (block total iterations vs the sum of B independent
    solves).  One JSON row per case.

    Measured over the 8-part mesh (the virtual CPU mesh off-TPU, the
    sweep_np provisioning): the per-iteration collectives are where
    the B-invariance pays -- a sequential loop moves B x the
    allreduces/halo exchanges of one batched solve.

    Re-baseline note: the nrhs/block keys join the bench-diff case key
    (perfmodel._batch_keyed), so the FIRST batched capture starts a
    fresh baseline series -- r05 was bench_backend_unavailable and no
    prior batched rows exist to diff against (ROADMAP Recent)."""
    import numpy as np

    from acg_tpu._platform import provision_host_mesh

    jax = provision_host_mesh(8)
    if len(jax.devices()) < 8:
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env.setdefault("XLA_FLAGS",
                       "--xla_force_host_platform_device_count=8")
        import subprocess
        return subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--batched",
             "--batched-side", str(args.batched_side),
             "--batched-its", str(args.batched_its),
             "--batched-aniso-side", str(args.batched_aniso_side)]
            + (["--stats-json", args.stats_json] if args.stats_json
               else [])
            + (["--baseline", args.baseline] if args.baseline else []),
            env=env).returncode

    import jax.numpy as jnp

    from acg_tpu.io.generators import batched_rhs
    from acg_tpu.ops.spmv import device_matrix_from_csr
    from acg_tpu.parallel.dist import DistCGSolver, DistributedProblem
    from acg_tpu.parallel.dist_batched import BatchedDistCGSolver
    from acg_tpu.partition import partition_rows
    from acg_tpu.solvers.batched import BatchedCGSolver
    from acg_tpu.solvers.jax_cg import JaxCGSolver
    from acg_tpu.solvers.stats import StoppingCriteria

    side, its = args.batched_side, args.batched_its
    csr = _build(side, 2)
    n = csr.shape[0]
    nparts = 8
    part = partition_rows(csr, nparts, seed=0, method="band")
    prob = DistributedProblem.build(csr, part, nparts,
                                    dtype=jnp.float32)
    Bcols = batched_rhs(n, 8, seed=0, dtype=np.float32)
    crit = StoppingCriteria(maxits=its)   # fixed-work protocol
    rows = []

    def best_of(fn, reps=3):
        ts = []
        for _ in range(reps):
            t0 = time.perf_counter()
            fn()
            ts.append(time.perf_counter() - t0)
        return min(ts)

    seq = DistCGSolver(prob)
    seq.solve(Bcols[:, 0], criteria=crit, host_result=False)  # compile
    for nb in (1, 4, 8):
        cols = Bcols[:, :nb]
        bs = BatchedDistCGSolver(prob)
        # compile outside timing (both sides)
        jax.block_until_ready(bs.solve(cols, criteria=crit, host_result=False))

        def batched_once():
            jax.block_until_ready(bs.solve(cols, criteria=crit,
                                           host_result=False))

        def sequential_once():
            for j in range(nb):
                jax.block_until_ready(seq.solve(cols[:, j], criteria=crit,
                                                host_result=False))

        t_b = best_of(batched_once)
        t_s = best_of(sequential_once)
        row = {
            "metric": f"batched_cg_solves_per_sec_poisson2d_n{side}"
                      f"_np{nparts}_f32_its{its}",
            "nrhs": nb,
            "value": round(nb / t_b, 3),
            "unit": "solves/s",
            "dtype": "f32",
            "nparts": nparts,
            "sequential_solves_per_sec": round(nb / t_s, 3),
            "speedup_vs_sequential": round(t_s / t_b, 3),
        }
        print(f"# B={nb}: batched {t_b:.3f}s vs sequential {t_s:.3f}s "
              f"({t_s / t_b:.2f}x)", file=sys.stderr)
        print(json.dumps(row))
        rows.append(row)
        _sink_stats(row, bs)
        sys.stdout.flush()

    # block-CG iteration acceptance on the aniso family: total block
    # iterations (trips x B) vs the summed iterations of B independent
    # solves to the same tolerance
    from acg_tpu.io.generators import aniso_poisson2d_coo
    from acg_tpu.matrix import SymCsrMatrix
    jax.config.update("jax_enable_x64", True)
    r, c, v, N = aniso_poisson2d_coo(args.batched_aniso_side, 0.05)
    acsr = SymCsrMatrix.from_coo(N, r, c, v).to_csr()
    Aa = device_matrix_from_csr(acsr, dtype=jnp.float64)
    B8 = batched_rhs(N, 8, seed=0)
    tol = StoppingCriteria(maxits=50000, residual_rtol=1e-8)
    blk = BatchedCGSolver(Aa, mode="block")
    blk.solve(B8, criteria=tol)
    trips = blk.stats.batch["block_iterations"]
    indep = 0
    for j in range(8):
        s1 = JaxCGSolver(Aa, kernels="xla")
        s1.solve(B8[:, j], criteria=tol)
        indep += s1.stats.niterations
    ratio = trips * 8 / indep
    row = {
        "metric": f"block_cg_iters_ratio_aniso_n"
                  f"{args.batched_aniso_side}_eps0.05_rtol1e-8",
        "nrhs": 8,
        "block": True,
        "value": round(ratio, 4),
        "unit": "block_total/indep_sum",
        "block_iterations": int(trips),
        "block_total_iterations": int(trips * 8),
        "independent_iterations_sum": int(indep),
    }
    print(f"# block-CG: {trips} trips x 8 = {trips * 8} vs "
          f"{indep} independent ({ratio:.3f}x)", file=sys.stderr)
    print(json.dumps(row))
    rows.append(row)
    _sink_stats(row, blk)
    return _finish(args, rows, 0)


def run_algorithms_mode(args) -> int:
    """``bench.py --algorithms``: the communication-avoiding recurrence
    sweep (ISSUE 12 acceptance) -- s/iteration and the static comm
    ledger for classic, GV-pipelined, sstep:{2,4,8} and p(l):{2,3} over
    ONE Poisson matrix on the 8-part mesh (the virtual CPU mesh
    off-TPU, the sweep_np provisioning), fixed-iteration protocol so
    every row does comparable numerical work.  One JSON row per
    algorithm; the ledger columns show the reduction-count drop
    (classic 2 allreduce/iter -> sstep 1 per S iterations, p(l) 1
    fused) that is the whole point of the tier."""
    import numpy as np

    from acg_tpu._platform import provision_host_mesh

    jax = provision_host_mesh(8)
    if len(jax.devices()) < 8:
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env.setdefault("XLA_FLAGS",
                       "--xla_force_host_platform_device_count=8")
        import subprocess
        return subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--algorithms",
             "--algorithms-side", str(args.algorithms_side),
             "--algorithms-its", str(args.algorithms_its),
             "--fail-on-regress", str(args.fail_on_regress)]
            + (["--stats-json", args.stats_json] if args.stats_json
               else [])
            + (["--baseline", args.baseline] if args.baseline else []),
            env=env).returncode

    import jax.numpy as jnp

    from acg_tpu.parallel.dist import DistCGSolver, DistributedProblem
    from acg_tpu.partition import partition_rows
    from acg_tpu.solvers.stats import StoppingCriteria

    side, its = args.algorithms_side, args.algorithms_its
    csr = _build(side, 2)
    n = csr.shape[0]
    nparts = 8
    part = partition_rows(csr, nparts, seed=0, method="band")
    prob = DistributedProblem.build(csr, part, nparts,
                                    dtype=jnp.float32)
    rng = np.random.default_rng(0)
    b = rng.standard_normal(n).astype(np.float32)
    crit = StoppingCriteria(maxits=its)   # fixed-work protocol
    rows = []

    def best_of(fn, reps=3):
        ts = []
        for _ in range(reps):
            t0 = time.perf_counter()
            fn()
            ts.append(time.perf_counter() - t0)
        return min(ts)

    algs = [("classic", dict()),
            ("pipelined", dict(pipelined=True)),
            ("sstep:2", dict(algorithm="sstep:2")),
            ("sstep:4", dict(algorithm="sstep:4")),
            ("sstep:8", dict(algorithm="sstep:8")),
            ("pipelined:2", dict(algorithm="pipelined:2")),
            ("pipelined:3", dict(algorithm="pipelined:3"))]
    for name, kw in algs:
        s = DistCGSolver(prob, **kw)
        s.solve(b, criteria=crit, host_result=False,
                raise_on_divergence=False).block_until_ready()  # compile

        def once():
            s.solve(b, criteria=crit, host_result=False,
                    raise_on_divergence=False).block_until_ready()

        t = best_of(once)
        led = s.comm_profile()
        tag = name.replace(":", "")
        row = {
            "metric": f"ca_cg_iters_per_sec_poisson2d_n{side}"
                      f"_np{nparts}_f32_its{its}_{tag}",
            "algorithm": name,
            "value": round(its / t, 2),
            "unit": "iters/s",
            "s_per_iter": round(t / its, 6),
            "dtype": "f32",
            "nparts": nparts,
            "iterations": int(s.stats.niterations),
            "allreduce_per_iteration":
                led["allreduce_per_iteration"],
            "allreduce_scalars": led["allreduce_scalars"],
            "halo_exchanges_per_iteration":
                led["halo_exchanges_per_iteration"],
        }
        print(f"# {name}: {t:.3f}s for {its} its "
              f"({its / t:.1f} iters/s, "
              f"{led['allreduce_per_iteration']:g} allreduce/iter)",
              file=sys.stderr)
        print(json.dumps(row))
        rows.append(row)
        _sink_stats(row, s)
        sys.stdout.flush()
    return _finish(args, rows, 0)


def run_overlap_mode(args) -> int:
    """``bench.py --overlap``: the fused-iteration overlap sweep (ISSUE
    13 acceptance) -- comm={xla,dma} x kernels={auto,fused} at small
    n/P on the 8-part mesh (the regime where collective latency
    dominates), fixed-iteration protocol.  Each
    case is timed AND captured under the jax profiler so the row
    carries the measured solve-windowed overlap-efficiency score
    (acg_tpu.tracing -- the PR 8 protocol the ISSUE 13 acceptance
    gates on) next to s/iter and the ledger's interior/border split."""
    import shutil
    import tempfile

    import numpy as np

    from acg_tpu._platform import provision_host_mesh

    jax = provision_host_mesh(8)
    if len(jax.devices()) < 8:
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env.setdefault("XLA_FLAGS",
                       "--xla_force_host_platform_device_count=8")
        import subprocess
        return subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--overlap",
             "--overlap-side", str(args.overlap_side),
             "--overlap-its", str(args.overlap_its),
             "--fail-on-regress", str(args.fail_on_regress)]
            + (["--stats-json", args.stats_json] if args.stats_json
               else [])
            + (["--baseline", args.baseline] if args.baseline else []),
            env=env).returncode

    import jax.numpy as jnp

    from acg_tpu import tracing
    from acg_tpu.parallel.dist import DistCGSolver, DistributedProblem
    from acg_tpu.partition import partition_rows
    from acg_tpu.solvers.stats import StoppingCriteria

    side, its = args.overlap_side, args.overlap_its
    csr = _build(side, 2)
    n = csr.shape[0]
    nparts = 8
    part = partition_rows(csr, nparts, seed=0, method="band")
    prob = DistributedProblem.build(csr, part, nparts,
                                    dtype=jnp.float32)
    rng = np.random.default_rng(0)
    b = rng.standard_normal(n).astype(np.float32)
    crit = StoppingCriteria(maxits=its)   # fixed-work protocol
    rows = []

    def best_of(fn, reps=3):
        ts = []
        for _ in range(reps):
            t0 = time.perf_counter()
            fn()
            ts.append(time.perf_counter() - t0)
        return min(ts)

    for comm in ("xla", "dma"):
        for kern in ("auto", "fused"):
            s = DistCGSolver(prob, comm=comm, kernels=kern)
            s.solve(b, criteria=crit, host_result=False,
                    raise_on_divergence=False).block_until_ready()  # compile

            def once():
                s.solve(b, criteria=crit,
                        host_result=False,
                        raise_on_divergence=False).block_until_ready()

            t = best_of(once)
            # per-case profiler capture -> measured solve-windowed
            # overlap-efficiency (degrades to null where the capture
            # is unusable; the timing row stands either way)
            cap = tempfile.mkdtemp(prefix="acg_overlap_")
            try:
                with tracing.profiler_trace(cap):
                    once()
                analysis = tracing.analyze_trace(cap)
            finally:
                shutil.rmtree(cap, ignore_errors=True)
            eff = (analysis.get("overlap_efficiency")
                   if analysis.get("available") else None)
            led = s.comm_profile()
            row = {
                "metric": f"overlap_cg_iters_per_sec_poisson2d_n{side}"
                          f"_np{nparts}_f32_its{its}_{comm}_{kern}",
                "comm": comm,
                "kernels": s.kernels,
                "value": round(its / t, 2),
                "unit": "iters/s",
                "s_per_iter": round(t / its, 6),
                "dtype": "f32",
                "nparts": nparts,
                "iterations": int(s.stats.niterations),
                "overlap_efficiency": eff,
                "halo_bytes_per_iteration":
                    led["halo_bytes_per_iteration"],
            }
            if led.get("overlap"):
                row["interior_rows"] = led["overlap"]["interior_rows"]
                row["border_rows"] = led["overlap"]["border_rows"]
            print(f"# {comm}/{kern}: {t:.3f}s for {its} its "
                  f"({its / t:.1f} iters/s, overlap-efficiency "
                  f"{eff if eff is not None else 'n/a'})",
                  file=sys.stderr)
            print(json.dumps(row))
            rows.append(row)
            _sink_stats(row, s)
            sys.stdout.flush()
    return _finish(args, rows, 0)


def run_matfree_mode(args) -> int:
    """``bench.py --matfree``: the matrix-free operator sweep (ISSUE 15
    acceptance) -- s/iteration of the matrix-free stencil apply vs the
    assembled ``gen:`` DIA planes vs the general assembled gather
    format (ELL, the CSR-class fallback) at 2-3 sizes on the single
    device AND the assembled-vs-matfree pair on the 8-part mesh,
    fixed-iteration protocol.  Matrix-free rows carry the ``operator``
    identity so bench_diff keys them apart from assembled captures
    (perfmodel._operator_keyed), and the headline comparison is the
    matfree-vs-DIA s/iter at the largest (most HBM-bound) size."""
    import numpy as np

    from acg_tpu._platform import provision_host_mesh

    jax = provision_host_mesh(8)
    if len(jax.devices()) < 8:
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env.setdefault("XLA_FLAGS",
                       "--xla_force_host_platform_device_count=8")
        import subprocess
        return subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--matfree",
             "--matfree-sides", args.matfree_sides,
             "--matfree-its", str(args.matfree_its),
             "--fail-on-regress", str(args.fail_on_regress)]
            + (["--stats-json", args.stats_json] if args.stats_json
               else [])
            + (["--baseline", args.baseline] if args.baseline else []),
            env=env).returncode

    import jax.numpy as jnp

    from acg_tpu.ops.operator import poisson_stencil
    from acg_tpu.ops.spmv import device_matrix_from_csr, dia_from_csr
    from acg_tpu.parallel.dist import (DistCGSolver, DistributedProblem,
                                       arm_matfree)
    from acg_tpu.partition import partition_rows
    from acg_tpu.solvers.jax_cg import JaxCGSolver
    from acg_tpu.solvers.stats import StoppingCriteria

    sides = [int(s) for s in args.matfree_sides.split(",") if s]
    its = args.matfree_its
    crit = StoppingCriteria(maxits=its)   # fixed-work protocol
    rows = []

    def best_of(fn, reps=3):
        ts = []
        for _ in range(reps):
            t0 = time.perf_counter()
            fn()
            ts.append(time.perf_counter() - t0)
        return min(ts)

    def emit(name, side, nparts, t, solver, op=None, extra=None):
        row = {
            "metric": f"matfree_cg_iters_per_sec_poisson2d_n{side}"
                      f"_np{nparts}_f32_its{its}_{name}",
            "case": name,
            "value": round(its / t, 2),
            "unit": "iters/s",
            "s_per_iter": round(t / its, 8),
            "dtype": "f32",
            "nparts": nparts,
            "iterations": int(solver.stats.niterations),
        }
        if op is not None:
            row["operator"] = op.identity()
        if extra:
            row.update(extra)
        print(f"# n={side} np={nparts} {name}: {t:.3f}s for {its} its "
              f"({its / t:.1f} iters/s)", file=sys.stderr)
        print(json.dumps(row))
        rows.append(row)
        _sink_stats(row, solver)
        sys.stdout.flush()

    for side in sides:
        csr = _build(side, 2)
        n = csr.shape[0]
        rng = np.random.default_rng(0)
        b = rng.standard_normal(n).astype(np.float32)
        op = poisson_stencil(side, 2, dtype=jnp.float32)
        single = [
            ("matfree", op, op),
            ("dia", dia_from_csr(csr, dtype=jnp.float32), None),
            ("ell", device_matrix_from_csr(csr, dtype=jnp.float32,
                                           format="ell"), None),
        ]
        for name, A, op_row in single:
            s = JaxCGSolver(A, kernels="xla")
            s.solve(b, criteria=crit, host_result=False,
                    raise_on_divergence=False).block_until_ready()  # compile

            def once(s=s):
                s.solve(b, criteria=crit, host_result=False,
                        raise_on_divergence=False).block_until_ready()

            emit(name, side, 1, best_of(once), s, op=op_row)

        # 8-part mesh pair: assembled DIA vs armed matfree over the
        # SAME band partition / halo plan
        part = partition_rows(csr, 8, seed=0, method="band")
        for name, armed in (("dist_dia", False), ("dist_matfree", True)):
            prob = DistributedProblem.build(csr, part, 8,
                                            dtype=jnp.float32)
            if armed:
                arm_matfree(prob, op)
            s = DistCGSolver(prob)
            s.solve(b, criteria=crit, host_result=False,
                    raise_on_divergence=False).block_until_ready()  # compile

            def once(s=s):
                s.solve(b, criteria=crit, host_result=False,
                        raise_on_divergence=False).block_until_ready()

            led = s.comm_profile()
            emit(name, side, 8, best_of(once), s,
                 op=op if armed else None,
                 extra={"matrix_free": bool(led.get("matrix_free"))})
    return _finish(args, rows, 0)


def _finish(args, rows, rc: int) -> int:
    """Apply the --baseline regression gate to this run's emitted rows
    (the perfmodel tier's case-by-case diff -- same engine as
    scripts/bench_diff.py): exit nonzero when any common case fell more
    than --fail-on-regress percent below the baseline capture, or when
    nothing was comparable at all (a renamed metric must not silently
    green the gate)."""
    if not args.baseline:
        return rc
    from acg_tpu.perfmodel import check_regression

    gate = check_regression(rows, args.baseline, args.fail_on_regress)
    return rc or gate


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="run the whole BASELINE ladder (one JSON line/row)")
    ap.add_argument("--baseline", metavar="FILE", default=None,
                    help="compare this run's rows against a prior "
                         "capture (a --stats-json JSONL or a bench "
                         "row file like BENCH_*.json) and exit nonzero "
                         "on regression -- the enforced form of the "
                         "BENCH trajectory.  A DIRECTORY is a "
                         "--history run ledger: the best USABLE prior "
                         "capture per case baselines, with "
                         "bench_backend_unavailable entries skipped "
                         "(an all-unavailable ledger refuses, exit 2)")
    ap.add_argument("--fail-on-regress", type=float, default=10.0,
                    metavar="PCT",
                    help="with --baseline: regression threshold in "
                         "percent (default: 10)")
    ap.add_argument("--row", metavar="SUBSTR", default=None,
                    help="with --full: run only ladder rows whose metric "
                         "name contains SUBSTR (per-row driver "
                         "invocations -- scripts/ladder.sh -- so one "
                         "failed row cannot take out subsequent rows)")
    ap.add_argument("--sweep-np", action="store_true",
                    help="multi-chip CPU-mesh correctness sweep")
    ap.add_argument("--algorithms", action="store_true",
                    help="run the communication-avoiding recurrence "
                         "sweep (classic/pipelined/sstep:S/p(l)) on "
                         "the 8-part CPU mesh: s/iter + comm ledger "
                         "per algorithm")
    ap.add_argument("--algorithms-side", type=int, default=128,
                    metavar="N",
                    help="with --algorithms: Poisson grid side "
                         "(default 128 -> n=16384: small n/P, the "
                         "latency-dominated regime)")
    ap.add_argument("--algorithms-its", type=int, default=200,
                    metavar="K",
                    help="with --algorithms: fixed iterations per "
                         "solve (default 200)")
    ap.add_argument("--overlap", action="store_true",
                    help="run the fused-iteration overlap sweep "
                         "(comm={xla,dma} x kernels={auto,fused} on "
                         "the 8-part mesh at small n/P, each case "
                         "profiler-captured for its measured "
                         "overlap-efficiency; one JSON line per case)")
    ap.add_argument("--overlap-side", type=int, default=64,
                    metavar="N",
                    help="with --overlap: Poisson grid side (default "
                         "64 -- small n/P, the collective-latency-"
                         "dominated regime)")
    ap.add_argument("--overlap-its", type=int, default=200,
                    metavar="K",
                    help="with --overlap: fixed iterations per case "
                         "(default 200)")
    ap.add_argument("--matfree", action="store_true",
                    help="run the matrix-free operator sweep (matfree "
                         "vs assembled DIA vs assembled ELL on the "
                         "single device, assembled-vs-matfree on the "
                         "8-part mesh; fixed-iteration protocol, one "
                         "JSON line per case; matfree rows carry the "
                         "operator identity for bench_diff keying)")
    ap.add_argument("--matfree-sides", default="256,512,1024",
                    metavar="N,N",
                    help="with --matfree: comma-separated Poisson grid "
                         "sides (default 256,512,1024 -- the largest "
                         "is bandwidth-bound on every backend "
                         "measured, where deleting the plane reads "
                         "shows up)")
    ap.add_argument("--matfree-its", type=int, default=200, metavar="K",
                    help="with --matfree: fixed iterations per case "
                         "(default 200)")
    ap.add_argument("--batched", action="store_true",
                    help="batched multi-RHS throughput case: solves/s "
                         "at B in {1,4,8}, one batched solve vs a "
                         "sequential B-solve loop of the same columns, "
                         "plus the block-CG iteration-ratio case on "
                         "the --aniso family (ISSUE 11 acceptance).  "
                         "nrhs/block join the bench-diff case key; the "
                         "first batched capture starts a fresh "
                         "baseline series")
    ap.add_argument("--batched-side", type=int, default=128, metavar="N",
                    help="with --batched: Poisson grid side "
                         "(default: 128)")
    ap.add_argument("--batched-its", type=int, default=200, metavar="K",
                    help="with --batched: fixed iterations per solve "
                         "(default: 200)")
    ap.add_argument("--batched-aniso-side", type=int, default=48,
                    metavar="N",
                    help="with --batched: aniso grid side for the "
                         "block-CG iteration case (default: 48)")
    ap.add_argument("--stats-json", metavar="FILE", default=None,
                    help="JSONL-append each timed case's full solver "
                         "stats document (the CLI's --stats-json "
                         "schema, acg_tpu.telemetry) next to the "
                         "summary rows on stdout")
    ap.add_argument("--soak", type=int, default=0, metavar="N",
                    help="service-soak mode: N repeated fixed-work "
                         "solves of one Poisson system through "
                         "acg_tpu.soak (p50/p95/p99 latency row, EWMA "
                         "drift detector, --fail-on-drift exit 7)")
    ap.add_argument("--soak-side", type=int, default=256, metavar="N",
                    help="with --soak: Poisson grid side (default: 256)")
    ap.add_argument("--soak-dim", type=int, default=2, choices=(2, 3),
                    help="with --soak: Poisson dimension (default: 2)")
    ap.add_argument("--soak-its", type=int, default=200, metavar="K",
                    help="with --soak: fixed iterations per solve "
                         "(default: 200)")
    ap.add_argument("--soak-dtype", default="f32",
                    choices=("f32", "mixed", "bf16"),
                    help="with --soak: storage tier (default: f32)")
    ap.add_argument("--precond", default="none", metavar="KIND",
                    help="with --soak: preconditioner selection "
                         "(none | jacobi | bjacobi[:BS] | cheby:K, "
                         "acg_tpu.precond); joins the case metric so "
                         "preconditioned captures never diff against "
                         "plain ones")
    ap.add_argument("--fail-on-drift", type=float, default=None,
                    metavar="PCT",
                    help="with --soak: exit 7 when EWMA solve latency "
                         "drifts more than PCT percent over the "
                         "baseline window's median")
    ap.add_argument("--metrics-file", metavar="FILE", default=None,
                    help="with --soak: flush the service-metrics "
                         "registry to FILE in Prometheus text format "
                         "(atomic rename; also written on SIGTERM)")
    ap.add_argument("--calibration", metavar="FILE", default=None,
                    help="a saved acg-tpu-commbench/1 document "
                         "(acg-tpu --commbench): its calibration id is "
                         "stamped on every --stats-json case document, "
                         "so bench_diff keys differently-calibrated "
                         "captures apart instead of diffing them "
                         "silently")
    ap.add_argument("--no-probe-cache", action="store_true",
                    help="ignore the on-disk backend-keyed triad-probe "
                         "sidecar and re-measure HBM bandwidth")
    args = ap.parse_args(argv)
    global _STATS_SINK, _CALIBRATION_ID, _USE_PROBE_CACHE
    _STATS_SINK = args.stats_json
    _USE_PROBE_CACHE = not args.no_probe_cache
    if args.calibration:
        from acg_tpu.commbench import load_calibration
        try:
            _CALIBRATION_ID = load_calibration(
                args.calibration)["calibration_id"]
        except (OSError, ValueError) as e:
            ap.error(f"--calibration {args.calibration}: {e}")
    if not args.soak and (args.metrics_file
                          or args.fail_on_drift is not None
                          or args.precond != "none"):
        # only the soak harness reads these; silently ignoring them
        # would let an operator believe a gate/capture ran
        ap.error("--metrics-file/--fail-on-drift/--precond need "
                 "--soak N")
    if args.fail_on_drift is not None:
        from acg_tpu.soak import gate_is_vacuous
        if args.fail_on_drift <= 0:
            ap.error("--fail-on-drift must be positive percent")
        if gate_is_vacuous(args.soak):
            ap.error(f"--fail-on-drift is vacuous at --soak "
                     f"{args.soak} (the baseline window consumes the "
                     f"whole run); use --soak 4 or more")

    if args.sweep_np:
        return sweep_np()

    if args.algorithms:
        # like --sweep-np/--batched, provisions its own 8-part virtual
        # CPU mesh (re-executing itself when the flags must be set
        # before jax init), so it runs BEFORE the backend probe
        return run_algorithms_mode(args)

    if args.overlap:
        # like --algorithms: provisions its own 8-part virtual CPU
        # mesh, so it runs BEFORE the backend probe
        return run_overlap_mode(args)

    if args.matfree:
        # like --overlap: provisions its own 8-part virtual CPU mesh,
        # so it runs BEFORE the backend probe
        return run_matfree_mode(args)

    if args.batched:
        # like --sweep-np, provisions its own 8-part virtual CPU mesh
        # (re-executing itself when the flags must be set before jax
        # init), so it runs BEFORE the backend probe
        return run_batched_mode(args)

    import jax

    _enable_compile_cache()

    if args.soak:
        return run_soak_mode(args)

    if not args.full:
        # flagship: measure the kernel AND storage tiers and report
        # the best SOUND config.  "f32"/"mixed"
        # are sound by construction ("mixed" is arithmetic-identical to
        # f32); the bf16-family tiers must DEMONSTRATE soundness -- the
        # measured true relative residual under the manufactured-
        # solution protocol must clear SOUND_REL_RESIDUAL.  Plain bf16
        # stalls at ~2e-1 at flagship kappa (context keys only);
        # "bf16rr" (periodic f32 residual replacement) measures ~1e-6
        # and competes for the headline at ~0.94x plain-bf16 speed.
        # one stable metric name across rounds/runs; the winning tier is
        # recorded in the "dtype"/"kernels" fields (a name that changed
        # with the winner would split the longitudinal series)
        name = "cg_iters_per_sec_poisson2d_n2048_f32"
        csr = _build(2048, 2)
        rows = {}

        # a driver-side timeout must not cost the whole capture: on
        # SIGTERM/SIGINT, emit the best row measured so far (marked
        # partial) before dying
        import signal

        def _emit_partial(signum, frame):
            if rows:
                best = max(rows.values(), key=lambda r: r["value"])
                best = dict(best)
                best["partial_capture"] = True
                print(json.dumps(best))
                sys.stdout.flush()
                # the baseline gate runs on the partial row too: a
                # truncated capture is exactly when a silent regression
                # would otherwise green the gate
                sys.exit(_finish(args, [best], 0))
            sys.exit(124)

        for sig in (signal.SIGTERM, signal.SIGINT):
            signal.signal(sig, _emit_partial)
        for dtn in ("f32", "mixed", "bf16", "bf16rr"):
            # a tier that fails (compile flake, OOM) must not sink the
            # tiers already measured
            try:
                best = run_case(csr, name, False, False, "auto", dtn)
                if best.get("kernels") != "xla":
                    alt = run_case(csr, name, False, False, "xla", dtn)
                    if alt["value"] > best["value"]:
                        best = alt
                # the two-phase fused iteration competes for the
                # headline too.  No fused hook for the replacement
                # program -> bf16rr keeps its tiers.
                if dtn != "bf16rr" and jax.default_backend() == "tpu":
                    # TPU only: off-TPU the tier resolves to interpret
                    # mode, which is unusable at flagship size
                    try:
                        alt = run_case(csr, name, False, False, "fused",
                                       dtn)
                        if alt["value"] > best["value"]:
                            best = alt
                    except Exception as e:  # noqa: BLE001 -- keep `best`
                        print(f"# {dtn} fused tier skipped: "
                              f"{type(e).__name__}: "
                              f"{str(e).splitlines()[0][:160]}",
                              file=sys.stderr)
                rows[dtn] = best
            except Exception as e:  # noqa: BLE001 -- report and continue
                print(f"# {dtn} tier skipped: {type(e).__name__}: "
                      f"{str(e).splitlines()[0][:200]}", file=sys.stderr)
        if not rows:
            return 1
        sound = [rows[k] for k in ("f32", "mixed") if k in rows]
        for dtn in ("bf16", "bf16rr"):
            row = rows.get(dtn)
            if row is None:
                continue
            row = _accuracy_context(csr, row, dtn)
            if row.get("rel_residual_1000it",
                       float("inf")) < SOUND_REL_RESIDUAL:
                sound.append(row)
        best = max(sound or rows.values(), key=lambda r: r["value"])
        for dtn in ("bf16", "bf16rr"):
            row = rows.get(dtn)
            if row is not None and best is not row:
                best[f"{dtn}_iters_per_sec"] = row["value"]
                if "rel_residual_1000it" in row:
                    best[f"{dtn}_rel_residual_1000it"] = \
                        row["rel_residual_1000it"]
        print(json.dumps(best))
        return _finish(args, [best], 0)

    cases = [
            ("cg_iters_per_sec_poisson2d_n2048_f32",
             2048, 2, False, False, "auto", "f32"),
            ("cg_xla_iters_per_sec_poisson2d_n2048_f32",
             2048, 2, False, False, "xla", "f32"),
            ("cg_iters_per_sec_poisson2d_n2048_mixed",
             2048, 2, False, False, "auto", "mixed"),
            ("cg_iters_per_sec_poisson2d_n2048_bf16",
             2048, 2, False, False, "auto", "bf16"),
            ("cg_iters_per_sec_poisson2d_n2048_bf16rr",
             2048, 2, False, False, "auto", "bf16rr"),
            ("cg_pipelined_iters_per_sec_poisson2d_n2048_f32",
             2048, 2, True, False, "xla", "f32"),
            ("cg_iters_per_sec_poisson3d_n128_f32",
             128, 3, False, False, "xla", "f32"),
            ("cg_pipelined_iters_per_sec_poisson3d_n128_f32",
             128, 3, True, False, "xla", "f32"),
            ("cg_iters_per_sec_poisson3d_n256_f32",
             256, 3, False, False, "xla", "f32"),
            ("cg_iters_per_sec_poisson3d_n256_mixed",
             256, 3, False, False, "xla", "mixed"),
            ("cg_dist1_iters_per_sec_poisson2d_n2048_f32",
             2048, 2, False, True, "xla", "f32"),
            # auto -> binned ELL (the merge-CSR-goal format); the COO row
            # stays as the within-window A/B partner
            ("cg_iters_per_sec_irregular_n500k_d16_f32",
             500_000, 0, False, False, "xla", "f32"),
            ("cg_coo_iters_per_sec_irregular_n500k_d16_f32",
             500_000, 0, False, False, "xla", "f32"),
        ]

    built: dict[tuple, object] = {}
    emitted: list[dict] = []  # every row this run printed (baseline gate)

    def emit(row: dict) -> None:
        emitted.append(row)
        print(json.dumps(row))

    if args.row:
        # exact name match wins (several row names are substrings of
        # others, e.g. ..._bf16 / ..._bf16rr); substring is the
        # fallback for family selections
        exact = [c for c in cases if c[0] == args.row]
        cases = exact or [c for c in cases if args.row in c[0]]
    for name, side, dim, pipelined, dist, kernels, dtn in cases:
        # one failing case (device flake, OOM) must not sink the rest of
        # the ladder -- report it and keep going
        try:
            key = (side, dim)
            if key not in built:
                t0 = time.perf_counter()
                built[key] = _build(side, dim)
                csr = built[key]
                print(f"# setup: {dim}D n={side} N={csr.shape[0]} "
                      f"nnz={csr.nnz} in {time.perf_counter() - t0:.1f}s on "
                      f"{jax.devices()[0].platform}", file=sys.stderr)
            emit(run_case(
                built[key], name, pipelined, dist, kernels, dtn,
                spmv_format="coo" if "_coo_" in name else "auto"))
        except Exception as e:  # noqa: BLE001 -- report and continue
            print(f"# {name} skipped: {type(e).__name__}: "
                  f"{str(e).splitlines()[0][:200]}", file=sys.stderr)
        sys.stdout.flush()

    # external/host baselines on the SAME 128^3 matrix (the reference's
    # PETSc performance-baseline role, cgpetsc.c:335-378): scipy-CG
    # oracle and the native C++ core, timed under the same protocol so
    # the cross-implementation perf comparison is reproducible here
    for name, kind in (
            ("cg_iters_per_sec_poisson3d_n128_petsc_f64", "petsc"),
            ("cg_iters_per_sec_poisson3d_n128_hostnative_f64", "native")):
        if args.row and args.row not in name:
            continue
        try:
            if (128, 3) not in built:
                built[(128, 3)] = _build(128, 3)
            emit(run_host_baseline(built[(128, 3)], name, kind))
        except Exception as e:  # noqa: BLE001 -- report and continue
            print(f"# {name} skipped: {type(e).__name__}: "
                  f"{str(e).splitlines()[0][:200]}", file=sys.stderr)
        sys.stdout.flush()

    # the north-star problem size, single chip, direct-DIA assembly;
    # skipped gracefully where the device memory cannot hold it.  The
    # bf16rr rows (256^3 + 512^3) carry a measured soundness gate at 3D
    # conditioning (round-4 verdict item 1)
    built.clear()
    for side, dtn in ((512, "f32"), (512, "mixed"), (512, "bf16rr"),
                      (256, "bf16rr")):
        name = f"cg_iters_per_sec_poisson3d_n{side}_{dtn}_dia"
        if args.row and args.row not in name:
            continue
        try:
            emit(run_case_dia(side, 3, name, dtn))
        except Exception as e:  # noqa: BLE001 -- report and continue
            print(f"# {side}^3 {dtn} row skipped: {type(e).__name__}: "
                  f"{str(e).splitlines()[0][:200]}", file=sys.stderr)
        sys.stdout.flush()
    return _finish(args, emitted, 0)


if __name__ == "__main__":
    sys.exit(main())
