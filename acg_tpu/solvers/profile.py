"""Per-op device timing -- the reference's ``ACG_ENABLE_PROFILING`` tier.

The reference brackets every GPU op with CUDA event pairs
(``acgEventRecord``, ``cgcuda.c:73-76``; event arrays ``:585-610``;
summed post-solve ``:1057-1095``) and reports per-op seconds and GB/s in
the stats block (``:1942-1957``).  Under XLA the whole solve is ONE
compiled program -- bracketing ops inside it would break the fusion that
makes it fast -- so this tier *replays* each op class standalone on the
solver's own device-resident arrays (best-of-``reps`` timings of
chained in-program repetitions, see below) and scales by the op counts
the always-on counters already track.

Honest caveats, also noted in the stats block docs:
  * replay times are per-op upper bounds: in the real loop XLA fuses
    vector updates into neighbouring ops, so the per-op sum can exceed
    ``tsolve`` (the surplus appears as negative "other" time -- itself a
    measure of how much fusion saves);
  * the distributed ``gemv`` replay includes the overlapped halo
    exchange (they are one fused program by design); the halo is also
    measured alone so the overlap benefit is visible by comparison;
  * per-program dispatch latency can exceed the ops themselves by
    orders of magnitude and fluctuates, so each op is measured as the
    DIFFERENCE
    between two chained programs (4*INNER vs INNER in-program
    repetitions): the dispatch term cancels instead of being estimated.
    The raw dispatch latency is returned under ``"dispatch"`` for
    context (the in-loop ops pay it once per solve, not once per op);
  * chaining a scalar-result op (dot, halo, allreduce) requires folding
    its result back into the carried vector to keep the data
    dependence, which adds ~one vector read+write per repetition --
    those entries are therefore upper bounds by roughly one
    axpy-equivalent (reported alongside, so readers can discount it);
  * a ``--trace`` capture SUPERSEDES this tier where it can: the CLI
    applies :func:`acg_tpu.tracing.apply_measured_ops` after the
    replay, so any op class the profiler resolved to real device
    events (TPU captures carry per-HLO-op timelines) reports MEASURED
    seconds instead of the replayed estimate, and the stats block's
    ``tracing: ops_source`` line says which rows were replaced.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np


# op repetitions chained INSIDE one jitted program: per-call dispatch
# latency (itself fluctuating) is paid once per program, so the op cost
# is recovered from the DIFFERENCE between a 4*INNER-iteration chain and an INNER-iteration
# chain -- the dispatch term cancels.  Chains carry a data dependence
# so XLA cannot elide them.
INNER = 64


def _best_time(fn, *args, reps: int = 10) -> float:
    reps = max(int(reps), 1)

    jax.block_until_ready(fn(*args))  # compile + warm
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ts.append(time.perf_counter() - t0)
    # min: disturbances only ever add time, so the fastest run is the
    # least-disturbed estimate (same estimator as bench)
    return min(ts)


def _chain(op, inner, x0, *extra):
    """jit(fori_loop) chaining ``inner`` applications of ``op`` through
    its first argument (op must preserve that argument's shape)."""
    def run(x, *e):
        return jax.lax.fori_loop(0, inner, lambda _, y: op(y, *e), x)

    return jax.jit(run), (x0, *extra)


def _time_op(op, x0, *extra, reps: int = 10) -> float:
    """Two-point amortised estimate of one op application's seconds."""
    lo_fn, args = _chain(op, INNER, x0, *extra)
    hi_fn, _ = _chain(op, 4 * INNER, x0, *extra)
    lo = _best_time(lo_fn, *args, reps=reps)
    hi = _best_time(hi_fn, *args, reps=reps)
    return max(hi - lo, 0.0) / (3 * INNER)


def profile_ops(solver, b, reps: int = 10) -> dict[str, float]:
    """Fill ``solver.stats.ops[*].t`` with replayed per-op device times.

    Returns ``{op: seconds_per_call}`` for the measured op classes.
    Dispatches on solver type; host solvers already time ops for real
    (eager mode) and are returned unchanged.
    """
    # unwrap mixed-precision refinement down to the device solver
    while hasattr(solver, "inner"):
        solver = solver.inner

    from acg_tpu.parallel.dist import DistCGSolver
    from acg_tpu.solvers.jax_cg import JaxCGSolver

    if isinstance(solver, JaxCGSolver):
        per_call = _profile_single(solver, b, reps)
    elif isinstance(solver, DistCGSolver):
        per_call = _profile_dist(solver, b, reps)
    else:
        return {}

    for op, t in per_call.items():
        s = solver.stats.ops[op]
        s.t = t * s.n
    # the scalar-chain replay caveat as a NUMBER, not prose (the module
    # docstring's last bullet): chaining a scalar-result op (dot, nrm2,
    # halo, allreduce) folds its scalar back into the carried vector to
    # keep the data dependence, ~one axpy-equivalent extra per
    # repetition -- so those entries are upper bounds by about this
    # much per call.  Reported as an explicit key so consumers can
    # discount it mechanically instead of reading a docstring.
    per_call["chain_overhead"] = per_call.get("axpy", 0.0)
    # per-program dispatch latency, reported for context (the in-loop
    # ops pay it once per solve, not once per op).  The noop rides the
    # SOLVER'S value dtype, not the default: under x64 the default
    # would dispatch an f64 program while the solve runs f32 (and
    # vice versa for bf16 tiers) -- the measurement must match the
    # solve's programs
    vdt = _value_dtype(solver)
    noop = jax.jit(lambda v: v + jnp.asarray(1, v.dtype))
    per_call["dispatch"] = _best_time(noop, jnp.zeros((8,), vdt),
                                      reps=reps)
    return per_call


def _value_dtype(solver):
    """The dtype of the solve's VECTORS (they differ from the matrix
    dtype under --dtype mixed; replacement solves run f32 outer)."""
    import numpy as _np

    if getattr(solver, "replace_every", 0):
        return jnp.float32
    vdt = getattr(solver, "vector_dtype", None)
    if vdt is not None:
        return jnp.dtype(vdt)
    prob = getattr(solver, "problem", None)
    if prob is not None:
        return jnp.dtype(prob.vdtype)
    A = solver.A
    dt = (A.dtype if hasattr(A, "dtype")
          else A.data.dtype if hasattr(A, "data")
          else A.vals.dtype if hasattr(A, "vals")  # CooMatrix
          else _np.float32)
    return jnp.dtype(dt)


def _profile_single(solver, b, reps: int) -> dict[str, float]:
    from acg_tpu.solvers.jax_cg import _spmv_fn

    # the matrix the PROGRAMS consume: for the pallas-roll tier this is
    # the per-shard-padded twin its callable kernel expects (the clean
    # solver.A would feed it mis-shaped planes)
    A = solver._A_program
    dtype = (A.dtype if hasattr(A, "dtype")
             else A.data.dtype if hasattr(A, "data") else A.vals.dtype)
    # b may already live on device (gen-direct path): no host round-trip
    x = jnp.asarray(b, dtype=dtype)
    # the fused tier's gemv replay uses the closest standalone kernel
    # (its phase kernels have no standalone-SpMV form); callable tiers
    # (PallasRollSpmv) pass through _spmv_fn unchanged
    spmv_f = _spmv_fn("pallas" if (isinstance(solver.kernels, str)
                                   and solver.kernels.startswith("fused"))
                      else solver.kernels)
    if solver.precise_dots:
        from acg_tpu.ops.precision import dot_compensated

        def _dot(a, c):
            hi, lo = dot_compensated(a, c)
            return hi + lo
    else:
        _dot = jnp.dot
    # chains: gemv feeds y back as x (square A); dot folds its scalar
    # into the next input (unfoldable data dependence); axpy chains y
    alpha = jnp.asarray(0.5, dtype)
    tiny = jnp.asarray(1e-30, dtype)
    # the matrix rides as an ARGUMENT, not a closure: captured device
    # arrays become compile-time constants and are shipped with the
    # program (gigabytes at large N)
    out = {
        "gemv": _time_op(lambda v, M: spmv_f(M, v), x, A, reps=reps),
        "dot": _time_op(lambda v, c: v + tiny * _dot(v, c), x, x,
                        reps=reps),
        # the convergence test's (r, r): one vector read (vs the dot
        # class's two) -- its counters are now filled analytically by
        # the solvers, so the replay must price it too
        "nrm2": _time_op(lambda v: v + tiny * _dot(v, v), x, reps=reps),
        "axpy": _time_op(lambda y, a, p: y + a * p, x, alpha, x,
                         reps=reps),
        # copy (p = r at setup): one read + one write; a scale by ~1
        # keeps the chain's data dependence where a literal jnp.copy
        # would be elided inside the fused chain
        "copy": _time_op(lambda y, a: y * a, x,
                         jnp.asarray(1.0000001, dtype), reps=reps),
    }
    spec = getattr(solver, "precond_spec", None)
    if spec is not None:
        # replay the M^-1 apply too: the analytic precond counters
        # must not print 0 seconds next to replayed times (the could-
        # never-fire discipline).  The replayed seconds are divided by
        # the per-apply op count (cheby counts its degree-many SpMVs),
        # so ops["precond"].t = seconds/op x n reconstructs the true
        # per-apply cost
        from acg_tpu.precond import make_apply

        mstate = solver._ensure_precond_state()
        papply = make_apply(spec, spmv_f)
        per = spec.degree if spec.kind == "cheby" else 1
        out["precond"] = _time_op(
            lambda v, M, ms: papply(ms, M, v), x, A, mstate,
            reps=reps) / per
    return out


def _profile_dist(solver, b, reps: int) -> dict[str, float]:
    from jax import lax
    from jax.sharding import PartitionSpec as P

    from acg_tpu.parallel.dist import make_dist_spmv
    from acg_tpu.parallel.halo import halo_exchange
    from acg_tpu.parallel.halo_dma import halo_exchange_dma
    from acg_tpu.parallel.mesh import PARTS_AXIS

    prob = solver.problem
    mesh = solver.mesh
    axis = PARTS_AXIS
    pspec = P(PARTS_AXIS)
    bd, x0, la, ga, sidx, gsrc, gval, scnt, rcnt = solver.device_args(b)
    if str(solver.kernels).startswith("fused"):
        # the fused tier's device_args extends ga with the interior
        # row lists; replay the SAME overlapped SpMV the solve runs
        from acg_tpu.parallel.dist import make_dist_spmv_overlapped
        spmv_shard = make_dist_spmv_overlapped(prob, solver.comm,
                                               solver._interpret)
    else:
        spmv_shard = make_dist_spmv(prob, solver.comm, solver._interpret,
                                    kernels=solver.kernels)

    tiny = jnp.asarray(1e-30, prob.vdtype)


    def smap(body, in_specs):
        return jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                             out_specs=pspec, check_vma=False)

    # every op is expressed as x -> x' (shape/sharding preserved) so
    # _chain can amortise INNER executions inside one program; scalarish
    # results fold back through `tiny` to keep the data dependence
    # matrix blocks ride as ARGUMENTS (captured device arrays become
    # compile-time constants shipped with the program)
    def gemv_once(x, la, ga, sidx, gsrc, gval, scnt, rcnt):
        def body(la, ga, sidx, gsrc, gval, scnt, rcnt, x):
            la, ga = (jax.tree.map(lambda a: a[0], t) for t in (la, ga))
            sidx, gsrc, gval, scnt, rcnt, x = (
                a[0] for a in (sidx, gsrc, gval, scnt, rcnt, x))
            return spmv_shard(x, la, ga, sidx, gsrc, gval, scnt, rcnt)[None]

        return smap(body, (pspec,) * 8)(la, ga, sidx, gsrc, gval, scnt,
                                        rcnt, x)

    out = {"gemv": _time_op(gemv_once, bd, la, ga, sidx, gsrc, gval,
                            scnt, rcnt, reps=reps)}

    # halo exchange alone (reference times it per exchange, halo.h:176-186)
    if prob.halo.has_ghosts:
        if solver.comm == "dma":
            interpret = solver._interpret

            def halo_once(x, sidx, gsrc, gval, scnt, rcnt):
                def body(x, sidx, gsrc, gval, scnt, rcnt):
                    ghost = halo_exchange_dma(x[0], sidx[0], gsrc[0],
                                              gval[0], scnt[0], rcnt[0],
                                              axis, interpret=interpret)
                    return (x[0] + tiny * jnp.sum(ghost))[None]

                return smap(body, (pspec,) * 6)(x, sidx, gsrc, gval,
                                                scnt, rcnt)

            out["halo"] = _time_op(halo_once, bd, sidx, gsrc, gval,
                                   scnt, rcnt, reps=reps)
        else:
            def halo_once(x, sidx, gsrc):
                def body(x, sidx, gsrc):
                    ghost = halo_exchange(x[0], sidx[0], gsrc[0], axis)
                    return (x[0] + tiny * jnp.sum(ghost))[None]

                return smap(body, (pspec,) * 3)(x, sidx, gsrc)

            out["halo"] = _time_op(halo_once, bd, sidx, gsrc, reps=reps)

    # local dot (no reduction) and the scalar allreduce, separately --
    # the reference's cublasDdot + acgcomm_allreduce split
    def dot_once(x, c):
        def body(a, c):
            # two-vector dot (the loop's (p,t)/(r,r-after-update)
            # class): carried vector against a fixed second operand
            return (a[0] + tiny * jnp.dot(a[0], c[0]))[None]

        return smap(body, (pspec, pspec))(x, c)

    out["dot"] = _time_op(dot_once, bd, x0 + 1.0, reps=reps)

    def nrm2_once(x):
        def body(a):
            # single-vector read: the convergence test's (r, r) class
            return (a[0] + tiny * jnp.dot(a[0], a[0]))[None]

        return smap(body, (pspec,))(x)

    out["nrm2"] = _time_op(nrm2_once, bd, reps=reps)
    # copy (p = r at setup): one read + one write per part; the
    # scale-by-~1 keeps the chain's data dependence (like axpy below,
    # sharding propagates through the plain jit chain)
    out["copy"] = _time_op(lambda y, a: y * a, bd,
                           jnp.asarray(1.0000001, prob.vdtype), reps=reps)

    def allreduce_once(s):
        def body(s):
            return (s[0] + tiny * lax.psum(s[0], axis))[None]

        return smap(body, (pspec,))(s)

    from acg_tpu.parallel.multihost import put_global

    pair = put_global(np.zeros((prob.nparts, 2), dtype=prob.vdtype),
                      jax.sharding.NamedSharding(mesh, pspec))
    out["allreduce"] = _time_op(allreduce_once, pair, reps=reps)

    out["axpy"] = _time_op(lambda y, a, p: y + a * p, bd,
                           jnp.asarray(0.5, prob.vdtype), bd, reps=reps)

    spec = getattr(solver, "precond_spec", None)
    if spec is not None:
        # the sharded M^-1 apply (the single-device replay's twin):
        # jacobi/bjacobi run per shard with no communication, cheby
        # through the same halo'd SpMV the gemv replay times
        from acg_tpu.precond import make_apply

        mstate = solver._ensure_precond_state(
            (bd, x0, la, ga, sidx, gsrc, gval, scnt, rcnt))

        def precond_once(x, la, ga, sidx, gsrc, gval, scnt, rcnt, ms):
            def body(la, ga, sidx, gsrc, gval, scnt, rcnt, x, ms):
                la, ga = (jax.tree.map(lambda a: a[0], t)
                          for t in (la, ga))
                sidx, gsrc, gval, scnt, rcnt, x = (
                    a[0] for a in (sidx, gsrc, gval, scnt, rcnt, x))
                ms = jax.tree.map(lambda a: a[0], ms)
                papply = make_apply(
                    spec, lambda _A, v: spmv_shard(v, la, ga, sidx,
                                                   gsrc, gval, scnt,
                                                   rcnt))
                return papply(ms, None, x)[None]

            return smap(body, (pspec,) * 9)(la, ga, sidx, gsrc, gval,
                                            scnt, rcnt, x, ms)

        per = spec.degree if spec.kind == "cheby" else 1
        out["precond"] = _time_op(precond_once, bd, la, ga, sidx, gsrc,
                                  gval, scnt, rcnt, mstate,
                                  reps=reps) / per
    return out
