"""Single-device CG solvers as compiled XLA programs.

The reference implements four execution models of CG (host/device x
classic/pipelined, ``acg/cgcuda.c``, ``acg/cg-kernels-cuda.cu``).  On TPU
these collapse into compiled whole-solve programs (SURVEY.md section 7):
XLA's execution model *is* the reference's monolithic persistent-kernel
variant (``acgsolvercuda_cg_kernel``, ``cg-kernels-cuda.cu:627-970``) --
one program per solve, `lax.while_loop` for the iteration, scalars resident
on device (the reference keeps alpha/beta/||r||^2 in device memory for the
same reason, ``cgcuda.c:465-486``), and the convergence test a device-side
predicate (``cg-kernels-cuda.cu:948-957``).

Two algorithms:

* :func:`solve_cg` -- classic CG (`acgsolver_solve` recurrences).
* :func:`solve_cg_pipelined` -- Ghysels-Vanroose pipelined CG with the
  fused 6-vector update of the reference's cooperative kernel
  (``cg-kernels-cuda.cu:187-269``): beta = gamma/gamma_prev, alpha =
  gamma/(delta - beta*gamma/alpha_prev), z=q+beta z, t=w+beta t, p=r+beta p,
  x+=alpha p, r-=alpha t, w-=alpha z, with gamma_prev=alpha_prev=inf on the
  first iteration (``cgcuda.c:1553-1560``).  On a single chip the pipelined
  variant exists for parity and numerics; its payoff (one fused allreduce)
  appears on the mesh.
"""

from __future__ import annotations

import dataclasses
import functools
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from acg_tpu.errors import AcgError, ErrorCode, NotConvergedError
from acg_tpu.ops.precision import dot2
from acg_tpu.ops.spmv import (DeviceMatrix, DiaMatrix, acc_dtype,
                              matrix_dtype, matrix_index_bytes, spmv,
                              spmv_flops)
from acg_tpu.solvers.stats import (SolverStats, StoppingCriteria,
                                   cg_flops_per_iteration)


def _spmv_fn(kernels):
    """Select the SpMV implementation: "xla" = ops.spmv (compiler-fused);
    "pallas"/"pallas-interpret" = the hand-written single-x-pass DIA kernel
    (ops.pallas_kernels.dia_spmv, measured ~1.2x faster on TPU v5e --
    BASELINE.md); "xla-roll" = the cyclic-shift DIA formulation whose
    shifts XLA's SPMD partitioner turns into boundary collective-permutes
    (the sharded/multi-chip route, ops.spmv.dia_mv_roll).  Falls back to
    XLA for non-DIA / rectangular matrices.

    A CALLABLE ``kernels`` is used directly as ``f(A, x) -> y`` -- the
    hook for mesh-aware SpMV objects (parallel.sharded_dia.
    PallasRollSpmv); instances hash by identity, so each rides its own
    jit cache entry."""
    if callable(kernels):
        return kernels
    if kernels == "xla-roll":
        from acg_tpu.ops.spmv import dia_mv_roll

        def f(A, x):
            if isinstance(A, DiaMatrix) and A.ncols_padded == A.nrows:
                return dia_mv_roll(A.data, A.offsets, x)
            return spmv(A, x)

        return f
    if kernels.startswith("pallas"):
        from acg_tpu.ops.pallas_kernels import dia_spmv, stencil_spmv

        interp = kernels.endswith("interpret")

        def f(A, x):
            if isinstance(A, DiaMatrix) and A.ncols_padded == A.nrows:
                return dia_spmv(A.data, A.offsets, x, interpret=interp)
            if getattr(A, "kind", None) == "poisson" \
                    and hasattr(A, "matfree_apply"):
                # the matrix-free stencil's Pallas path: coefficient
                # masks generated IN-KERNEL while x streams through
                # VMEM once (falls back to the operator's XLA apply
                # off the single-window route)
                return stencil_spmv(A, x, interpret=interp)
            return spmv(A, x)

        return f
    return spmv


def _scalar_setup(dtype, precise: bool):
    """``(dot, sdt)``: the CG-scalar dot product and the scalar dtype for
    ``dtype`` vector storage.

    bf16 storage (the half-traffic tier; the designed deviation from the
    reference's all-f64 arithmetic, ``comm.h:180-183``) computes every
    scalar in f32: plain mode accumulates the dots in f32
    (``preferred_element_type``), precise mode runs the compensated dot2
    over f32-widened reads.  Either way only bf16 bytes cross HBM; the
    widening rides the VPU.  f32/f64 storage keeps its native scalar
    path (dot2 when ``precise``)."""
    sdt = acc_dtype(dtype)
    if jnp.dtype(dtype) == jnp.bfloat16:
        if precise:
            def dot(a, b):
                return dot2(a.astype(sdt), b.astype(sdt))
        else:
            def dot(a, b):
                return jnp.dot(a, b, preferred_element_type=sdt)
        return dot, sdt
    return (dot2 if precise else jnp.dot), sdt


@functools.partial(jax.tree_util.register_dataclass,
                   data_fields=["x", "niterations", "rnrm2", "r0nrm2",
                                "bnrm2", "x0nrm2", "dxnrm2", "converged",
                                "breakdown"],
                   meta_fields=[])
@dataclasses.dataclass
class CGResult:
    """Device-resident solve result (one host transfer at the end).

    ``breakdown`` is the detector flag (``detect=True`` programs): the
    loop exited because the residual went non-finite or (p, Ap)
    non-positive -- the host recovery policy (solvers.resilience)
    decides restart-vs-abort.  Always False when detection is off."""

    x: jax.Array
    niterations: jax.Array
    rnrm2: jax.Array
    r0nrm2: jax.Array
    bnrm2: jax.Array
    x0nrm2: jax.Array
    dxnrm2: jax.Array
    converged: jax.Array
    breakdown: jax.Array


def _tolerances(crit: StoppingCriteria, r0nrm2, x0nrm2, dtype):
    """Device-side residual/diff thresholds; 0 disables (cf. cg.c:844-848)."""
    res_tol = jnp.maximum(jnp.asarray(crit.residual_atol, dtype),
                          jnp.asarray(crit.residual_rtol, dtype) * r0nrm2)
    diff_tol = jnp.maximum(jnp.asarray(crit.diff_atol, dtype),
                           jnp.asarray(crit.diff_rtol, dtype) * x0nrm2)
    return res_tol, diff_tol


def _converged(rnrm2sqr, dxnrm2sqr, res_tol, diff_tol):
    ok = jnp.asarray(False)
    ok = ok | jnp.where(res_tol > 0, rnrm2sqr < res_tol * res_tol, False)
    ok = ok | jnp.where(diff_tol > 0, dxnrm2sqr < diff_tol * diff_tol, False)
    return ok


def _breakdown_guard(gamma, denom):
    """``(bad, alpha)``: the ONE breakdown predicate every detecting
    loop shares -- non-finite gamma/denominator, or a non-positive
    denominator while progress remains -- and the guarded step size
    (a jnp.where select, NOT a zeroed multiply: 0 * inf is NaN, so a
    multiplied-out alpha would still poison the frozen vectors)."""
    bad = ((~jnp.isfinite(denom)) | (~jnp.isfinite(gamma))
           | ((denom <= 0) & (gamma > 0)))
    return bad, jnp.where(bad, jnp.zeros_like(gamma), gamma / denom)


def _iterate(iter_body, init_state, gamma_of, maxits, res_tol,
             diff_tol, dx_of, unbounded: bool, init_gamma=None,
             bad_of=None):
    """Run the CG iteration to maxits (traced scalar) or convergence.

    ``iter_body(k, state)`` receives the 0-based iteration index -- the
    hook the deterministic fault injector (acg_tpu.faults) keys on.

    Loop-structure choice, measured on TPU v5e (poisson2d n=2048, f32):
      * `fori_loop` with a *traced* bound and a minimal carry runs at the
        same speed as a static bound (~0.43 ms/iter) -- so a dynamic
        maxits costs nothing and one compile serves every iteration cap;
      * a `while_loop` whose predicate reads a data-dependent scalar costs
        ~+0.2 ms/iter (the carry dependence defeats loop pipelining), and
        testing only every K-th iteration in an outer while is *worse*
        (~3.7 ms per chunk boundary drain).
    Hence: tolerance-free solves (benchmark mode) take the pure fori path
    with no convergence predicate at all -- the analog of the reference
    always running with a deferred, one-iteration-stale test
    (``cgcuda.c:980-1052``) -- and tolerance-driven solves pay for the
    per-iteration device-side test exactly like the reference's
    device-initiated variant (``cg-kernels-cuda.cu:948-957``).

    ``bad_of`` (breakdown detection, ``detect=True`` programs) reads the
    carried breakdown flag; a flagged state exits the loop early so the
    host recovery policy can act.  Detection forces the while path even
    for unbounded solves -- the ~+0.2 ms/iter predicate cost is the
    price of early exit, paid only when recovery is requested.
    """
    if unbounded and bad_of is None:
        state = jax.lax.fori_loop(0, maxits, iter_body, init_state)
        return maxits, state, jnp.asarray(True)

    def body(carry):
        k, state, _ = carry
        state = iter_body(k, state)
        done = (jnp.asarray(False) if unbounded else
                _converged(gamma_of(state), dx_of(state), res_tol, diff_tol))
        return (k + 1, state, done)

    def cond(carry):
        go = (~carry[2]) & (carry[0] < maxits)
        if bad_of is not None:
            go = go & (~bad_of(carry[1]))
        return go

    # init_gamma overrides the carried value for the entry test: the
    # pipelined recurrence carries gamma_prev = inf at entry, but an
    # already-converged start (r0 = 0) must return x0 in 0 iterations,
    # not divide 0/0 in the first update.
    init_done = (jnp.asarray(False) if unbounded else _converged(
        gamma_of(init_state) if init_gamma is None else init_gamma,
        dx_of(init_state), res_tol, diff_tol))
    k, state, done = jax.lax.while_loop(cond, body,
                                        (jnp.int32(0), init_state,
                                         init_done))
    if unbounded:
        # unbounded semantics: "converged" = ran the full budget without
        # a detected breakdown (the only early exit on this path)
        done = ~bad_of(state)
    return k, state, done


@functools.partial(jax.jit,
                   static_argnames=("unbounded", "needs_diff", "precise",
                                    "kernels", "detect", "fault", "trace",
                                    "progress", "precond", "health",
                                    "state_io"))
def _cg_program(A: DeviceMatrix, b, x0, res_atol, res_rtol, diff_atol,
                diff_rtol, maxits, unbounded: bool, needs_diff: bool,
                precise: bool = False, kernels: str = "xla",
                detect: bool = False, fault=None, trace: int = 0,
                progress: int = 0, precond=None, mstate=None,
                health=None, state_io: bool = False, carry=None,
                k_offset=None):
    """Whole classic-CG solve as one XLA program.

    ``precise`` switches the CG scalars' dot products to the compensated
    dot2 (acg_tpu.ops.precision): ~2x working precision for gamma and
    (p, t), which is what lets plain-f32 storage converge past the
    ~1e-6 relative-residual stall.  bf16 storage keeps every scalar in
    f32 (``_scalar_setup``) and rounds the updated vectors once on
    store, so only half-width bytes cross HBM.

    ``detect`` (the resilience tier) carries a breakdown flag: a
    non-finite gamma or non-positive (p, Ap) FREEZES the iterate --
    alpha/beta would otherwise launder the poison into x -- and exits
    the loop so the host recovery policy can restart from the last good
    x.  ``fault`` is a static acg_tpu.faults.FaultSpec the injector
    threads into the loop (None compiles the unchanged program).

    ``trace`` (the telemetry tier, acg_tpu.telemetry) rides a
    ``(trace, 4)`` ring buffer of per-iteration ``(||r||^2, alpha,
    beta, pAp)`` in the loop carry -- recorded device-side, fetched
    ONCE with the result, no per-iteration host traffic -- and makes
    the program return ``(CGResult, buffer)``.  ``progress`` emits a
    host heartbeat every that-many iterations (jax.debug.callback).
    Both are static: 0 compiles the byte-identical pristine program.

    ``precond`` (a static :class:`acg_tpu.precond.PrecondSpec`) turns
    the loop into PRECONDITIONED CG: ``mstate`` (the preconditioner
    state pytree, an ordinary argument) feeds ``z = M^-1 r`` after each
    residual update, the CG scalar becomes ``gamma = (r, z)``, and the
    carry grows one extra true-residual scalar ``rr = (r, r)`` so the
    convergence test (and the reported rnrm2) keep the UNpreconditioned
    meaning while the telemetry ring records the preconditioned norm.
    ``None`` compiles the byte-identical unpreconditioned program
    (pinned in tests/test_hlo_structure.py).

    ``health`` (a static :class:`acg_tpu.health.HealthSpec`) arms the
    numerical-health tier: every ``health.every`` iterations a
    ``lax.cond``-guarded audit recomputes the TRUE residual
    ``b - A x`` through this program's own SpMV and carries the
    relative gap ``||r_true - r_rec||/||b||`` in a 4-scalar audit
    vector (returned with the result; an extra ``gap`` ring column
    when telemetry is also armed); the stagnation/sign detectors and a
    tripped gap feed the breakdown flag (``detect`` must then be
    armed).  ``None`` compiles the byte-identical unaudited program.
    ``health.abft`` additionally arms the Huang-Abraham
    checksum-protected SpMV: the column checksum ``c = A^T 1`` is
    computed once at setup through this program's own SpMV and a
    ``lax.cond``-guarded in-loop test compares ``sum(A p)`` against
    ``(c, p)`` at the audit cadence -- silent bit-level corruption
    (``sdc:flip``) detected on device and routed into the breakdown
    path.

    ``state_io``/``carry`` (the survivability tier, acg_tpu.
    checkpoint): ``state_io`` makes the program ALSO return the final
    loop carry ``(r, p, gamma[, rr])`` (x rides the result already),
    and a non-None ``carry`` of that shape re-enters the recurrence
    exactly where a previous chunk left it (``x0`` then holds the
    snapshot iterate; the setup ``r = b - A x0`` is skipped, so the
    chunked trajectory is ITERATION-IDENTICAL to an uninterrupted
    run).  ``k_offset`` (chunked dispatches only; None otherwise) is
    the trajectory iteration this chunk starts at, so the health
    tier's audit/ABFT cadence stays phased to GLOBAL iteration
    numbers across chunk boundaries.  Disarmed programs never name
    any of the three and lower byte-identical code (pinned in
    tests/test_checkpoint.py)."""
    dtype = b.dtype
    dot, sdt = _scalar_setup(dtype, precise)
    store = (lambda v: v.astype(dtype)) if sdt != dtype else (lambda v: v)
    spmv_ = _spmv_fn(kernels)
    bnrm2 = jnp.sqrt(dot(b, b))
    x0nrm2 = jnp.sqrt(dot(x0, x0))
    if precond is not None:
        from acg_tpu.precond import make_apply
        papply = make_apply(precond, spmv_)
    if carry is not None:
        # resume: the provided carry IS the loop state; nothing is
        # recomputed, so the Krylov recurrence continues exactly
        if precond is not None:
            r, p, gamma, rr = carry
            r0nrm2 = jnp.sqrt(rr)
        else:
            r, p, gamma = carry
            r0nrm2 = jnp.sqrt(gamma)
    elif precond is not None:
        r = b - spmv_(A, x0)
        z0 = papply(mstate, A, r)
        p = store(z0)
        gamma = dot(r, z0)
        rr = dot(r, r)
        r0nrm2 = jnp.sqrt(rr)
    else:
        r = b - spmv_(A, x0)
        p = r
        gamma = dot(r, r)
        r0nrm2 = jnp.sqrt(gamma)
    res_tol = jnp.maximum(res_atol, res_rtol * r0nrm2)
    diff_tol = jnp.maximum(diff_atol, diff_rtol * x0nrm2)
    inf = jnp.asarray(jnp.inf, sdt)

    if trace or progress:
        from acg_tpu import telemetry
    if health is not None:
        from acg_tpu import health as _health
    if health is not None and health.abft:
        # the column checksum c = A^T 1 (= A 1: symmetric systems),
        # through THIS program's own SpMV selection -- one extra SpMV
        # per solve, zero per-check SpMVs
        cvec = spmv_(A, jnp.ones_like(b)).astype(sdt)

        def dot3(a1, c1, a2, c2, a3, c3):
            return dot(a1, c1), dot(a2, c2), dot(a3, c3)

    # carry layout: (x, r, p, gamma [, rr] [, dx] [, bad] [, aud]
    # [, ring]) -- rr (the true residual the convergence test reads)
    # joins only under precond, dx only under a diff criterion, the
    # audit vector only under an armed health spec
    dx_i = 5 if precond is not None else 4

    # dxsqr joins the carry only when a diff criterion is active: every
    # extra loop-carried scalar measurably slows the TPU loop (~0.1 ms/it)
    def body(k, state):
        if trace:
            buf, state = state[-1], state[:-1]
        if health is not None:
            aud, state = state[-1], state[:-1]
        x, r, p, gamma = state[:4]
        # NOT the fused dia_spmv_dot: measured in-loop, the in-kernel
        # (p,t) scalar costs ~15% (1,355 vs 1,589 iters/s interleaved
        # A/B) -- the opaque kernel boundary forfeits XLA's fusion of
        # the updates, the same verdict as the fused 6-vector update
        # (BASELINE.md)
        t = spmv_(A, p)
        if fault is not None:
            t = fault.apply_spmv(t, k)
        pdott = dot(p, t)
        if fault is not None:
            pdott = fault.apply_dot(pdott, k)
        if detect:
            # breakdown BEFORE the updates: a non-finite t/pdott or an
            # indefiniteness signal must not reach x
            bad, alpha = _breakdown_guard(gamma, pdott)
            x = store(jnp.where(bad, x, x + alpha * p))
            r = store(jnp.where(bad, r, r - alpha * t))
        else:
            alpha = gamma / pdott
            x = store(x + alpha * p)
            r = store(r - alpha * t)
        if precond is not None:
            z = papply(mstate, A, r)
            if fault is not None:
                z = fault.apply_precond(z, k)
            gamma_next = dot(r, z)
            rr_next = dot(r, r)
            beta = gamma_next / gamma
            p_next = store(z + beta * p)
            out = (x, r, p_next, gamma_next, rr_next)
        else:
            gamma_next = dot(r, r)
            beta = gamma_next / gamma
            p_next = store(r + beta * p)
            out = (x, r, p_next, gamma_next)
        if needs_diff:
            dx = alpha * alpha * dot(p, p)
            if detect:
                # freeze dx too: a zeroed alpha would make the frozen
                # iteration "satisfy" the diff criterion and launder the
                # breakdown into a converged exit
                dx = jnp.where(bad, state[dx_i], dx)
            out = out + (dx,)
        fire = None
        if health is not None:
            # the health cadence is phased to TRAJECTORY iterations:
            # chunked dispatches (the checkpoint tier) pass the chunk's
            # starting iteration so audits fire at the same global
            # iterations as an uninterrupted run
            kk = k if k_offset is None else k + k_offset

            # in-loop true-residual audit: b - A x through THIS
            # program's SpMV, guarded by lax.cond so non-audited
            # iterations pay only the predicate
            def compute_gap():
                return _health.relative_gap(b - spmv_(A, x), r,
                                            dot, bnrm2, sdt)

            aud, fire = _health.audit_update(aud, health, kk, compute_gap)
            # residual non-decrease, measured on the scalar the
            # convergence test reads (preconditioned: the carried rr)
            prog_now = out[4] if precond is not None else gamma_next
            prog_prev = state[4] if precond is not None else gamma
            aud = _health.stall_update(aud, health,
                                       prog_now < prog_prev)
            if health.abft:
                # Huang-Abraham checksum test of this iteration's
                # t = A p against the precomputed column checksum
                aud = _health.abft_update(aud, health, kk, t, p, cvec,
                                          dot3, sdt, t.shape[0])
        if detect:
            # a poison that slipped past pdott (e.g. a NaN row of t with
            # a finite dot) lands in r: flag it one iteration deferred.
            # Under precond, a NEGATIVE (r, z) is the non-SPD-M signal
            # (the precond: fault site's deterministic twin)
            deferred = bad | (~jnp.isfinite(gamma_next))
            if precond is not None:
                deferred = deferred | (gamma_next < 0)
            if health is not None:
                if precond is None:
                    # sign anomaly: a negative computed (r, r) is
                    # arithmetic poison the finite-value guard misses
                    deferred = deferred | (gamma_next < 0)
                deferred = deferred | _health.trip(aud, health)
            out = out + (deferred,)
        if health is not None:
            out = out + (aud,)
        if trace:
            # record the RAW scalars (a poisoned pdott/gamma_next stays
            # visible in the window the recovery log quotes); under
            # precond gamma IS the preconditioned residual norm^2
            audit_col = (_health.ring_gap(aud, fire, sdt)
                         if health is not None else None)
            out = out + (telemetry.ring_record(buf, k, gamma_next, alpha,
                                               beta, pdott,
                                               audit=audit_col),)
        if progress:
            telemetry.heartbeat(k, gamma_next, progress)
        return out

    # the audit vector and ring buffer ride LAST in the carry (in that
    # order) so every existing index (dx, the deferred-bad freeze
    # reads) is untouched; only the tail accessors below shift
    init_state = (x0, r, p, gamma)
    if precond is not None:
        init_state = init_state + (rr,)
    init_state = init_state + ((inf,) if needs_diff else ())
    if detect:
        init_state = init_state + (jnp.asarray(False),)
    if health is not None:
        init_state = init_state + (_health.audit_init(sdt, health),)
    if trace:
        init_state = init_state + (telemetry.ring_init(
            trace, sdt, audit=health is not None),)
    ntail = (1 if trace else 0) + (1 if health is not None else 0)
    bad_i = -1 - ntail
    # the convergence test reads the TRUE residual either way: gamma
    # itself unpreconditioned, the carried rr under precond
    conv_i = 4 if precond is not None else 3
    k, state, done = _iterate(
        body, init_state, lambda s: s[conv_i], maxits,
        res_tol, diff_tol,
        (lambda s: s[dx_i]) if needs_diff else (lambda s: inf),
        unbounded, bad_of=(lambda s: s[bad_i]) if detect else None)
    x, r, p, gamma = state[:4]
    rnrm2sqr = state[4] if precond is not None else gamma
    dxsqr = state[dx_i] if needs_diff else inf
    breakdown = state[bad_i] if detect else jnp.asarray(False)
    # a breakdown flagged on the same iteration the tolerance was met is
    # convergence, not breakdown: at the f32 floor the (p, Ap) scalar
    # legitimately rounds to <= 0 once progress is exhausted
    breakdown = breakdown & ~done
    res = CGResult(x=x, niterations=k, rnrm2=jnp.sqrt(rnrm2sqr),
                   r0nrm2=r0nrm2, bnrm2=bnrm2, x0nrm2=x0nrm2,
                   dxnrm2=jnp.sqrt(dxsqr), converged=done,
                   breakdown=breakdown)
    extras = ()
    if trace:
        extras = extras + (state[-1],)
    if health is not None:
        extras = extras + (state[-2] if trace else state[-1],)
    if state_io:
        # the loop carry, strictly last (x rides the result already):
        # what the checkpoint chunk driver snapshots and threads into
        # the next dispatch's ``carry``
        core = (r, p, gamma)
        if precond is not None:
            core = core + (state[4],)
        extras = extras + (core,)
    return (res,) + extras if extras else res


@functools.partial(jax.jit,
                   static_argnames=("K", "unbounded", "restart", "kernels"))
def _cg_replaced_program(A: DeviceMatrix, b, x0, res_atol, res_rtol,
                         maxits, K: int, unbounded: bool, restart: bool,
                         kernels: str = "xla"):
    """Classic CG over bf16 vector storage made SOUND by periodic f32
    residual replacement -- the accuracy contract for the half-traffic
    tier at high condition numbers.

    Plain bf16 CG diverges once kappa exceeds ~1/u_bf16 ~ 500 (measured:
    rel residual 1e3 after 1000 iterations at the flagship's kappa ~
    1.7e6, BASELINE.md): the bf16-rounded r/p recurrences drift off the
    true residual and the drift compounds.  The classical fix (residual
    replacement, Van der Vorst & Ye) bounds the drift window: every
    ``K`` iterations the true residual ``r = b - A x`` is recomputed in
    f32 and swapped in for the recurrence residual.

    Device layout: ``x`` accumulates in f32 but is NOT touched by the
    inner loop -- each segment accumulates its correction ``d`` from
    zero in bf16 and adds it to x once per segment, so the per-iteration
    HBM traffic stays identical to the plain bf16 tier (~40 B/row on the
    5-point flagship) and the replacement costs one mixed-precision
    SpMV (bf16 planes x f32 vector, f32 accumulation -- lossless for
    bf16-exact stencil values, the ``--dtype mixed`` arithmetic) per K
    iterations: ~2% at K=50.  ``d``'s bf16 rounding does not feed back
    into the inner recurrences at all (r evolves independently); it only
    caps the per-segment residual reduction, and the next replacement
    measures whatever reduction was actually achieved, so the outer
    iteration is self-correcting -- iterative refinement with an
    inner-bf16-CG solver (the same structure as solvers.refine, fully
    device-resident).

    ``restart=True`` additionally resets ``p = r`` at each replacement
    (restarted CG: discards Krylov memory, maximally stable);
    ``restart=False`` carries ``p`` across segments (classical residual
    replacement: keeps the convergence rate, slightly less protection).
    Convergence is tested once per segment on the TRUE f32 residual --
    so unlike the plain tiers, a converged report from this program is
    grounded in an f32-accurate residual by construction.

    The role of the reference's strictly-f64 contract (``comm.h:
    180-183``) restated for TPU storage tiers; SURVEY.md section 7
    "hard parts" (f64-on-TPU mitigation ladder).
    """
    sdt = jnp.float32
    vdt = jnp.bfloat16
    spmv_ = _spmv_fn(kernels)

    def dot(u, v):
        return jnp.dot(u, v, preferred_element_type=sdt)

    b = b.astype(sdt)
    x0 = x0.astype(sdt)
    bnrm2 = jnp.sqrt(dot(b, b))
    x0nrm2 = jnp.sqrt(dot(x0, x0))
    r32 = b - spmv_(A, x0)
    gamma32 = dot(r32, r32)
    r0nrm2 = jnp.sqrt(gamma32)
    res_tol = jnp.maximum(res_atol.astype(sdt), res_rtol.astype(sdt) * r0nrm2)
    inf = jnp.asarray(jnp.inf, sdt)

    def segment(x32, r32, p, its):
        """One replacement period: inner bf16 CG on A d = r32 from d=0,
        then x += d and ONE f32-accurate SpMV for the fresh residual.

        The inner loop's trip count is the STATIC K even when fewer
        iterations remain (a carry-dependent bound would compile to a
        dynamic-trip loop XLA cannot software-pipeline -- measured 0.55x
        the plain-bf16 rate, uniformly over K and mode); the final
        partial segment instead masks the updates of its dead tail via
        ``live`` (at most K-1 wasted iterations per solve, and none at
        all when maxits is a multiple of K, as in the bench protocol)."""
        r = r32.astype(vdt)
        gamma = dot(r, r)
        if restart:
            p = r
        else:
            # carried-direction health check: at kappa*u_bf16 >> 1 the
            # bf16 p-recurrence can blow up across segments (beta > 1
            # sustained); once p overflows, alpha*p = 0*inf would poison
            # d with NaNs.  Reset the direction to r when it has grown
            # out of all proportion to the residual -- a restart at
            # exactly the boundaries where one is needed.
            pn = dot(p, p)
            bad = (~jnp.isfinite(pn)) | (pn > jnp.asarray(1e24, sdt) * gamma)
            p = jnp.where(bad, r, p)
        nin = jnp.minimum(jnp.int32(K), maxits - its)

        def ibody(j, st):
            d, r, p, gamma = st
            live = j < nin
            t = spmv_(A, p)
            pdott = dot(p, t)
            # carried directions (restart=False) are not orthogonal to
            # the replaced residual, so the classic numerator gamma =
            # (r, r) misestimates the step along p -- measured:
            # catastrophic overshoot (rel residual 1e18).  The general
            # line-search numerator (r, p) reduces to gamma under exact
            # conjugacy and stays correct without it; restarted segments
            # keep the cheaper classic form.
            num = gamma if restart else dot(r, p)
            # breakdown guards: bf16 rounding noise can drive (p, Ap)
            # to 0 or negative once the segment's progress is
            # exhausted; freeze the updates (alpha = 0) instead of
            # poisoning d -- the next replacement resets the segment
            # either way.  The same freeze implements the dead tail of
            # the final partial segment.
            alpha = jnp.where(live & (pdott > 0), num / pdott,
                              jnp.zeros_like(gamma))
            d = (d.astype(sdt) + alpha * p.astype(sdt)).astype(vdt)
            r_new = (r.astype(sdt) - alpha * t.astype(sdt)).astype(vdt)
            gamma_next = dot(r_new, r_new)
            beta = jnp.where(gamma > 0, gamma_next / gamma,
                             jnp.zeros_like(gamma))
            # alpha = 0 already freezes d and r; p needs an explicit
            # select (beta freezes at 1 there, which would add r to p)
            p = jnp.where(live,
                          (r_new.astype(sdt)
                           + beta * p.astype(sdt)).astype(vdt), p)
            return (d, r_new, p, gamma_next)

        d, _, p, _ = jax.lax.fori_loop(
            0, K, ibody, (jnp.zeros_like(r), r, p, gamma))
        x32 = x32 + d.astype(sdt)
        r32 = b - spmv_(A, x32)
        return x32, r32, p, its + nin, dot(r32, r32)

    p0 = r32.astype(vdt)
    if unbounded:
        nouter = (maxits + jnp.int32(K) - 1) // jnp.int32(K)

        def obody(_, carry):
            x32, r32, p, its, _ = carry
            return segment(x32, r32, p, its)

        x32, r32f, _, its, gamma_f = jax.lax.fori_loop(
            0, nouter, obody, (x0, r32, p0, jnp.int32(0), gamma32))
        # per-segment true-residual breakdown flag: the replacement
        # machinery IS this tier's detector (a poisoned segment leaves a
        # non-finite recomputed residual), no in-loop cost
        return CGResult(x=x32, niterations=its, rnrm2=jnp.sqrt(gamma_f),
                        r0nrm2=r0nrm2, bnrm2=bnrm2, x0nrm2=x0nrm2,
                        dxnrm2=inf, converged=jnp.isfinite(gamma_f),
                        breakdown=~jnp.isfinite(gamma_f))

    def wcond(carry):
        _, _, _, its, gamma = carry
        return (gamma >= res_tol * res_tol) & (its < maxits)

    def wbody(carry):
        x32, r32, p, its, _ = carry
        return segment(x32, r32, p, its)

    x32, r32f, _, its, gamma_f = jax.lax.while_loop(
        wcond, wbody, (x0, r32, p0, jnp.int32(0), gamma32))
    # a non-finite recomputed residual exits wcond (NaN >= x is False):
    # the segment boundary doubles as the breakdown detector for free
    return CGResult(x=x32, niterations=its, rnrm2=jnp.sqrt(gamma_f),
                    r0nrm2=r0nrm2, bnrm2=bnrm2, x0nrm2=x0nrm2,
                    dxnrm2=inf, converged=gamma_f < res_tol * res_tol,
                    breakdown=~jnp.isfinite(gamma_f))


@functools.partial(jax.jit,
                   static_argnames=("unbounded", "interpret"))
def _cg_fused_program(A: DeviceMatrix, b, x0, res_atol, res_rtol,
                      maxits, unbounded: bool, interpret: bool = False):
    """Whole classic-CG solve with the TWO-PHASE fused iteration
    (ops.pallas_kernels.cg_phase_a/b): the reference's monolithic
    device-kernel concept (``cg-kernels-cuda.cu:627-970``) done the TPU
    way -- each iteration is exactly two streamed kernels with scalars
    in SMEM, ~15 HBM passes vs the XLA formulation's ~20 (and ~12.5
    with bf16 planes).  Unlike round 2's single fused kernels, nothing
    is left outside the kernels for XLA to fuse, so there is no fusion
    to forfeit.  Scalars are f32 throughout; supports residual criteria
    (the carried gamma IS the fresh ||r||^2 -- the convergence test is
    free) but not diff criteria."""
    from acg_tpu.ops.pallas_kernels import cg_phase_a, cg_phase_b

    dtype = b.dtype
    sdt = jnp.float32
    bnrm2 = jnp.sqrt(jnp.dot(b, b, preferred_element_type=sdt))
    x0nrm2 = jnp.sqrt(jnp.dot(x0, x0, preferred_element_type=sdt))
    r = b - spmv(A, x0)
    gamma = jnp.dot(r, r, preferred_element_type=sdt)
    r0nrm2 = jnp.sqrt(gamma)
    res_tol = jnp.maximum(res_atol.astype(sdt),
                          res_rtol.astype(sdt) * r0nrm2)
    inf = jnp.asarray(jnp.inf, sdt)
    p0 = jnp.zeros_like(b)

    def body(st):
        x, r, p, gamma, gamma_prev = st
        p, t, pdott = cg_phase_a(A.data, A.offsets, r, p, gamma,
                                 gamma_prev, interpret=interpret)
        x, r, gamma_next = cg_phase_b(x, p, r, t, gamma, pdott,
                                      interpret=interpret)
        return (x, r, p, gamma_next, gamma)

    init = (x0, r, p0, gamma, inf)
    if unbounded:
        state = jax.lax.fori_loop(0, maxits, lambda _, s: body(s), init)
        k, done = maxits, jnp.asarray(True)
    else:
        def wcond(carry):
            k, st, done = carry
            return (~done) & (k < maxits)

        def wbody(carry):
            k, st, _ = carry
            st = body(st)
            return (k + 1, st, st[3] < res_tol * res_tol)

        k, state, done = jax.lax.while_loop(
            wcond, wbody, (jnp.int32(0), init, gamma < res_tol * res_tol))
    x, r_fin, _, gamma_fin, _ = state
    return CGResult(x=x, niterations=k, rnrm2=jnp.sqrt(gamma_fin),
                    r0nrm2=r0nrm2, bnrm2=bnrm2, x0nrm2=x0nrm2,
                    dxnrm2=inf, converged=done,
                    breakdown=jnp.asarray(False))


@functools.partial(jax.jit,
                   static_argnames=("unbounded", "needs_diff", "precise",
                                    "kernels", "detect", "fault", "trace",
                                    "progress", "precond", "health",
                                    "state_io"))
def _cg_pipelined_program(A: DeviceMatrix, b, x0, res_atol, res_rtol,
                          diff_atol, diff_rtol, maxits, unbounded: bool,
                          needs_diff: bool, precise: bool = False,
                          kernels: str = "xla", detect: bool = False,
                          fault=None, trace: int = 0, progress: int = 0,
                          precond=None, mstate=None, health=None,
                          state_io: bool = False, carry=None,
                          k_offset=None):
    """Whole pipelined-CG (Ghysels-Vanroose) solve as one XLA program.

    ``detect``/``fault``/``trace``/``progress`` as in
    :func:`_cg_program`.  The pipelined recurrences are the brittle ones
    (deep pipelining amplifies rounding -- Cornelis & Vanroose,
    arXiv:1801.04728), and a poisoned q/w shows up one iteration
    deferred in the (w, r) reduction: detection here is inherently one
    iteration stale, like the convergence test.  The telemetry window
    records the CARRIED gamma = ||r||^2 from before the update (the
    same one-iteration-stale quantity the convergence test uses) and
    the alpha denominator in the pAp slot -- exactly the recurrence
    scalars whose drift the deep-pipelining literature plots.

    ``precond``/``mstate`` arm the PRECONDITIONED pipelined variant
    (Ghysels-Vanroose's M^-1 formulation, the method arXiv:1801.04728 /
    1905.06850 actually pipeline): the carry grows ``u = M^-1 r`` and
    ``q = M^-1 s`` plus the extra ``w/m/n`` recurrences -- one
    preconditioner apply (``m = M^-1 w``) and one SpMV (``n = A m``)
    per iteration, both overlapping the fused reduction exactly like
    the unpreconditioned q = A w.  The fused reduction carries THREE
    scalars (gamma = (r, u), delta = (w, u), rr = (r, r)) so the mesh
    tiers keep the single-allreduce property.  ``None`` compiles the
    byte-identical unpreconditioned program.

    ``health`` (acg_tpu.health.HealthSpec) arms the in-loop
    true-residual audit + stagnation/sign detectors exactly as in
    :func:`_cg_program` -- this is the tier the audit matters MOST for:
    the pipelined recurrences are the ones whose recursively-updated
    residual drifts from ``b - A x`` (arXiv:1801.04728), and the audit
    measures that drift with the loop's own SpMV."""
    dtype = b.dtype
    dot, sdt = _scalar_setup(dtype, precise)
    store = (lambda v: v.astype(dtype)) if sdt != dtype else (lambda v: v)
    spmv_ = _spmv_fn(kernels)
    bnrm2 = jnp.sqrt(dot(b, b))
    x0nrm2 = jnp.sqrt(dot(x0, x0))
    if precond is not None:
        from acg_tpu.precond import make_apply
        papply = make_apply(precond, spmv_)
    # resume (the survivability tier): a provided carry re-enters the
    # GV recurrence exactly -- x0 holds the snapshot iterate, and the
    # carried vectors (incl. w = A-image and the z/t/q scratch whose
    # recurrences the pipelined variant never rebuilds) replace the
    # whole setup.  carry layout matches checkpoint.carry_names
    c_in = None
    if carry is not None:
        c_in = carry
        if precond is not None:
            r, rr0 = c_in[0], c_in[9]
            r0nrm2 = jnp.sqrt(rr0)
        else:
            r = c_in[0]
            r0nrm2 = jnp.sqrt(jnp.maximum(c_in[5], 0))
    elif precond is not None:
        r = b - spmv_(A, x0)
        u0 = store(papply(mstate, A, r))
        w = spmv_(A, u0)
        rr0 = dot(r, r)
        r0nrm2 = jnp.sqrt(rr0)
    else:
        r = b - spmv_(A, x0)
        w = spmv_(A, r)
        r0nrm2 = jnp.sqrt(dot(r, r))
    res_tol = jnp.maximum(res_atol, res_rtol * r0nrm2)
    diff_tol = jnp.maximum(diff_atol, diff_rtol * x0nrm2)
    inf = jnp.asarray(jnp.inf, sdt)
    zeros = jnp.zeros_like(b)
    if trace or progress:
        from acg_tpu import telemetry
    if health is not None:
        from acg_tpu import health as _health
    if health is not None and health.abft:
        # column checksum through this program's own SpMV (see
        # _cg_program); the pipelined test verifies q = A w / n = A m
        cvec = spmv_(A, jnp.ones_like(b)).astype(sdt)

        def dot3(a1, c1, a2, c2, a3, c3):
            return dot(a1, c1), dot(a2, c2), dot(a3, c3)

    def pbody(k, state):
        """Preconditioned GV body: carry (x, r, u, w, p, s, q, z,
        gamma_prev, alpha_prev, rr) -- s is the A-direction (the
        unpreconditioned t), z the A M^-1 A-direction, q the M^-1
        A-direction."""
        if trace:
            buf, state = state[-1], state[:-1]
        if health is not None:
            aud, state = state[-1], state[:-1]
        x, r, u, w, p, s, q, z, gamma_prev, alpha_prev = state[:10]
        rr_prev = state[10]
        # the iteration's three reductions, fused (ONE allreduce on a
        # mesh): gamma/delta drive the recurrences, rr feeds the true-
        # residual convergence test (stale by one, like gamma)
        gamma = dot(r, u)
        delta = dot(w, u)
        rr = dot(r, r)
        if fault is not None:
            delta = fault.apply_dot(delta, k)
        # m = M^-1 w and n = A m overlap the reduction under XLA's
        # scheduler -- the preconditioned restatement of q = A w
        m = papply(mstate, A, w)
        if fault is not None:
            m = fault.apply_precond(m, k)
        nvec = spmv_(A, m)
        if fault is not None:
            nvec = fault.apply_spmv(nvec, k)
        beta = gamma / gamma_prev               # inf -> 0 on first iteration
        denom = delta - beta * (gamma / alpha_prev)
        if detect:
            bad, alpha = _breakdown_guard(gamma, denom)
            # a negative (r, u) is the non-SPD-M signal (precond: fault
            # twin); the unpreconditioned guard cannot see it
            bad = bad | (gamma < 0)
            alpha = jnp.where(bad, jnp.zeros_like(alpha), alpha)
        else:
            alpha = gamma / denom
        z = store(nvec + beta * z)
        q = store(m + beta * q)
        s = store(w + beta * s)
        p = store(u + beta * p)
        if detect:
            x = store(jnp.where(bad, x, x + alpha * p))
            r = store(jnp.where(bad, r, r - alpha * s))
            u = store(jnp.where(bad, u, u - alpha * q))
            w = store(jnp.where(bad, w, w - alpha * z))
        else:
            x = store(x + alpha * p)
            r = store(r - alpha * s)
            u = store(u - alpha * q)
            w = store(w - alpha * z)
        out = (x, r, u, w, p, s, q, z, gamma, alpha, rr)
        if needs_diff:
            dx = alpha * alpha * dot(p, p)
            if detect:
                dx = jnp.where(bad, state[11], dx)
            out = out + (dx,)
        fire = None
        if health is not None:
            kk = k if k_offset is None else k + k_offset

            def compute_gap():
                return _health.relative_gap(b - spmv_(A, x), r,
                                            dot, bnrm2, sdt)

            aud, fire = _health.audit_update(aud, health, kk, compute_gap)
            # progress measured on the fused (r, r) scalar (stale by
            # one, like the convergence test)
            aud = _health.stall_update(aud, health, rr < rr_prev)
            if health.abft:
                # checksum test of this iteration's n = A m
                aud = _health.abft_update(aud, health, kk, nvec, m,
                                          cvec, dot3, sdt,
                                          nvec.shape[0])
        if detect:
            flag = bad
            if health is not None:
                flag = flag | _health.trip(aud, health)
            out = out + (flag,)
        if health is not None:
            out = out + (aud,)
        if trace:
            # gamma = the PRECONDITIONED residual norm^2 (stale by one,
            # like the convergence test); alpha denominator in pAp slot
            audit_col = (_health.ring_gap(aud, fire, sdt)
                         if health is not None else None)
            out = out + (telemetry.ring_record(buf, k, gamma, alpha,
                                               beta, denom,
                                               audit=audit_col),)
        if progress:
            telemetry.heartbeat(k, gamma, progress)
        return out

    def body(k, state):
        if trace:
            buf, state = state[-1], state[:-1]
        if health is not None:
            aud, state = state[-1], state[:-1]
        x, r, w, p, t, z, gamma_prev, alpha_prev = state[:8]
        # both reductions of the iteration, fused (one allreduce on a mesh)
        gamma = dot(r, r)
        delta = dot(w, r)
        if fault is not None:
            delta = fault.apply_dot(delta, k)
        # SpMV overlaps the allreduce in the reference (cgcuda.c:1750-1790);
        # under XLA the scheduler owns that overlap.
        q = spmv_(A, w)
        if fault is not None:
            q = fault.apply_spmv(q, k)
        # the SpMV input, before the 6-vector update rebinds w below
        # (the ABFT check verifies q against THIS vector)
        w_in = w
        beta = gamma / gamma_prev               # inf -> 0 on first iteration
        denom = delta - beta * (gamma / alpha_prev)
        if detect:
            # the alpha denominator plays the (p, Ap) role here; freeze
            # x/r/w on breakdown (p/t/z are scratch once the loop exits)
            bad, alpha = _breakdown_guard(gamma, denom)
            if health is not None:
                # sign anomaly: a negative computed (r, r) is
                # arithmetic poison (the finite-value guard misses it)
                bad = bad | (gamma < 0)
                alpha = jnp.where(bad, jnp.zeros_like(alpha), alpha)
        else:
            alpha = gamma / denom
        # the 6-vector update stays in XLA even under kernels="pallas":
        # the hand-written fused kernel (ops.pallas_kernels.
        # fused_pipelined_update) wins in isolation (~1.35x) but inside
        # the loop it is an opaque call that forfeits XLA's fusion of the
        # *next* iteration's dots into these writes -- measured 894 vs
        # 1818 iters/s on the flagship (BASELINE.md)
        z = store(q + beta * z)
        t = store(w + beta * t)
        p = store(r + beta * p)
        if detect:
            x = store(jnp.where(bad, x, x + alpha * p))
            r = store(jnp.where(bad, r, r - alpha * t))
            w = store(jnp.where(bad, w, w - alpha * z))
        else:
            x = store(x + alpha * p)
            r = store(r - alpha * t)
            w = store(w - alpha * z)
        out = (x, r, w, p, t, z, gamma, alpha)
        if needs_diff:
            dx = alpha * alpha * dot(p, p)
            if detect:
                # freeze dx on breakdown (see _cg_program): alpha = 0
                # must not fake the diff criterion
                dx = jnp.where(bad, state[8], dx)
            out = out + (dx,)
        fire = None
        if health is not None:
            kk = k if k_offset is None else k + k_offset

            def compute_gap():
                return _health.relative_gap(b - spmv_(A, x), r,
                                            dot, bnrm2, sdt)

            aud, fire = _health.audit_update(aud, health, kk, compute_gap)
            aud = _health.stall_update(aud, health, gamma < gamma_prev)
            if health.abft:
                # checksum test of this iteration's q = A w (w_in: the
                # pre-update input that produced q)
                aud = _health.abft_update(aud, health, kk, q, w_in,
                                          cvec, dot3, sdt, q.shape[0])
        if detect:
            flag = bad
            if health is not None:
                flag = flag | _health.trip(aud, health)
            out = out + (flag,)
        if health is not None:
            out = out + (aud,)
        if trace:
            # the carried gamma (stale by one, like the convergence
            # test) and the alpha denominator in the pAp slot
            audit_col = (_health.ring_gap(aud, fire, sdt)
                         if health is not None else None)
            out = out + (telemetry.ring_record(buf, k, gamma, alpha,
                                               beta, denom,
                                               audit=audit_col),)
        if progress:
            telemetry.heartbeat(k, gamma, progress)
        return out

    # convergence tests the carried gamma = ||r||^2 from *before* the
    # update -- one iteration stale, the reference's deferred test
    # (cgcuda.c:1798-1810); saves a fresh dot per iteration.  The
    # preconditioned carry tests the carried rr (same staleness), so
    # tolerances keep the true-residual meaning
    if precond is not None:
        if c_in is not None:
            init_state = (x0,) + tuple(c_in) + (
                (inf,) if needs_diff else ())
        else:
            init_state = (x0, r, u0, w, zeros, zeros, zeros, zeros, inf,
                          inf, rr0) + ((inf,) if needs_diff else ())
        loop_body = pbody
        conv_of = lambda s: s[10]
        dx_of = (lambda s: s[11]) if needs_diff else (lambda s: inf)
        init_gamma = rr0
    else:
        if c_in is not None:
            init_state = (x0,) + tuple(c_in) + (
                (inf,) if needs_diff else ())
            init_gamma = c_in[5]
        else:
            init_state = (x0, r, w, zeros, zeros, zeros, inf, inf) + (
                (inf,) if needs_diff else ())
            init_gamma = r0nrm2 * r0nrm2
        loop_body = body
        conv_of = lambda s: s[6]
        dx_of = (lambda s: s[8]) if needs_diff else (lambda s: inf)
    if detect:
        init_state = init_state + (jnp.asarray(False),)
    if health is not None:
        init_state = init_state + (_health.audit_init(sdt, health),)
    if trace:
        init_state = init_state + (telemetry.ring_init(
            trace, sdt, audit=health is not None),)
    ntail = (1 if trace else 0) + (1 if health is not None else 0)
    bad_i = -1 - ntail
    k, state, done = _iterate(
        loop_body, init_state, conv_of, maxits,
        res_tol, diff_tol, dx_of,
        unbounded, init_gamma=init_gamma,
        bad_of=(lambda s: s[bad_i]) if detect else None)
    x, r = state[0], state[1]
    dxsqr = ((state[11] if precond is not None else state[8])
             if needs_diff else inf)
    breakdown = state[bad_i] if detect else jnp.asarray(False)
    rnrm2 = jnp.sqrt(dot(r, r))
    # the in-loop test is one iteration stale; at the maxits boundary a
    # solve whose final *fresh* residual meets tolerance must not report
    # converged=False with a below-tolerance rnrm2 in the same stats block
    done = jnp.logical_or(done, rnrm2 <= res_tol)
    # ... and a breakdown whose frozen residual already meets tolerance
    # is convergence: near the floor the pipelined denominator
    # legitimately rounds <= 0 (the recurrences' known brittleness)
    breakdown = breakdown & ~done
    res = CGResult(x=x, niterations=k, rnrm2=rnrm2, r0nrm2=r0nrm2,
                   bnrm2=bnrm2, x0nrm2=x0nrm2, dxnrm2=jnp.sqrt(dxsqr),
                   converged=done, breakdown=breakdown)
    extras = ()
    if trace:
        extras = extras + (state[-1],)
    if health is not None:
        extras = extras + (state[-2] if trace else state[-1],)
    if state_io:
        # the GV loop carry, strictly last (checkpoint.carry_names
        # order minus x, which rides the result)
        core = tuple(state[1:11] if precond is not None
                     else state[1:8])
        extras = extras + (core,)
    return (res,) + extras if extras else res


class JaxCGSolver:
    """Single-device CG solver over a :class:`DeviceMatrix`.

    The role of ``acgsolvercuda_init/solvempi/solve_pipelined`` with
    commsize==1 (``cgcuda.c:143-332,403-1917``): keeps the matrix and
    workspace device-resident across solves and accumulates statistics.
    """

    def __init__(self, A: DeviceMatrix, pipelined: bool = False,
                 precise_dots: bool = False, kernels: str = "auto",
                 vector_dtype=None, replace_every: int = 0,
                 replace_restart: bool = True, recovery=None,
                 host_matrix=None, trace: int = 0, progress: int = 0,
                 precond=None, health=None, ckpt=None, algorithm=None):
        """``recovery`` (a :class:`acg_tpu.solvers.resilience.
        RecoveryPolicy`) arms breakdown detection in the compiled loop
        plus the host-side restart policy; ``host_matrix`` (scipy CSR)
        additionally enables the final host-solver fallback rung.
        Detection also arms automatically while the fault injector
        (acg_tpu.faults) is active, so injected faults are never
        silently laundered into a returned x.

        ``trace`` (iterations; 0 = off) arms the in-loop convergence
        telemetry ring (acg_tpu.telemetry): the last solve's trailing
        window lands on ``self.last_trace`` / ``stats.trace`` with one
        extra device fetch per solve.  ``progress`` (iterations; 0 =
        off) emits an in-loop heartbeat to stderr.  Both reach the
        direct classic/pipelined programs only -- the replacement and
        fused tiers refuse at solve time rather than silently record
        nothing (the fault-injector rationale).

        ``vector_dtype`` decouples vector storage from matrix storage
        (default: the matrix dtype).  The supported mix is bf16 matrix +
        f32 vectors (``--dtype mixed``): for matrices whose entries are
        exactly representable in bf16 (Poisson stencils: -1, 4, 6) the
        arithmetic is IDENTICAL to all-f32 -- the f32-accumulating SpMV
        reads the planes losslessly -- while matrix HBM traffic halves.
        Unlike the all-bf16 tier it has no kappa limit: bf16 vector
        storage caps convergence at kappa ~ 1/u_bf16 ~ 500 (measured:
        diverges on 2D Poisson n >= 512), whereas this tier's iterates
        never touch bf16.

        ``precond`` (an :class:`acg_tpu.precond.PrecondSpec`, a spec
        string like ``"jacobi"``/``"bjacobi:32"``/``"cheby:4"``, or
        None) arms preconditioned CG / pipelined CG: the state is built
        once (lazily, on device) and rides the solve programs as an
        argument; ``None`` leaves every lowered program byte-identical
        to an unpreconditioned build.

        ``health`` (an :class:`acg_tpu.health.HealthSpec` or None) arms
        the numerical-health tier: the in-loop true-residual audit
        (every ``health.every`` iterations, through this tier's own
        SpMV), the stagnation/sign detectors, and -- for tripping
        actions -- the breakdown path + recovery hand-off.  ``None``
        leaves every lowered program byte-identical to an unaudited
        build (pinned in tests/test_hlo_structure.py)."""
        self.A = A
        self.vector_dtype = vector_dtype
        self.pipelined = pipelined
        self.precise_dots = precise_dots
        # recurrence selection (acg_tpu.recurrence): classic/pipelined
        # resolve onto the existing hand-built programs (byte-identical
        # dispatch -- the builder emission is pinned equal in
        # tests/test_hlo_structure.py); sstep:S / pipelined:L dispatch
        # the communication-avoiding builder programs
        from acg_tpu.recurrence import parse_algorithm
        self.algo = parse_algorithm(algorithm)
        if self.algo is not None and not self.algo.communication_avoiding:
            self.pipelined = pipelined = (self.algo.kind == "pipelined")
            self.algo = None
        self._lam = None  # cached (lmin, lmax) spectral estimate
        if kernels == "auto":
            # the Pallas kernels win on TPU hardware (BASELINE.md); off
            # TPU they would run interpreted (slow), the measured win
            # only exists for the f32/bf16 fast path, and under x64 mode
            # Mosaic lowers index maps as i64 (rejected by TPU memrefs)
            # -- so auto gates on all three and falls back to XLA
            itemsize = (np.dtype(A.dtype).itemsize
                        if isinstance(A, DiaMatrix) else 0)
            kernels = ("pallas" if jax.default_backend() == "tpu"
                       and itemsize in (2, 4)
                       and not jax.config.jax_enable_x64 else "xla")
        elif kernels == "pallas" and jax.default_backend() != "tpu":
            kernels = "pallas-interpret"
        elif kernels == "pallas" and jax.config.jax_enable_x64:
            # Mosaic lowers x64-mode BlockSpec index maps as i64, which
            # the TPU memref ops reject: compiled Pallas needs x64 off
            raise ValueError("kernels='pallas' cannot compile with "
                             "jax_enable_x64 on TPU; disable x64 or use "
                             "kernels='xla'")
        elif kernels in ("fused", "fused-interpret"):
            from acg_tpu.ops.pallas_kernels import fused_cg_route

            if pipelined:
                raise ValueError("kernels='fused' implements classic CG "
                                 "on the single-device tier (use the "
                                 "pipelined variant with kernels="
                                 "'pallas'/'xla'; the DIST mesh fused "
                                 "tier supports pipelined)")
            if precise_dots:
                raise ValueError("kernels='fused' accumulates its dots "
                                 "in plain f32 SMEM; compensated dots "
                                 "(precise_dots) need kernels='xla'/"
                                 "'pallas'")
            vdt = (jnp.dtype(vector_dtype) if vector_dtype is not None
                   else matrix_dtype(A))
            if not (isinstance(A, DiaMatrix)
                    and A.ncols_padded == A.nrows
                    and fused_cg_route(A.offsets, A.nrows, vdt) is not None):
                raise ValueError("kernels='fused' needs a square DIA "
                                 "matrix on the single-window kernel "
                                 "route")
            if jax.default_backend() != "tpu":
                kernels = "fused-interpret"
            elif kernels == "fused" and jax.config.jax_enable_x64:
                # Mosaic lowers x64-mode index maps as i64, which the
                # TPU memref ops reject; compiled Pallas needs x64 off
                # (explicit 'fused-interpret' never compiles -> exempt)
                raise ValueError("kernels='fused' cannot compile with "
                                 "jax_enable_x64 on TPU; disable x64 "
                                 "or use kernels='xla'")
        if kernels not in ("xla", "xla-roll", "pallas", "pallas-interpret",
                           "fused", "fused-interpret"):
            raise ValueError(f"unknown kernels choice {kernels!r}")
        self.replace_every = int(replace_every)
        self.replace_restart = bool(replace_restart)
        if self.replace_every < 0:
            raise ValueError("replace_every must be >= 0 (a negative "
                             "period would compile a non-terminating "
                             "segment loop)")
        if self.replace_every:
            vdt = (jnp.dtype(vector_dtype) if vector_dtype is not None
                   else jnp.dtype(matrix_dtype(A)))
            if vdt != jnp.bfloat16:
                raise ValueError(
                    "replace_every is the bf16 tier's accuracy contract "
                    "(periodic f32 residual replacement); f32/f64 vector "
                    "storage has no replacement drift to correct -- use "
                    "precise_dots or a RefinedSolver there")
            if pipelined:
                raise ValueError("replace_every implements classic CG "
                                 "(the pipelined recurrence carries w=Ar, "
                                 "which replacement would invalidate)")
            if precise_dots:
                raise ValueError("replace_every computes its scalars in "
                                 "plain f32 (the bf16 tier's scalar "
                                 "path); precise_dots needs the direct "
                                 "programs")
            if kernels.startswith("fused"):
                raise ValueError("replace_every composes with "
                                 "kernels='xla'/'pallas' (the fused "
                                 "two-phase iteration has no replacement "
                                 "hook)")
        # matrix-free operator tier (acg_tpu.ops.operator): the apply
        # rides every program through the ops.spmv dispatch, so no
        # per-program changes exist -- but bf16 vector storage has no
        # matrix traffic to halve here (the planes are generated) and
        # its kappa cap buys nothing: refuse rather than run a
        # pointless degraded tier
        self._matfree = hasattr(A, "matfree_apply")
        if self._matfree:
            vdt = (jnp.dtype(vector_dtype) if vector_dtype is not None
                   else jnp.dtype(matrix_dtype(A)))
            if vdt == jnp.bfloat16:
                raise ValueError(
                    "matrix-free operators generate their plane values "
                    "in the storage dtype and have no matrix HBM "
                    "traffic for bf16 to halve; use f32/f64 vectors "
                    "(the assembled tiers keep the bf16 contract)")
        from acg_tpu.precond import parse_precond
        self.precond_spec = parse_precond(precond)
        if self.precond_spec is not None:
            if self.replace_every:
                raise ValueError(
                    "precond does not compose with replace_every: the "
                    "replacement segments restructure the recurrences "
                    "the preconditioner threads through (use the direct "
                    "classic/pipelined PCG programs)")
            if isinstance(kernels, str) and kernels.startswith("fused"):
                raise ValueError(
                    "kernels='fused' folds the whole iteration into two "
                    "streamed kernels and has no preconditioner hook; "
                    "precond needs kernels='xla'/'pallas'")
        # the preconditioner state pytree (device arrays); built lazily
        # at first dispatch so construction stays zero-transfer
        self._mstate = None
        # numerical-health tier (acg_tpu.health): the audit/detector
        # spec rides the direct programs as a static argument; the
        # replacement/fused tiers have no audit hook (the replacement
        # segments ARE periodic true-residual recomputation, and the
        # fused kernels fold the whole iteration), so an armed spec
        # refuses there rather than silently audit nothing
        if health is not None:
            from acg_tpu.health import HealthSpec
            if not isinstance(health, HealthSpec):
                raise ValueError("health must be an "
                                 "acg_tpu.health.HealthSpec or None")
            if not health.armed:
                health = None
        if health is not None:
            if self.replace_every:
                raise ValueError(
                    "the true-residual audit (health) does not compose "
                    "with replace_every: the replacement segments "
                    "already recompute b - A x every K iterations -- "
                    "the audit would measure its own mechanism")
            if isinstance(kernels, str) and kernels.startswith("fused"):
                raise ValueError(
                    "kernels='fused' folds the whole iteration into "
                    "two streamed kernels and has no audit hook; the "
                    "health tier needs kernels='xla'/'pallas'")
        self.health_spec = health
        # survivability tier (acg_tpu.checkpoint): an armed
        # CheckpointConfig turns solve() into the host-chunked
        # snapshot driver.  The chunking threads the FULL loop carry
        # through the direct programs, which the replacement/fused
        # tiers cannot expose -- refuse rather than silently skip
        # snapshots (the fault-injector discipline)
        if ckpt is not None:
            from acg_tpu.checkpoint import CheckpointConfig
            if not isinstance(ckpt, CheckpointConfig):
                raise ValueError("ckpt must be an acg_tpu.checkpoint."
                                 "CheckpointConfig or None")
            if replace_every:
                raise ValueError(
                    "checkpointing (ckpt) does not compose with "
                    "replace_every: the replacement segments' inner "
                    "state never leaves the program (use the direct "
                    "classic/pipelined programs)")
            if isinstance(kernels, str) and kernels.startswith("fused"):
                raise ValueError(
                    "kernels='fused' folds the whole iteration into "
                    "two streamed kernels and exposes no loop carry; "
                    "checkpointing needs kernels='xla'/'pallas'")
        self.ckpt = ckpt
        if self.algo is not None:
            # the communication-avoiding recurrences run unpreconditioned
            # over f32/f64 vectors and compose with telemetry, faults,
            # recovery and (sstep) the health audit; everything they do
            # NOT reach refuses here rather than silently dropping (the
            # could-never-fire discipline)
            ca = str(self.algo)
            if pipelined:
                raise ValueError(
                    f"--algorithm {ca} selects its own recurrence; it "
                    f"does not compose with the pipelined flag (use "
                    f"--algorithm pipelined for Ghysels-Vanroose)")
            if self.replace_every:
                raise ValueError(
                    f"{ca} does not compose with replace_every (the "
                    f"replacement segments restructure the recurrence)")
            if self.precise_dots:
                raise ValueError(
                    f"{ca} accumulates its fused Gram/window reductions "
                    f"in the scalar dtype; precise_dots composes with "
                    f"the classic/pipelined programs")
            if self.precond_spec is not None:
                raise ValueError(
                    f"{ca} runs unpreconditioned: the s-step basis and "
                    f"the p(l) auxiliary basis have no M^-1 hook yet "
                    f"(use --algorithm classic|pipelined with --precond)")
            if isinstance(kernels, str) and kernels.startswith("fused"):
                raise ValueError(
                    f"{ca} needs kernels='xla'/'pallas' (the fused "
                    f"two-phase iteration folds the classic recurrence)")
            vdt = (jnp.dtype(vector_dtype) if vector_dtype is not None
                   else jnp.dtype(matrix_dtype(A)))
            if vdt == jnp.bfloat16:
                raise ValueError(
                    f"{ca} amplifies storage rounding through its basis "
                    f"products; bf16 vectors need the classic/pipelined "
                    f"tiers (replace_every is the bf16 contract)")
            if ckpt is not None and ckpt.repartition:
                raise ValueError(
                    f"{ca} snapshots its own carry layout "
                    f"(checkpoint.ca_carry_names) which is not in the "
                    f"field-compatible repartition set; "
                    f"--resume-repartition needs --algorithm "
                    f"classic|pipelined")
            if (ckpt is not None and self.algo.kind == "pl"
                    and int(trace) > 0):
                raise ValueError(
                    f"{ca} checkpoints its pipeline counters in the "
                    f"ABSOLUTE iteration frame, but the trace ring is "
                    f"reconstructed chunk-relative; --ckpt with --trace "
                    f"needs --algorithm classic|pipelined|sstep")
            if self.health_spec is not None:
                if self.algo.kind == "pl":
                    raise ValueError(
                        f"{ca} has no in-loop audit hook (the basis "
                        f"recovery already detects its own breakdown); "
                        f"--audit-every needs classic/pipelined/sstep")
                if self.health_spec.abft:
                    raise ValueError(
                        f"{ca} has no checksum hook for its basis "
                        f"products; --abft needs classic/pipelined")
        self.kernels = kernels
        self.recovery = recovery
        if (self.algo is not None and self.algo.kind == "pl"
                and recovery is None):
            # restarted p(l)-CG: the square-root breakdown of the deep
            # pipeline is an EXPECTED algorithmic event; arm the
            # standard restart ladder with the algorithm's own budget
            # (recurrence.pl_restart_policy) so a breakdown restarts
            # from the current iterate instead of raising
            from acg_tpu.recurrence import pl_restart_policy
            self.recovery = pl_restart_policy()
        self.host_matrix = host_matrix
        self.trace = int(trace)
        self.progress = int(progress)
        if self.trace < 0 or self.progress < 0:
            raise ValueError("trace/progress must be >= 0 (iteration "
                             "counts; 0 disables)")
        # the last solve's ConvergenceTrace (telemetry tier), also on
        # stats.trace; None until a traced solve ran
        self.last_trace = None
        self.stats = SolverStats(unknowns=A.nrows)
        # the matrix the solve PROGRAMS consume; defaults to A.  The
        # sharded pallas-roll tier swaps in a per-shard-padded twin
        # whose planes suit the windowed kernel while self.A stays the
        # clean view every other consumer (manufactured, refine, spot
        # check) expects (parallel.sharded_dia.use_pallas_roll)
        self._A_program: DeviceMatrix = A
        # lazy: the device nnz count (for the flop statistic) runs at
        # first stats use, not construction -- a solver over on-device
        # planes must construct with zero transfers (VERDICT round 2)
        self._spmv_flops_cache: float | None = None

    @property
    def _spmv_flops(self) -> float:
        if self._spmv_flops_cache is None:
            self._spmv_flops_cache = spmv_flops(self.A)
        return self._spmv_flops_cache

    def _solve_dtype(self):
        """The vector dtype a solve converts b/x0 to: the matrix dtype
        unless ``vector_dtype`` overrides it; the replacement tier's
        outer iteration owns b/x0 in f32 (rounding b to bf16 would bake
        a u_bf16-sized backward error into every replaced residual)."""
        dtype = matrix_dtype(self.A)
        if self.vector_dtype is not None:
            dtype = jnp.dtype(self.vector_dtype)
        if self.replace_every:
            dtype = jnp.dtype(jnp.float32)
        return dtype

    def _ensure_precond_state(self):
        """Build (once, lazily) the preconditioner state pytree that
        rides the solve programs as an argument: diagonal / block
        factors extracted from the CLEAN matrix view ``self.A``, the
        Chebyshev lambda_max power iteration run through the SAME SpMV
        selection the programs dispatch (``self._A_program`` -- the
        per-shard-padded twin on the pallas-roll tier)."""
        if self.precond_spec is None or self._mstate is not None:
            return self._mstate
        from acg_tpu.precond import setup_single
        sdt = acc_dtype(self._solve_dtype())
        self._mstate = setup_single(self.precond_spec, self.A,
                                    _spmv_fn(self.kernels), sdt,
                                    A_program=self._A_program)
        return self._mstate

    def _ensure_lam(self):
        """Cached (lmin, lmax) spectral interval for the
        communication-avoiding recurrences (Chebyshev s-step basis,
        p(l) shifts): one power iteration through THIS tier's own SpMV
        selection at first dispatch; (0, 0) when the armed recurrence
        never reads it (monomial basis)."""
        if self._lam is None:
            from acg_tpu.recurrence import estimate_lam
            if self.algo is not None and self.algo.needs_lam:
                self._lam = estimate_lam(
                    self._A_program, self.A.nrows,
                    acc_dtype(self._solve_dtype()), kernels=self.kernels)
            else:
                self._lam = (0.0, 0.0)
        return self._lam

    def _select_program(self, b, x0, crit: StoppingCriteria,
                        detect: bool = False, fault=None):
        """``(program, args, kwargs, traced)``: this configuration's
        whole-solve program dispatch -- ONE function shared by
        :meth:`solve` and :meth:`lower_solve`, so the observability tier
        (:mod:`acg_tpu.perfmodel`) interrogates EXACTLY the program a
        solve runs, never a reconstruction that could drift.  ``b``/``x0``
        must already be device arrays in :meth:`_solve_dtype`.  Raises
        the same configuration refusals a solve would."""
        # tolerances ride in the scalar dtype (f32 for bf16 storage) so a
        # 1e-9 rtol is not pre-rounded to 8 mantissa bits
        sdt = acc_dtype(b.dtype)
        telem = self.trace or self.progress
        if self.algo is not None:
            # communication-avoiding recurrences (acg_tpu.recurrence):
            # the builder programs composed with this tier's SpMV
            # selection.  ``lam`` rides between the tolerances and
            # maxits so the recovery ladder's generic restart arg
            # surgery (args[5:-1]) carries it through restarts
            from acg_tpu import recurrence as rec
            if crit.needs_diff:
                raise ValueError(
                    f"{self.algo} supports residual criteria only (the "
                    f"coefficient-space/pipelined updates carry no "
                    f"||dx|| scalar)")
            lam = self._ensure_lam()
            if self.algo.kind == "sstep":
                program = rec._cg_sstep_program
                args = (self._A_program, b, x0,
                        jnp.asarray(crit.residual_atol, sdt),
                        jnp.asarray(crit.residual_rtol, sdt),
                        (jnp.asarray(lam[0], sdt),
                         jnp.asarray(lam[1], sdt)),
                        jnp.int32(crit.maxits))
                kwargs = dict(s=self.algo.param, basis=self.algo.basis,
                              unbounded=crit.unbounded,
                              kernels=self.kernels, fault=fault,
                              trace=self.trace, progress=self.progress)
                if self.health_spec is not None:
                    kwargs["health"] = self.health_spec
            else:
                program = rec._cg_pl_program
                args = (self._A_program, b, x0,
                        jnp.asarray(crit.residual_atol, sdt),
                        jnp.asarray(crit.residual_rtol, sdt),
                        (jnp.asarray(lam[0], sdt),
                         jnp.asarray(lam[1], sdt)),
                        jnp.int32(crit.maxits))
                kwargs = dict(l=self.algo.param,
                              unbounded=crit.unbounded,
                              kernels=self.kernels, fault=fault,
                              trace=self.trace, progress=self.progress)
            return program, args, kwargs, bool(self.trace)
        if self.replace_every:
            if crit.needs_diff:
                raise ValueError("replace_every supports residual "
                                 "criteria only (the diff criterion has "
                                 "no meaning across replacement segments)")
            if telem:
                # the replacement program's inner fori does not thread
                # a global iteration index, so the telemetry hooks
                # would silently record nothing -- refuse (the fault-
                # injector rationale)
                raise AcgError(
                    ErrorCode.INVALID_VALUE,
                    "convergence telemetry (trace/progress) does not "
                    "reach the replacement-segment program "
                    "(replace_every); use the direct classic/pipelined "
                    "programs")
            if fault is not None:
                # the replacement program's inner fori does not thread a
                # global iteration index, so an armed injector would
                # silently never fire -- refuse rather than report a
                # clean solve the operator believes was fault-tested
                raise AcgError(
                    ErrorCode.INVALID_VALUE,
                    "fault injection does not reach the replacement-"
                    "segment program (replace_every); inject into the "
                    "direct classic/pipelined programs instead")
            program = _cg_replaced_program
            args = (self._A_program, b, x0,
                    jnp.asarray(crit.residual_atol, sdt),
                    jnp.asarray(crit.residual_rtol, sdt),
                    jnp.int32(crit.maxits))
            kwargs = dict(K=self.replace_every, unbounded=crit.unbounded,
                          restart=self.replace_restart,
                          kernels=self.kernels)
        elif (isinstance(self.kernels, str)
              and self.kernels.startswith("fused")):
            if crit.needs_diff:
                raise ValueError("kernels='fused' supports residual "
                                 "criteria only")
            if detect:
                raise AcgError(
                    ErrorCode.INVALID_VALUE,
                    "kernels='fused' folds its scalars into "
                                 "the two streamed kernels and has no "
                                 "breakdown-detection hook; recovery/"
                                 "fault injection need kernels='xla'/"
                                 "'pallas'")
            if telem:
                raise AcgError(
                    ErrorCode.INVALID_VALUE,
                    "kernels='fused' keeps its scalars in SMEM inside "
                    "the two streamed kernels; convergence telemetry "
                    "(trace/progress) needs kernels='xla'/'pallas'")
            program = _cg_fused_program
            args = (self._A_program, b, x0,
                    jnp.asarray(crit.residual_atol, sdt),
                    jnp.asarray(crit.residual_rtol, sdt),
                    jnp.int32(crit.maxits))
            kwargs = dict(unbounded=crit.unbounded,
                          interpret=self.kernels.endswith("interpret"))
        else:
            program = _cg_pipelined_program if self.pipelined else _cg_program
            args = (self._A_program, b, x0,
                    jnp.asarray(crit.residual_atol, sdt),
                    jnp.asarray(crit.residual_rtol, sdt),
                    jnp.asarray(crit.diff_atol, sdt),
                    jnp.asarray(crit.diff_rtol, sdt),
                    jnp.int32(crit.maxits))
            kwargs = dict(unbounded=crit.unbounded,
                          needs_diff=crit.needs_diff,
                          precise=self.precise_dots, kernels=self.kernels,
                          detect=detect, fault=fault,
                          trace=self.trace, progress=self.progress)
            if self.precond_spec is not None:
                # the disarmed call site stays byte-identical: neither
                # kwarg is passed at all without a spec
                kwargs["precond"] = self.precond_spec
                kwargs["mstate"] = self._ensure_precond_state()
            if self.health_spec is not None:
                # same discipline: an unaudited build never even names
                # the kwarg
                kwargs["health"] = self.health_spec
        tr = self.trace and not (self.replace_every
                                 or (isinstance(self.kernels, str)
                                     and self.kernels.startswith("fused")))
        return program, args, kwargs, tr

    def lower_solve(self, b, x0=None, criteria=None):
        """Lower (but do not run) the EXACT whole-solve XLA program this
        configuration dispatches for ``(b, x0, criteria)`` and return
        the ``jax.stages.Lowered`` handle -- the observability hook the
        perfmodel tier (:mod:`acg_tpu.perfmodel`) compiles to extract
        the compiler's own cost/memory analysis.

        Never mutates solver state, and shares :meth:`_select_program`
        with :meth:`solve`, so the lowered program is byte-identical to
        the one a solve compiles (asserted in tests/test_hlo_structure.
        py).  Breakdown detection mirrors a clean solve: armed iff a
        recovery policy is set.  The fault injector is deliberately NOT
        consulted -- analysis describes the pristine program."""
        crit = criteria or StoppingCriteria()
        dtype = self._solve_dtype()
        b = jnp.asarray(b, dtype=dtype)
        x0 = (jnp.zeros_like(b) if x0 is None
              else jnp.asarray(x0, dtype=dtype))
        program, args, kwargs, _ = self._select_program(
            b, x0, crit, detect=self._detect(None), fault=None)
        return program.lower(*args, **kwargs)

    def _detect(self, fault) -> bool:
        """Whether the compiled loop carries the breakdown flag:
        recovery armed, an active injector, or a health spec whose
        detectors trip the breakdown path -- shared by solve() and the
        lower_solve hook so the analyzed program is the dispatched
        one."""
        return (self.recovery is not None or fault is not None
                or (self.health_spec is not None
                    and self.health_spec.arms_detect))

    def _fault_refusals(self, fault) -> None:
        """Armed-injector configurations this tier can never fire:
        refuse instead of reporting a clean 'fault-tested' solve --
        shared by the plain and checkpoint-chunked solve paths."""
        from acg_tpu import faults
        spec = faults.active_fault()
        if (spec is not None and spec.site == "crash"
                and (self.ckpt is None or self.ckpt.path is None)):
            raise AcgError(
                ErrorCode.INVALID_VALUE,
                "crash:exit fires from the checkpoint chunk driver "
                "between snapshots; arm --ckpt FILE --ckpt-every K "
                "(a crash with no snapshot to resume from proves "
                "nothing)")
        if fault is None:
            return
        if fault.site == "halo":
            # no halo exists on the single-device solver: an armed
            # injector that can never fire must refuse, not report a
            # clean "fault-tested" solve (the replace_every rationale)
            raise AcgError(
                ErrorCode.INVALID_VALUE,
                "halo fault injection needs a distributed problem with "
                "ghost exchange (DistCGSolver, nparts > 1); the "
                "single-device solver has no halo to poison")
        if (fault is not None and fault.site == "precond"
                and self.precond_spec is None):
            # no preconditioner is armed: the apply the fault poisons
            # never runs (the replace_every rationale)
            raise AcgError(
                ErrorCode.INVALID_VALUE,
                "precond fault injection needs an armed preconditioner "
                "(--precond jacobi|bjacobi|cheby:K); this solve runs "
                "unpreconditioned CG")
        if (self.algo is not None and fault is not None
                and self.algo.kind == "sstep"
                and fault.site in ("spmv", "sdc", "halo")
                and fault.iteration % self.algo.param != 0):
            # the s-step basis products carry the BLOCK-START iteration
            # index: a vector fault armed mid-block could never fire
            raise AcgError(
                ErrorCode.INVALID_VALUE,
                f"sstep:{self.algo.param} applies SpMV/halo faults at "
                f"block boundaries; arm an iteration that is a "
                f"multiple of {self.algo.param} (got "
                f"{fault.iteration})")
        if (self.algo is not None and fault is not None
                and self.algo.kind == "pl" and fault.site == "dot"):
            # p(l) has no scalar dot in its loop (the window reduction
            # is a fused matvec): the armed injector could never fire
            raise AcgError(
                ErrorCode.INVALID_VALUE,
                "dot fault injection has no site in the p(l) "
                "recurrence (its reductions are fused window matvecs); "
                "use spmv:, or the classic/pipelined/sstep programs")
        if fault is not None and fault.part > 0:
            # _fault_nparts distinguishes the true single-device solver
            # from multi-part subclasses that reuse this solve (the
            # sharded roll tier): NEITHER can honour part targeting --
            # these programs apply faults to the global vector -- but
            # the diagnosis must name the right reason
            if getattr(self, "_fault_nparts", 1) == 1:
                raise AcgError(
                    ErrorCode.INVALID_VALUE,
                    f"fault spec targets part {fault.part}, but the "
                    f"single-device solver has only part 0 -- the fault "
                    f"could never fire")
            raise AcgError(
                ErrorCode.INVALID_VALUE,
                f"the sharded single-program tier applies faults to the "
                f"global vector and cannot target part {fault.part}; "
                f"drop part= or use the partitioned DistCGSolver path "
                f"for part-targeted injection")

    def solve(self, b, x0=None, criteria: StoppingCriteria | None = None,
              raise_on_divergence: bool = True, warmup: int = 0,
              host_result: bool = True) -> np.ndarray:
        """Solve Ax=b.  ``host_result=False`` returns the device array
        instead of copying x to the host -- at pod-filling sizes the
        copy dwarfs the solve (537 MB for 512^3), and a caller that only
        needs the timing/stats (benchmarks) or feeds x to another device
        computation should not pay it.  The FP-exception report then
        comes from a device-side finiteness check instead of the host
        scan.

        An armed checkpoint (``ckpt``) routes through the survivability
        tier's chunked driver (:meth:`_solve_ckpt`): same programs,
        dispatched in snapshot-bounded chunks."""
        if self.ckpt is not None:
            return self._solve_ckpt(b, x0=x0, criteria=criteria,
                                    raise_on_divergence=raise_on_divergence,
                                    warmup=warmup,
                                    host_result=host_result)
        crit = criteria or StoppingCriteria()
        st = self.stats
        st.criteria = crit
        from acg_tpu import faults
        fault = faults.device_fault()
        self._fault_refusals(fault)
        # detection arms with the recovery policy, an active injector
        # (an injected fault must surface, never launder into x), or a
        # tripping health spec; the detect=False programs stay
        # byte-identical to the seed's
        detect = self._detect(fault)
        # dtype policy (vector_dtype override, f32 replacement outer)
        # lives in _solve_dtype, shared with the lower_solve hook
        dtype = self._solve_dtype()
        from acg_tpu import telemetry
        if fault is not None:
            # timestamped twin of the injector's stderr line for the
            # structured sink (--stats-json)
            telemetry.record_event(st, "fault-armed",
                                   f"{fault.site}:{fault.mode}"
                                   f"@{fault.iteration}")
        t_xfer = time.perf_counter()
        with telemetry.annotate("transfer"):
            b = jnp.asarray(b, dtype=dtype)
            x0 = (jnp.zeros_like(b) if x0 is None
                  else jnp.asarray(x0, dtype=dtype))
        telemetry.add_timing(st, "transfer",
                             time.perf_counter() - t_xfer)
        # scalar dtype for recovery's re-derived tolerances below; the
        # program dispatch itself -- tolerances, static kwargs, the
        # configuration refusals -- is shared with lower_solve
        sdt = acc_dtype(dtype)
        program, args, kwargs, tr = self._select_program(
            b, x0, crit, detect=detect, fault=fault)

        hl = "health" in kwargs

        def run(*a, **kw):
            """One program invocation, normalised to
            (CGResult, ring, audit-vector)."""
            out = program(*a, **kw)
            if not tr and not hl:
                return out, None, None
            out = out if isinstance(out, tuple) else (out,)
            return (out[0], out[1] if tr else None,
                    out[-1] if hl else None)

        def attempt_trace(res, tbuf):
            """The ONE host fetch of a traced solve: un-rotate this
            attempt's ring against its iteration count."""
            if tbuf is None:
                return None
            return telemetry.ConvergenceTrace.from_ring(
                np.asarray(tbuf), int(res.niterations),
                solver=self._solver_name())

        # warmup solves outside the timed region (the reference warms up
        # each op class before timing, cgcuda.c:612-710)
        t_warm = time.perf_counter()
        with telemetry.annotate("compile"):
            for _ in range(max(warmup, 0)):
                jax.block_until_ready(run(*args, **kwargs)[0].x)
        if warmup > 0:
            # warmup absorbs the compile; with warmup=0 it lands in the
            # solve phase (documented in the README observability notes)
            telemetry.add_timing(st, "compile",
                                 time.perf_counter() - t_warm)
        t0 = time.perf_counter()
        with telemetry.annotate("solve"):
            res, tbuf, aud = run(*args, **kwargs)
            jax.block_until_ready(res.x)
        niter = int(res.niterations)
        first_norms = None
        # the first note_audit of this solve resets the health summary;
        # later attempts MERGE (gap_max keeps the worst gap that
        # tripped, naudits accumulates across restarts).  gap_tripped
        # remembers whether the LATEST attempt's exit was a gap trip,
        # so the no-rungs-left raise below can name the real cause
        # instead of the generic arithmetic-breakdown diagnosis
        aud_fresh = True
        gap_tripped = False
        if detect and bool(res.breakdown):
            # host-side recovery (solvers.resilience): bounded restarts
            # from the last finite iterate -- the program's setup
            # recomputes the TRUE residual r = b - A x0, so each restart
            # discards the poisoned recurrence state -- then the host-
            # solver fallback, then a diagnosis-carrying raise
            from acg_tpu.solvers.resilience import RecoveryDriver
            driver = RecoveryDriver(self.recovery, st, "jax-cg")
            x0_dev = args[2]
            # the stats block reports the ORIGINAL solve's norms; the
            # restarted attempts' r0/x0 are recovery internals
            first_norms = (float(res.bnrm2), float(res.x0nrm2),
                           float(res.r0nrm2))
            # restarts keep the FIRST attempt's residual target: the
            # rtol baseline is r0 of the original x0, not of the restart
            # (re-baselining would demand an unreachable 1e-6 reduction
            # of an already-small restart residual)
            abs_tol = max(crit.residual_atol,
                          crit.residual_rtol * float(res.r0nrm2))
            while bool(res.breakdown):
                k_done = int(res.niterations)
                if hl and aud is not None:
                    # this attempt's audit evidence BEFORE the restart
                    # decision: an accuracy_degraded event marks a gap
                    # trip apart from an arithmetic breakdown, and the
                    # restart's true-residual recompute IS the
                    # residual-replacement fix
                    from acg_tpu import health as health_mod
                    gap_tripped = health_mod.note_audit(
                        st, aud, self.health_spec, "jax-cg",
                        fresh=aud_fresh)
                    aud_fresh = False
                if tr:
                    # the trajectory that led INTO the breakdown -- the
                    # evidence the post-hoc stats block cannot show
                    st.trace = self.last_trace = attempt_trace(res, tbuf)
                    driver.log_trace_window(st.trace)
                if gap_tripped and self.health_spec.action == "abort":
                    # host-tier parity: --on-gap abort is a hard stop,
                    # the restart budget belongs to replace -- without
                    # this an armed recovery policy would silently turn
                    # abort into replace
                    st.tsolve += time.perf_counter() - t0
                    st.converged = False
                    from acg_tpu.errors import BreakdownError
                    raise BreakdownError(
                        f"jax-cg: true-residual gap "
                        f"{st.health.get('gap_max', 0.0):.3e} exceeds "
                        f"threshold {self.health_spec.threshold:g} at "
                        f"iteration {niter} (--on-gap abort)")
                if driver.on_breakdown(k_done):
                    x_next = res.x
                    if not bool(jnp.isfinite(x_next).all()):
                        driver.record("iterate non-finite; restarting "
                                      "from the initial guess")
                        x_next = x0_dev
                    if fault is not None and "fault" in kwargs:
                        if (self.algo is not None
                                and self.algo.kind == "sstep"
                                and fault.device_site
                                and fault.iteration <= k_done):
                            # the poisoned basis block froze BEFORE
                            # executing its steps, so niterations never
                            # passes the fault's index: the fault FIRED
                            # -- vanish it (the chunk drivers'
                            # vanish-not-rebase rationale) instead of
                            # rebasing it into the restart's first block
                            fault = None
                            kwargs["fault"] = None
                        elif (self.algo is not None
                              and self.algo.kind == "pl"):
                            # p(l) faults key on the AUXILIARY-basis
                            # counter j, which runs l ahead of the
                            # trajectory count (j = adv + l exactly at
                            # a breakdown, since advances only freeze
                            # on exit conditions): shift in the
                            # z-counter frame so a fired fault vanishes
                            # (shift -> None) instead of re-triggering
                            # the same breakdown across every restart
                            fault = fault.shift(
                                k_done + self.algo.param + 1)
                            kwargs["fault"] = fault
                        else:
                            fault = fault.shift(k_done)
                            kwargs["fault"] = fault
                    if self.precond_spec is not None:
                        # preserve finite preconditioner state across
                        # the restart, rebuild it when poisoned
                        from acg_tpu.precond import refresh_state
                        if refresh_state(self, driver):
                            kwargs["mstate"] = self._mstate
                    remaining = max(crit.maxits - niter, 1)
                    args = (args[:2] + (x_next,)
                            + (jnp.asarray(abs_tol, sdt),
                               jnp.asarray(0.0, sdt)) + args[5:-1]
                            + (jnp.int32(remaining),))
                    res, tbuf, aud = run(*args, **kwargs)
                    jax.block_until_ready(res.x)
                    niter += int(res.niterations)
                    continue
                pol = self.recovery
                if (pol is not None and pol.fallback_host
                        and self.host_matrix is not None):
                    driver.on_fallback("fallback: host reference solver")
                    st.tsolve += time.perf_counter() - t0
                    return self._host_fallback(
                        b, crit, raise_on_divergence, host_result)
                st.tsolve += time.perf_counter() - t0
                st.converged = False
                if gap_tripped:
                    # name the REAL cause: this exit was an accuracy
                    # gate, not arithmetic poison (host-tier parity)
                    from acg_tpu.errors import BreakdownError
                    raise BreakdownError(
                        f"jax-cg: true-residual gap "
                        f"{st.health.get('gap_max', 0.0):.3e} exceeds "
                        f"threshold {self.health_spec.threshold:g} at "
                        f"iteration {niter} (--on-gap "
                        f"{self.health_spec.action}); "
                        f"{st.nrestarts} restart(s) exhausted and no "
                        f"fallback available")
                raise driver.give_up(niter, float(res.rnrm2))
        t_solve = time.perf_counter() - t0
        st.tsolve += t_solve
        telemetry.add_timing(st, "solve", t_solve)
        if tr:
            # the ONE extra host fetch of a traced solve (acceptance
            # contract: zero additional transfers per iteration)
            st.trace = self.last_trace = attempt_trace(res, tbuf)
        st.nsolves += 1
        st.niterations = niter
        st.ntotaliterations += niter
        st.bnrm2, st.x0nrm2, st.r0nrm2 = (
            first_norms if first_norms is not None
            else (float(res.bnrm2), float(res.x0nrm2), float(res.r0nrm2)))
        st.rnrm2 = float(res.rnrm2)
        st.dxnrm2 = float(res.dxnrm2)
        st.converged = bool(res.converged) or crit.unbounded
        if hl and aud is not None:
            # the health: section's audit summary + the acg_health_*
            # metrics + (threshold exceeded) the accuracy_degraded event
            from acg_tpu import health as health_mod
            health_mod.note_audit(st, aud, self.health_spec, "jax-cg",
                                  fresh=aud_fresh)
        # service-metrics tier: one completed solve (no-op disarmed;
        # the sharded subclass reuses this solve, so its comm ledger
        # rides through the same hook)
        from acg_tpu import metrics
        metrics.record_solve(t_solve, niter, st.converged,
                             solver=self._solver_name())
        metrics.observe_solver_comm(self, niter)
        self._account_ops(st, niter, dtype)
        if host_result:
            x = np.asarray(res.x)
            st.fexcept_arrays = [x]
        else:
            x = res.x
            # device-side scans; only two bools cross the wire.  The
            # sentinels reproduce the host report's NaN/Inf distinction
            # (errors.fexcept_str).
            has_nan = bool(jnp.isnan(res.x).any())
            has_inf = bool(jnp.isinf(res.x).any())
            st.fexcept_arrays = [np.asarray([np.nan if has_nan else 0.0,
                                             np.inf if has_inf else 0.0])]
        if not st.converged and raise_on_divergence:
            raise NotConvergedError(
                f"{niter} iterations, residual {st.rnrm2:.3e}")
        return x

    def _solver_name(self) -> str:
        """Telemetry/metrics label: the recurrence decides (the CA
        names deliberately avoid the 'pipelined' substring -- see
        recurrence.RecurrenceSpec.solver_name)."""
        if self.algo is not None:
            return self.algo.solver_name("cg")
        return "cg-pipelined" if self.pipelined else "cg"

    def _account_ops(self, st, niter: int, dtype) -> None:
        """Analytic flop/byte census of ``niter`` iterations on this
        configuration -- shared by the plain and checkpoint-chunked
        solve paths so their stats blocks cannot drift apart."""
        n = self.A.nrows
        per_it = cg_flops_per_iteration(self._spmv_flops / 3.0, n,
                                        self.pipelined)
        st.nflops += per_it * niter + self._spmv_flops + 2.0 * n
        dbl = np.dtype(dtype).itemsize
        # matrix bytes in the MATRIX storage dtype (they differ from the
        # vector dtype under --dtype mixed) + per-format index bytes
        mat_dbl = np.dtype(matrix_dtype(self.A)).itemsize
        idx_b = matrix_index_bytes(self.A)
        mat_bytes = int((self._spmv_flops / 3.0) * (mat_dbl + idx_b))
        if self.replace_every:
            # inner vectors are bf16 regardless of the (f32) outer dtype;
            # each segment adds one f32-vector replacement SpMV
            nseg = -(-niter // self.replace_every) if niter else 0
            st.nflops += self._spmv_flops * nseg
            vb = 2
            st.ops["gemv"].add(niter + nseg + 1, 0.0,
                               (mat_bytes + 2 * n * vb) * niter
                               + (mat_bytes + 2 * n * 4) * (nseg + 1))
            # carried-direction mode adds the (r, p) line-search dot per
            # iteration and a (p, p) health check per segment
            ndot = (2 * niter if self.replace_restart
                    else 3 * niter + nseg)
            st.ops["dot"].add(ndot, 0.0, 2 * n * vb * ndot)
            st.ops["axpy"].add(3 * niter, 0.0, 3 * n * vb * 3 * niter)
        elif (isinstance(self.kernels, str)
              and self.kernels.startswith("fused")):
            # both dots and all updates are folded into the two streamed
            # kernels: bill phase A (planes + r/p windows + p/t writes)
            # as gemv and phase B (4 reads + 2 writes) as axpy; nothing
            # re-reads vectors for dots
            st.ops["gemv"].add(niter + 1, 0.0,
                               (mat_bytes + 4 * n * dbl) * (niter + 1))
            st.ops["axpy"].add(niter, 0.0, 6 * n * dbl * niter)
        elif self.algo is not None:
            # communication-avoiding recurrences: s-step runs (2s-1)/s
            # SpMV-equivalents per iteration (the matrix-powers basis)
            # plus the Gram matmul and three map-back GEMVs per block;
            # p(l) runs 1 SpMV + the fused (2l+2)-window matvec per
            # iteration plus the v-recovery combination.  Billed as the
            # dominant op classes; flops fold the basis overhead
            from acg_tpu.recurrence import reduction_schedule
            sched = reduction_schedule(self.algo, False)
            spmv_eq = sched["spmv_per_iteration"]
            st.nflops += self._spmv_flops * (spmv_eq - 1.0) * niter
            st.ops["gemv"].add(int(niter * spmv_eq) + 1, 0.0,
                               int((mat_bytes + 2 * n * dbl)
                                   * (niter * spmv_eq + 1)))
            wred = sched["allreduce_scalars"]
            ndot = int(niter * sched["allreduce_per_iteration"])
            st.ops["dot"].add(max(ndot, 1), 0.0,
                              int(2 * n * dbl * wred ** 0.5
                                  * max(ndot, 1)))
            st.ops["nrm2"].add(niter + 1, 0.0, n * dbl * (niter + 1))
            st.ops["axpy"].add(3 * niter, 0.0, 3 * n * dbl * 3 * niter)
        else:
            # per-iteration op census matching the eager host solver's
            # (host_cg.solve): the convergence test's (r, r) is the nrm2
            # class -- niter in-loop + 1 at setup -- and classic CG's
            # p = r setup is the one copy.  These were the permanently-
            # zero rows of the compiled solvers' stats block (the
            # reference fills both, cgcuda.c:1942-1957).
            st.ops["gemv"].add(niter + 1, 0.0,
                               (mat_bytes + 2 * n * dbl) * (niter + 1))
            st.ops["dot"].add(niter, 0.0, 2 * n * dbl * niter)
            st.ops["nrm2"].add(niter + 1, 0.0, n * dbl * (niter + 1))
            st.ops["axpy"].add(3 * niter, 0.0, 3 * n * dbl * 3 * niter)
            if not self.pipelined:
                st.ops["copy"].add(1, 0.0, 2 * n * dbl)
            if self.precond_spec is not None:
                self._account_precond(st, niter, n, dbl, mat_bytes)

    def _account_precond(self, st, niter: int, n: int, dbl: int,
                         mat_bytes: int) -> None:
        """Analytic accounting for the preconditioner (the precond_apply
        satellite): niter + 1 applies per solve (setup z0 + one per
        iteration); cheby's op count bills its degree-many SpMVs per
        apply, the PCG scalar (r, z) adds one dot per apply, and the
        ``precond:`` stats section records the armed configuration."""
        from acg_tpu import metrics, precond as precond_mod

        spec = self.precond_spec
        nappl = niter + 1
        per_apply_flops = precond_mod.flops_per_apply(
            spec, n, self._spmv_flops)
        st.nflops += per_apply_flops * nappl
        sb = precond_mod.state_bytes(self._mstate)
        per_apply_bytes = precond_mod.bytes_per_apply(
            spec, n, dbl, mat_bytes + 2 * n * dbl, sb)
        nops = nappl * (spec.degree if spec.kind == "cheby" else 1)
        st.ops["precond"].add(nops, 0.0, int(per_apply_bytes * nappl))
        # the extra PCG scalar (r, z) per apply
        st.ops["dot"].add(nappl, 0.0, 2 * n * dbl * nappl)
        st.precond.update({
            "kind": str(spec),
            "applies": nappl,
            "flops_per_apply": per_apply_flops,
            "state_bytes": sb,
        })
        if spec.kind == "cheby":
            st.precond["lambda_min"] = float(self._mstate[0])
            st.precond["lambda_max"] = float(self._mstate[1])
        metrics.record_precond(spec.kind, nops)

    def _host_fallback(self, b, crit, raise_on_divergence: bool,
                       host_result: bool):
        """The last recovery rung: re-solve on the host reference solver
        (f64 numpy) from the ORIGINAL b -- the device state is junk by
        definition here.  Stats for the last solve reflect the host run;
        the op-class byte accounting keeps the device attempts."""
        from acg_tpu import faults
        from acg_tpu.solvers.host_cg import HostCGSolver
        from acg_tpu.solvers.resilience import adopt_host_stats

        hs = HostCGSolver(self.host_matrix)
        with faults.suppressed():
            x = hs.solve(np.asarray(b, np.float64), criteria=crit,
                         raise_on_divergence=raise_on_divergence)
        adopt_host_stats(self.stats, hs.stats)
        return x if host_result else jnp.asarray(x)

    # -- survivability tier: checkpoint-chunked solve ---------------------

    _ckpt_tier = "jax-cg"

    def _solve_ckpt(self, b, x0=None, criteria=None,
                    raise_on_divergence: bool = True, warmup: int = 0,
                    host_result: bool = True):
        """Checkpoint-armed solve (acg_tpu.checkpoint): the UNCHANGED
        direct program dispatched in host chunks of at most
        ``ckpt.every`` iterations with the full loop carry threaded
        through (``state_io``), a checksummed snapshot committed by
        atomic rename at every boundary, and detected breakdowns
        answered by the recovery ladder's new FIRST rung -- rollback to
        the last snapshot -- before the existing restart/fallback/abort
        ladder.  Because the carry continues the recurrence exactly,
        the chunked trajectory is iteration-identical to solve()'s
        (asserted in tests/test_checkpoint.py); snapshot time is billed
        to its own ``ckpt`` phase, never the solve."""
        from acg_tpu import checkpoint as ckpt_mod
        from acg_tpu import faults, metrics, observatory, telemetry, \
            tracing
        from acg_tpu import health as health_mod
        from acg_tpu.solvers.resilience import RecoveryDriver

        cfg = self.ckpt
        crit = criteria or StoppingCriteria()
        st = self.stats
        st.criteria = crit
        fault0 = faults.device_fault()
        self._fault_refusals(fault0)
        detect = self._detect(fault0)
        dtype = self._solve_dtype()
        sdt = acc_dtype(dtype)
        if fault0 is not None:
            telemetry.record_event(st, "fault-armed",
                                   f"{fault0.site}:{fault0.mode}"
                                   f"@{fault0.iteration}")
        t_xfer = time.perf_counter()
        with telemetry.annotate("transfer"):
            b_host = np.asarray(b, dtype=dtype)
            b_dev = jnp.asarray(b_host)
            x0_dev = (jnp.zeros_like(b_dev) if x0 is None
                      else jnp.asarray(x0, dtype=dtype))
        telemetry.add_timing(st, "transfer",
                             time.perf_counter() - t_xfer)
        b_crc = ckpt_mod.vector_checksum(b_host)
        program, base, kwargs, tr = self._select_program(
            b_dev, x0_dev, crit, detect=detect, fault=fault0)
        kwargs = dict(kwargs)
        kwargs["state_io"] = True
        hl = "health" in kwargs
        pc_kind = (str(self.precond_spec)
                   if self.precond_spec is not None else None)
        algo_name = str(self.algo) if self.algo is not None else None
        is_pl = self.algo is not None and self.algo.kind == "pl"
        if self.algo is not None:
            names = ckpt_mod.ca_carry_names(self.algo.kind)
            solver_name = f"cg-{self.algo.kind}"
        else:
            names = ckpt_mod.carry_names(self.pipelined,
                                         self.precond_spec is not None)
            solver_name = ("cg-pipelined" if self.pipelined else "cg")

        if self.algo is not None:
            # CA base is the 7-tuple (A, b, x0, atol, rtol, lam,
            # maxits): lam rides at base[5], there are no diff tols
            def chunk_args(x_dev, atol, rtol, m):
                return (base[0], base[1], x_dev,
                        jnp.asarray(atol, sdt), jnp.asarray(rtol, sdt),
                        base[5], jnp.int32(m))
        else:
            def chunk_args(x_dev, atol, rtol, m):
                return (base[0], base[1], x_dev,
                        jnp.asarray(atol, sdt), jnp.asarray(rtol, sdt),
                        base[5], base[6], jnp.int32(m))

        def pl_adv(carry):
            # the deep pipeline's advance counter rides IN the carry
            # (frame-absolute since the last restart): the chunk cap
            # and the per-chunk iteration count are both relative to it
            return int(jnp.asarray(carry[-1])) if carry is not None \
                else 0

        def run(a, carry, k0):
            # the chunk's starting trajectory iteration keeps the
            # health tier's audit/ABFT cadence phased to GLOBAL
            # iteration numbers (a dynamic arg: chunks never retrace)
            koff = jnp.int32(k0) if hl else None
            out = program(*a, carry=carry, k_offset=koff, **kwargs)
            i = 1
            ring = out[i] if tr else None
            i += 1 if tr else 0
            aud = out[i] if hl else None
            i += 1 if hl else 0
            return out[0], ring, aud, out[i]

        # -- resume reconstruction ------------------------------------
        consumed = 0          # trajectory iterations (incl. pre-crash)
        executed = 0          # iterations THIS process actually ran
        resumed_from = None
        carry = None
        x_cur = x0_dev
        abs_tol = None
        first_norms = None
        snap = cfg.resume
        repartitioned = None
        if snap is not None:
            ckpt_mod.validate_resume(
                snap, tier=self._ckpt_tier, pipelined=self.pipelined,
                precond=pc_kind, n=int(self.A.nrows), dtype=dtype,
                b_crc=b_crc, repartition=cfg.repartition,
                algorithm=algo_name)
            ckpt_mod.check_resume_env(snap, st)
            if cfg.repartition:
                # shape-portable resume: reassemble the carry into
                # global row order (an N-part snapshot's vectors come
                # back as plain length-n arrays -- this tier's native
                # layout); the recurrence continues with the same
                # global Krylov state, so convergence carries over up
                # to dot-product re-association
                snap, repartitioned = ckpt_mod.apply_repartition(
                    snap, tier=self._ckpt_tier, nparts=1, stats=st,
                    precond_spec=self.precond_spec)
            consumed = snap.iteration
            resumed_from = consumed
            sm = snap.meta
            abs_tol = float(sm["abs_tol"])
            first_norms = (float(sm["bnrm2"]), float(sm["x0nrm2"]),
                           float(sm["r0nrm2"]))
            x_cur = jnp.asarray(snap.arrays["x"])
            carry = tuple(jnp.asarray(snap.arrays[nm])
                          for nm in names[1:])
            metrics.record_resume()
            telemetry.record_event(
                st, "resume",
                f"resumed from snapshot at iteration {consumed}")
            sys.stderr.write(f"acg-tpu: {self._ckpt_tier}: resumed "
                             f"from snapshot at iteration "
                             f"{consumed}\n")
        last_snap = ((consumed, {"x": np.asarray(x_cur),
                                 **{nm: np.asarray(leaf)
                                    for nm, leaf in zip(names[1:],
                                                        carry)}})
                     if carry is not None else None)

        driver = RecoveryDriver(self.recovery, st, self._ckpt_tier)
        if warmup > 0:
            # ONE zero-iteration dispatch absorbs the chunk program's
            # compile outside the timed window (further chunk variants
            # -- the carry-armed retrace -- land in the solve phase)
            t_w = time.perf_counter()
            with telemetry.annotate("compile"):
                jax.block_until_ready(run(chunk_args(x_cur, 0.0, 0.0, 0),
                                          carry, consumed)[0].x)
            telemetry.add_timing(st, "compile",
                                 time.perf_counter() - t_w)

        unbounded = crit.unbounded
        fault = fault0
        seq = 0
        nsnaps = 0
        ck_secs = 0.0
        rate = None
        aud_fresh = True
        gap_tripped = False
        res = None
        t0 = time.perf_counter()
        with telemetry.annotate("solve"):
            while True:
                remaining = crit.maxits - consumed
                if remaining <= 0:
                    break
                m = min(cfg.chunk_for(rate), remaining)
                if (self.algo is not None
                        and self.algo.kind == "sstep" and m < remaining):
                    # block-boundary-aligned cadence: a non-final chunk
                    # must end where a block ends -- the carried
                    # (r, p, gamma) only EQUALS the monolithic
                    # trajectory there (mid-block, the basis/Gram
                    # state is live and classic-shaped state is stale)
                    s_ = int(self.algo.param)
                    m = min(remaining, max(s_, (m // s_) * s_))
                if is_pl:
                    # the pipeline's cap/advance counters are frame-
                    # absolute (they ride in the carry): cap this chunk
                    # at carry-advance + m
                    m_cap = pl_adv(carry) + m
                else:
                    m_cap = m
                if abs_tol is None:
                    a = chunk_args(x_cur, crit.residual_atol,
                                   crit.residual_rtol, m_cap)
                else:
                    # later chunks keep the FIRST attempt's absolute
                    # target (the recovery-restart convention: never
                    # re-baseline rtol against an already-small
                    # residual)
                    a = chunk_args(x_cur, abs_tol, 0.0, m_cap)
                if "fault" in kwargs:
                    # the pl counters never reset across chunks, so its
                    # injector already fires in the right frame --
                    # shifting would double-subtract
                    kwargs["fault"] = (fault if is_pl
                                       else fault.shift(executed)
                                       if fault is not None else None)
                t_chunk = time.time()
                adv_in = pl_adv(carry) if is_pl else 0
                res, tbuf, aud, core = run(a, carry, consumed)
                jax.block_until_ready(res.x)
                t_end = time.time()
                k_chunk = int(res.niterations) - adv_in
                if k_chunk > 0:
                    # measured s/iteration sizes the next chunk under
                    # the wall-clock cadence (cfg.chunk_for)
                    rate = (t_end - t_chunk) / k_chunk
                # timeline tier: one span per chunked dispatch, named
                # by its trajectory window (no-op disarmed)
                tracing.record_span(
                    f"chunk k{consumed}..{consumed + k_chunk}",
                    t_chunk, t_end, cat="chunk",
                    k_offset=consumed, iterations=k_chunk)
                consumed += k_chunk
                executed += k_chunk
                if first_norms is None:
                    first_norms = (float(res.bnrm2), float(res.x0nrm2),
                                   float(res.r0nrm2))
                    abs_tol = max(crit.residual_atol,
                                  crit.residual_rtol * first_norms[2])
                if tr:
                    st.trace = self.last_trace = \
                        telemetry.ConvergenceTrace.from_ring(
                            np.asarray(tbuf), k_chunk,
                            solver=solver_name,
                            offset=consumed - k_chunk)
                # live-observatory tier: the per-chunk carry return is
                # a REAL mid-solve iteration/residual sample for the
                # status endpoint (no-op disarmed; host-side only, so
                # the compiled programs are untouched)
                observatory.note_chunk(
                    self._ckpt_tier, consumed, float(res.rnrm2),
                    abs_tol=abs_tol,
                    trace=(st.trace if tr else None),
                    rtol=crit.residual_rtol)
                if hl and aud is not None:
                    gap_tripped = health_mod.note_audit(
                        st, aud, self.health_spec, self._ckpt_tier,
                        fresh=aud_fresh)
                    aud_fresh = False
                if detect and bool(res.breakdown):
                    if tr:
                        driver.log_trace_window(st.trace)
                    if (gap_tripped
                            and self.health_spec.action == "abort"):
                        st.tsolve += time.perf_counter() - t0 - ck_secs
                        st.converged = False
                        from acg_tpu.errors import BreakdownError
                        raise BreakdownError(
                            f"{self._ckpt_tier}: true-residual gap "
                            f"{st.health.get('gap_max', 0.0):.3e} "
                            f"exceeds threshold "
                            f"{self.health_spec.threshold:g} at "
                            f"iteration {consumed} (--on-gap abort)")
                    driver.note_breakdown(consumed)
                    # a fault that fired (that is what broke the solve)
                    # must not deterministically re-fire after the
                    # rollback/restart; `fault` stays in the TRAJECTORY
                    # frame (the per-dispatch shift above rebases it),
                    # so vanish it once its iteration has executed --
                    # rebasing here would make the dispatch shift
                    # double-subtract a still-pending fault
                    if (fault is not None and fault.device_site
                            and (is_pl
                                 or fault.iteration <= executed)):
                        # pl: the injector frame is the pipeline's own
                        # counter, which a rollback/restart rewinds --
                        # a deterministic re-fire would livelock the
                        # ladder, so vanish it outright
                        fault = None
                    # FIRST RUNG: roll the carry back to the last
                    # committed snapshot -- exact pre-corruption Krylov
                    # state, restart budget untouched
                    if (last_snap is not None
                            and driver.on_rollback(consumed,
                                                   last_snap[0])):
                        arrs = last_snap[1]
                        x_cur = jnp.asarray(arrs["x"])
                        carry = tuple(jnp.asarray(arrs[nm])
                                      for nm in names[1:])
                        consumed = last_snap[0]
                        continue
                    # second rung: restart from the recomputed true
                    # residual (carry=None re-enters the setup path)
                    if driver.on_breakdown(consumed, noted=True):
                        x_next = res.x
                        if not bool(jnp.isfinite(x_next).all()):
                            driver.record("iterate non-finite; "
                                          "restarting from the "
                                          "initial guess")
                            x_next = x0_dev
                        if self.precond_spec is not None:
                            from acg_tpu.precond import refresh_state
                            if refresh_state(self, driver):
                                kwargs["mstate"] = self._mstate
                        x_cur = x_next
                        carry = None
                        continue
                    pol = self.recovery
                    if (pol is not None and pol.fallback_host
                            and self.host_matrix is not None):
                        driver.on_fallback(
                            "fallback: host reference solver")
                        st.tsolve += time.perf_counter() - t0 - ck_secs
                        return self._host_fallback(
                            b_host, crit, raise_on_divergence,
                            host_result)
                    st.tsolve += time.perf_counter() - t0 - ck_secs
                    st.converged = False
                    raise driver.give_up(
                        consumed, float(res.rnrm2),
                        snapshot=cfg.path if nsnaps else None)
                finished = (consumed >= crit.maxits if unbounded
                            else bool(res.converged))
                x_cur = res.x
                carry = core
                if cfg.path is not None and not finished:
                    t_ck = time.perf_counter()
                    arrs = {"x": np.asarray(res.x)}
                    for nm, leaf in zip(names[1:], core):
                        arrs[nm] = np.asarray(leaf)
                    seq += 1
                    meta = {
                        "tier": self._ckpt_tier,
                        "pipelined": bool(self.pipelined),
                        "algorithm": algo_name,
                        "precond": pc_kind,
                        "n": int(self.A.nrows),
                        "dtype": str(np.dtype(dtype)),
                        "iteration": consumed,
                        "seq": seq,
                        "abs_tol": float(abs_tol),
                        "bnrm2": first_norms[0],
                        "x0nrm2": first_norms[1],
                        "r0nrm2": first_norms[2],
                        "b_crc": b_crc,
                        "fault": (str(faults.active_fault())
                                  if faults.active_fault() is not None
                                  else None),
                        "trace_tail": ckpt_mod.trace_tail(
                            st.trace if tr else None),
                    }
                    ckpt_mod.agree_seq(seq, consumed)
                    nbytes = ckpt_mod.save_snapshot(cfg.path, meta,
                                                    arrs)
                    dt = time.perf_counter() - t_ck
                    ck_secs += dt
                    telemetry.add_timing(st, "ckpt", dt)
                    metrics.record_snapshot(nbytes, dt)
                    nsnaps += 1
                    last_snap = (consumed, arrs)
                    # the crash:exit site models preemption BETWEEN
                    # iterations, after the snapshot committed
                    faults.maybe_crash(consumed - k_chunk, consumed)
                if finished:
                    break
        if res is None:
            # a resumed snapshot already at (or past) the iteration
            # cap: no chunk ever ran -- nothing sensible to report
            raise AcgError(
                ErrorCode.INVALID_VALUE,
                f"snapshot iteration {consumed} already meets the "
                f"iteration cap {crit.maxits}; raise --max-iterations "
                f"to continue this solve")
        t_solve = time.perf_counter() - t0 - ck_secs
        st.tsolve += t_solve
        telemetry.add_timing(st, "solve", t_solve)
        st.nsolves += 1
        st.niterations = executed
        st.ntotaliterations += executed
        st.bnrm2, st.x0nrm2, st.r0nrm2 = first_norms
        st.rnrm2 = float(res.rnrm2)
        st.dxnrm2 = float(res.dxnrm2)
        st.converged = bool(res.converged) or crit.unbounded
        st.ckpt = {
            "path": cfg.path,
            "every": int(cfg.every),
            "snapshots": nsnaps,
            "iteration": consumed,
            "rollbacks": driver.rollbacks,
        }
        if cfg.secs > 0:
            st.ckpt["secs"] = float(cfg.secs)
        if resumed_from is not None:
            st.ckpt["resumed_from"] = resumed_from
        if repartitioned is not None:
            st.ckpt["repartitioned_from"] = repartitioned
        metrics.record_solve(t_solve, executed, st.converged,
                             solver=solver_name)
        metrics.observe_solver_comm(self, executed)
        self._account_ops(st, executed, dtype)
        if host_result:
            x = np.asarray(res.x)
            st.fexcept_arrays = [x]
        else:
            x = res.x
            has_nan = bool(jnp.isnan(res.x).any())
            has_inf = bool(jnp.isinf(res.x).any())
            st.fexcept_arrays = [np.asarray([np.nan if has_nan else 0.0,
                                             np.inf if has_inf
                                             else 0.0])]
        if not st.converged and raise_on_divergence:
            raise NotConvergedError(
                f"{executed} iterations, residual {st.rnrm2:.3e}")
        return x
