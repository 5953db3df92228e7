"""Batched multi-RHS CG and block-CG: solve B systems for ~one's price.

Every solver tier before this one handled exactly one ``Ax=b`` per
process; a serving fleet answers MANY right-hand sides against a cached
operator (ROADMAP item 1).  Two rungs, sharing one batch layout -- RHS
are COLUMNS, every vector becomes ``(n, B)``:

* **Batched CG** (:func:`_batched_cg_program` /
  :func:`_batched_cg_pipelined_program`): the classic and
  Ghysels-Vanroose recurrences with a trailing batch axis.  ONE
  multi-vector SpMV per iteration amortizes the matrix HBM traffic
  B-fold (the planes/gather indices are read once for all columns),
  every per-RHS dot product collapses into a single B-wide column
  reduction, and per-RHS convergence masks ride the loop carry:
  a converged column FREEZES (``jnp.where`` on the mask -- its x/r/p
  never move again and its iteration counter stops) while the loop
  runs to the slowest unconverged RHS.  Per-column trajectories are
  exactly the single-RHS solver's (same update order, same
  convergence test), pinned by tests/test_batched.py.

* **Block CG** (:func:`_block_cg_program`): the true O'Leary block
  recurrence -- ONE shared Krylov block, B x B Gram matrices
  ``W = P^T A P`` / ``G = Z^T R`` solved per iteration, directions
  coupled across columns.  Converges in measurably fewer total
  iterations than B independent solves on ill-conditioned families
  (the ``--aniso`` acceptance): each block iteration expands the
  search space by up to B directions, implicitly deflating the
  extremal eigenvalues that dominate single-vector CG's count.
  Breakdown (a rank-deficient direction block -- converged columns
  deflate, near-parallel RHS collide) is handled by RANK DEFLATION:
  converged/dead columns are masked out of the search block and their
  Gram rows/columns replaced by identity, plus a relative Tikhonov
  jitter sized to the working precision so the B x B solves stay
  defined through exact rank collapse.

Disarmament contract: a batch of ONE delegates every program to the
plain :class:`~acg_tpu.solvers.jax_cg.JaxCGSolver` -- B=1 lowers
byte-identical HLO to the single-RHS tier (pinned in
tests/test_batched.py), and a CLI run without ``--nrhs`` never imports
this module on its solve path.
"""

from __future__ import annotations

import dataclasses
import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

from acg_tpu.errors import AcgError, ErrorCode, NotConvergedError
from acg_tpu.ops.precision import dot_compensated
from acg_tpu.ops.spmv import (BinnedEllMatrix, CooMatrix, DeviceMatrix,
                              DiaMatrix, EllMatrix, acc_dtype,
                              matrix_dtype, matrix_index_bytes,
                              spmv_flops)
from acg_tpu.solvers.stats import (SolverStats, StoppingCriteria,
                                   cg_flops_per_iteration)

__all__ = ["spmv_multi", "BatchedCGResult", "BatchedCGSolver"]


def spmv_multi(A: DeviceMatrix, X: jax.Array) -> jax.Array:
    """``Y = A @ X`` for a multi-column ``X`` of shape ``(n, B)``: one
    pass over the matrix amortized across all B columns -- the batched
    tier's throughput lever.  Every device format is supported; the
    DIA path stays gather-free (statically-sliced 2-D views)."""
    adt = acc_dtype(X.dtype)
    with jax.named_scope("acg.spmv"):
        if hasattr(A, "matfree_apply_multi"):
            # matrix-free operator tier (ops.operator): the batched
            # twin of the generated-plane apply -- the amortization is
            # total (there was no matrix traffic to amortize)
            return A.matfree_apply_multi(X)
        if hasattr(A, "matfree_apply"):
            # user operators registered without a multi-column form:
            # vmap the single-column apply over the batch axis
            return jax.vmap(lambda col: A.matfree_apply(col),
                            in_axes=1, out_axes=1)(X)
        if isinstance(A, DiaMatrix):
            L = max(0, -min(A.offsets))
            R = max(0, max(A.offsets) + A.nrows - X.shape[0])
            Xp = jnp.pad(X, ((L, R), (0, 0)))
            Y = jnp.zeros((A.nrows, X.shape[1]), dtype=adt)
            for plane, off in zip(A.data, A.offsets):
                sl = jax.lax.dynamic_slice_in_dim(Xp, L + off, A.nrows, 0)
                Y = Y + plane[:, None].astype(adt) * sl.astype(adt)
            return Y.astype(X.dtype)
        if isinstance(A, EllMatrix):
            return jnp.einsum("nk,nkb->nb", A.data, X[A.cols],
                              preferred_element_type=adt).astype(X.dtype)
        if isinstance(A, CooMatrix):
            prod = A.vals[:, None].astype(adt) * X[A.cols].astype(adt)
            return jax.ops.segment_sum(
                prod, A.rows, num_segments=A.nrows,
                indices_are_sorted=True).astype(X.dtype)
        if isinstance(A, BinnedEllMatrix):
            Y = jnp.zeros((A.nrows, X.shape[1]), dtype=adt)
            for rows, data, cols in zip(A.bin_rows, A.bin_data,
                                        A.bin_cols):
                contrib = jnp.einsum("mk,mkb->mb", data, X[cols],
                                     preferred_element_type=adt)
                Y = Y.at[rows].add(contrib, unique_indices=True)
            if A.tail_rows.size:
                prod = (A.tail_vals[:, None].astype(adt)
                        * X[A.tail_cols].astype(adt))
                Y = Y + jax.ops.segment_sum(
                    prod, A.tail_rows, num_segments=A.nrows,
                    indices_are_sorted=True)
            return Y.astype(X.dtype)
    raise TypeError(f"unsupported device matrix {type(A)}")


def _coldot_setup(dtype, precise: bool):
    """``(coldot, sdt)``: the per-column dot product (``(n,B),(n,B) ->
    (B,)`` -- ALL per-RHS dots in one fused reduction) and the scalar
    dtype, mirroring jax_cg._scalar_setup's storage policy."""
    sdt = acc_dtype(dtype)
    if precise:
        def one(u, v):
            hi, lo = dot_compensated(u.astype(sdt), v.astype(sdt))
            return hi + lo

        def coldot(a, c):
            return jax.vmap(one, in_axes=1)(a, c)
        return coldot, sdt

    def coldot(a, c):
        return jnp.einsum("nb,nb->b", a, c, preferred_element_type=sdt)
    return coldot, sdt


@functools.partial(jax.tree_util.register_dataclass,
                   data_fields=["x", "niterations", "k_total", "rnrm2",
                                "r0nrm2", "bnrm2", "x0nrm2", "converged"],
                   meta_fields=[])
@dataclasses.dataclass
class BatchedCGResult:
    """Device-resident batched solve result: every field except
    ``k_total`` (the loop trip count -- the slowest RHS's iteration
    number) carries a per-RHS column."""

    x: jax.Array            # (n, B)
    niterations: jax.Array  # (B,) int32: per-RHS frozen-at count
    k_total: jax.Array      # () int32: loop trip count (slowest RHS)
    rnrm2: jax.Array        # (B,)
    r0nrm2: jax.Array       # (B,)
    bnrm2: jax.Array        # (B,)
    x0nrm2: jax.Array       # (B,)
    converged: jax.Array    # (B,) bool


def _res_tols(res_atol, res_rtol, r0nrm2_cols):
    return jnp.maximum(res_atol, res_rtol * r0nrm2_cols)


def _col_where(mask, new, old):
    """Column-masked select: ``mask`` (B,), arrays ``(n, B)``."""
    return jnp.where(mask[None, :], new, old)


def _safe_div(num, den, active):
    """Masked per-column division: inactive columns get exactly 0 (a
    frozen column's update scale), and a 0 denominator on an active
    column -- progress exhausted at the precision floor -- freezes
    that column's step instead of poisoning it with inf."""
    ok = active & (den != 0)
    return jnp.where(ok, num / jnp.where(den != 0, den, 1.0),
                     jnp.zeros_like(num))


@functools.partial(jax.jit,
                   static_argnames=("unbounded", "precise", "precond",
                                    "trace", "state_io"))
def _batched_cg_program(A: DeviceMatrix, Bm, X0, res_atol, res_rtol,
                        maxits, unbounded: bool, precise: bool = False,
                        precond=None, mstate=None, trace: int = 0,
                        state_io: bool = False, carry=None):
    """Whole batched classic-CG solve as one XLA program.

    Per-column recurrences are the single-RHS classic program's; the
    B-wide column reductions fuse all per-RHS dots.  ``carry`` /
    ``state_io`` are the survivability tier's hooks (per-RHS leaves:
    r/p ``(n, B)``, gamma/done/iters ``(B,)``) -- the chunk driver
    threads them so a batch survives preemption mid-solve."""
    dtype = Bm.dtype
    coldot, sdt = _coldot_setup(dtype, precise)
    store = (lambda v: v.astype(dtype)) if sdt != dtype else (lambda v: v)
    nrhs = Bm.shape[1]
    bnrm2 = jnp.sqrt(coldot(Bm, Bm))
    x0nrm2 = jnp.sqrt(coldot(X0, X0))
    papply = None
    if precond is not None:
        from acg_tpu.precond import make_apply_batched
        papply = make_apply_batched(precond)
    if carry is not None:
        if precond is not None:
            R, P, gamma, rr, done0, iters0 = carry
            r0nrm2 = jnp.sqrt(rr)
        else:
            R, P, gamma = carry[:3]
            done0, iters0 = carry[3], carry[4]
            rr = gamma
            r0nrm2 = jnp.sqrt(gamma)
    elif precond is not None:
        R = Bm - spmv_multi(A, X0)
        Z0 = papply(mstate, A, R)
        P = store(Z0)
        gamma = coldot(R, Z0)
        rr = coldot(R, R)
        r0nrm2 = jnp.sqrt(rr)
    else:
        R = Bm - spmv_multi(A, X0)
        P = R
        gamma = rr = coldot(R, R)
        r0nrm2 = jnp.sqrt(gamma)
    res_tol = _res_tols(res_atol, res_rtol, r0nrm2)
    if trace:
        from acg_tpu import telemetry

    def body(k, st):
        if trace:
            buf, st = st[-1], st[:-1]
        X, R, P, gamma, done, iters = st[:6]
        rr_c = st[6] if precond is not None else None
        active = ~done
        T = spmv_multi(A, P)
        pdott = coldot(P, T)
        alpha = _safe_div(gamma, pdott, active)
        X = _col_where(active, store(X + alpha[None, :] * P), X)
        R = _col_where(active, store(R - alpha[None, :] * T), R)
        if precond is not None:
            Z = papply(mstate, A, R)
            gamma_next = coldot(R, Z)
            rr_next = coldot(R, R)
            conv_sqr = rr_next
        else:
            gamma_next = conv_sqr = coldot(R, R)
        beta = _safe_div(gamma_next, gamma, active)
        nextP = store(((Z if precond is not None else R)
                       + beta[None, :] * P))
        P = _col_where(active, nextP, P)
        iters = iters + active.astype(jnp.int32)
        gamma = jnp.where(active, gamma_next, gamma)
        if not unbounded:
            done = done | (active & (conv_sqr < res_tol * res_tol))
        out = (X, R, P, gamma, done, iters)
        if precond is not None:
            out = out + (jnp.where(active, rr_next, rr_c),)
        if trace:
            out = out + (telemetry.ring_record_batched(
                buf, k, conv_sqr),)
        return out

    if carry is not None:
        done0 = done0.astype(bool)
        iters0 = iters0.astype(jnp.int32)
    else:
        iters0 = jnp.zeros((nrhs,), jnp.int32)
        done0 = (jnp.zeros((nrhs,), bool) if unbounded
                 else rr < res_tol * res_tol)
    init = (X0, R, P, gamma, done0, iters0)
    if precond is not None:
        init = init + (rr,)
    if trace:
        init = init + (telemetry.ring_init_batched(trace, nrhs, sdt),)

    if unbounded:
        state = jax.lax.fori_loop(0, maxits, body, init)
        k = maxits
    else:
        def cond(c):
            k, st = c
            return (k < maxits) & jnp.any(~st[4])

        def wbody(c):
            k, st = c
            return (k + 1, body(k, st))

        k, state = jax.lax.while_loop(cond, wbody, (jnp.int32(0), init))
    X, R, P, gamma, done, iters = state[:6]
    rr_fin = state[6] if precond is not None else gamma
    # "converged" = ran the budget on the unbounded path -- but ONLY
    # in the reported result: the state_io carry keeps the loop's own
    # mask and iteration totals, or a later chunk would see every
    # column frozen and silently do nothing
    done_res = jnp.ones((nrhs,), bool) if unbounded else done
    res = BatchedCGResult(
        x=X, niterations=iters, k_total=jnp.asarray(k, jnp.int32),
        rnrm2=jnp.sqrt(rr_fin), r0nrm2=r0nrm2, bnrm2=bnrm2,
        x0nrm2=x0nrm2, converged=done_res)
    extras = ()
    if trace:
        extras = extras + (state[-1],)
    if state_io:
        core = (R, P, gamma)
        if precond is not None:
            core = core + (rr_fin,)
        core = core + (done, iters)
        extras = extras + (core,)
    return (res,) + extras if extras else res


@functools.partial(jax.jit,
                   static_argnames=("unbounded", "precise", "precond",
                                    "trace"))
def _batched_cg_pipelined_program(A: DeviceMatrix, Bm, X0, res_atol,
                                  res_rtol, maxits, unbounded: bool,
                                  precise: bool = False, precond=None,
                                  mstate=None, trace: int = 0):
    """Whole batched Ghysels-Vanroose solve as one XLA program: the
    pipelined recurrences with a trailing batch axis.  BOTH per-RHS
    reduction families (gamma and delta, 2B scalars) are computed at
    one program point, so the distributed twin fuses them into a
    SINGLE allreduce whose payload grows with B while the collective
    COUNT stays 1 (acg_tpu.parallel.dist_batched)."""
    dtype = Bm.dtype
    coldot, sdt = _coldot_setup(dtype, precise)
    store = (lambda v: v.astype(dtype)) if sdt != dtype else (lambda v: v)
    nrhs = Bm.shape[1]
    bnrm2 = jnp.sqrt(coldot(Bm, Bm))
    x0nrm2 = jnp.sqrt(coldot(X0, X0))
    papply = None
    if precond is not None:
        from acg_tpu.precond import make_apply_batched
        papply = make_apply_batched(precond)
        R = Bm - spmv_multi(A, X0)
        U0 = store(papply(mstate, A, R))
        W = spmv_multi(A, U0)
        rr0 = coldot(R, R)
        r0nrm2 = jnp.sqrt(rr0)
    else:
        R = Bm - spmv_multi(A, X0)
        W = spmv_multi(A, R)
        rr0 = coldot(R, R)
        r0nrm2 = jnp.sqrt(rr0)
    res_tol = _res_tols(res_atol, res_rtol, r0nrm2)
    inf = jnp.full((nrhs,), jnp.inf, sdt)
    zeros = jnp.zeros_like(Bm)
    if trace:
        from acg_tpu import telemetry

    def pbody(k, st):
        """Preconditioned GV, batched: carry mirrors jax_cg's pbody
        with per-RHS scalar vectors."""
        if trace:
            buf, st = st[-1], st[:-1]
        (X, R, U, W, P, S, Q, Z, gamma_prev, alpha_prev, rr, done,
         iters) = st
        active = ~done
        gamma = coldot(R, U)
        delta = coldot(W, U)
        rr_new = coldot(R, R)
        M_ = papply(mstate, A, W)
        Nv = spmv_multi(A, M_)
        beta = _safe_div(gamma, gamma_prev, active)
        denom = delta - beta * _safe_div(gamma, alpha_prev, active)
        alpha = _safe_div(gamma, denom, active)
        Z = _col_where(active, store(Nv + beta[None, :] * Z), Z)
        Q = _col_where(active, store(M_ + beta[None, :] * Q), Q)
        S = _col_where(active, store(W + beta[None, :] * S), S)
        P = _col_where(active, store(U + beta[None, :] * P), P)
        X = _col_where(active, store(X + alpha[None, :] * P), X)
        R = _col_where(active, store(R - alpha[None, :] * S), R)
        U = _col_where(active, store(U - alpha[None, :] * Q), U)
        W = _col_where(active, store(W - alpha[None, :] * Z), W)
        iters = iters + active.astype(jnp.int32)
        if not unbounded:
            # the stale test of the pipelined tier: rr_new is this
            # body's pre-update ||r||^2 (jax_cg convergence semantics)
            done = done | (active & (rr_new < res_tol * res_tol))
        gamma_c = jnp.where(active, gamma, gamma_prev)
        alpha_c = jnp.where(active, alpha, alpha_prev)
        rr_c = jnp.where(active, rr_new, rr)
        out = (X, R, U, W, P, S, Q, Z, gamma_c, alpha_c, rr_c, done,
               iters)
        if trace:
            out = out + (telemetry.ring_record_batched(buf, k, rr_new),)
        return out

    def body(k, st):
        if trace:
            buf, st = st[-1], st[:-1]
        X, R, W, P, T, Z, gamma_prev, alpha_prev, done, iters = st
        active = ~done
        # BOTH reduction families at one point: the fused 2B-scalar
        # allreduce of the distributed twin
        gamma = coldot(R, R)
        delta = coldot(W, R)
        Q = spmv_multi(A, W)
        beta = _safe_div(gamma, gamma_prev, active)
        denom = delta - beta * _safe_div(gamma, alpha_prev, active)
        alpha = _safe_div(gamma, denom, active)
        Z = _col_where(active, store(Q + beta[None, :] * Z), Z)
        T = _col_where(active, store(W + beta[None, :] * T), T)
        P = _col_where(active, store(R + beta[None, :] * P), P)
        X = _col_where(active, store(X + alpha[None, :] * P), X)
        R = _col_where(active, store(R - alpha[None, :] * T), R)
        W = _col_where(active, store(W - alpha[None, :] * Z), W)
        iters = iters + active.astype(jnp.int32)
        if not unbounded:
            done = done | (active & (gamma < res_tol * res_tol))
        gamma_c = jnp.where(active, gamma, gamma_prev)
        alpha_c = jnp.where(active, alpha, alpha_prev)
        out = (X, R, W, P, T, Z, gamma_c, alpha_c, done, iters)
        if trace:
            out = out + (telemetry.ring_record_batched(buf, k, gamma),)
        return out

    iters0 = jnp.zeros((nrhs,), jnp.int32)
    done0 = (jnp.zeros((nrhs,), bool) if unbounded
             else rr0 < res_tol * res_tol)
    if precond is not None:
        init = (X0, R, U0, W, zeros, zeros, zeros, zeros, inf, inf,
                rr0, done0, iters0)
        loop = pbody
    else:
        init = (X0, R, W, zeros, zeros, zeros, inf, inf, done0, iters0)
        loop = body
    if trace:
        init = init + (telemetry.ring_init_batched(trace, nrhs, sdt),)
    if unbounded:
        state = jax.lax.fori_loop(0, maxits, loop, init)
        k = maxits
    else:
        def cond(c):
            k, st = c
            done = st[11] if precond is not None else st[8]
            return (k < maxits) & jnp.any(~done)

        def wbody(c):
            k, st = c
            return (k + 1, loop(k, st))

        k, state = jax.lax.while_loop(cond, wbody, (jnp.int32(0), init))
    if trace:
        tbuf, state = state[-1], state[:-1]
    X, R = state[0], state[1]
    done = state[11] if precond is not None else state[8]
    iters = state[12] if precond is not None else state[9]
    if unbounded:
        done = jnp.ones((nrhs,), bool)
    rnrm2 = jnp.sqrt(coldot(R, R))
    # stale-test consistency (jax_cg rationale): a fresh final residual
    # at tolerance counts as converged even if the in-loop stale test
    # never fired before maxits
    done = done | (rnrm2 <= res_tol)
    res = BatchedCGResult(
        x=X, niterations=iters, k_total=jnp.asarray(k, jnp.int32),
        rnrm2=rnrm2, r0nrm2=r0nrm2, bnrm2=bnrm2, x0nrm2=x0nrm2,
        converged=done)
    return (res, tbuf) if trace else res


@functools.partial(jax.jit,
                   static_argnames=("unbounded", "precond", "trace"))
def _block_cg_program(A: DeviceMatrix, Bm, X0, res_atol, res_rtol,
                      maxits, unbounded: bool, precond=None,
                      mstate=None, trace: int = 0):
    """Whole block-CG solve (O'Leary 1980) as one XLA program.

    One shared Krylov block: per iteration ONE multi-vector SpMV, two
    B x B Gram systems (``W alpha = G`` for the step, ``G beta =
    G_new`` for the direction update).  Unlike the batched mode, a
    converged column KEEPS RIDING the shared block (the coupling is
    what buys the iteration-count win); its crossing iteration is
    recorded in the per-RHS counter and further updates only refine
    it.  Rank deflation on breakdown: a rank-deficient Gram matrix
    (parallel RHS, a direction exhausted, the whole block converged)
    is deflated by a relative Tikhonov jitter sized to the scalar
    precision -- the null directions contribute ~nothing to the step
    instead of producing NaNs.  All B x B arithmetic runs in the
    scalar dtype ``sdt``."""
    dtype = Bm.dtype
    coldot, sdt = _coldot_setup(dtype, False)
    store = (lambda v: v.astype(dtype)) if sdt != dtype else (lambda v: v)
    nrhs = Bm.shape[1]
    eps = jnp.asarray(jnp.finfo(sdt).eps, sdt)
    bnrm2 = jnp.sqrt(coldot(Bm, Bm))
    x0nrm2 = jnp.sqrt(coldot(X0, X0))
    papply = None
    if precond is not None:
        from acg_tpu.precond import make_apply_batched
        papply = make_apply_batched(precond)

    def gram(Aa, Bb):
        return jnp.einsum("ni,nj->ij", Aa.astype(sdt), Bb.astype(sdt),
                          preferred_element_type=sdt)

    def deflated_solve(M, G):
        """Solve ``M a = G`` through a relative Tikhonov jitter: a
        rank-deficient M (breakdown: parallel RHS, exhausted
        directions, a fully-converged block) deflates its null
        directions to ~zero step instead of NaNs."""
        tr = jnp.trace(M) / M.shape[0]
        jitter = 64.0 * eps * jnp.maximum(jnp.abs(tr), eps)
        return jnp.linalg.solve(M + jitter * jnp.eye(M.shape[0],
                                                     dtype=sdt), G)

    R = (Bm - spmv_multi(A, X0)).astype(sdt)
    rr0 = coldot(R, R)
    r0nrm2 = jnp.sqrt(rr0)
    res_tol = _res_tols(res_atol, res_rtol, r0nrm2)
    done0 = (jnp.zeros((nrhs,), bool) if unbounded
             else rr0 < res_tol * res_tol)
    Z = papply(mstate, A, R).astype(sdt) if precond is not None else R
    P = Z
    G0 = gram(Z, R)
    if trace:
        from acg_tpu import telemetry

    def body(k, st):
        if trace:
            buf, st = st[-1], st[:-1]
        X, R, P, G, done, iters = st
        active = ~done
        Q = spmv_multi(A, store(P)).astype(sdt)
        W = gram(P, Q)
        alpha = deflated_solve(W, G)
        X = X + P @ alpha
        R = R - Q @ alpha
        rr = coldot(R, R)
        iters = iters + active.astype(jnp.int32)
        if not unbounded:
            done = done | (active & (rr < res_tol * res_tol))
        Zn = (papply(mstate, A, store(R)).astype(sdt)
              if precond is not None else R)
        G_new = gram(Zn, R)
        beta = deflated_solve(G, G_new)
        P = Zn + P @ beta
        out = (X, R, P, G_new, done, iters)
        if trace:
            out = out + (telemetry.ring_record_batched(buf, k, rr),)
        return out

    init = (X0.astype(sdt), R, P, G0, done0,
            jnp.zeros((nrhs,), jnp.int32))
    if trace:
        init = init + (telemetry.ring_init_batched(trace, nrhs, sdt),)
    if unbounded:
        state = jax.lax.fori_loop(0, maxits, body, init)
        k = maxits
    else:
        def cond(c):
            k, st = c
            return (k < maxits) & jnp.any(~st[4])

        def wbody(c):
            k, st = c
            return (k + 1, body(k, st))

        k, state = jax.lax.while_loop(cond, wbody, (jnp.int32(0), init))
    if trace:
        tbuf, state = state[-1], state[:-1]
    X, R, P, G, done, iters = state
    rr_fin = coldot(R, R)
    if unbounded:
        done = jnp.ones((nrhs,), bool)
    res = BatchedCGResult(
        x=store(X), niterations=iters, k_total=jnp.asarray(k, jnp.int32),
        rnrm2=jnp.sqrt(rr_fin), r0nrm2=r0nrm2, bnrm2=bnrm2,
        x0nrm2=x0nrm2, converged=done)
    return (res, tbuf) if trace else res


class BatchedCGSolver:
    """Multi-RHS CG over one :class:`DeviceMatrix`: B systems sharing
    the operator, solved by the batched (default), batched-pipelined
    or block recurrence.

    ``mode``: ``"batched"`` (vmapped classic), ``"pipelined"``
    (vmapped Ghysels-Vanroose) or ``"block"`` (true block CG).
    ``precond`` broadcasts over the batch axis
    (:func:`acg_tpu.precond.make_apply_batched`).  ``trace`` arms the
    per-RHS residual ring (telemetry.BatchedConvergenceTrace);
    ``ckpt`` (an acg_tpu.checkpoint.CheckpointConfig) arms the
    host-chunked snapshot driver for the batched-classic mode -- the
    carry's per-RHS leaves (r/p columns, gamma/done/iters vectors)
    survive preemption and resume exactly.

    A single-column ``b`` (B=1) delegates solve AND lower_solve to a
    plain :class:`JaxCGSolver` with the same configuration -- the
    lowered program is byte-identical to the single-RHS tier's (the
    disarmed-identity discipline, pinned in tests/test_batched.py)."""

    _ckpt_tier = "jax-cg-batched"

    def __init__(self, A: DeviceMatrix, mode: str = "batched",
                 precise_dots: bool = False, kernels: str = "auto",
                 vector_dtype=None, precond=None, trace: int = 0,
                 ckpt=None, host_matrix=None):
        if mode not in ("batched", "pipelined", "block"):
            raise ValueError(f"unknown batched mode {mode!r} "
                             f"(batched, pipelined, block)")
        if kernels not in ("auto", "xla"):
            raise ValueError(
                "the batched tiers run the XLA multi-vector SpMV "
                "(one matrix pass over all B columns); kernels="
                f"{kernels!r} is single-RHS only -- use 'auto'/'xla'")
        if mode == "block" and precise_dots:
            raise ValueError("block-CG's scalars are B x B Gram solves "
                             "in the scalar dtype; precise_dots applies "
                             "to the batched/pipelined modes")
        self.A = A
        self.mode = mode
        self.precise_dots = bool(precise_dots)
        self.vector_dtype = vector_dtype
        from acg_tpu.precond import parse_precond
        self.precond_spec = parse_precond(precond)
        self._mstate = None
        self.trace = int(trace)
        if self.trace < 0:
            raise ValueError("trace must be >= 0")
        if ckpt is not None:
            from acg_tpu.checkpoint import CheckpointConfig
            if not isinstance(ckpt, CheckpointConfig):
                raise ValueError("ckpt must be an acg_tpu.checkpoint."
                                 "CheckpointConfig or None")
            if mode != "batched":
                raise ValueError(
                    "batched checkpointing threads the batched-classic "
                    "carry (r/p columns + gamma/done/iters); the "
                    "pipelined/block modes do not expose state_io -- "
                    "use mode='batched'")
        self.ckpt = ckpt
        self.host_matrix = host_matrix
        self.last_trace = None
        self.stats = SolverStats(unknowns=A.nrows)
        # the B=1 delegate: constructed lazily, shares this tier's
        # configuration so delegation is byte-identical to a plain
        # single-RHS build
        self._inner1 = None
        self._spmv_flops_cache = None

    # -- shared plumbing --------------------------------------------------

    def _solve_dtype(self):
        dtype = matrix_dtype(self.A)
        if self.vector_dtype is not None:
            dtype = jnp.dtype(self.vector_dtype)
        return dtype

    def _inner(self):
        if self._inner1 is None:
            from acg_tpu.solvers.jax_cg import JaxCGSolver
            self._inner1 = JaxCGSolver(
                self.A, pipelined=(self.mode == "pipelined"),
                precise_dots=self.precise_dots, kernels="xla",
                vector_dtype=self.vector_dtype,
                precond=self.precond_spec, trace=self.trace,
                host_matrix=self.host_matrix,
                ckpt=self.ckpt)
        return self._inner1

    def _ensure_precond_state(self):
        if self.precond_spec is None or self._mstate is not None:
            return self._mstate
        from acg_tpu.ops.spmv import spmv
        from acg_tpu.precond import setup_single
        sdt = acc_dtype(self._solve_dtype())
        self._mstate = setup_single(self.precond_spec, self.A,
                                    spmv, sdt)
        return self._mstate

    def _as_columns(self, v, dtype):
        v = jnp.asarray(v, dtype=dtype)
        if v.ndim == 1:
            v = v[:, None]
        if v.ndim != 2 or v.shape[0] != self.A.nrows:
            raise ValueError(
                f"batched right-hand sides are (n, B) columns; got "
                f"shape {tuple(v.shape)} for n={self.A.nrows}")
        return v

    def _check_criteria(self, crit: StoppingCriteria):
        if crit.needs_diff:
            raise AcgError(
                ErrorCode.INVALID_VALUE,
                "the batched tiers support residual criteria only "
                "(a per-RHS diff criterion is not part of the batched "
                "carry)")

    def _select_program(self, Bm, X0, crit: StoppingCriteria,
                        state_io: bool = False, carry=None):
        sdt = acc_dtype(Bm.dtype)
        args = (self.A, Bm, X0,
                jnp.asarray(crit.residual_atol, sdt),
                jnp.asarray(crit.residual_rtol, sdt),
                jnp.int32(crit.maxits))
        kwargs = dict(unbounded=crit.unbounded, trace=self.trace)
        if self.mode == "block":
            program = _block_cg_program
        elif self.mode == "pipelined":
            program = _batched_cg_pipelined_program
            kwargs["precise"] = self.precise_dots
        else:
            program = _batched_cg_program
            kwargs["precise"] = self.precise_dots
            if state_io:
                kwargs["state_io"] = True
            if carry is not None:
                kwargs["carry"] = carry
        if self.precond_spec is not None:
            kwargs["precond"] = self.precond_spec
            kwargs["mstate"] = self._ensure_precond_state()
        return program, args, kwargs

    def lower_solve(self, b, x0=None, criteria=None):
        """Lower (don't run) the exact program this configuration
        dispatches -- the HLO-pin hook.  B=1 delegates to the plain
        single-RHS solver, so the lowered text is byte-identical to
        the unbatched tier's."""
        crit = criteria or StoppingCriteria()
        dtype = self._solve_dtype()
        Bm = self._as_columns(b, dtype)
        if Bm.shape[1] == 1:
            return self._inner().lower_solve(
                Bm[:, 0], x0=None if x0 is None
                else self._as_columns(x0, dtype)[:, 0],
                criteria=criteria)
        self._check_criteria(crit)
        X0 = (jnp.zeros_like(Bm) if x0 is None
              else self._as_columns(x0, dtype))
        program, args, kwargs = self._select_program(Bm, X0, crit)
        return program.lower(*args, **kwargs)

    # -- solve ------------------------------------------------------------

    def solve(self, b, x0=None, criteria: StoppingCriteria | None = None,
              raise_on_divergence: bool = True, warmup: int = 0,
              host_result: bool = True):
        """Solve ``A X = B`` for the (n, B) column block ``b``.
        Returns the (n, B) solution block (host numpy unless
        ``host_result=False``); per-RHS evidence lands in
        ``stats.batch``."""
        crit = criteria or StoppingCriteria()
        dtype = self._solve_dtype()
        from acg_tpu import telemetry
        st = self.stats
        st.criteria = crit
        t_xfer = time.perf_counter()
        with telemetry.annotate("transfer"):
            Bm = self._as_columns(b, dtype)
            X0 = (jnp.zeros_like(Bm) if x0 is None
                  else self._as_columns(x0, dtype))
        telemetry.add_timing(st, "transfer",
                             time.perf_counter() - t_xfer)
        nrhs = int(Bm.shape[1])
        if nrhs == 1:
            # the disarmed-identity path: ONE column runs the plain
            # single-RHS program byte-for-byte
            inner = self._inner()
            x = inner.solve(np.asarray(Bm[:, 0]) if host_result
                            else Bm[:, 0],
                            x0=None if x0 is None else np.asarray(X0[:, 0]),
                            criteria=crit,
                            raise_on_divergence=raise_on_divergence,
                            warmup=warmup, host_result=host_result)
            self.stats = st = inner.stats
            self.last_trace = inner.last_trace
            st.batch = {"nrhs": 1, "mode": self.mode,
                        "iterations": [int(st.niterations)],
                        "rnrm2": [float(st.rnrm2)],
                        "converged": [bool(st.converged)],
                        "iterations_max": int(st.niterations),
                        "iterations_sum": int(st.niterations)}
            if host_result:
                return np.asarray(x).reshape(-1, 1)
            return x[:, None] if x.ndim == 1 else x
        self._check_criteria(crit)
        if self.ckpt is not None:
            return self._solve_ckpt(Bm, X0, crit, raise_on_divergence,
                                    warmup, host_result)
        program, args, kwargs = self._select_program(Bm, X0, crit)

        def run():
            out = program(*args, **kwargs)
            if self.trace:
                return out[0], out[1]
            return out, None

        if warmup > 0:
            t_warm = time.perf_counter()
            with telemetry.annotate("compile"):
                for _ in range(warmup):
                    jax.block_until_ready(run()[0].x)
            telemetry.add_timing(st, "compile",
                                 time.perf_counter() - t_warm)
        t0 = time.perf_counter()
        with telemetry.annotate("solve"):
            res, tbuf = run()
            jax.block_until_ready(res.x)
        t_solve = time.perf_counter() - t0
        st.tsolve += t_solve
        telemetry.add_timing(st, "solve", t_solve)
        self._finish_stats(res, t_solve, nrhs, tbuf)
        x = np.asarray(res.x) if host_result else res.x
        if host_result:
            st.fexcept_arrays = [x]
        else:
            has_nan = bool(jnp.isnan(res.x).any())
            has_inf = bool(jnp.isinf(res.x).any())
            st.fexcept_arrays = [np.asarray([np.nan if has_nan else 0.0,
                                             np.inf if has_inf else 0.0])]
        if not st.converged and raise_on_divergence:
            worst = int(np.argmax(np.asarray(res.rnrm2)))
            raise NotConvergedError(
                f"{st.niterations} iterations, {st.batch['unconverged']}"
                f" of {nrhs} RHS unconverged (worst rhs {worst}, "
                f"residual {float(np.asarray(res.rnrm2)[worst]):.3e})")
        return x

    def _finish_stats(self, res: BatchedCGResult, t_solve: float,
                      nrhs: int, tbuf=None, executed=None) -> None:
        """Per-RHS evidence -> stats.batch + the service hooks; the
        aggregate fields keep their single-RHS meaning via the
        slowest/worst RHS."""
        from acg_tpu import metrics, observatory, telemetry
        st = self.stats
        iters = np.asarray(res.niterations).astype(int).tolist()
        rn = [float(v) for v in np.asarray(res.rnrm2)]
        conv = [bool(v) for v in np.asarray(res.converged)]
        k_total = int(res.k_total) if executed is None else int(executed)
        st.nsolves += 1
        st.niterations = k_total
        st.ntotaliterations += k_total
        st.bnrm2 = float(np.max(np.asarray(res.bnrm2)))
        st.x0nrm2 = float(np.max(np.asarray(res.x0nrm2)))
        st.r0nrm2 = float(np.max(np.asarray(res.r0nrm2)))
        st.rnrm2 = float(max(rn))
        st.dxnrm2 = float("inf")
        st.converged = all(conv)
        st.batch = {
            "nrhs": nrhs,
            "mode": self.mode,
            "iterations": iters,
            "iterations_max": int(max(iters) if iters else 0),
            "iterations_sum": int(sum(iters)),
            "rnrm2": rn,
            "converged": conv,
            "unconverged": int(sum(1 for c in conv if not c)),
        }
        if self.mode == "block":
            # the work metric of the acceptance criterion: each block
            # iteration advances all B columns, so the comparable
            # "total iterations" figure is trips x B
            st.batch["block_iterations"] = k_total
            st.batch["total_iterations"] = k_total * nrhs
        if tbuf is not None:
            st.trace = self.last_trace = \
                telemetry.BatchedConvergenceTrace.from_ring(
                    np.asarray(tbuf), k_total,
                    solver=f"cg-{self.mode}")
        metrics.record_solve(t_solve, k_total, st.converged,
                             solver=f"cg-{self.mode}"
                             if self.mode != "batched" else "cg-batched")
        observatory.note_batch(nrhs, rn, conv)
        self._account_ops(st, k_total, nrhs)

    def _account_ops(self, st, k_total: int, nrhs: int) -> None:
        """Analytic census: matrix bytes are read ONCE per iteration
        for the whole batch (the amortization this tier exists for);
        vector traffic and flops scale with B."""
        if self._spmv_flops_cache is None:
            self._spmv_flops_cache = spmv_flops(self.A)
        n = self.A.nrows
        nnz3 = self._spmv_flops_cache / 3.0
        per_it = cg_flops_per_iteration(nnz3, n,
                                        self.mode == "pipelined")
        # flops scale with B (every column multiplies every nonzero);
        # only the matrix BYTES amortize -- that asymmetry is the tier
        st.nflops += (per_it * k_total + self._spmv_flops_cache
                      + 2.0 * n) * nrhs
        dtype = self._solve_dtype()
        dbl = np.dtype(dtype).itemsize
        mat_dbl = np.dtype(matrix_dtype(self.A)).itemsize
        idx_b = matrix_index_bytes(self.A)
        mat_bytes = int(nnz3 * (mat_dbl + idx_b))
        st.ops["gemv"].add(k_total + 1, 0.0,
                           (mat_bytes + 2 * n * dbl * nrhs)
                           * (k_total + 1))
        st.ops["dot"].add(k_total, 0.0, 2 * n * dbl * nrhs * k_total)
        st.ops["nrm2"].add(k_total + 1, 0.0,
                           n * dbl * nrhs * (k_total + 1))
        st.ops["axpy"].add(3 * k_total, 0.0,
                           3 * n * dbl * nrhs * 3 * k_total)

    # -- survivability: chunked batched solve ------------------------------

    def _solve_ckpt(self, Bm, X0, crit, raise_on_divergence: bool,
                    warmup: int, host_result: bool):
        """Checkpoint-armed batched solve: the UNCHANGED batched
        classic program dispatched in chunks with the per-RHS carry
        (r/p columns + gamma/done/iters vectors) threaded through and
        snapshotted -- a batch survives preemption with every RHS's
        progress intact, and resumes to the original per-RHS
        tolerances."""
        from acg_tpu import checkpoint as ckpt_mod
        from acg_tpu import metrics, observatory, telemetry
        cfg = self.ckpt
        st = self.stats
        st.criteria = crit
        nrhs = int(Bm.shape[1])
        dtype = self._solve_dtype()
        sdt = acc_dtype(dtype)
        b_crc = ckpt_mod.vector_checksum(np.asarray(Bm))
        names = ckpt_mod.batched_carry_names(
            self.precond_spec is not None)

        def chunk_args(x_dev, atol_cols, rtol, m):
            return (self.A, Bm, x_dev,
                    jnp.asarray(atol_cols, sdt),
                    jnp.asarray(rtol, sdt), jnp.int32(m))

        consumed = 0
        executed = 0
        resumed_from = None
        carry = None
        x_cur = X0
        abs_tol = None
        first_r0 = None
        snap = cfg.resume
        if snap is not None:
            ckpt_mod.validate_resume(
                snap, tier=self._ckpt_tier, pipelined=False,
                precond=(str(self.precond_spec)
                         if self.precond_spec is not None else None),
                n=int(self.A.nrows), dtype=dtype, b_crc=b_crc,
                nrhs=nrhs)
            consumed = resumed_from = snap.iteration
            sm = snap.meta
            abs_tol = np.asarray(sm["abs_tol"], dtype=np.float64)
            first_r0 = np.asarray(sm["r0nrm2"], dtype=np.float64)
            x_cur = jnp.asarray(snap.arrays["x"], dtype=dtype)
            carry = tuple(jnp.asarray(snap.arrays[nm])
                          for nm in names[1:])
            metrics.record_resume()
            telemetry.record_event(
                st, "resume",
                f"resumed batched solve ({nrhs} RHS) from snapshot at "
                f"iteration {consumed}")

        def run(a, carry):
            out = _batched_cg_program(
                *a, unbounded=crit.unbounded,
                precise=self.precise_dots, trace=self.trace,
                state_io=True, carry=carry,
                **({"precond": self.precond_spec,
                    "mstate": self._ensure_precond_state()}
                   if self.precond_spec is not None else {}))
            ring = out[1] if self.trace else None
            return out[0], ring, out[-1]

        seq = 0
        nsnaps = 0
        ck_secs = 0.0
        res = None
        t0 = time.perf_counter()
        with telemetry.annotate("solve"):
            while True:
                remaining = crit.maxits - consumed
                if remaining <= 0:
                    break
                m = min(cfg.chunk_for(None), remaining)
                if abs_tol is None:
                    a = chunk_args(x_cur,
                                   jnp.full((nrhs,), crit.residual_atol),
                                   crit.residual_rtol, m)
                else:
                    a = chunk_args(x_cur, abs_tol, 0.0, m)
                res, tbuf, core = run(a, carry)
                jax.block_until_ready(res.x)
                k_chunk = int(res.k_total)
                consumed += k_chunk
                executed += k_chunk
                if first_r0 is None:
                    first_r0 = np.asarray(res.r0nrm2, dtype=np.float64)
                    abs_tol = np.maximum(crit.residual_atol,
                                         crit.residual_rtol * first_r0)
                if self.trace and tbuf is not None:
                    st.trace = self.last_trace = \
                        telemetry.BatchedConvergenceTrace.from_ring(
                            np.asarray(tbuf), k_chunk,
                            solver="cg-batched",
                            offset=consumed - k_chunk)
                # status plane: the ETA keys to the SLOWEST unconverged
                # RHS -- its residual is the one the endpoint samples
                rn = np.asarray(res.rnrm2)
                conv = np.asarray(res.converged)
                worst = (float(np.max(rn[~conv])) if (~conv).any()
                         else float(np.max(rn)))
                observatory.note_chunk(
                    self._ckpt_tier, consumed, worst,
                    abs_tol=float(np.max(abs_tol)),
                    rtol=crit.residual_rtol)
                observatory.note_batch(
                    nrhs, [float(v) for v in rn],
                    [bool(v) for v in conv])
                finished = (consumed >= crit.maxits if crit.unbounded
                            else bool(conv.all()))
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
                        "pipelined": False,
                        "precond": (str(self.precond_spec)
                                    if self.precond_spec is not None
                                    else None),
                        "n": int(self.A.nrows),
                        "nrhs": nrhs,
                        "dtype": str(np.dtype(dtype)),
                        "iteration": consumed,
                        "seq": seq,
                        "abs_tol": [float(v) for v in abs_tol],
                        "bnrm2": [float(v)
                                  for v in np.asarray(res.bnrm2)],
                        "x0nrm2": [float(v)
                                   for v in np.asarray(res.x0nrm2)],
                        "r0nrm2": [float(v) for v in first_r0],
                        "b_crc": b_crc,
                        "trace_tail": [],
                    }
                    nbytes = ckpt_mod.save_snapshot(cfg.path, meta,
                                                    arrs)
                    dt = time.perf_counter() - t_ck
                    ck_secs += dt
                    telemetry.add_timing(st, "ckpt", dt)
                    metrics.record_snapshot(nbytes, dt)
                    nsnaps += 1
                if finished:
                    break
        if res is None:
            raise AcgError(
                ErrorCode.INVALID_VALUE,
                f"snapshot iteration {consumed} already meets the "
                f"iteration cap {crit.maxits}; raise --max-iterations "
                f"to continue this solve")
        t_solve = time.perf_counter() - t0 - ck_secs
        st.tsolve += t_solve
        telemetry.add_timing(st, "solve", t_solve)
        self._finish_stats(res, t_solve, nrhs, None, executed=executed)
        st.ckpt = {
            "path": cfg.path,
            "every": int(cfg.every),
            "snapshots": nsnaps,
            "iteration": consumed,
            "rollbacks": 0,
        }
        if resumed_from is not None:
            st.ckpt["resumed_from"] = resumed_from
        x = np.asarray(res.x) if host_result else res.x
        if host_result:
            st.fexcept_arrays = [x]
        if not st.converged and raise_on_divergence:
            raise NotConvergedError(
                f"{executed} iterations, "
                f"{st.batch['unconverged']} of {nrhs} RHS unconverged")
        return x
