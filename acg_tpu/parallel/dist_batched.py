"""Batched multi-RHS CG over the device mesh: B systems, ONE solve.

The distributed twin of :mod:`acg_tpu.solvers.batched` -- the classic
and pipelined SPMD recurrences of :mod:`acg_tpu.parallel.dist` with a
trailing batch axis.  The communication contract is the tentpole:

* the halo exchange moves ``(maxcnt, B)`` windows through the SAME
  single ``all_to_all`` per iteration (payload grows with B, the
  collective count does not);
* ALL per-RHS dot products fuse into B-WIDE allreduces -- classic CG
  keeps its 2 psums per iteration (now of (B,) vectors), pipelined CG
  keeps its SINGLE fused psum (now 2B scalars; the
  ``pdot2_fused``/``pdot3_fused`` column variants).  On a multi-hop
  ICI mesh B allreduces of 1 scalar cost ~B x the latency of 1
  allreduce of B scalars (arXiv 1905.06850's global-reduction
  argument), so the collective count staying INVARIANT IN B is the
  whole point -- pinned at the HLO level in tests/test_batched.py.

Per-RHS convergence masks ride the carry exactly as on the
single-device tier; every masked scalar is psum'd, so the masks are
mesh-uniform and the loop runs to the slowest unconverged RHS on every
shard alike.  A single-column batch delegates to the plain
:class:`~acg_tpu.parallel.dist.DistCGSolver` -- B=1 lowers
byte-identical HLO (the disarmed-identity discipline)."""

from __future__ import annotations

import functools
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from acg_tpu.errors import AcgError, ErrorCode, NotConvergedError
from acg_tpu.ops.spmv import acc_dtype
from acg_tpu.parallel.dist import DistributedProblem, place_operator
from acg_tpu.parallel.mesh import PARTS_AXIS, solve_mesh
from acg_tpu.parallel.reductions import make_pdot_cols, make_pdotk_cols
from acg_tpu.parallel.multihost import get_global, put_global
from acg_tpu.solvers.stats import (SolverStats, StoppingCriteria,
                                   cg_flops_per_iteration)

__all__ = ["BatchedDistCGSolver"]


def _local_mv_multi(block, arrays, X, interpret: bool = True):
    """``Y = A_local @ X`` for one shard's local block, multi-column
    ``X`` (nrows, B) -- one pass over the block for all columns (a
    row-tiled DIA block: one kernel call a column, compiled unless
    ``interpret``)."""
    adt = acc_dtype(X.dtype)
    if block.format == "dia":
        # dia_mv generalises column-wise via a vmap over the batch
        # axis of the statically-sliced views; the planes are read
        # once per slice either way (XLA hoists the broadcast)
        L = max(0, -min(block.offsets))
        R = max(0, max(block.offsets) + block.nrows - X.shape[0])
        Xp = jnp.pad(X, ((L, R), (0, 0)))
        Y = jnp.zeros((block.nrows, X.shape[1]), dtype=adt)
        for plane, off in zip(arrays, block.offsets):
            sl = lax.dynamic_slice_in_dim(Xp, L + off, block.nrows, 0)
            Y = Y + plane[:, None].astype(adt) * sl.astype(adt)
        return Y.astype(X.dtype)
    if block.format == "tiledia":
        from acg_tpu.ops.pallas_kernels import tiledia_spmv

        return jnp.stack([tiledia_spmv(*arrays, X[:, c], block.offsets,
                                       interpret=interpret)
                          for c in range(X.shape[1])], axis=1)
    if block.format == "binnedell":
        bin_rows, bin_data, bin_cols, t_rows, t_cols, t_vals = arrays
        Y = jnp.zeros((block.nrows, X.shape[1]), dtype=adt)
        for rows, data, cols in zip(bin_rows, bin_data, bin_cols):
            contrib = jnp.einsum("mk,mkb->mb", data, X[cols],
                                 preferred_element_type=adt)
            Y = Y.at[rows].add(contrib)
        if t_vals.shape[-1]:
            prod = t_vals[:, None].astype(adt) * X[t_cols].astype(adt)
            Y = Y.at[t_rows].add(prod)
        return Y.astype(X.dtype)
    data, cols = arrays
    return jnp.einsum("nk,nkb->nb", data, X[cols],
                      preferred_element_type=adt).astype(X.dtype)


def _ghost_mv_multi(block, arrays, Xg):
    rows, data, cols = arrays
    contrib = jnp.einsum("bk,bkc->bc", data, Xg[cols],
                         preferred_element_type=acc_dtype(Xg.dtype)
                         ).astype(Xg.dtype)
    return jnp.zeros((block.nrows, Xg.shape[1]), Xg.dtype).at[rows].add(
        contrib, indices_are_sorted=True)


def _squeeze_col(x0):
    """A single-column (n, 1) x0 -> the (n,) vector the delegated
    single-RHS solver's scatter consumes (B=1 delegation)."""
    if x0 is None:
        return None
    x0 = np.asarray(x0)
    return x0[:, 0] if x0.ndim == 2 else x0


def _halo_exchange_multi(X_loc, send_idx, ghost_src,
                         axis: str = PARTS_AXIS):
    """Multi-column halo exchange: the SAME single all_to_all as the
    single-RHS transport, its payload widened by the batch axis."""
    with jax.named_scope("acg.halo"):
        sendbuf = X_loc[send_idx]           # (nparts, maxcnt, B)
        recvbuf = lax.all_to_all(sendbuf, axis, split_axis=0,
                                 concat_axis=0, tiled=True)
        return recvbuf.reshape(-1, X_loc.shape[1])[ghost_src]


class BatchedDistCGSolver:
    """Whole-solve batched SPMD CG over a 1-D mesh: B right-hand-side
    columns against one partitioned operator, collective count
    invariant in B.

    Supports the classic (2 B-wide psums/iteration) and pipelined
    (1 fused 2B-scalar psum) recurrences, per-RHS convergence masks,
    the per-RHS telemetry ring, and -- classic mode -- checkpointed
    chunked solves whose per-part per-RHS carry leaves survive
    preemption and ``--resume-repartition`` onto a different mesh."""

    _ckpt_tier = "dist-cg-batched"

    def __init__(self, problem: DistributedProblem,
                 pipelined: bool = False, mesh=None,
                 precise_dots: bool = False, precond=None,
                 trace: int = 0, ckpt=None):
        if precond is not None:
            from acg_tpu.precond import parse_precond
            if parse_precond(precond) is not None:
                raise ValueError(
                    "the batched distributed tier runs unpreconditioned "
                    "CG (preconditioned batching lives on the "
                    "single-device tier, acg_tpu.solvers.batched); "
                    "drop precond or use nparts=1")
        if problem.local.format == "matfree":
            raise ValueError(
                "the batched distributed tier runs assembled local "
                "blocks (its multi-vector shard SpMV has no generated-"
                "plane form yet); matrix-free batching lives on the "
                "single-device tier (acg_tpu.solvers.batched), or drop "
                "--nrhs for the matrix-free mesh solve")
        self.problem = problem
        self.pipelined = bool(pipelined)
        self.precise_dots = bool(precise_dots)
        self.mesh = mesh if mesh is not None else solve_mesh(problem.nparts)
        self.stats = SolverStats(unknowns=problem.n)
        self._sharding = NamedSharding(self.mesh, P(PARTS_AXIS))
        self.trace = int(trace)
        if self.trace < 0:
            raise ValueError("trace must be >= 0")
        if ckpt is not None:
            from acg_tpu.checkpoint import CheckpointConfig
            if not isinstance(ckpt, CheckpointConfig):
                raise ValueError("ckpt must be an acg_tpu.checkpoint."
                                 "CheckpointConfig or None")
            if self.pipelined:
                raise ValueError(
                    "batched checkpointing threads the batched-classic "
                    "carry; the pipelined batched mode does not expose "
                    "state_io")
        self.ckpt = ckpt
        self.last_trace = None
        self._inner1 = None
        self._programs: dict = {}
        # the placed operator (place_operator), held from the first call
        self._op = None

    # -- B=1 delegation ----------------------------------------------------

    def _inner(self):
        if self._inner1 is None:
            from acg_tpu.parallel.dist import DistCGSolver
            self._inner1 = DistCGSolver(
                self.problem, pipelined=self.pipelined, mesh=self.mesh,
                precise_dots=self.precise_dots, trace=self.trace,
                ckpt=self.ckpt)
        return self._inner1

    # -- program construction ---------------------------------------------

    def _program_for(self, nrhs: int, state_io: bool = False):
        key = (int(nrhs), bool(state_io))
        prog = self._programs.get(key)
        if prog is None:
            prog = self._programs[key] = self._compile(nrhs, state_io)
        return prog

    def _compile(self, nrhs: int, state_io: bool):
        prob = self.problem
        pipelined = self.pipelined
        axis = PARTS_AXIS
        precise = self.precise_dots
        trace = self.trace
        halo = prob.halo
        local_block = prob.local
        ghost_block = prob.ghost
        single_shard = self.mesh.devices.size == 1
        interpret = self.mesh.devices.flat[0].platform != "tpu"
        if trace:
            from acg_tpu import telemetry

        def psum(v):
            return v if single_shard else lax.psum(v, axis)

        def shard_body(la, ga, sidx, gsrc, gval, scnt, rcnt, b, x0,
                       atols, rtol, maxits, unbounded=False,
                       carry=None):
            la, ga = (jax.tree.map(lambda a: a[0], t) for t in (la, ga))
            sidx, gsrc, gval, scnt, rcnt, b, x0 = (
                a[0] for a in (sidx, gsrc, gval, scnt, rcnt, b, x0))
            if carry is not None:
                # vector leaves arrive stacked (1, pad, B); the per-RHS
                # column vectors (B,) arrive replicated
                carry = tuple(a[0] if a.ndim == 3 else a for a in carry)
            maxits = maxits.astype(jnp.int32)
            dtype = b.dtype
            sdt = acc_dtype(dtype)
            store = ((lambda v: v.astype(dtype)) if sdt != dtype
                     else (lambda v: v))
            # atols may be a scalar (first dispatch) or the chunk
            # driver's per-RHS absolute-target vector (resume keeps
            # every column's ORIGINAL tolerance)
            res_atol, res_rtol = atols, rtol

            def spmv(X):
                y = _local_mv_multi(local_block, la, X, interpret)
                if halo.has_ghosts:
                    ghost = _halo_exchange_multi(X, sidx, gsrc, axis)
                    y = y + _ghost_mv_multi(ghost_block, ga, ghost)
                return y

            def lcoldot(a, c):
                return jnp.einsum("nb,nb->b", a, c,
                                  preferred_element_type=sdt)

            # the fused-reduction family (parallel.reductions), B-wide:
            # ONE psum carries k B-column payloads (the mesh collective
            # count stays invariant in B; compensated mode interleaves
            # hi/lo column pairs) -- byte-identical emission to the
            # hand-written ladders this replaced (tests/test_batched.py
            # pins the counts)
            pdot_cols = make_pdot_cols(psum, lcoldot, sdt, precise)
            _pdotk_cols = make_pdotk_cols(psum, lcoldot, sdt, precise)

            def pdot2_fused_cols(a1, c1, a2, c2):
                return _pdotk_cols((a1, c1), (a2, c2))

            bnrm2 = jnp.sqrt(pdot_cols(b, b))
            x0nrm2 = jnp.sqrt(pdot_cols(x0, x0))
            inf = jnp.full((nrhs,), jnp.inf, sdt)
            if carry is not None:
                r = carry[0]
                gamma = carry[2]
                done0, iters0 = (carry[3].astype(bool),
                                 carry[4].astype(jnp.int32))
                r0nrm2 = jnp.sqrt(gamma)
            else:
                r = b - spmv(x0)
                gamma = pdot_cols(r, r)
                r0nrm2 = jnp.sqrt(gamma)
                done0 = iters0 = None
            res_tol = jnp.maximum(res_atol, res_rtol * r0nrm2)

            def active_div(num, den, active):
                ok = active & (den != 0)
                return jnp.where(ok, num / jnp.where(den != 0, den, 1.0),
                                 jnp.zeros_like(num))

            def colw(mask, new, old):
                return jnp.where(mask[None, :], new, old)

            if not pipelined:
                def body(k, st):
                    if trace:
                        buf, st = st[-1], st[:-1]
                    X, R, Pv, gamma, done, iters = st
                    active = ~done
                    T = spmv(Pv)
                    pdott = pdot_cols(Pv, T)         # psum 1: (B,)
                    alpha = active_div(gamma, pdott, active)
                    X = colw(active, store(X + alpha[None, :] * Pv), X)
                    R = colw(active, store(R - alpha[None, :] * T), R)
                    gamma_next = pdot_cols(R, R)     # psum 2: (B,)
                    beta = active_div(gamma_next, gamma, active)
                    Pv = colw(active, store(R + beta[None, :] * Pv), Pv)
                    iters = iters + active.astype(jnp.int32)
                    gamma = jnp.where(active, gamma_next, gamma)
                    if not unbounded:
                        done = done | (active
                                       & (gamma_next
                                          < res_tol * res_tol))
                    out = (X, R, Pv, gamma, done, iters)
                    if trace:
                        out = out + (telemetry.ring_record_batched(
                            buf, k, gamma_next),)
                    return out

                if done0 is None:
                    done0 = (jnp.zeros((nrhs,), bool) if unbounded
                             else gamma < res_tol * res_tol)
                    iters0 = jnp.zeros((nrhs,), jnp.int32)
                if carry is not None:
                    init = (x0, carry[0], carry[1], gamma, done0,
                            iters0)
                else:
                    init = (x0, r, r, gamma, done0, iters0)
            else:
                w0 = spmv(r)
                zeros = jnp.zeros_like(b)

                def body(k, st):
                    if trace:
                        buf, st = st[-1], st[:-1]
                    (X, R, W, Pv, T, Z, gamma_prev, alpha_prev, done,
                     iters) = st
                    active = ~done
                    # the SINGLE fused B-wide allreduce per iteration
                    gamma, delta = pdot2_fused_cols(R, R, W, R)
                    Q = spmv(W)
                    beta = active_div(gamma, gamma_prev, active)
                    denom = delta - beta * active_div(gamma, alpha_prev,
                                                      active)
                    alpha = active_div(gamma, denom, active)
                    Z = colw(active, store(Q + beta[None, :] * Z), Z)
                    T = colw(active, store(W + beta[None, :] * T), T)
                    Pv = colw(active, store(R + beta[None, :] * Pv), Pv)
                    X = colw(active, store(X + alpha[None, :] * Pv), X)
                    R = colw(active, store(R - alpha[None, :] * T), R)
                    W = colw(active, store(W - alpha[None, :] * Z), W)
                    iters = iters + active.astype(jnp.int32)
                    if not unbounded:
                        done = done | (active
                                       & (gamma < res_tol * res_tol))
                    gamma_c = jnp.where(active, gamma, gamma_prev)
                    alpha_c = jnp.where(active, alpha, alpha_prev)
                    out = (X, R, W, Pv, T, Z, gamma_c, alpha_c, done,
                           iters)
                    if trace:
                        out = out + (telemetry.ring_record_batched(
                            buf, k, gamma),)
                    return out

                done0 = (jnp.zeros((nrhs,), bool) if unbounded
                         else gamma < res_tol * res_tol)
                iters0 = jnp.zeros((nrhs,), jnp.int32)
                init = (x0, r, w0, zeros, zeros, zeros, inf, inf,
                        done0, iters0)

            if trace:
                init = init + (telemetry.ring_init_batched(
                    trace, nrhs, sdt),)
            if unbounded:
                state = lax.fori_loop(0, maxits, body, init)
                k = maxits
            else:
                di = 4 if not pipelined else 8

                def cond(c):
                    k, st = c
                    return (k < maxits) & jnp.any(~st[di])

                def wbody(c):
                    k, st = c
                    return (k + 1, body(k, st))

                k, state = lax.while_loop(cond, wbody,
                                          (jnp.int32(0), init))
            tbuf = None
            if trace:
                tbuf, state = state[-1], state[:-1]
            if not pipelined:
                X, R, Pv, gamma, done, iters = state
                rnrm2 = jnp.sqrt(gamma)
            else:
                X, R = state[0], state[1]
                done, iters = state[8], state[9]
                rnrm2 = jnp.sqrt(pdot_cols(R, R))
                done = done | (rnrm2 <= res_tol)
            # unbounded: "converged" = ran the budget, but only in
            # the reported tuple -- the state_io carry keeps the
            # loop's own mask/totals so a later chunk is not frozen
            done_res = (jnp.ones((nrhs,), bool) if unbounded
                        else done)
            out = (X[None], iters, jnp.asarray(k, jnp.int32), rnrm2,
                   r0nrm2, bnrm2, x0nrm2, done_res)
            if trace:
                out = out + (tbuf,)
            if state_io and not pipelined:
                out = out + (R[None], Pv[None], gamma, done, iters)
            return out

        if single_shard and not prob.halo.has_ghosts:
            @functools.partial(jax.jit, static_argnames=("unbounded",))
            def program(la, ga, sidx, gsrc, gval, scnt, rcnt, b, x0,
                        atols, rtol, maxits, unbounded, carry=None):
                return shard_body(la, ga, sidx, gsrc, gval, scnt, rcnt,
                                  b, x0, atols, rtol, maxits,
                                  unbounded=unbounded, carry=carry)

            return program

        pspec = P(PARTS_AXIS)
        rspec = P()
        in_specs = (pspec, pspec, pspec, pspec, pspec, pspec, pspec,
                    pspec, pspec, rspec, rspec, rspec)
        out_specs = (pspec,) + (rspec,) * 7
        if trace:
            out_specs = out_specs + (rspec,)
        carry_specs = (pspec, pspec, rspec, rspec, rspec)
        if state_io:
            out_specs = out_specs + carry_specs

        @functools.partial(jax.jit, static_argnames=("unbounded",))
        def program(la, ga, sidx, gsrc, gval, scnt, rcnt, b, x0,
                    atols, rtol, maxits, unbounded, carry=None):
            extra = ()
            specs = in_specs
            if carry is not None:
                extra = (tuple(carry),)
                specs = specs + (carry_specs,)

            def smb(la, ga, sidx, gsrc, gval, scnt, rcnt, b, x0,
                    atols, rtol, maxits, *rest):
                cr = rest[0] if rest else None
                return shard_body(la, ga, sidx, gsrc, gval, scnt, rcnt,
                                  b, x0, atols, rtol, maxits,
                                  unbounded=unbounded, carry=cr)

            return jax.shard_map(
                smb, mesh=self.mesh, in_specs=specs,
                out_specs=out_specs,
                check_vma=False,
            )(la, ga, sidx, gsrc, gval, scnt, rcnt, b, x0, atols,
              rtol, maxits, *extra)

        return program

    # -- placement ---------------------------------------------------------

    def _scatter_cols(self, Xg, dtype):
        """(n, B) global columns -> (nparts, nmax_owned, B) stacked."""
        prob = self.problem
        Xg = np.asarray(Xg)
        out = np.zeros((prob.nparts, prob.nmax_owned, Xg.shape[1]),
                       dtype=np.dtype(dtype))
        for j in range(Xg.shape[1]):
            out[:, :, j] = prob.scatter(Xg[:, j], dtype=dtype)
        return out

    def _gather_cols(self, stacked):
        prob = self.problem
        st = np.asarray(stacked)
        out = np.zeros((prob.n, st.shape[2]), dtype=st.dtype)
        for j in range(st.shape[2]):
            out[:, j] = prob.gather(st[:, :, j])
        return out

    def _held_operator(self):
        """The operator leaves on the mesh, placed at the first call
        (:func:`~acg_tpu.parallel.dist.place_operator`) and held."""
        if self._op is None:
            self._op, _ = place_operator(self)
        return self._op

    def device_args(self, B_global, x0=None):
        """B's columns and a given x0 scattered and put, beside the held
        operator; the zero x0 is put per call (its width is B's)."""
        prob = self.problem
        dtype = np.dtype(prob.vdtype)
        put = functools.partial(put_global, sharding=self._sharding)
        Bg = np.asarray(B_global)
        if Bg.ndim == 1:
            Bg = Bg[:, None]
        b = put(self._scatter_cols(Bg, dtype))
        x0_st = put(self._scatter_cols(np.asarray(x0), dtype)
                    if x0 is not None
                    else np.zeros((prob.nparts, prob.nmax_owned,
                                   Bg.shape[1]), dtype=dtype))
        return (b, x0_st) + self._held_operator()

    def lower_solve(self, B_global, x0=None, criteria=None):
        """Lower (don't run) the dispatched program -- the HLO-pin
        hook asserting the collective count is invariant in B.  A
        single column delegates to the plain DistCGSolver (byte
        identity)."""
        Bg = np.asarray(B_global)
        if Bg.ndim == 1 or Bg.shape[1] == 1:
            return self._inner().lower_solve(
                Bg.reshape(Bg.shape[0]), x0=_squeeze_col(x0),
                criteria=criteria)
        crit = criteria or StoppingCriteria()
        self._check_criteria(crit)
        sdt = acc_dtype(np.dtype(self.problem.vdtype))
        dev = self.device_args(Bg, x0)
        b, x0_st, la, ga, sidx, gsrc, gval, scnt, rcnt = dev
        program = self._program_for(int(Bg.shape[1]))
        return program.lower(la, ga, sidx, gsrc, gval, scnt, rcnt, b,
                             x0_st, jnp.asarray(crit.residual_atol, sdt),
                             jnp.asarray(crit.residual_rtol, sdt),
                             jnp.int32(crit.maxits),
                             unbounded=crit.unbounded)

    def _check_criteria(self, crit):
        if crit.needs_diff:
            raise AcgError(
                ErrorCode.INVALID_VALUE,
                "the batched tiers support residual criteria only")

    # -- solve --------------------------------------------------------------

    def solve(self, B_global, x0=None,
              criteria: StoppingCriteria | None = None,
              raise_on_divergence: bool = True, warmup: int = 0,
              host_result: bool = True):
        Bg = np.asarray(B_global)
        if Bg.ndim == 1:
            Bg = Bg[:, None]
        nrhs = int(Bg.shape[1])
        crit = criteria or StoppingCriteria()
        st = self.stats
        st.criteria = crit
        if nrhs == 1:
            inner = self._inner()
            x = inner.solve(Bg[:, 0], x0=_squeeze_col(x0),
                            criteria=crit,
                            raise_on_divergence=raise_on_divergence,
                            warmup=warmup, host_result=host_result)
            self.stats = st = inner.stats
            self.last_trace = inner.last_trace
            st.batch = {"nrhs": 1, "mode": "pipelined"
                        if self.pipelined else "batched",
                        "iterations": [int(st.niterations)],
                        "rnrm2": [float(st.rnrm2)],
                        "converged": [bool(st.converged)],
                        "iterations_max": int(st.niterations),
                        "iterations_sum": int(st.niterations)}
            return (np.asarray(x).reshape(-1, 1) if host_result
                    else x)
        self._check_criteria(crit)
        if self.ckpt is not None:
            return self._solve_ckpt(Bg, x0, crit, raise_on_divergence,
                                    warmup, host_result)
        from acg_tpu import telemetry
        self._held_operator()
        t_xfer = time.perf_counter()
        with telemetry.annotate("transfer"):
            dev = self.device_args(Bg, x0)
            b, x0_st, la, ga, sidx, gsrc, gval, scnt, rcnt = dev
        telemetry.add_timing(st, "transfer",
                             time.perf_counter() - t_xfer)
        sdt = acc_dtype(np.dtype(self.problem.vdtype))
        program = self._program_for(nrhs)
        args = (la, ga, sidx, gsrc, gval, scnt, rcnt, b, x0_st,
                jnp.asarray(crit.residual_atol, sdt),
                jnp.asarray(crit.residual_rtol, sdt),
                jnp.int32(crit.maxits))
        if warmup > 0:
            t_warm = time.perf_counter()
            with telemetry.annotate("compile"):
                for _ in range(warmup):
                    jax.block_until_ready(
                        program(*args, unbounded=crit.unbounded)[0])
            telemetry.add_timing(st, "compile",
                                 time.perf_counter() - t_warm)
        t0 = time.perf_counter()
        with telemetry.annotate("solve"):
            out = program(*args, unbounded=crit.unbounded)
            jax.block_until_ready(out[0])
        t_solve = time.perf_counter() - t0
        st.tsolve += t_solve
        telemetry.add_timing(st, "solve", t_solve)
        tbuf = out[8] if self.trace else None
        self._finish_stats(out, t_solve, nrhs, tbuf)
        x_st = out[0]
        x = self._gather_cols(get_global(x_st)) if host_result else x_st
        if host_result:
            st.fexcept_arrays = [x]
        if not st.converged and raise_on_divergence:
            raise NotConvergedError(
                f"{st.niterations} iterations, "
                f"{st.batch['unconverged']} of {nrhs} RHS unconverged")
        return x

    def _finish_stats(self, out, t_solve, nrhs, tbuf=None,
                      executed=None) -> None:
        from acg_tpu import metrics, observatory, telemetry
        st = self.stats
        iters = np.asarray(out[1]).astype(int).tolist()
        k_total = int(out[2]) if executed is None else int(executed)
        rn = [float(v) for v in np.asarray(out[3])]
        conv = [bool(v) for v in np.asarray(out[7])]
        st.nsolves += 1
        st.niterations = k_total
        st.ntotaliterations += k_total
        st.r0nrm2 = float(np.max(np.asarray(out[4])))
        st.bnrm2 = float(np.max(np.asarray(out[5])))
        st.x0nrm2 = float(np.max(np.asarray(out[6])))
        st.rnrm2 = float(max(rn))
        st.dxnrm2 = float("inf")
        st.converged = all(conv)
        st.batch = {
            "nrhs": nrhs,
            "mode": "pipelined" if self.pipelined else "batched",
            "iterations": iters,
            "iterations_max": int(max(iters) if iters else 0),
            "iterations_sum": int(sum(iters)),
            "rnrm2": rn,
            "converged": conv,
            "unconverged": int(sum(1 for c in conv if not c)),
        }
        if tbuf is not None:
            st.trace = self.last_trace = \
                telemetry.BatchedConvergenceTrace.from_ring(
                    np.asarray(tbuf), k_total,
                    solver="dist-cg-batched-pipelined"
                    if self.pipelined else "dist-cg-batched")
        metrics.record_solve(t_solve, k_total, st.converged,
                             solver="dist-cg-batched")
        observatory.note_batch(nrhs, rn, conv)
        self._account_ops(st, k_total, nrhs)

    def _account_ops(self, st, k_total: int, nrhs: int) -> None:
        prob = self.problem
        dtype = np.dtype(prob.vdtype)
        n = prob.n
        st.nflops += (cg_flops_per_iteration(prob.nnz_total, n,
                                             self.pipelined) * k_total
                      + 3.0 * prob.nnz_total + 2.0 * n) * nrhs
        dbl = dtype.itemsize
        mat_dbl = np.dtype(prob.dtype).itemsize
        idx_b = 0 if prob.local.format in ("dia", "tiledia") else 4
        st.ops["gemv"].add(k_total + 1, 0.0,
                           (prob.nnz_total * (mat_dbl + idx_b)
                            + 2 * n * dbl * nrhs) * (k_total + 1))
        st.ops["dot"].add(k_total, 0.0, 2 * n * dbl * nrhs * k_total)
        st.ops["axpy"].add(3 * k_total, 0.0,
                           3 * n * dbl * nrhs * 3 * k_total)
        # the B-invariant property in the ledger: collective COUNT
        # unchanged, payload widened to B scalars
        nred = 1 if self.pipelined else 2
        st.ops["allreduce"].add(nred * k_total, 0.0,
                                8 * nrhs * nred * k_total)
        st.ops["halo"].add(k_total + 1, 0.0,
                           prob.halo_entries() * dbl * nrhs * (k_total + 1))

    # -- survivability: chunked batched dist solve --------------------------

    def _solve_ckpt(self, Bg, x0, crit, raise_on_divergence: bool,
                    warmup: int, host_result: bool):
        """Chunked batched SPMD solve with per-part per-RHS snapshot
        leaves ((nparts, pad, B) stacks + the row-permutation sidecar)
        -- a whole BATCH survives preemption, and
        ``--resume-repartition`` reassembles every column onto a
        different mesh through checkpoint.reassemble_global's batched
        path."""
        from acg_tpu import checkpoint as ckpt_mod
        from acg_tpu import metrics, observatory, telemetry
        cfg = self.ckpt
        st = self.stats
        prob = self.problem
        nrhs = int(Bg.shape[1])
        dtype = np.dtype(prob.vdtype)
        sdt = acc_dtype(dtype)
        put = functools.partial(put_global, sharding=self._sharding)
        b_crc = ckpt_mod.vector_checksum(np.asarray(Bg))
        names = ckpt_mod.batched_carry_names(False)
        dev = self.device_args(Bg, x0)
        b, x0_st, la, ga, sidx, gsrc, gval, scnt, rcnt = dev
        fixed = (la, ga, sidx, gsrc, gval, scnt, rcnt, b)
        program = self._program_for(nrhs, state_io=True)

        def run(x_cur, atol_cols, rtol, m, carry):
            # per-RHS absolute targets ride the atol argument whole:
            # resumed chunks keep every column's ORIGINAL tolerance
            # (never re-baselined against an already-small residual)
            out = program(*fixed, x_cur,
                          jnp.asarray(atol_cols, dtype=sdt),
                          jnp.asarray(rtol, dtype=sdt), jnp.int32(m),
                          unbounded=crit.unbounded, carry=carry)
            ring = out[8] if self.trace else None
            core = out[-5:]
            return out[:8], ring, core

        consumed = 0
        executed = 0
        resumed_from = None
        repartitioned = None
        carry = None
        x_cur = x0_st
        abs_tol = None
        first_r0 = None
        snap = cfg.resume
        if snap is not None:
            ckpt_mod.validate_resume(
                snap, tier=self._ckpt_tier, pipelined=False,
                precond=None, n=int(prob.n), dtype=dtype, b_crc=b_crc,
                nparts=int(prob.nparts),
                repartition=cfg.repartition, nrhs=nrhs)
            if cfg.repartition:
                snap, repartitioned = ckpt_mod.apply_repartition(
                    snap, tier=self._ckpt_tier,
                    nparts=int(prob.nparts), stats=st,
                    precond_spec=None)
                arrs_g = {}
                for nm, a in snap.arrays.items():
                    a = np.asarray(a)
                    if nm in ckpt_mod.BATCHED_COL_LEAVES or a.ndim < 2:
                        arrs_g[nm] = a
                    else:
                        arrs_g[nm] = self._scatter_cols(a, a.dtype)
                snap = ckpt_mod.SolverSnapshot(meta=snap.meta,
                                               arrays=arrs_g)
            consumed = resumed_from = snap.iteration
            sm = snap.meta
            abs_tol = np.asarray(sm["abs_tol"], dtype=np.float64)
            first_r0 = np.asarray(sm["r0nrm2"], dtype=np.float64)
            x_cur = put(np.asarray(snap.arrays["x"], dtype=dtype))
            carry = tuple(
                jnp.asarray(snap.arrays[nm]) if nm in
                ckpt_mod.BATCHED_COL_LEAVES
                else put(np.asarray(snap.arrays[nm], dtype=dtype))
                for nm in names[1:])
            metrics.record_resume()
            telemetry.record_event(
                st, "resume",
                f"resumed batched dist solve ({nrhs} RHS) at "
                f"iteration {consumed}")
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
                    res, tbuf, core = run(
                        x_cur, np.full(nrhs, crit.residual_atol),
                        crit.residual_rtol, m, carry)
                else:
                    res, tbuf, core = run(x_cur, abs_tol, 0.0, m,
                                          carry)
                jax.block_until_ready(res[0])
                k_chunk = int(res[2])
                consumed += k_chunk
                executed += k_chunk
                if first_r0 is None:
                    first_r0 = np.asarray(res[4], dtype=np.float64)
                    abs_tol = np.maximum(crit.residual_atol,
                                         crit.residual_rtol * first_r0)
                if self.trace and tbuf is not None:
                    st.trace = self.last_trace = \
                        telemetry.BatchedConvergenceTrace.from_ring(
                            np.asarray(tbuf), k_chunk,
                            solver="dist-cg-batched",
                            offset=consumed - k_chunk)
                rn = np.asarray(res[3])
                conv = np.asarray(res[7])
                worst = (float(np.max(rn[~conv])) if (~conv).any()
                         else float(np.max(rn)))
                observatory.note_chunk(self._ckpt_tier, consumed,
                                       worst,
                                       abs_tol=float(np.max(abs_tol)),
                                       rtol=crit.residual_rtol)
                observatory.note_batch(nrhs, [float(v) for v in rn],
                                       [bool(v) for v in conv])
                finished = (consumed >= crit.maxits if crit.unbounded
                            else bool(conv.all()))
                x_cur = res[0]
                carry = core
                if cfg.path is not None and not finished:
                    t_ck = time.perf_counter()
                    arrs = {"x": np.asarray(get_global(res[0]))}
                    for nm, leaf in zip(names[1:], core):
                        arrs[nm] = np.asarray(
                            get_global(leaf) if nm not in
                            ckpt_mod.BATCHED_COL_LEAVES else leaf)
                    seq += 1
                    meta = {
                        "tier": self._ckpt_tier,
                        "pipelined": False,
                        "precond": None,
                        "n": int(prob.n),
                        "nparts": int(prob.nparts),
                        "nrhs": nrhs,
                        "dtype": str(dtype),
                        "iteration": consumed,
                        "seq": seq,
                        "abs_tol": [float(v) for v in abs_tol],
                        "bnrm2": [float(v) for v in np.asarray(res[5])],
                        "x0nrm2": [float(v)
                                   for v in np.asarray(res[6])],
                        "r0nrm2": [float(v) for v in first_r0],
                        "b_crc": b_crc,
                        "trace_tail": [],
                    }
                    rp = prob.row_permutation()
                    if rp is not None:
                        arrs["_rowperm"] = rp
                        meta["part_rows"] = prob.part_rows()
                    ckpt_mod.agree_seq(seq, consumed)
                    if jax.process_index() == 0:
                        nbytes = ckpt_mod.save_snapshot(cfg.path, meta,
                                                        arrs)
                    else:
                        nbytes = 0
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
        if repartitioned is not None:
            st.ckpt["repartitioned_from"] = repartitioned
        x_st = res[0]
        x = self._gather_cols(get_global(x_st)) if host_result else x_st
        if host_result:
            st.fexcept_arrays = [x]
        if not st.converged and raise_on_divergence:
            raise NotConvergedError(
                f"{executed} iterations, "
                f"{st.batch['unconverged']} of {nrhs} RHS unconverged")
        return x
