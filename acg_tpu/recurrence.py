"""Unified recurrence builder: one place where a CG recurrence is
defined, composed with any tier's SpMV/reduction machinery.

ROADMAP item 3.  Before this module every (recurrence x tier) cell of
the program matrix was hand-built -- classic and Ghysels-Vanroose
pipelined each copied into solvers/jax_cg.py, parallel/dist.py,
solvers/batched.py, parallel/dist_batched.py -- and PRs 5-11 threaded
each cross-cutting feature (precond, health, ABFT, checkpoint carry,
telemetry ring, batching) through every copy by hand.  Here a
recurrence contributes three things:

* its **carry layout** (what rides the loop),
* its **per-iteration update** (pure math over the tier's ops),
* its **reduction schedule** (what crosses the mesh, and how often --
  the ledger entry perfmodel's comm profile reports),

and the builder composes it with a :class:`TierOps` bundle -- the
tier's SpMV (halo'd or not), its global dot / fused k-dot family
(:mod:`acg_tpu.parallel.reductions`), its psum, its storage rounding.

Recurrences:

``classic`` / ``pipelined``
    The existing hand-built programs stay dispatched (zero risk), but
    the builder can emit both, and tests/test_hlo_structure.py pins the
    builder emission BYTE-IDENTICAL (StableHLO) to the hand-built
    programs on the single-device and dist tiers -- the proof that this
    refactor is a no-op for current users and that new features can
    land in the builder instead of per-copy.

``sstep:S`` -- communication-avoiding s-step CG (arXiv:2501.03743
    lineage; Chronopoulos-Gear / Carson formulation).  Per outer block:
    build the 2s+1-column Krylov basis ``[p, th_1(A)p, ..., th_s(A)p,
    r, ..., th_{s-1}(A)r]`` (2s-1 SpMVs), reduce its Gram matrix in ONE
    allreduce, then run s CG steps entirely in coefficient space --
    mesh reduction count drops from 2/iteration (classic) to 1 per s
    iterations.  Monomial basis below S = 4, scaled-Chebyshev basis
    (power-iteration lambda_max) at S >= 4 for conditioning -- measured
    in the prototype: monomial s=8 drifts (+12% iterations on 2D
    Poisson), Chebyshev s=8 matches classic's count exactly.

``pipelined:L`` -- deep-pipelined p(l)-CG (Cornelis-Cools-Vanroose,
    arXiv:1801.04728 lineage).  Lanczos-basis CG where the basis vector
    v_m is recovered with lag l from an auxiliary basis z_j = P_l(A)
    v_{j-l} (P_l = degree-l shifted polynomial, Chebyshev shifts):
    per iteration ONE SpMV and ONE fused allreduce of the 2l+2-scalar
    z-window dot vector whose result is only consumed l iterations
    later -- l reduction latencies hidden behind l SpMVs.  The z-Gram
    is stream-Cholesky-factored on the fly; the known square-root
    breakdown of the method (the Gram loses positivity as convergence
    proceeds) exits through the breakdown flag into the standard
    restart ladder (restart from the current iterate = the literature's
    remedy; measured total iterations stay within ~1.8x classic on the
    aniso family at rtol 1e-8).  ``pipelined:1`` is p(1)-CG, NOT the
    Ghysels-Vanroose variant (different recurrence family).

Both new recurrences ride the single-device tier (and its sharded-DIA
subclass -- the SpMV is a parameter) through the programs in this
module, and the dist tier through :func:`dist_flow` composed with the
mesh machinery in parallel/dist.py.  They currently run
unpreconditioned over f32/f64 vectors: precond / bf16 / replacement /
checkpoint-carry composition is refused explicitly at solver setup
(the could-never-fire discipline) rather than silently dropped.
"""

from __future__ import annotations

import dataclasses
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np

from acg_tpu.ops.spmv import acc_dtype

# the classic/pipelined body + loop driver live in jax_cg; imported
# lazily inside functions to avoid a circular import at module load
# (jax_cg does not import recurrence at module level either -- the
# solver imports it inside _select_program)

POWER_ITERS = 24          # lambda_max power iteration length (setup)
LAM_SAFETY = 1.05         # spectral headroom on the estimated lambda_max
PL_RESTART_BUDGET = 64    # sqrt-breakdown restarts before giving up


# -- recurrence specs ------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class RecurrenceSpec:
    """Hashable static-argument recurrence selector (the PrecondSpec /
    FaultSpec design): ``kind`` in {"classic", "pipelined", "sstep",
    "pl"}; ``param`` is s (block length) or l (pipeline depth)."""

    kind: str
    param: int = 0

    def __post_init__(self):
        if self.kind not in ("classic", "pipelined", "sstep", "pl"):
            raise ValueError(f"unknown recurrence kind {self.kind!r}")
        if self.kind == "sstep" and not 2 <= self.param <= 16:
            raise ValueError(
                f"sstep:S needs 2 <= S <= 16 (got {self.param}): S = 1 "
                f"is classic CG, and the 2S+1-column basis loses full "
                f"rank in floating point well before S = 16")
        if self.kind == "pl" and not 1 <= self.param <= 4:
            raise ValueError(
                f"pipelined:L needs 1 <= L <= 4 (got {self.param}): "
                f"the z-basis Gram conditioning degrades with the "
                f"polynomial degree")

    @property
    def communication_avoiding(self) -> bool:
        return self.kind in ("sstep", "pl")

    @property
    def basis(self) -> str:
        """s-step basis selection: monomial below the measured
        stability knee, scaled Chebyshev at s >= 4."""
        return "chebyshev" if self.kind == "sstep" and self.param >= 4 \
            else "monomial"

    @property
    def needs_lam(self) -> bool:
        """Whether the program consumes the (lmin, lmax) spectral
        estimate: the Chebyshev s-step basis and every p(l) shift."""
        return self.kind == "pl" or (self.kind == "sstep"
                                     and self.basis == "chebyshev")

    def __str__(self):
        if self.kind == "sstep":
            return f"sstep:{self.param}"
        if self.kind == "pl":
            return f"pipelined:{self.param}"
        return self.kind

    def solver_name(self, tier: str = "cg") -> str:
        """Telemetry/metrics solver label.  Deliberately does NOT
        contain the substring "pipelined": health.spectrum_estimate
        keys its Lanczos (alpha, beta) re-alignment on that substring,
        and BOTH new recurrences record classic-aligned rows (s-step
        records the plain CG scalars of each inner step; p(l) records
        (q^2, 1/d, l^2, d) at solution-advance time, which satisfies
        the classic identity by construction)."""
        if self.kind == "sstep":
            return f"{tier}-sstep{self.param}"
        if self.kind == "pl":
            return f"{tier}-pl{self.param}"
        return tier


def parse_algorithm(name) -> RecurrenceSpec | None:
    """``--algorithm`` parser: classic | pipelined | sstep:S |
    pipelined:L.  None/"auto" -> None (the --solver name decides)."""
    if name is None or isinstance(name, RecurrenceSpec):
        return name
    s = str(name).strip().lower()
    if s in ("", "auto"):
        return None
    if s == "classic":
        return RecurrenceSpec("classic")
    if s == "pipelined":
        return RecurrenceSpec("pipelined")
    m = re.fullmatch(r"sstep:(\d+)", s)
    if m:
        return RecurrenceSpec("sstep", int(m.group(1)))
    m = re.fullmatch(r"pipelined:(\d+)", s)
    if m:
        return RecurrenceSpec("pl", int(m.group(1)))
    raise ValueError(
        f"unknown --algorithm {name!r}: expected classic, pipelined, "
        f"sstep:S (2 <= S <= 16) or pipelined:L (1 <= L <= 4)")


def reduction_schedule(spec: RecurrenceSpec | None, pipelined: bool,
                       precond: bool = False) -> dict:
    """The recurrence's per-iteration mesh-reduction schedule -- the
    single source the comm ledger (perfmodel via DistCGSolver.
    comm_profile) reports.  Fractional values are exact per-iteration
    averages of per-block quantities (communication-avoiding
    recurrences amortize; an int would lie)."""
    if spec is not None and spec.kind == "sstep":
        s = spec.param
        w = 2 * s + 1
        return {
            "allreduce_per_iteration": 1.0 / s,
            "allreduce_scalars": w * w,
            "spmv_per_iteration": (2 * s - 1) / s,
            "iterations_per_reduction": s,
        }
    if spec is not None and spec.kind == "pl":
        return {
            "allreduce_per_iteration": 1.0,
            "allreduce_scalars": 2 * spec.param + 2,
            "spmv_per_iteration": 1.0,
            "reduction_latency_hidden": spec.param,
        }
    if pipelined:
        return {"allreduce_per_iteration": 1.0,
                "allreduce_scalars": 3 if precond else 2,
                "spmv_per_iteration": 1.0}
    return {"allreduce_per_iteration": 2.0,
            "allreduce_scalars": 2 if precond else 1,
            "spmv_per_iteration": 1.0}


# -- tier ops --------------------------------------------------------------

@dataclasses.dataclass
class TierOps:
    """What a tier contributes to the builder: its SpMV (halo machinery
    included), its global dot, its stacked-payload reduction (the ONE
    collective of the communication-avoiding recurrences), and its
    storage rounding.  ``spmv(v, k)`` takes the iteration index so the
    deterministic fault injector can key on it."""

    spmv: callable
    dot: callable            # (a, c) -> global scalar in sdt
    psum_stack: callable     # stacked local payload -> reduced payload
    store: callable
    sdt: object

    def gram(self, V):
        """Global Gram matrix of the stacked basis V ((m, n) rows):
        one local matmul, ONE reduction."""
        local = jnp.matmul(V, V.T, preferred_element_type=self.sdt)
        return self.psum_stack(local)

    def windots(self, Z, znew):
        """The p(l) fused window reduction: (2l+2,) dots of the rolled
        z-window against the new z -- one local matvec, ONE psum."""
        local = jnp.matmul(Z.astype(self.sdt),
                           znew.astype(self.sdt),
                           preferred_element_type=self.sdt)
        return self.psum_stack(local)


def single_ops(A, kernels, dot, sdt, store, fault=None):
    """TierOps for the single-device tier (and the sharded-DIA tier,
    whose mesh-aware SpMV arrives as a callable ``kernels``).

    ``A`` may be any DeviceMatrix OR a matrix-free operator
    (acg_tpu.ops.operator): ``_spmv_fn`` routes through the ops.spmv
    protocol dispatch, so this is the ONE SpMV source through which
    every builder recurrence -- classic, pipelined, sstep:S,
    pipelined:L -- inherits matrix-free operation (the s-step basis
    products and the p(l) auxiliary-basis SpMVs are all ``ops.spmv``
    calls; nothing below ever touches stored planes)."""
    from acg_tpu.solvers.jax_cg import _spmv_fn
    spmv_ = _spmv_fn(kernels)

    def spmv(v, k=None):
        y = spmv_(A, v)
        if fault is not None and k is not None:
            y = fault.apply_spmv(y, k)
        return y

    return TierOps(spmv=spmv, dot=dot, psum_stack=lambda s: s,
                   store=store, sdt=sdt)


# -- s-step CG -------------------------------------------------------------

def sstep_basis_matrix(s: int, basis: str, lam, sdt):
    """(s+1, s+1) change-of-basis B with A V[:, j] = V B[:, j] for
    j < s (the last column is never consumed: coefficient vectors keep
    total degree <= s inside a block)."""
    if basis == "monomial":
        B = np.zeros((s + 1, s + 1))
        for j in range(s):
            B[j + 1, j] = 1.0
        return jnp.asarray(B, sdt)
    lmin, lmax = lam
    d = (lmax + lmin) / 2.0
    c = (lmax - lmin) / 2.0
    B = jnp.zeros((s + 1, s + 1), sdt)
    for j in range(s):
        if j == 0:
            B = B.at[0, 0].set(d)
            B = B.at[1, 0].set(c)
        else:
            B = B.at[j - 1, j].set(c / 2.0)
            B = B.at[j, j].set(d)
            B = B.at[j + 1, j].set(c / 2.0)
    return B


def sstep_combined_bmat(s: int, basis: str, lam, sdt):
    """(2s+1, 2s+1) block-diagonal change-of-basis for the combined
    [P-basis | R-basis] stack, top-degree columns zeroed."""
    m = 2 * s + 1
    Bp = sstep_basis_matrix(s, basis, lam, sdt)
    B = jnp.zeros((m, m), sdt)
    B = B.at[:s + 1, :s + 1].set(Bp)
    if s > 1:
        Br = sstep_basis_matrix(s - 1, basis, lam, sdt)
        B = B.at[s + 1:, s + 1:].set(Br)
    B = B.at[:, s].set(0.0)
    B = B.at[:, m - 1].set(0.0)
    return B


def sstep_build_basis(ops: TierOps, v, deg: int, basis: str, lam, k):
    """The matrix-powers stack [v, th_1(A)v, ..., th_deg(A)v] as a
    (deg+1, n) array -- deg SpMVs through the tier's own machinery
    (halo exchanges and all), zero reductions."""
    rows = [v]
    if basis == "monomial":
        for j in range(deg):
            rows.append(ops.store(ops.spmv(rows[-1], k)))
        return jnp.stack(rows)
    lmin, lmax = lam
    d = (lmax + lmin) / 2.0
    c = (lmax - lmin) / 2.0
    for j in range(deg):
        w = ops.spmv(rows[-1], k) - d * rows[-1]
        if j == 0:
            rows.append(ops.store(w / c))
        else:
            rows.append(ops.store(2.0 * w / c - rows[-2]))
    return jnp.stack(rows)


def make_sstep_block(ops: TierOps, s: int, basis: str, lam, res_tol,
                     maxits, fault=None, trace: int = 0,
                     progress: int = 0, health=None, what: str = "cg",
                     leader=None, k_offset=None):
    """The s-step outer-block body, tier-agnostic.

    Carry: ``(x, r, p, gamma, k, bad)`` (+ audit vector, + telemetry
    ring -- the jax_cg tail discipline: feature leaves ride LAST).
    ``gamma`` is the coefficient-space ||r||^2 carried across blocks --
    the convergence test's scalar, one reduction-free byproduct of the
    Gram.  Returns ``(body, tails)`` where tails counts the armed
    feature leaves."""
    sdt = ops.sdt
    tol2 = res_tol * res_tol
    Bmat = sstep_combined_bmat(s, basis, lam, sdt)
    w = 2 * s + 1
    if trace or progress:
        from acg_tpu import telemetry
    if health is not None:
        from acg_tpu import health as _health

    def body(state):
        if trace:
            buf, state = state[-1], state[:-1]
        if health is not None:
            aud, state = state[-1], state[:-1]
        x, r, p, gamma, k, bad = state
        # -- basis: 2s-1 SpMVs, zero reductions ----------------------
        Vp = sstep_build_basis(ops, p, s, basis, lam, k)
        if s > 1:
            Vr = sstep_build_basis(ops, r, s - 1, basis, lam, k)
            V = jnp.concatenate([Vp, Vr], axis=0)
        else:
            V = jnp.concatenate([Vp, r[None]], axis=0)
        # -- the block's ONE reduction -------------------------------
        G = ops.gram(V)
        # -- s CG steps in coefficient space (unrolled: s is static) --
        pc = jnp.zeros((w,), sdt).at[0].set(1.0)
        rc = jnp.zeros((w,), sdt).at[s + 1].set(1.0)
        xc = jnp.zeros((w,), sdt)
        # the coefficient-space gamma of the FRESH basis: rc' G rc is
        # the (s+1, s+1) Gram entry -- re-anchors the carried scalar
        # against basis-change drift each block
        gamma_blk = G[s + 1, s + 1]
        nsteps = jnp.int32(0)
        for j in range(s):
            wc = Bmat @ pc
            Gw = G @ wc
            denom = pc @ Gw
            if fault is not None:
                denom = fault.apply_dot(denom, k + j)
            bad_j = ((~jnp.isfinite(denom)) | (~jnp.isfinite(gamma_blk))
                     | ((denom <= 0) & (gamma_blk > 0)))
            step = ((~bad) & (~bad_j) & (gamma_blk >= tol2)
                    & (k + jnp.int32(j) < maxits))
            bad = bad | (bad_j & (gamma_blk >= tol2)
                         & (k + jnp.int32(j) < maxits))
            alpha = jnp.where(step, gamma_blk
                              / jnp.where(denom == 0, 1.0, denom), 0.0)
            xc = xc + alpha * pc
            rc_new = rc - alpha * wc
            Gr = G @ rc_new
            gamma_next = rc_new @ Gr
            beta = jnp.where(step, gamma_next
                             / jnp.where(gamma_blk == 0, 1.0,
                                         gamma_blk), 0.0)
            pc = jnp.where(step, rc_new + beta * pc, pc)
            rc = jnp.where(step, rc_new, rc)
            if trace:
                buf = jnp.where(
                    step,
                    telemetry.ring_record(buf, k + jnp.int32(j),
                                          gamma_next, alpha, beta,
                                          denom),
                    buf)
            gamma_blk = jnp.where(step, gamma_next, gamma_blk)
            nsteps = nsteps + step.astype(jnp.int32)
        # -- map back: 3 small GEMVs, zero reductions ----------------
        x = ops.store(x + xc.astype(sdt) @ V.astype(sdt))
        r = ops.store(rc.astype(sdt) @ V.astype(sdt))
        p = ops.store(pc.astype(sdt) @ V.astype(sdt))
        k_new = k + nsteps
        out_gamma = gamma_blk
        if health is not None:
            k0 = k if k_offset is None else k + k_offset
            k1 = k_new if k_offset is None else k_new + k_offset

            def compute_gap():
                bb = health_ctx["b"]
                return _health.relative_gap(bb - ops.spmv(x, None), r,
                                            ops.dot, health_ctx["bnrm2"],
                                            sdt)

            aud, fire = audit_update_crossing(
                aud, health, k0, k1, compute_gap)
            aud = _health.stall_update(aud, health, out_gamma < gamma)
            bad = bad | _health.trip(aud, health)
        if progress:
            telemetry.heartbeat(k_new, out_gamma, progress,
                                leader=leader, what=what)
        out = (x, r, p, out_gamma, k_new, bad)
        if health is not None:
            out = out + (aud,)
        if trace:
            out = out + (buf,)
        return out

    # the audit closure needs b/bnrm2 which only the caller has; it
    # fills this context dict before running the loop
    health_ctx: dict = {}
    body.health_ctx = health_ctx
    ntails = (1 if trace else 0) + (1 if health is not None else 0)
    return body, ntails


def audit_update_crossing(aud, spec, k0, k1, compute_gap):
    """Block-granular twin of health.audit_update: fire the audit when
    the cadence boundary was crossed anywhere in [k0, k1) -- the s-step
    tier advances s trajectory iterations per block, so equality
    against the cadence would skip audits whenever ``every`` is not a
    multiple of s."""
    every = jnp.int32(spec.every if spec.every else 1)
    fire = (spec.every > 0) & ((k1 // every) > (k0 // every))

    def do(a):
        gap = compute_gap()
        worst = jnp.maximum(a[1], gap)
        return jnp.stack([gap, worst, a[2] + 1.0, a[3]]).astype(a.dtype)

    new = jax.lax.cond(fire, do, lambda a: a, aud)
    return new, fire


# -- p(l)-CG ---------------------------------------------------------------

def pl_shifts(l: int, lam, sdt):
    """Chebyshev points of [lmin, lmax] -- the polynomial shifts
    sigma_0..sigma_{l-1} of the auxiliary basis z = P_l(A) v."""
    lmin, lmax = lam
    d = (lmax + lmin) / 2.0
    c = (lmax - lmin) / 2.0
    cosv = np.cos((2 * np.arange(l) + 1) * np.pi / (2 * l))
    return (d + c * jnp.asarray(cosv, sdt)).astype(sdt)


def make_pl_step(ops: TierOps, l: int, sigma, res_tol, maxits,
                 fault=None, trace: int = 0, progress: int = 0,
                 what: str = "cg", leader=None):
    """The p(l)-CG iteration body, tier-agnostic.

    Carry (all window buffers rolled newest-last; static ``l`` makes
    every index a Python constant):

    ``j``        auxiliary-basis iteration counter
    ``adv``      trajectory iterations (solution advances) -- the
                 reported niterations
    ``x, q, dprev, ptilde``  the LDL^T solution recurrence (d = 1/alpha)
    ``Z (2l+2, n)``  auxiliary basis window  z_{j-2l-1}..z_j
    ``V (2l, n)``    recovered Lanczos window v_{m-2l}..v_{m-1}
    ``zzq (l, 2l+2)``  the reduction delay line: window dots initiated
                 at iteration t are consumed at t+l (the l hidden
                 reduction latencies)
    ``gb (2l+1, 2l+1)``  banded columns of the stream-Cholesky factor
                 of the z-Gram (g[c][rr] = (v_{col-2l+rr}, z_col))
    ``gammas (l+2,), deltas (l+1,)``  Lanczos T windows
    ``conv, bad``  convergence / square-root-breakdown flags
    (+ telemetry ring, LAST)."""
    sdt = ops.sdt
    tol2 = res_tol * res_tol
    W = 2 * l + 2
    if trace or progress:
        from acg_tpu import telemetry

    def safe(x):
        return jnp.where(x == 0, jnp.asarray(1.0, sdt), x)

    def body(state):
        if trace:
            buf, state = state[-1], state[:-1]
        (j, adv, x, q, dprev, ptilde, Z, V, zzq, gb, gammas, deltas,
         conv, bad) = state
        m = j + 1 - l
        have_m = m >= 0
        y = zzq[0]
        # -- stream-Cholesky: g column m from the delayed z-dots ------
        newcol = []
        for rr in range(2 * l):
            r_abs = m - 2 * l + rr
            valid = have_m & (r_abs >= 0)
            acc = y[rr + 1]
            for tt in range(rr):
                acc = acc - gb[rr + 1][tt - rr + 2 * l] * newcol[tt]
            den = gb[rr + 1][2 * l]
            newcol.append(jnp.where(valid, acc / safe(den), 0.0))
        diag2 = y[2 * l + 1]
        for rr in range(2 * l):
            diag2 = diag2 - newcol[rr] * newcol[rr]
        bad_sqrt = have_m & ((diag2 <= 0) | (~jnp.isfinite(diag2)))
        gmm = jnp.sqrt(jnp.where(diag2 > 0, diag2, 1.0))
        newcol.append(gmm)
        # -- Lanczos T entries at index m-1 ---------------------------
        # window invariants AT ITERATION START (rolled last iteration):
        #   gammas[i] = gamma_{m-3-l+i}  -> gamma_{m-2}   = gammas[l+1]
        #                                   gamma_{m-1-l} = gammas[2]
        #   deltas[i] = delta_{m-2-l+i}  -> delta_{m-1-l} = deltas[1]
        startup = (m - 1) < l
        gm1m1 = safe(gb[2 * l][2 * l])
        gm2m1 = gb[2 * l][2 * l - 1]
        gm1m = newcol[2 * l - 1]
        gamma_m1 = jnp.where(startup, gmm / gm1m1,
                             gammas[2] * gmm / gm1m1)
        sig_m1 = sigma[jnp.clip(m - 1, 0, l - 1)]
        delta_start = sig_m1 + (gm1m - gammas[l + 1] * gm2m1) / gm1m1
        delta_main = ((gammas[2] * gm1m + deltas[1] * gm1m1
                       - gammas[l + 1] * gm2m1) / gm1m1)
        delta_m1 = jnp.where(startup, delta_start, delta_main)
        # -- recover v_m ---------------------------------------------
        zm = Z[l + 2]
        acc_v = zm.astype(sdt)
        for rr in range(2 * l):
            acc_v = acc_v - newcol[rr] * V[rr].astype(sdt)
        vm = ops.store(acc_v / safe(gmm))
        vmm = V[2 * l - 1]
        # -- advance the solution to trajectory index mm = m-1 --------
        is0 = (m - 1) == 0
        lprev = gammas[l + 1] / safe(dprev)
        dd = jnp.where(is0, delta_m1, delta_m1 - gammas[l + 1] * lprev)
        pt_new = jnp.where(is0, vmm.astype(sdt),
                           vmm.astype(sdt) - lprev * ptilde)
        do_adv = (have_m & (m >= 1) & (~bad_sqrt) & (~conv) & (~bad)
                  & (adv < maxits))
        x = jnp.where(do_adv, x + (q / safe(dd)) * pt_new, x)
        q_next = -(gamma_m1 / safe(dd)) * q
        if trace:
            alpha_rec = 1.0 / safe(dd)
            beta_rec = (q_next / safe(q)) ** 2
            buf = jnp.where(
                do_adv,
                telemetry.ring_record(buf, adv, q_next * q_next,
                                      alpha_rec, beta_rec, dd),
                buf)
        conv = conv | (do_adv & (q_next * q_next < tol2))
        bad = bad | bad_sqrt
        adv = adv + do_adv.astype(jnp.int32)
        q = jnp.where(do_adv, q_next, q)
        dprev = jnp.where(do_adv, dd, dprev)
        ptilde = jnp.where(do_adv, pt_new, ptilde)
        if progress:
            telemetry.heartbeat(adv, q * q, progress, leader=leader,
                                what=what)
        # -- build z_{j+1} (the iteration's ONE SpMV) -----------------
        zj = Z[2 * l + 1]
        Az = ops.spmv(zj, j)
        if fault is not None:
            pass  # spmv fault applied inside ops.spmv via k=j
        sig_j = sigma[jnp.clip(j, 0, l - 1)]
        z_start = Az.astype(sdt) - sig_j * zj.astype(sdt)
        z_main = (Az.astype(sdt) - delta_m1 * zj.astype(sdt)
                  - gammas[l + 1] * Z[2 * l].astype(sdt)) / safe(gamma_m1)
        znew = ops.store(jnp.where(j < l, z_start, z_main))
        # -- initiate the fused window reduction (ONE allreduce) ------
        Zr = jnp.roll(Z, -1, axis=0).at[2 * l + 1].set(znew)
        y_new = ops.windots(Zr, znew)
        zzq = jnp.roll(zzq, -1, axis=0).at[l - 1].set(y_new)
        # -- roll the g/T/V windows (only when column m materialized) --
        gb_new = jnp.roll(gb, -1, axis=0).at[2 * l].set(
            jnp.stack(newcol))
        gb = jnp.where(have_m, gb_new, gb)
        V_new = jnp.roll(V, -1, axis=0).at[2 * l - 1].set(vm)
        V = jnp.where(have_m, V_new, V)
        roll_T = have_m & (m >= 1)
        gammas = jnp.where(roll_T,
                           jnp.roll(gammas, -1).at[l + 1].set(gamma_m1),
                           gammas)
        deltas = jnp.where(roll_T,
                           jnp.roll(deltas, -1).at[l].set(delta_m1),
                           deltas)
        out = (j + 1, adv, x, q, dprev, ptilde, Zr, V, zzq, gb, gammas,
               deltas, conv, bad)
        if trace:
            out = out + (buf,)
        return out

    return body


def pl_init(l: int, n: int, x0, eta, dtype, sdt, z0):
    """Initial p(l) carry (minus the ring tail): see make_pl_step."""
    W = 2 * l + 2
    Z = jnp.zeros((W, n), dtype).at[2 * l + 1].set(z0)
    V = jnp.zeros((2 * l, n), dtype)
    zzq = jnp.zeros((l, W), sdt).at[l - 1, 2 * l + 1].set(1.0)
    gb = jnp.zeros((2 * l + 1, 2 * l + 1), sdt)
    gammas = jnp.zeros((l + 2,), sdt)
    deltas = jnp.zeros((l + 1,), sdt)
    return (jnp.int32(0), jnp.int32(0), x0.astype(sdt), eta,
            jnp.asarray(1.0, sdt), jnp.zeros((n,), sdt), Z, V, zzq, gb,
            gammas, deltas)


# -- the recurrence loops, tier-agnostic ----------------------------------

def run_sstep_loop(ops: TierOps, s: int, basis: str, lam, b, x0, r,
                   gamma, res_tol, maxits, unbounded: bool, fault=None,
                   trace: int = 0, progress: int = 0, health=None,
                   what: str = "cg-sstep", leader=None, bnrm2=None,
                   k_offset=None, p=None, state_io: bool = False):
    """The s-step outer loop, shared verbatim by every tier: the tier
    contributes ``ops`` (its SpMV/halo machinery, its global dot, its
    ONE stacked reduction); the recurrence contributes everything else.
    Returns ``(x, k, gamma_f, bad, done, extras)`` with extras =
    (ring?, audit?) in the jax_cg tail order.

    ``p``/``state_io`` (the survivability tier): at a BLOCK BOUNDARY
    the s-step state is exactly classic-shaped -- the basis and Gram
    products are rebuilt from (r, p) at every block start -- so a
    checkpoint carry is just ``(r, p, gamma)``.  A non-None ``p``
    re-enters mid-trajectory (``r``/``gamma`` then come from the
    snapshot too; ``p = r`` is the fresh-start value), and
    ``state_io`` appends the final ``(r, p, gamma)`` to the return."""
    sdt = ops.sdt
    tol2 = res_tol * res_tol
    if health is not None:
        from acg_tpu import health as _health
    body, ntails = make_sstep_block(
        ops, s, basis, lam, res_tol, maxits, fault=fault, trace=trace,
        progress=progress, health=health, what=what, leader=leader,
        k_offset=k_offset)
    body.health_ctx.update({"b": b, "bnrm2": bnrm2})
    init = (x0, r, r if p is None else p, gamma, jnp.int32(0),
            jnp.asarray(False))
    if health is not None:
        init = init + (_health.audit_init(sdt, health),)
    if trace:
        from acg_tpu import telemetry
        init = init + (telemetry.ring_init(trace, sdt),)

    def cond(state):
        g, k, bad = state[3], state[4], state[5]
        go = (k < maxits) & (~bad)
        if not unbounded:
            go = go & (g >= tol2)
        return go

    state = jax.lax.while_loop(cond, lambda st: body(st), init)
    gamma_f, k, bad = state[3], state[4], state[5]
    done = (~bad) if unbounded else (gamma_f < tol2)
    extras = ()
    if trace:
        extras = extras + (state[-1],)
    if health is not None:
        extras = extras + (state[-2] if trace else state[-1],)
    if state_io:
        return state[0], k, gamma_f, bad, done, extras, \
            (state[1], state[2], gamma_f)
    return state[0], k, gamma_f, bad, done, extras


def run_pl_loop(ops: TierOps, l: int, lam, x0, z0, eta, eta2, res_tol,
                maxits, unbounded: bool, fault=None, trace: int = 0,
                progress: int = 0, what: str = "cg-pl", leader=None,
                carry=None, state_io: bool = False):
    """The p(l) iteration loop, shared verbatim by every tier.  Returns
    ``(x, adv, q, conv, bad, extras)``.

    ``carry``/``state_io`` (the survivability tier): the deep-pipeline
    recurrence has no classic-shaped boundary -- its whole working set
    (z-window ``Z``/``V``, Gram column ``zzq``/``gb``, scalar histories
    ``gammas``/``deltas``, pipeline counters ``j``/``adv``) must
    round-trip through a snapshot.  ``carry`` re-enters from the full
    11-leaf state with ABSOLUTE ``j``/``adv`` (``maxits`` must then be
    absolute too), ``state_io`` appends that state to the return."""
    sdt = ops.sdt
    tol2 = res_tol * res_tol
    n = x0.shape[0]
    sigma = pl_shifts(l, lam, sdt)
    body = make_pl_step(ops, l, sigma, res_tol, maxits, fault=fault,
                        trace=trace, progress=progress, what=what,
                        leader=leader)
    if carry is None:
        init = pl_init(l, n, x0, eta, x0.dtype, sdt, z0)
        init = init + (eta2 < tol2, jnp.asarray(False))
    else:
        (q, dprev, ptilde, Z, V, zzq, gb, gammas, deltas, j, adv) = carry
        init = (j.astype(jnp.int32), adv.astype(jnp.int32),
                x0.astype(sdt), q, dprev, ptilde, Z, V, zzq, gb,
                gammas, deltas, jnp.asarray(False), jnp.asarray(False))
    if trace:
        from acg_tpu import telemetry
        init = init + (telemetry.ring_init(trace, sdt),)
    jcap = maxits + jnp.int32(2 * l + 2)

    def cond(state):
        j, adv, conv, bad = state[0], state[1], state[12], state[13]
        go = (~bad) & (adv < maxits) & (j < jcap)
        if not unbounded:
            go = go & (~conv)
        return go

    state = jax.lax.while_loop(cond, lambda st: body(st), init)
    extras = (state[-1],) if trace else ()
    out = (state[2], state[1], state[3], state[12], state[13], extras)
    if state_io:
        out = out + ((state[3], state[4], state[5], state[6], state[7],
                      state[8], state[9], state[10], state[11],
                      state[0], state[1]),)
    return out


# -- single-device programs ------------------------------------------------

@functools.partial(jax.jit,
                   static_argnames=("s", "basis", "unbounded", "kernels",
                                    "fault", "trace", "progress",
                                    "health", "state_io"))
def _cg_sstep_program(A, b, x0, res_atol, res_rtol, lam, maxits,
                      s: int, basis: str, unbounded: bool,
                      kernels: str = "xla", fault=None, trace: int = 0,
                      progress: int = 0, health=None,
                      state_io: bool = False, carry=None, k_offset=None):
    """Whole s-step-CG solve as one XLA program (single-device tier;
    the sharded-DIA tier rides through the callable ``kernels`` SpMV
    exactly like _cg_program).

    ``carry``/``state_io``/``k_offset`` are the checkpoint hooks: a
    carry re-enters from a block-boundary ``(r, p, gamma)`` snapshot
    (the setup SpMV is skipped; ``r0nrm2`` from the carried gamma is
    only meaningful on the first chunk, which never carries), state_io
    appends the final ``(r, p, gamma)`` to the return, and k_offset
    keeps the health audit cadence in the ABSOLUTE iteration frame."""
    from acg_tpu.solvers.jax_cg import CGResult, _scalar_setup
    dtype = b.dtype
    dot, sdt = _scalar_setup(dtype, False)
    store = (lambda v: v.astype(dtype)) if sdt != dtype else (lambda v: v)
    ops = single_ops(A, kernels, dot, sdt, store, fault=fault)
    bnrm2 = jnp.sqrt(dot(b, b))
    x0nrm2 = jnp.sqrt(dot(x0, x0))
    if carry is not None:
        r, p, gamma = carry
    else:
        r = b - ops.spmv(x0, None)
        p = None
        gamma = dot(r, r)
    r0nrm2 = jnp.sqrt(gamma)
    res_tol = jnp.maximum(res_atol, res_rtol * r0nrm2)
    inf = jnp.asarray(jnp.inf, sdt)
    lam = (jnp.asarray(lam[0], sdt), jnp.asarray(lam[1], sdt))
    out = run_sstep_loop(
        ops, s, basis, lam, b, x0, r, gamma, res_tol, maxits,
        unbounded, fault=fault, trace=trace, progress=progress,
        health=health, bnrm2=bnrm2, k_offset=k_offset, p=p,
        state_io=state_io)
    x, k, gamma_f, bad, done, extras = out[:6]
    breakdown = bad & ~done
    res = CGResult(x=x, niterations=k,
                   rnrm2=jnp.sqrt(jnp.maximum(gamma_f, 0.0)),
                   r0nrm2=r0nrm2, bnrm2=bnrm2, x0nrm2=x0nrm2,
                   dxnrm2=inf, converged=done, breakdown=breakdown)
    tail = extras + (out[6],) if state_io else extras
    return (res,) + tail if tail else res


@functools.partial(jax.jit,
                   static_argnames=("l", "unbounded", "kernels", "fault",
                                    "trace", "progress", "state_io"))
def _cg_pl_program(A, b, x0, res_atol, res_rtol, lam, maxits, l: int,
                   unbounded: bool, kernels: str = "xla", fault=None,
                   trace: int = 0, progress: int = 0,
                   state_io: bool = False, carry=None, k_offset=None):
    """Whole p(l)-CG solve as one XLA program (single-device tier).

    The checkpoint hooks carry the FULL deep-pipeline working set (see
    run_pl_loop): ``carry`` re-enters from a snapshot whose ``j``/
    ``adv`` counters are ABSOLUTE -- the caller must then pass an
    absolute ``maxits`` (consumed + chunk) and read ``niterations`` as
    an absolute count.  ``k_offset`` is accepted for signature parity
    with the s-step program and ignored (the pipeline's own ``j``
    counter is already absolute)."""
    from acg_tpu.solvers.jax_cg import CGResult, _scalar_setup
    dtype = b.dtype
    dot, sdt = _scalar_setup(dtype, False)
    store = (lambda v: v.astype(dtype)) if sdt != dtype else (lambda v: v)
    ops = single_ops(A, kernels, dot, sdt, store, fault=fault)
    bnrm2 = jnp.sqrt(dot(b, b))
    x0nrm2 = jnp.sqrt(dot(x0, x0))
    if carry is not None:
        # mid-pipeline re-entry: the recurrence residual lives in the
        # carried q; no setup SpMV, and r0nrm2 is only cosmetic here
        # (later chunks run with rtol=0 against the first chunk's
        # absolute target)
        eta = eta2 = z0 = None
        r0nrm2 = jnp.abs(carry[0])
    else:
        r0 = b - ops.spmv(x0, None)
        eta2 = dot(r0, r0)
        eta = jnp.sqrt(eta2)
        r0nrm2 = eta
    res_tol = jnp.maximum(res_atol, res_rtol * r0nrm2)
    inf = jnp.asarray(jnp.inf, sdt)
    lam = (jnp.asarray(lam[0], sdt), jnp.asarray(lam[1], sdt))
    if carry is None:
        z0 = store(r0 / jnp.where(eta == 0, 1.0, eta))
    out = run_pl_loop(
        ops, l, lam, x0, z0, eta, eta2, res_tol, maxits, unbounded,
        fault=fault, trace=trace, progress=progress, carry=carry,
        state_io=state_io)
    x, adv, q, conv, bad, extras = out[:6]
    done = (~bad) if unbounded else conv
    breakdown = bad & ~done
    res = CGResult(x=x.astype(dtype), niterations=adv,
                   rnrm2=jnp.abs(q), r0nrm2=r0nrm2, bnrm2=bnrm2,
                   x0nrm2=x0nrm2, dxnrm2=inf, converged=done,
                   breakdown=breakdown)
    tail = extras + (out[6],) if state_io else extras
    return (res,) + tail if tail else res


@functools.partial(jax.jit, static_argnames=("kernels", "iters"))
def _lmax_program(A, v0, kernels: str = "xla", iters: int = POWER_ITERS):
    """Power-iteration lambda_max through the tier's own SpMV -- the
    communication-avoiding recurrences' spectral estimate (one compile
    at setup; the dist tier reuses DistCGSolver._power_lmax)."""
    from acg_tpu.solvers.jax_cg import _spmv_fn
    spmv_ = _spmv_fn(kernels)
    sdt = acc_dtype(v0.dtype)

    def ldot(a, c):
        return jnp.dot(a, c, preferred_element_type=sdt)

    def it(_, v):
        w = spmv_(A, v)
        return (w.astype(sdt)
                / jnp.sqrt(ldot(w, w))).astype(v.dtype)

    v = jax.lax.fori_loop(0, iters, it, v0)
    w = spmv_(A, v)
    return ldot(v, w) / ldot(v, v)


def estimate_lam(A, n: int, dtype, kernels: str = "xla"):
    """(lmin, lmax) host floats for the basis/shift interval: power
    iteration with spectral headroom, lmin = 0 (SPD; the Chebyshev
    interval does not need the low end resolved)."""
    rng = np.random.default_rng(0)
    v0 = jnp.asarray(rng.standard_normal(n), dtype=dtype)
    lmax = float(_lmax_program(A, v0, kernels=kernels)) * LAM_SAFETY
    return (0.0, lmax)


# -- builder emission of the existing recurrences (byte-identity) ----------
#
# The classic and Ghysels-Vanroose recurrences as BUILDER bodies over
# TierOps -- the same carry layout / update / reduction schedule the
# hand-built programs in solvers/jax_cg.py and parallel/dist.py trace.
# tests/test_hlo_structure.py pins the builder emission byte-identical
# (StableHLO) to the hand-built programs on both tiers: the proof that
# the builder is a faithful home for the recurrence matrix, and that
# flipping the dispatch (or landing a new cross-cutting feature in the
# builder instead of per-copy) is a no-op for current users.

def classic_recurrence(ops: TierOps):
    """Classic CG as a builder body: carry ``(x, r, p, gamma)``, two
    global dots per iteration ((p, t) and (r, r))."""
    def body(k, state):
        x, r, p, gamma = state
        t = ops.spmv(p, k)
        pdott = ops.dot(p, t)
        alpha = gamma / pdott
        x = ops.store(x + alpha * p)
        r = ops.store(r - alpha * t)
        gamma_next = ops.dot(r, r)
        beta = gamma_next / gamma
        p_next = ops.store(r + beta * p)
        return (x, r, p_next, gamma_next)
    return body


def pipelined_recurrence(ops: TierOps, dot2):
    """Ghysels-Vanroose pipelined CG as a builder body: carry
    ``(x, r, w, p, t, z, gamma_prev, alpha_prev)``, ONE fused 2-scalar
    reduction per iteration (``dot2`` -- two plain dots on a single
    device, pdot2_fused on the mesh)."""
    def body(k, state):
        x, r, w, p, t, z, gamma_prev, alpha_prev = state
        gamma, delta = dot2(r, r, w, r)
        q = ops.spmv(w, k)
        beta = gamma / gamma_prev
        denom = delta - beta * (gamma / alpha_prev)
        alpha = gamma / denom
        z = ops.store(q + beta * z)
        t = ops.store(w + beta * t)
        p = ops.store(r + beta * p)
        x = ops.store(x + alpha * p)
        r = ops.store(r - alpha * t)
        w = ops.store(w - alpha * z)
        return (x, r, w, p, t, z, gamma, alpha)
    return body


@functools.partial(jax.jit,
                   static_argnames=("unbounded", "needs_diff",
                                    "pipelined", "kernels"))
def _builder_cg_program(A, b, x0, res_atol, res_rtol, diff_atol,
                        diff_rtol, maxits, unbounded: bool,
                        needs_diff: bool, pipelined: bool = False,
                        kernels: str = "xla"):
    """The builder's single-device emission of classic/GV-pipelined CG
    (base configuration): byte-identity with jax_cg._cg_program /
    _cg_pipelined_program is pinned in tests/test_hlo_structure.py."""
    from acg_tpu.solvers.jax_cg import CGResult, _iterate, _scalar_setup
    assert not needs_diff
    dtype = b.dtype
    dot, sdt = _scalar_setup(dtype, False)
    store = (lambda v: v.astype(dtype)) if sdt != dtype else (lambda v: v)
    ops = single_ops(A, kernels, dot, sdt, store)

    def dot2(a1, c1, a2, c2):
        return dot(a1, c1), dot(a2, c2)

    bnrm2 = jnp.sqrt(dot(b, b))
    x0nrm2 = jnp.sqrt(dot(x0, x0))
    if pipelined:
        r = b - ops.spmv(x0, None)
        w = ops.spmv(r, None)
        r0nrm2 = jnp.sqrt(dot(r, r))
    else:
        r = b - ops.spmv(x0, None)
        p = r
        gamma = dot(r, r)
        r0nrm2 = jnp.sqrt(gamma)
    res_tol = jnp.maximum(res_atol, res_rtol * r0nrm2)
    diff_tol = jnp.maximum(diff_atol, diff_rtol * x0nrm2)
    inf = jnp.asarray(jnp.inf, sdt)
    if pipelined:
        zeros = jnp.zeros_like(b)
        body = pipelined_recurrence(ops, dot2)
        init_state = (x0, r, w, zeros, zeros, zeros, inf, inf)
        init_gamma = r0nrm2 * r0nrm2
        k, state, done = _iterate(
            body, init_state, lambda s: s[6], maxits, res_tol,
            diff_tol, lambda s: inf, unbounded,
            init_gamma=init_gamma, bad_of=None)
        x, r = state[0], state[1]
        dxsqr = inf
        breakdown = jnp.asarray(False)
        rnrm2 = jnp.sqrt(dot(r, r))
        done = jnp.logical_or(done, rnrm2 <= res_tol)
        breakdown = breakdown & ~done
        return CGResult(x=x, niterations=k, rnrm2=rnrm2,
                        r0nrm2=r0nrm2, bnrm2=bnrm2, x0nrm2=x0nrm2,
                        dxnrm2=jnp.sqrt(dxsqr), converged=done,
                        breakdown=breakdown)
    body = classic_recurrence(ops)
    init_state = (x0, r, p, gamma)
    k, state, done = _iterate(
        body, init_state, lambda s: s[3], maxits, res_tol, diff_tol,
        lambda s: inf, unbounded, bad_of=None)
    x, r, p, gamma = state[:4]
    rnrm2sqr = gamma
    dxsqr = inf
    breakdown = jnp.asarray(False)
    breakdown = breakdown & ~done
    return CGResult(x=x, niterations=k, rnrm2=jnp.sqrt(rnrm2sqr),
                    r0nrm2=r0nrm2, bnrm2=bnrm2, x0nrm2=x0nrm2,
                    dxnrm2=jnp.sqrt(dxsqr), converged=done,
                    breakdown=breakdown)


def build_dist_program(solver):
    """The builder's dist-tier emission of classic/GV-pipelined CG
    (base configuration), composed with the solver's OWN machinery
    (halo'd SpMV, psum, fused reductions, mesh specs): byte-identity
    with DistCGSolver._compile()'s hand-built program is pinned in
    tests/test_hlo_structure.py.

    ``kernels='fused'`` swaps in the interior|border OVERLAPPED SpMV
    (``make_dist_spmv_overlapped``: halo exchange issued first,
    interior rows computed while it is in flight, border rows finished
    after) -- this IS the dispatched program of the distributed fused
    tier, so the overlapped SpMV lands once here and every recurrence
    the builder emits inherits it.  Everything else (carry layout,
    reduction ladder, shard specs) is identical, keeping the non-fused
    emission byte-stable."""
    import jax.numpy as _jnp
    from jax import lax
    from jax.sharding import PartitionSpec as P
    from acg_tpu.parallel.dist import (make_dist_spmv,
                                       make_dist_spmv_overlapped)
    from acg_tpu.parallel.mesh import PARTS_AXIS
    from acg_tpu.parallel.reductions import make_pdot, make_pdotk
    from acg_tpu.solvers.jax_cg import _iterate

    prob = solver.problem
    pipelined = solver.pipelined
    axis = PARTS_AXIS
    if isinstance(solver.kernels, str) and \
            solver.kernels.startswith("fused"):
        dist_spmv = make_dist_spmv_overlapped(prob, solver.comm,
                                              solver._interpret)
    else:
        dist_spmv = make_dist_spmv(prob, solver.comm, solver._interpret,
                                   kernels=solver.kernels, fault=None)
    single_shard = solver.mesh.devices.size == 1

    def psum(v):
        return v if single_shard else lax.psum(v, axis)

    def shard_body(la, ga, sidx, gsrc, gval, scnt, rcnt, b, x0, tols,
                   maxits, unbounded=False, needs_diff=False):
        la, ga = (jax.tree.map(lambda a: a[0], t) for t in (la, ga))
        sidx, gsrc, gval, scnt, rcnt, b, x0 = (
            a[0] for a in (sidx, gsrc, gval, scnt, rcnt, b, x0))
        maxits = maxits.astype(jnp.int32)
        dtype = b.dtype
        sdt = acc_dtype(dtype)
        store = ((lambda v: v.astype(dtype)) if sdt != dtype
                 else (lambda v: v))
        res_atol, res_rtol, diff_atol, diff_rtol = tols

        def spmv(x, k=None):
            return dist_spmv(x, la, ga, sidx, gsrc, gval, scnt, rcnt,
                             k=k, pidx=None)

        def ldot(a, c):
            return jnp.dot(a, c, preferred_element_type=sdt)

        pdot = make_pdot(psum, ldot, sdt, False)
        _pdotk = make_pdotk(psum, ldot, sdt, False)

        def pdot2_fused(a1, c1, a2, c2):
            return _pdotk((a1, c1), (a2, c2))

        ops = TierOps(spmv=spmv, dot=pdot, psum_stack=psum,
                      store=store, sdt=sdt)
        bnrm2 = jnp.sqrt(pdot(b, b))
        x0nrm2 = jnp.sqrt(pdot(x0, x0))
        r = b - spmv(x0)
        if not pipelined:
            gamma = pdot(r, r)
            r0nrm2 = jnp.sqrt(gamma)
        else:
            gamma = pdot(r, r)
            r0nrm2 = jnp.sqrt(gamma)
        res_tol = jnp.maximum(res_atol, res_rtol * r0nrm2)
        diff_tol = jnp.maximum(diff_atol, diff_rtol * x0nrm2)
        inf = jnp.asarray(jnp.inf, sdt)
        if not pipelined:
            body = classic_recurrence(ops)
            init_state = (x0, r, r, gamma)
            k, state, done = _iterate(
                body, init_state, lambda s: s[3], maxits, res_tol,
                diff_tol, lambda s: inf, unbounded, bad_of=None)
            x, r_fin, gamma_fin = state[0], state[1], state[3]
            dxsqr = inf
            breakdown = jnp.asarray(False)
            rnrm2 = jnp.sqrt(gamma_fin)
        else:
            w = spmv(r)
            zeros = jnp.zeros_like(b)
            body = pipelined_recurrence(ops, pdot2_fused)
            init_state = (x0, r, w, zeros, zeros, zeros, inf, inf)
            init_gamma = gamma
            k, state, done = _iterate(
                body, init_state, lambda s: s[6], maxits, res_tol,
                diff_tol, lambda s: inf, unbounded,
                init_gamma=init_gamma, bad_of=None)
            x, r_fin = state[0], state[1]
            dxsqr = inf
            breakdown = jnp.asarray(False)
            rnrm2 = jnp.sqrt(pdot(r_fin, r_fin))
            done = jnp.logical_or(done, rnrm2 <= res_tol)
        breakdown = breakdown & ~done
        dxnrm2 = jnp.sqrt(dxsqr)
        out = (x[None], k, rnrm2, r0nrm2, bnrm2, x0nrm2, dxnrm2,
               done, breakdown)
        return out

    if single_shard and not prob.halo.has_ghosts:
        @functools.partial(jax.jit,
                           static_argnames=("unbounded", "needs_diff",
                                            "detect"))
        def program(la, ga, sidx, gsrc, gval, scnt, rcnt, b, x0,
                    tols, maxits, unbounded, needs_diff,
                    detect=False, mstate=None, carry=None,
                    k_offset=None):
            return shard_body(la, ga, sidx, gsrc, gval, scnt, rcnt,
                              b, x0, tols, maxits,
                              unbounded=unbounded,
                              needs_diff=needs_diff)

        return program

    pspec = P(PARTS_AXIS)
    rspec = P()
    in_specs = (pspec, pspec,
                pspec, pspec, pspec, pspec, pspec,
                pspec, pspec,
                rspec, rspec)
    out_specs = (pspec,) + (rspec,) * 8

    @functools.partial(jax.jit,
                       static_argnames=("unbounded", "needs_diff",
                                        "detect"))
    def program(la, ga, sidx, gsrc, gval, scnt, rcnt, b, x0,
                tols, maxits, unbounded, needs_diff, detect=False,
                mstate=None, carry=None, k_offset=None):
        extra = ()
        specs = in_specs

        def smb(la, ga, sidx, gsrc, gval, scnt, rcnt, b, x0, tols,
                maxits, *rest):
            return shard_body(la, ga, sidx, gsrc, gval, scnt, rcnt,
                              b, x0, tols, maxits,
                              unbounded=unbounded,
                              needs_diff=needs_diff)

        return jax.shard_map(
            smb,
            mesh=solver.mesh, in_specs=specs, out_specs=out_specs,
            check_vma=False,
        )(la, ga, sidx, gsrc, gval, scnt, rcnt, b, x0, tols, maxits,
          *extra)

    return program


# -- measured segment probes (the communication observatory) ---------------
#
# SpMV-only / halo-only / reduction-only probe programs for
# acg_tpu.commbench.segment_decomposition, built from the SAME machinery
# the builder's dispatched emission composes -- the overlapped-or-not
# dist SpMV selection of build_dist_program, the make_pdot/make_pdotk
# reduction ladders, _spmv_fn on the single-device tier -- so a measured
# segment times the ops a real iteration runs, not a replay stand-in.
# Each probe chains `reps` rounds inside ONE dispatched fori_loop (data
# dependence between rounds, so XLA can neither elide nor batch them)
# and clamps the SpMV chain (repeated A.v grows as lambda_max^reps;
# the clamp keeps values finite and out of denormal range without a
# norm, which would smuggle a reduction into the SpMV segment).
# Building or running probes never mutates solver state: the dispatched
# solve programs stay byte-identical (pinned in tests/test_commbench.py).

def _probe_reduction_calls(pipelined: bool) -> tuple[str, float]:
    """(probe flavour, calls/iteration) of the reduction segment:
    classic runs TWO single-scalar pdots per iteration, the pipelined
    recurrence ONE fused 2-scalar pdotk -- the probe reproduces the
    exact ladder so the segment prices what the mesh actually moves."""
    return ("pdotk2", 1.0) if pipelined else ("pdot", 2.0)


def build_single_segment_probes(solver, b, reps: int) -> list[tuple]:
    """``[(name, runner, calls_per_iteration), ...]`` for the
    single-device tier: SpMV-only and reduction-only (no halo on one
    chip)."""
    from acg_tpu.solvers.jax_cg import _scalar_setup, _spmv_fn

    if getattr(solver, "algo", None) is not None:
        raise ValueError("segment probes cover the classic/pipelined "
                         "recurrences")
    spmv_ = _spmv_fn(solver.kernels)
    dtype = solver._solve_dtype()
    v0 = jnp.asarray(np.ones(int(np.asarray(b).shape[0])), dtype)
    dot, sdt = _scalar_setup(dtype, solver.precise_dots)
    A = solver._A_program
    flavour, red_calls = _probe_reduction_calls(solver.pipelined)

    @functools.partial(jax.jit, static_argnames="reps")
    def spmv_prog(A, v, reps):
        def rnd(_, v):
            return jnp.clip(spmv_(A, v), -1e3, 1e3)
        return jax.lax.fori_loop(0, reps, rnd, v)

    @functools.partial(jax.jit, static_argnames="reps")
    def red_prog(v, reps):
        tiny = jnp.asarray(1e-30, sdt)

        def rnd(_, v):
            if flavour == "pdotk2":
                g = dot(v, v) + dot(v, v)
            else:
                g = dot(v, v)
            return v + (g * tiny).astype(v.dtype)
        return jax.lax.fori_loop(0, reps, rnd, v)

    r = int(reps)
    return [("spmv", lambda: spmv_prog(A, v0, r), 1.0),
            ("reduction", lambda: red_prog(v0, r), red_calls)]


def build_dist_segment_probes(solver, b_global, reps: int) -> list[tuple]:
    """``[(name, runner, calls_per_iteration), ...]`` for the dist
    tier: the halo'd SpMV (overlapped when ``kernels='fused'`` -- the
    same selection :func:`build_dist_program` dispatches), the halo
    exchange alone (xla all_to_all or one-sided DMA, per the solver's
    armed transport), and the psum reduction ladder."""
    from jax import lax
    from jax.sharding import PartitionSpec as P
    from acg_tpu.parallel.dist import (make_dist_spmv,
                                       make_dist_spmv_overlapped)
    from acg_tpu.parallel.halo import halo_exchange
    from acg_tpu.parallel.halo_dma import halo_exchange_dma
    from acg_tpu.parallel.mesh import PARTS_AXIS
    from acg_tpu.parallel.reductions import make_pdot, make_pdotk

    if getattr(solver, "algo", None) is not None:
        raise ValueError("segment probes cover the classic/pipelined "
                         "recurrences")
    prob = solver.problem
    axis = PARTS_AXIS
    if isinstance(solver.kernels, str) and \
            solver.kernels.startswith("fused"):
        dist_spmv = make_dist_spmv_overlapped(prob, solver.comm,
                                              solver._interpret)
    else:
        dist_spmv = make_dist_spmv(prob, solver.comm, solver._interpret,
                                   kernels=solver.kernels, fault=None)
    single_shard = solver.mesh.devices.size == 1
    comm = solver.comm
    interpret = solver._interpret
    precise = solver.precise_dots
    flavour, red_calls = _probe_reduction_calls(solver.pipelined)

    def psum(v):
        return v if single_shard else lax.psum(v, axis)

    dev = solver.device_args(np.asarray(b_global), None)
    b, _x0, la, ga, sidx, gsrc, gval, scnt, rcnt = dev

    def make_probe(round_of):
        """One probe program over the solver's own stacked device args:
        shard body unstacks exactly like the emission's shard_body,
        builds the round from the tier ops, and chains ``reps``
        rounds."""
        def shard(la, ga, sidx, gsrc, gval, scnt, rcnt, b):
            la, ga = (jax.tree.map(lambda a: a[0], t)
                      for t in (la, ga))
            sidx, gsrc, gval, scnt, rcnt, b = (
                a[0] for a in (sidx, gsrc, gval, scnt, rcnt, b))
            sdt = acc_dtype(b.dtype)

            def spmv(x):
                return dist_spmv(x, la, ga, sidx, gsrc, gval, scnt,
                                 rcnt, k=None, pidx=None)

            def halo(x):
                if comm == "dma":
                    return halo_exchange_dma(x, sidx, gsrc, gval,
                                             scnt, rcnt, axis,
                                             interpret=interpret)
                return halo_exchange(x, sidx, gsrc, axis)

            def ldot(a, c):
                return jnp.dot(a, c, preferred_element_type=sdt)

            pdot = make_pdot(psum, ldot, sdt, precise)
            pdotk = make_pdotk(psum, ldot, sdt, precise)
            rnd = round_of(spmv, halo, pdot, pdotk, sdt)
            v = jax.lax.fori_loop(0, reps, lambda _, v: rnd(v), b)
            return v[None]

        if single_shard and not prob.halo.has_ghosts:
            prog = jax.jit(lambda *a: shard(*a))
        else:
            pspec = P(axis)
            prog = jax.jit(jax.shard_map(
                shard, mesh=solver.mesh, in_specs=(pspec,) * 8,
                out_specs=pspec, check_vma=False))
        return lambda: prog(la, ga, sidx, gsrc, gval, scnt, rcnt, b)

    def spmv_round(spmv, halo, pdot, pdotk, sdt):
        return lambda v: jnp.clip(spmv(v), -1e3, 1e3)

    def halo_round(spmv, halo, pdot, pdotk, sdt):
        def rnd(v):
            g = halo(v)
            return v.at[0].add((g[0]
                                * jnp.asarray(1e-30, g.dtype)))
        return rnd

    def red_round(spmv, halo, pdot, pdotk, sdt):
        tiny = jnp.asarray(1e-30, sdt)

        def rnd(v):
            if flavour == "pdotk2":
                g1, g2 = pdotk((v, v), (v, v))
                g = g1 + g2
            else:
                g = pdot(v, v)
            return v + (g * tiny).astype(v.dtype)
        return rnd

    probes = [("spmv", make_probe(spmv_round), 1.0)]
    if prob.halo.has_ghosts:
        probes.append(("halo", make_probe(halo_round), 1.0))
    probes.append(("reduction", make_probe(red_round), red_calls))
    return probes


# -- the p(l) restart driver (shared by every tier) ------------------------

def pl_restart_policy():
    """The default recovery policy a p(l) solver arms when the caller
    provided none: sqrt breakdown is an EXPECTED algorithmic event of
    deep pipelines (the z-Gram loses positivity as convergence
    proceeds), and the literature's remedy -- restart from the current
    iterate -- is exactly the existing recovery ladder's
    restart-from-true-residual rung.  Budgeted generously; restarts
    keep the original absolute tolerance target (the ladder's
    convention), and the measured total iteration count stays within
    ~1.8x classic on the aniso family at rtol 1e-8."""
    from acg_tpu.solvers.resilience import RecoveryPolicy
    return RecoveryPolicy(max_restarts=PL_RESTART_BUDGET,
                          fallback_comm=False, fallback_host=False)


# -- host oracles ----------------------------------------------------------

def host_sstep_cg(A, b, x0=None, rtol=1e-8, maxits=1000, s=4,
                  basis=None, lam=None):
    """Eager f64 s-step CG oracle (scipy matvec) -- the trajectory-
    parity reference of tests/test_recurrence.py."""
    import scipy.sparse as sp
    A = sp.csr_matrix(A)
    n = A.shape[0]
    b = np.asarray(b, np.float64)
    x = np.zeros(n) if x0 is None else np.asarray(x0, np.float64).copy()
    basis = basis or ("chebyshev" if s >= 4 else "monomial")
    if lam is None and basis == "chebyshev":
        v = np.random.default_rng(0).standard_normal(n)
        for _ in range(POWER_ITERS):
            v = A @ v
            v /= np.linalg.norm(v)
        lam = (0.0, float(v @ (A @ v)) * LAM_SAFETY)
    lam = lam or (0.0, 0.0)
    r = b - A @ x
    p = r.copy()
    gamma = float(r @ r)
    r0 = np.sqrt(gamma)
    tol2 = (rtol * r0) ** 2
    w = 2 * s + 1
    Bm = np.asarray(sstep_combined_bmat(s, basis, lam, jnp.float64))
    traj = []
    k = 0
    while k < maxits and gamma >= tol2:
        rows = [p]
        if basis == "monomial":
            for _ in range(s):
                rows.append(A @ rows[-1])
        else:
            d = (lam[0] + lam[1]) / 2.0
            c = (lam[1] - lam[0]) / 2.0
            for j in range(s):
                wv = A @ rows[-1] - d * rows[-1]
                rows.append(wv / c if j == 0 else 2 * wv / c - rows[-2])
        rrows = [r]
        if basis == "monomial":
            for _ in range(s - 1):
                rrows.append(A @ rrows[-1])
        else:
            d = (lam[0] + lam[1]) / 2.0
            c = (lam[1] - lam[0]) / 2.0
            for j in range(s - 1):
                wv = A @ rrows[-1] - d * rrows[-1]
                rrows.append(wv / c if j == 0
                             else 2 * wv / c - rrows[-2])
        V = np.stack(rows + rrows)
        G = V @ V.T
        pc = np.zeros(w); pc[0] = 1.0
        rc = np.zeros(w); rc[s + 1] = 1.0
        xc = np.zeros(w)
        gamma = float(G[s + 1, s + 1])
        for j in range(s):
            if gamma < tol2 or k >= maxits:
                break
            wc = Bm @ pc
            denom = float(pc @ (G @ wc))
            alpha = gamma / denom
            xc += alpha * pc
            rc = rc - alpha * wc
            gamma_next = float(rc @ (G @ rc))
            beta = gamma_next / gamma
            pc = rc + beta * pc
            traj.append((gamma_next, alpha, beta, denom))
            gamma = gamma_next
            k += 1
        x = x + xc @ V
        r = rc @ V
        p = pc @ V
    return x, k, np.sqrt(max(gamma, 0.0)) / r0, traj
