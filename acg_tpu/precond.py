"""Preconditioning subsystem: PCG / pipelined-PCG across the solver tiers.

The reference aCG suite (and this reproduction until now) solves Ax=b
with UNpreconditioned classic and Ghysels-Vanroose pipelined CG -- but
the pipelined-CG literature the suite builds on is explicitly a
*preconditioned* method: both the deep-pipelines formulation
(arXiv:1801.04728) and the global-reduction-pipelining work
(arXiv:1905.06850) interleave the preconditioner apply with the hidden
reductions.  On ill-conditioned systems (the anisotropic/stretched
Poisson family, ``io.generators.aniso_poisson2d_coo``) iteration count,
not seconds/iteration, dominates wall-clock -- so M^-1 is the single
biggest lever left after the kernel tiers.

Three implementations, all of which stay inside the jitted loop carry
(state rides the solve programs as ARGUMENTS, the apply is traced into
the loop body -- no host round-trips, no extra dispatches):

* **Jacobi** (``--precond jacobi``): inverse-diagonal scaling.  The
  diagonal is extracted ONCE at setup from the local DIA/ELL/COO/binned
  planes (:func:`acg_tpu.ops.spmv.matrix_diagonal`; host numpy from the
  stacked per-part blocks on the explicit distributed path) -- zero
  extra communication, one elementwise multiply per apply.
* **block-Jacobi** (``--precond bjacobi[:BS]``): dense Cholesky factors
  of the BS x BS diagonal blocks of the (local) matrix, factored once
  at setup, applied as batched forward/back triangular solves --
  embarrassingly parallel across rows and across the mesh (blocks never
  cross a partition boundary on the distributed tiers), no halo
  traffic.  Zero diagonal entries (stacked-layout padding rows) are
  replaced by identity rows so the factorization stays defined.
* **Chebyshev polynomial** (``--precond cheby:K``): z = p_K(A) r with
  p_K the degree-K Chebyshev approximation of 1/lambda on
  ``[lambda_max / CHEBY_RATIO, CHEBY_SAFETY * lambda_max]``.  Each
  apply is exactly K SpMV applications REUSING the tier's existing SpMV
  + halo-exchange machinery -- the communication pattern is identical
  to K extra SpMVs, which is exactly what the pipelined tier is built
  to hide.  lambda_max comes from a power iteration at setup (run
  through the same SpMV selection the solve programs use).

Disarmament contract (the telemetry/faults/perfmodel discipline):
``--precond none`` programs lower BYTE-IDENTICAL to a build without
this module -- the precond spec is a static jit argument and the
``mstate`` pytree argument is None/absent when disarmed (pinned in
tests/test_hlo_structure.py).

SPD caveat the breakdown path guards: PCG requires M SPD.  A non-SPD M
(or a fault-injected ``precond:`` poison, acg_tpu.faults) surfaces as a
non-finite or NEGATIVE (r, z) scalar, which the detecting loops flag as
a breakdown; the recovery driver then preserves -- or, when the state
itself went non-finite, rebuilds -- the preconditioner state across the
restart (:func:`refresh_state`).
"""

from __future__ import annotations

import dataclasses

import numpy as np

# Chebyshev interval policy: the spectrum is assumed inside
# [lmax / CHEBY_RATIO, CHEBY_SAFETY * lmax].  RATIO 30 is the standard
# smoother heuristic (hypre/AMG practice); SAFETY 1.05 absorbs the power
# iteration's systematic underestimate so p_K stays positive on the
# whole spectrum (a lambda above the interval would make p_K(A)
# indefinite -- exactly the breakdown the detecting loops guard).
CHEBY_RATIO = 30.0
CHEBY_SAFETY = 1.05
POWER_ITERS = 24
DEFAULT_BLOCK = 32

KINDS = ("jacobi", "bjacobi", "cheby")


@dataclasses.dataclass(frozen=True)
class PrecondSpec:
    """One parsed preconditioner selection: immutable and hashable, so
    it rides the solve programs' STATIC jit arguments (the FaultSpec
    design) -- a given spec compiles its own cache entry and ``None``
    compiles the byte-identical unpreconditioned program."""

    kind: str                 # "jacobi" | "bjacobi" | "cheby"
    degree: int = 0           # cheby: SpMVs per apply
    block: int = DEFAULT_BLOCK  # bjacobi: dense block size

    def __str__(self) -> str:
        if self.kind == "cheby":
            return f"cheby:{self.degree}"
        if self.kind == "bjacobi":
            return f"bjacobi:{self.block}"
        return self.kind


def parse_precond(text) -> PrecondSpec | None:
    """``none | jacobi | bjacobi[:BS] | cheby:K`` -> spec (None = off).
    Raises ``ValueError`` naming the offending token."""
    if text is None or isinstance(text, PrecondSpec):
        return text
    t = str(text).strip()
    if t in ("", "none"):
        return None
    fields = t.split(":")
    kind = fields[0]
    if kind == "jacobi":
        if len(fields) != 1:
            raise ValueError(f"precond spec {text!r}: jacobi takes no "
                             f"parameter")
        return PrecondSpec(kind="jacobi")
    if kind == "bjacobi":
        if len(fields) > 2:
            raise ValueError(f"precond spec {text!r}: expected "
                             f"bjacobi[:BLOCKSIZE]")
        bs = DEFAULT_BLOCK
        if len(fields) == 2:
            try:
                bs = int(fields[1])
            except ValueError:
                raise ValueError(f"precond spec {text!r}: bad block size "
                                 f"{fields[1]!r}")
            if bs < 1 or bs > 1024:
                raise ValueError(f"precond spec {text!r}: block size must "
                                 f"be in [1, 1024]")
        return PrecondSpec(kind="bjacobi", block=bs)
    if kind == "cheby":
        if len(fields) != 2:
            raise ValueError(f"precond spec {text!r}: cheby needs a "
                             f"degree (e.g. cheby:4)")
        try:
            k = int(fields[1])
        except ValueError:
            raise ValueError(f"precond spec {text!r}: bad degree "
                             f"{fields[1]!r}")
        if k < 1 or k > 64:
            raise ValueError(f"precond spec {text!r}: cheby degree must "
                             f"be in [1, 64]")
        return PrecondSpec(kind="cheby", degree=k)
    raise ValueError(f"precond spec {text!r}: unknown kind {kind!r} "
                     f"(none, jacobi, bjacobi[:BS], cheby:K)")


# -- device-side state builders (single-program tiers) --------------------

def jacobi_state(A, sdt):
    """``(dinv,)``: the inverse diagonal in the scalar dtype, extracted
    on device (zero transfers).  Zero diagonal entries -- structural
    padding rows of the stacked layouts -- invert to 0, so padded
    residual entries (exactly 0 by construction) stay exactly 0."""
    import jax
    import jax.numpy as jnp

    from acg_tpu.ops.spmv import matrix_diagonal

    @jax.jit
    def build(A):
        d = matrix_diagonal(A).astype(sdt)
        return (jnp.where(d != 0, 1.0 / jnp.where(d != 0, d, 1.0),
                          jnp.zeros_like(d)),)

    return build(A)


def _dia_diag_blocks(planes, offsets, n: int, bs: int, sdt):
    """(nb, bs, bs) dense diagonal blocks of square DIA planes, built on
    device by one scatter per in-band offset (|off| < bs; wider offsets
    cannot land inside a bs x bs diagonal block)."""
    import jax.numpy as jnp

    nb = -(-n // bs)
    blocks = jnp.zeros((nb, bs, bs), dtype=sdt)
    rows = jnp.arange(n)
    bi = rows // bs
    i = rows % bs
    for plane, off in zip(planes, offsets):
        if abs(int(off)) >= bs:
            continue
        j = i + int(off)
        valid = (j >= 0) & (j < bs) & (rows + int(off) >= 0) \
            & (rows + int(off) < n)
        blocks = blocks.at[bi, i, jnp.clip(j, 0, bs - 1)].add(
            jnp.where(valid, plane[:n].astype(sdt), 0.0))
    return blocks


def _gather_diag_blocks(rows, cols, vals, n: int, bs: int, sdt):
    """(nb, bs, bs) diagonal blocks from flat (row, col, val) triples
    (the ELL/COO/binned gather formats flattened); entries outside the
    block diagonal contribute nothing."""
    import jax.numpy as jnp

    nb = -(-n // bs)
    blocks = jnp.zeros((nb, bs, bs), dtype=sdt)
    bi = rows // bs
    i = rows % bs
    j = cols - bi * bs
    valid = (j >= 0) & (j < bs) & (rows < n)
    return blocks.at[bi, i, jnp.clip(j, 0, bs - 1)].add(
        jnp.where(valid, vals.astype(sdt), 0.0))


def diag_blocks(A, bs: int, sdt):
    """(nb, bs, bs) dense diagonal blocks of any device matrix format,
    with identity substituted on empty-diagonal rows (padding) so the
    Cholesky below stays defined."""
    import jax.numpy as jnp

    from acg_tpu.ops.spmv import (BinnedEllMatrix, CooMatrix, DiaMatrix,
                                  EllMatrix)

    n = A.nrows
    if isinstance(A, DiaMatrix):
        blocks = _dia_diag_blocks(A.data, A.offsets, n, bs, sdt)
    elif isinstance(A, EllMatrix):
        rows = jnp.repeat(jnp.arange(n), A.data.shape[1])
        blocks = _gather_diag_blocks(rows, A.cols.reshape(-1),
                                     A.data.reshape(-1), n, bs, sdt)
    elif isinstance(A, CooMatrix):
        blocks = _gather_diag_blocks(A.rows, A.cols, A.vals, n, bs, sdt)
    elif isinstance(A, BinnedEllMatrix):
        blocks = jnp.zeros((-(-n // bs), bs, bs), dtype=sdt)
        for brows, bdata, bcols in zip(A.bin_rows, A.bin_data, A.bin_cols):
            K = bdata.shape[1]
            rr = jnp.repeat(brows, K)
            blocks = blocks + _gather_diag_blocks(
                rr, bcols.reshape(-1), bdata.reshape(-1), n, bs, sdt)
        if A.tail_rows.size:
            blocks = blocks + _gather_diag_blocks(
                A.tail_rows, A.tail_cols, A.tail_vals, n, bs, sdt)
    else:
        raise TypeError(f"unsupported device matrix {type(A)}")
    ar = jnp.arange(bs)
    dblk = blocks[:, ar, ar]
    return blocks.at[:, ar, ar].add(jnp.where(dblk == 0, 1.0, 0.0))


def bjacobi_state(A, bs: int, sdt):
    """``(chol,)``: batched lower Cholesky factors of the bs x bs
    diagonal blocks.  A non-SPD block leaves NaNs in its factor, which
    the first apply propagates into (r, z) -- the breakdown path, by
    design, rather than a silent wrong answer."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def build(A):
        return (jnp.linalg.cholesky(diag_blocks(A, bs, sdt)),)

    return build(A)


def estimate_lmax(spmv_fn, A, n: int, sdt, iters: int = POWER_ITERS,
                  seed: int = 0):
    """Power-iteration largest-eigenvalue estimate, run through the
    SAME SpMV selection the solve programs dispatch (so the sharded
    roll tiers estimate over exactly the operator they iterate).
    Returns a device scalar."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def run(A, key):
        v = jax.random.normal(key, (n,), dtype=sdt)

        def body(_, v):
            w = spmv_fn(A, v.astype(sdt)).astype(sdt)
            return w / jnp.linalg.norm(w)

        v = jax.lax.fori_loop(0, iters, body, v)
        w = spmv_fn(A, v).astype(sdt)
        return jnp.vdot(v, w) / jnp.vdot(v, v)

    return run(A, jax.random.key(seed))


def cheby_state(lmax, sdt):
    """``(lmin, lmax)`` device scalars bounding the Chebyshev interval
    (the RATIO/SAFETY policy above)."""
    import jax.numpy as jnp

    lmax = jnp.asarray(lmax, sdt) * jnp.asarray(CHEBY_SAFETY, sdt)
    return (lmax / jnp.asarray(CHEBY_RATIO, sdt), lmax)


def setup_single(spec: PrecondSpec, A, spmv_fn, sdt, A_program=None):
    """Build the state pytree for the single-program tiers
    (JaxCGSolver + the sharded DIA subclass): a tuple of device arrays
    that rides the solve programs as an argument.  ``A_program`` is
    the matrix the PROGRAMS consume when it differs from the clean
    view (the pallas-roll padded twin) -- diagonal/block extraction
    always reads the clean ``A``, the power iteration runs over the
    program's operator."""
    if spec.kind == "jacobi":
        # matrix-free operators reach this through the matrix_diagonal
        # operator hook (analytic stencil diagonal; typed refusal for
        # user operators registered without a diagonal_fn)
        return jacobi_state(A, sdt)
    if spec.kind == "bjacobi":
        from acg_tpu.ops.operator import is_matrix_free
        if is_matrix_free(A):
            from acg_tpu.errors import AcgError, ErrorCode
            raise AcgError(
                ErrorCode.NOT_SUPPORTED,
                "bjacobi factors stored diagonal blocks, which a "
                "matrix-free operator does not have; use --precond "
                "jacobi (analytic diagonal) or cheby:K (applies only)")
        return bjacobi_state(A, spec.block, sdt)
    Ap = A if A_program is None else A_program
    return cheby_state(estimate_lmax(spmv_fn, Ap, A.nrows, sdt), sdt)


# -- the in-loop apply (traced into the solve programs) -------------------

def make_apply(spec: PrecondSpec, spmv_fn):
    """``apply(mstate, A, r) -> z``, a pure jnp function traced into the
    jitted loop body.  ``spmv_fn(A, x)`` is the TIER'S OWN SpMV closure
    (halo exchange included on the mesh tiers), so the Chebyshev apply's
    communication pattern is exactly K extra SpMVs."""
    import jax
    import jax.numpy as jnp

    from acg_tpu.ops.spmv import acc_dtype

    if spec.kind == "jacobi":
        def apply(mstate, A, r):
            (dinv,) = mstate
            return (r.astype(dinv.dtype) * dinv).astype(r.dtype)
        return apply

    if spec.kind == "bjacobi":
        bs = spec.block

        def apply(mstate, A, r):
            (chol,) = mstate
            n = r.shape[0]
            npad = chol.shape[0] * bs
            rp = r.astype(chol.dtype)
            if npad != n:
                rp = jnp.pad(rp, (0, npad - n))
            R = rp.reshape(chol.shape[0], bs, 1)
            y = jax.lax.linalg.triangular_solve(
                chol, R, left_side=True, lower=True)
            z = jax.lax.linalg.triangular_solve(
                chol, y, left_side=True, lower=True, transpose_a=True)
            return z.reshape(-1)[:n].astype(r.dtype)
        return apply

    k = spec.degree

    def apply(mstate, A, r):
        lmin, lmax = mstate
        adt = acc_dtype(r.dtype)
        lmin = lmin.astype(adt)
        lmax = lmax.astype(adt)
        theta = (lmax + lmin) * 0.5
        delta = (lmax - lmin) * 0.5
        sigma = theta / delta
        rho = 1.0 / sigma
        rs = r.astype(adt)
        d = rs / theta
        z = d
        rcur = rs
        # K steps of the Chebyshev semi-iteration on A z = r from z = 0:
        # exactly K SpMVs, the degree-K polynomial in A applied to r
        for _ in range(k):
            rcur = rcur - spmv_fn(A, d.astype(r.dtype)).astype(adt)
            rho_new = 1.0 / (2.0 * sigma - rho)
            d = rho_new * rho * d + (2.0 * rho_new / delta) * rcur
            z = z + d
            rho = rho_new
        return z.astype(r.dtype)
    return apply


def make_apply_batched(spec: PrecondSpec, spmv_multi_fn=None):
    """``apply(mstate, A, R) -> Z`` over a MULTI-COLUMN residual block
    ``R`` of shape ``(n, B)`` -- the preconditioner apply broadcast
    over the batch axis (the batched multi-RHS tier,
    acg_tpu.solvers.batched).

    Jacobi broadcasts the inverse diagonal across columns in one
    elementwise multiply; block-Jacobi reuses the SAME batched
    triangular solves with B right-hand sides per block (the blocked
    reshape gains a trailing column axis); Chebyshev runs its K-step
    semi-iteration on the whole block through ``spmv_multi_fn``
    (default: the single-device multi-vector SpMV) -- K matrix passes
    for ALL B columns, the same amortization as the solve loop's."""
    import jax
    import jax.numpy as jnp

    from acg_tpu.ops.spmv import acc_dtype

    if spec.kind == "jacobi":
        def apply(mstate, A, R):
            (dinv,) = mstate
            return (R.astype(dinv.dtype) * dinv[:, None]).astype(R.dtype)
        return apply

    if spec.kind == "bjacobi":
        bs = spec.block

        def apply(mstate, A, R):
            (chol,) = mstate
            n, nb_cols = R.shape
            npad = chol.shape[0] * bs
            Rp = R.astype(chol.dtype)
            if npad != n:
                Rp = jnp.pad(Rp, ((0, npad - n), (0, 0)))
            Rb = Rp.reshape(chol.shape[0], bs, nb_cols)
            y = jax.lax.linalg.triangular_solve(
                chol, Rb, left_side=True, lower=True)
            z = jax.lax.linalg.triangular_solve(
                chol, y, left_side=True, lower=True, transpose_a=True)
            return z.reshape(npad, nb_cols)[:n].astype(R.dtype)
        return apply

    k = spec.degree
    if spmv_multi_fn is None:
        from acg_tpu.solvers.batched import spmv_multi as spmv_multi_fn

    def apply(mstate, A, R):
        lmin, lmax = mstate
        adt = acc_dtype(R.dtype)
        lmin = lmin.astype(adt)
        lmax = lmax.astype(adt)
        theta = (lmax + lmin) * 0.5
        delta = (lmax - lmin) * 0.5
        sigma = theta / delta
        rho = 1.0 / sigma
        Rs = R.astype(adt)
        d = Rs / theta
        z = d
        rcur = Rs
        for _ in range(k):
            rcur = rcur - spmv_multi_fn(A, d.astype(R.dtype)).astype(adt)
            rho_new = 1.0 / (2.0 * sigma - rho)
            d = rho_new * rho * d + (2.0 * rho_new / delta) * rcur
            z = z + d
            rho = rho_new
        return z.astype(R.dtype)
    return apply


# -- stacked host-side state builders (the explicit distributed tier) -----

def _np_diag_blocks_from_triples(rows, cols, vals, n: int, bs: int,
                                 out: np.ndarray) -> None:
    """Accumulate (row, col, val) triples into ``out`` ((nb, bs, bs)
    f64) wherever they land inside a bs x bs diagonal block."""
    rows = np.asarray(rows, np.int64)
    cols = np.asarray(cols, np.int64)
    vals = np.asarray(vals, np.float64)
    bi = rows // bs
    j = cols - bi * bs
    ok = (rows < n) & (j >= 0) & (j < bs) & (vals != 0)
    np.add.at(out, (bi[ok], (rows % bs)[ok], j[ok]), vals[ok])


def _np_local_block_triples(local, p: int):
    """Flat (rows, cols, vals) of part ``p``'s local block in any
    StackedLocalBlock format (host numpy views; zero-copy where the
    layout allows)."""
    if local.format == "dia":
        n = local.nrows
        rows = np.arange(n, dtype=np.int64)
        rs, cs, vs = [], [], []
        for plane, off in zip(local.arrays, local.offsets):
            cols = rows + int(off)
            ok = (cols >= 0) & (cols < n)
            rs.append(rows[ok])
            cs.append(cols[ok])
            vs.append(np.asarray(plane[p], np.float64)[ok])
        return (np.concatenate(rs), np.concatenate(cs),
                np.concatenate(vs))
    if local.format == "tiledia":
        planes, seg_off, seg_tile = (np.asarray(a[p]) for a in local.arrays)
        rows = (seg_tile.astype(np.int64)[:, None] * local.tile
                + np.arange(local.tile))
        cols = rows + seg_off.astype(np.int64)[:, None]
        ok = planes != 0
        return rows[ok], cols[ok], planes[ok].astype(np.float64)
    if local.format == "ell":
        data, cols = local.arrays
        n, K = data.shape[1], data.shape[2]
        rows = np.repeat(np.arange(n, dtype=np.int64), K)
        return rows, np.asarray(cols[p], np.int64).reshape(-1), \
            np.asarray(data[p], np.float64).reshape(-1)
    # binnedell
    bin_rows, bin_data, bin_cols, t_rows, t_cols, t_vals = local.arrays
    rs, cs, vs = [], [], []
    for br, bd, bc in zip(bin_rows, bin_data, bin_cols):
        K = bd.shape[2]
        rs.append(np.repeat(np.asarray(br[p], np.int64), K))
        cs.append(np.asarray(bc[p], np.int64).reshape(-1))
        vs.append(np.asarray(bd[p], np.float64).reshape(-1))
    rs.append(np.asarray(t_rows[p], np.int64))
    cs.append(np.asarray(t_cols[p], np.int64))
    vs.append(np.asarray(t_vals[p], np.float64))
    return np.concatenate(rs), np.concatenate(cs), np.concatenate(vs)


def stacked_jacobi_state(prob, sdt) -> tuple:
    """``(dinv,)`` with dinv (nparts, nmax_owned) host numpy for the
    explicit distributed tier: the diagonal of each part's LOCAL block
    (diagonal entries are owned x owned by construction -- the ghost
    block never holds them), inverted with the zero guard.  Non-owned
    parts of a multi-controller build stay zero: their shards are never
    read by this controller."""
    local = prob.local
    n = local.nrows
    dinv = np.zeros((prob.nparts, n), dtype=np.dtype(sdt))
    owned = (range(prob.nparts) if prob.owned_parts is None
             else prob.owned_parts)
    if local.format == "matfree":
        # the operator-path twin: the ANALYTIC stencil diagonal (host
        # numpy of the same rounded values the device generates),
        # sliced per part -- no stored planes exist to scan
        dglob = prob.operator.host_diagonal()
        for p in owned:
            s = prob.subs[p]
            gids = np.asarray(s.global_ids[: s.nowned], np.int64)
            d = dglob[gids]
            nz = d != 0
            dinv[p, : s.nowned][nz] = 1.0 / d[nz]
        return (dinv,)
    for p in owned:
        rows, cols, vals = _np_local_block_triples(local, p)
        d = np.zeros(n, np.float64)
        on_diag = rows == cols
        np.add.at(d, rows[on_diag], vals[on_diag])
        nz = d != 0
        dinv[p, nz] = 1.0 / d[nz]
    return (dinv,)


def stacked_bjacobi_state(prob, bs: int, sdt) -> tuple:
    """``(chol,)`` with chol (nparts, nb, bs, bs) host numpy: dense
    Cholesky factors of each part's local diagonal blocks (padding /
    non-owned rows become identity blocks).  numpy raises on a non-SPD
    owned block -- surfaced as a typed refusal at setup rather than
    NaNs mid-solve (host setup CAN check, unlike the on-device path)."""
    from acg_tpu.errors import AcgError, ErrorCode

    local = prob.local
    if local.format == "matfree":
        raise AcgError(
            ErrorCode.NOT_SUPPORTED,
            "bjacobi factors stored local diagonal blocks, which the "
            "matrix-free tier does not have; use --precond jacobi "
            "(analytic diagonal) or cheby:K (applies only)")
    n = local.nrows
    nb = -(-n // bs)
    chol = np.zeros((prob.nparts, nb, bs, bs), dtype=np.dtype(sdt))
    eye = np.eye(bs)
    owned = (range(prob.nparts) if prob.owned_parts is None
             else prob.owned_parts)
    for p in range(prob.nparts):
        if p not in owned:
            chol[p] = eye  # never read; keep the factor well-defined
            continue
        blocks = np.zeros((nb, bs, bs), np.float64)
        rows, cols, vals = _np_local_block_triples(local, p)
        _np_diag_blocks_from_triples(rows, cols, vals, n, bs, blocks)
        dblk = np.einsum("bii->bi", blocks)
        empty = dblk == 0
        np.einsum("bii->bi", blocks)[...] = np.where(empty, 1.0, dblk)
        try:
            chol[p] = np.linalg.cholesky(blocks)
        except np.linalg.LinAlgError:
            raise AcgError(
                ErrorCode.INVALID_VALUE,
                f"bjacobi:{bs}: a diagonal block of part {p} is not "
                f"positive definite -- the matrix (or this block size) "
                f"does not admit a block-Jacobi Cholesky")
    return (chol,)


# -- accounting (perfmodel / stats integration) ---------------------------

def flops_per_apply(spec: PrecondSpec, n: int, spmv_flops: float) -> float:
    """Analytic flops of ONE M^-1 apply (the reference's counting
    conventions: 2n per vector op, 3 per stored nonzero per SpMV)."""
    if spec.kind == "jacobi":
        return float(n)
    if spec.kind == "bjacobi":
        # two triangular solves over nb blocks of bs^2/2 entries each
        return 2.0 * n * spec.block
    return spec.degree * (float(spmv_flops) + 8.0 * n)


def bytes_per_apply(spec: PrecondSpec, n: int, vec_bytes: int,
                    mat_bytes_per_spmv: float, state_bytes: float) -> float:
    """Analytic HBM traffic of one apply: state read + vector passes
    (+ the K SpMV passes for cheby)."""
    if spec.kind == "jacobi":
        return state_bytes + 2.0 * n * vec_bytes
    if spec.kind == "bjacobi":
        return state_bytes + 2.0 * n * vec_bytes
    return spec.degree * (mat_bytes_per_spmv + 6.0 * n * vec_bytes)


def state_bytes(mstate) -> int:
    """Total bytes of a state pytree (host or device leaves)."""
    import jax

    total = 0
    for leaf in jax.tree_util.tree_leaves(mstate):
        dt = np.dtype(getattr(leaf, "dtype", np.float64))
        total += int(np.prod(np.shape(leaf))) * dt.itemsize
    return total


def comm_contribution(spec: PrecondSpec | None) -> dict:
    """The static comm-ledger stanza for one preconditioner: how many
    extra halo'd SpMV-equivalents each iteration performs.  Jacobi and
    block-Jacobi are strictly local (the whole point); cheby multiplies
    the halo pattern by its degree."""
    if spec is None:
        return {}
    extra = spec.degree if spec.kind == "cheby" else 0
    return {"kind": str(spec), "applies_per_iteration": 1,
            "halo_spmv_equivalents_per_apply": extra}


def state_finite(mstate) -> bool:
    """True when every leaf of the state pytree is finite -- the
    recovery driver's preserve-vs-rebuild predicate."""
    import jax
    import jax.numpy as jnp

    for leaf in jax.tree_util.tree_leaves(mstate):
        if not bool(jnp.isfinite(jnp.asarray(leaf)).all()):
            return False
    return True


def partition_sensitive(spec) -> bool:
    """True when the preconditioner OPERATOR depends on the row
    partition (bjacobi factors the LOCAL diagonal blocks on the
    distributed tier, so M changes when the partition does).  The
    repartition-resume path (acg_tpu.checkpoint) warns on these:
    continuing a PCG recurrence under a different M is flexible-CG
    territory -- it converges, but the short recurrence is no longer
    exactly conjugate.  Jacobi and Chebyshev are partition-invariant
    (diagonal / SpMV polynomial of the global operator)."""
    return spec is not None and getattr(spec, "kind", None) == "bjacobi"


def refresh_state(solver, driver) -> bool:
    """Recovery hook (solvers' restart loops): PRESERVE the
    preconditioner state across a restart when it is still finite --
    the state is immutable, so a numerical breakdown cannot have
    corrupted it -- and REBUILD it from the matrix when it is not
    (e.g. a non-SPD block factored to NaN, or operator-poisoned state).
    Returns True when a rebuild happened; every decision lands in the
    recovery log."""
    spec = getattr(solver, "precond_spec", None)
    if spec is None or getattr(solver, "_mstate", None) is None:
        return False
    if state_finite(solver._mstate):
        driver.record(f"preconditioner ({spec}) state preserved across "
                      f"restart")
        return False
    solver._mstate = None
    solver._ensure_precond_state()
    driver.record(f"preconditioner ({spec}) state non-finite; rebuilt "
                  f"from the matrix", kind="recovery")
    return True


# -- host (numpy/scipy) twins: the eager solver + the test oracle ---------

class HostPrecond:
    """Eager numpy preconditioner for the host reference solver (and
    the scipy-checked oracle the device applies are tested against).
    Same three kinds, same interval policy, f64 arithmetic."""

    def __init__(self, spec: PrecondSpec, csr):
        import scipy.sparse as sp

        self.spec = spec
        csr = sp.csr_matrix(csr)
        n = csr.shape[0]
        if spec.kind == "jacobi":
            d = csr.diagonal().astype(np.float64)
            dinv = np.zeros_like(d)
            dinv[d != 0] = 1.0 / d[d != 0]
            self.state = (dinv,)
        elif spec.kind == "bjacobi":
            bs = spec.block
            nb = -(-n // bs)
            blocks = np.zeros((nb, bs, bs), np.float64)
            coo = csr.tocoo()
            _np_diag_blocks_from_triples(coo.row, coo.col, coo.data, n,
                                         bs, blocks)
            dblk = np.einsum("bii->bi", blocks)
            np.einsum("bii->bi", blocks)[...] = np.where(dblk == 0, 1.0,
                                                         dblk)
            self.state = (np.linalg.cholesky(blocks),)
        else:
            rng = np.random.default_rng(0)
            v = rng.standard_normal(n)
            for _ in range(POWER_ITERS):
                w = csr @ v
                v = w / np.linalg.norm(w)
            lmax = float(v @ (csr @ v) / (v @ v)) * CHEBY_SAFETY
            self._csr = csr
            self.state = (lmax / CHEBY_RATIO, lmax)
        self.n = n

    def apply(self, r: np.ndarray) -> np.ndarray:
        spec = self.spec
        if spec.kind == "jacobi":
            return self.state[0] * r
        if spec.kind == "bjacobi":
            import scipy.linalg as sla

            (chol,) = self.state
            bs = spec.block
            npad = chol.shape[0] * bs
            rp = np.zeros(npad)
            rp[: self.n] = r
            out = np.empty_like(rp)
            for b in range(chol.shape[0]):
                out[b * bs:(b + 1) * bs] = sla.cho_solve(
                    (chol[b], True), rp[b * bs:(b + 1) * bs])
            return out[: self.n]
        lmin, lmax = self.state
        theta = (lmax + lmin) * 0.5
        delta = (lmax - lmin) * 0.5
        sigma = theta / delta
        rho = 1.0 / sigma
        d = r / theta
        z = d.copy()
        rcur = r.astype(np.float64).copy()
        for _ in range(spec.degree):
            rcur = rcur - self._csr @ d
            rho_new = 1.0 / (2.0 * sigma - rho)
            d = rho_new * rho * d + (2.0 * rho_new / delta) * rcur
            z = z + d
            rho = rho_new
        return z
