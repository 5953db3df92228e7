"""Bisect the nparts=1 distributed-program slowdown (VERDICT r4 item 4;
reproduced round 5 same-window: dist1 0.036x of the single-chip solver).

Times stripped-down CG-shaped loops at the flagship size (n=2048^2,
5-diagonal DIA), all on one device, isolating one suspect per variant:

  single      plain jit fori: spmv(DiaMatrix) + jnp.dot      (control)
  single_dia  plain jit fori: dia_mv (the dist shard formulation)
  smap_local  shard_map(1-device): dia_mv + LOCAL dots (no psum)
  smap_psum   shard_map(1-device): dia_mv + psum dots (the PRE-FIX
              dist program shape: the 2-all-reduces-per-iteration
              pathology)
  smap_pad    shard_map(1-device): the dist layout (leading parts axis,
              stripped inside the shard), psum dots
  dist_fixed  the REAL DistCGSolver at nparts=1, post-fix: with the
              commsize==1 parity bypass (parallel/dist.py) it should
              time within noise of `single` -- the fix's on-chip proof

Per-iteration rate comes from the (400 - 100)-iteration difference of
two program sizes, so the broken-completion-signal dispatch round-trip
cancels (bench two-point rationale).  One JSON line per variant.
"""

from __future__ import annotations

import functools
import json
import sys
import time

ROOT = __file__.rsplit("/", 2)[0]
sys.path.insert(0, ROOT)


def main() -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from acg_tpu._platform import enable_compile_cache
    from acg_tpu.io.generators import poisson2d_coo, poisson_dia_device
    from acg_tpu.matrix import SymCsrMatrix
    from acg_tpu.ops.spmv import DiaMatrix, dia_mv, spmv
    from acg_tpu.parallel.dist import DistCGSolver, DistributedProblem
    from acg_tpu.parallel.mesh import PARTS_AXIS, solve_mesh
    from acg_tpu.partition import partition_rows
    from acg_tpu.solvers.stats import StoppingCriteria

    enable_compile_cache()
    n = 2048
    planes, offsets, N = poisson_dia_device(n, 2, dtype=jnp.float32)
    A = DiaMatrix(data=tuple(planes), offsets=offsets,
                  nrows=N, ncols_padded=N)
    b = jnp.ones(N, jnp.float32)
    mesh = solve_mesh(1)

    def cg_loop(spmv_fn, dot_fn, b, its):
        x = jnp.zeros_like(b)
        r = b
        p = r
        gamma = dot_fn(r, r)

        def body(_, st):
            x, r, p, gamma = st
            t = spmv_fn(p)
            alpha = gamma / dot_fn(p, t)
            x = x + alpha * p
            r = r - alpha * t
            g2 = dot_fn(r, r)
            p = r + (g2 / gamma) * p
            return (x, r, p, g2)

        return lax.fori_loop(0, its, body, (x, r, p, gamma))[0]

    fdot = lambda a, c: jnp.dot(a, c)  # noqa: E731
    pdot = lambda a, c: lax.psum(jnp.dot(a, c), PARTS_AXIS)  # noqa: E731

    sh = NamedSharding(mesh, P(PARTS_AXIS))
    planes_sh = tuple(jax.device_put(p, sh) for p in A.data)
    b_sh = jax.device_put(b, sh)
    planes_st = tuple(jax.device_put(jnp.asarray(p)[None], sh)
                      for p in A.data)
    b_st = jax.device_put(b[None], sh)

    def make(variant):
        if variant == "single":
            @functools.partial(jax.jit, static_argnames="its")
            def prog(planes, b, its):
                Ad = DiaMatrix(data=planes, offsets=offsets,
                               nrows=N, ncols_padded=N)
                return cg_loop(lambda v: spmv(Ad, v), fdot, b, its)
            return lambda its: prog(A.data, b, its).block_until_ready()
        if variant == "single_dia":
            @functools.partial(jax.jit, static_argnames="its")
            def prog(planes, b, its):
                return cg_loop(lambda v: dia_mv(planes, offsets, N, v),
                               fdot, b, its)
            return lambda its: prog(A.data, b, its).block_until_ready()
        if variant in ("smap_local", "smap_psum"):
            dot = fdot if variant == "smap_local" else pdot

            @functools.partial(jax.jit, static_argnames="its")
            def prog(planes, b, its):
                return jax.shard_map(
                    lambda p_, b_: cg_loop(
                        lambda v: dia_mv(p_, offsets, N, v), dot, b_, its),
                    mesh=mesh, in_specs=(P(PARTS_AXIS), P(PARTS_AXIS)),
                    out_specs=P(PARTS_AXIS), check_vma=False)(planes, b)
            return lambda its: prog(planes_sh, b_sh, its).block_until_ready()
        if variant == "smap_pad":
            def shard(p_, b_, its):
                p_ = tuple(q[0] for q in p_)
                y = cg_loop(lambda v: dia_mv(p_, offsets, N, v),
                            pdot, b_[0], its)
                return y[None]

            @functools.partial(jax.jit, static_argnames="its")
            def prog(planes, b, its):
                return jax.shard_map(
                    functools.partial(shard, its=its),
                    mesh=mesh, in_specs=(P(PARTS_AXIS), P(PARTS_AXIS)),
                    out_specs=P(PARTS_AXIS), check_vma=False)(planes, b)
            return lambda its: prog(planes_st, b_st, its).block_until_ready()
        if variant == "dist_fixed":
            rr, cc, vv, _ = poisson2d_coo(n)
            csr = SymCsrMatrix.from_coo(N, rr, cc, vv).to_csr()
            part = partition_rows(csr, 1, seed=0)
            prob = DistributedProblem.build(csr, part, 1,
                                            dtype=jnp.float32)
            solver = DistCGSolver(prob, kernels="xla")
            b_host = np.ones(N, np.float32)

            def run(its):
                # solve() waits for its result internally
                solver.solve(b_host,
                             criteria=StoppingCriteria(maxits=its),
                             host_result=False)
            return run
        raise ValueError(variant)

    for name in ("single", "single_dia", "smap_local", "smap_psum",
                 "smap_pad", "dist_fixed"):
        run = make(name)

        def timed(its, run=run):
            run(its)  # compile + warm
            ts = []
            for _ in range(3):
                t0 = time.perf_counter()
                run(its)
                ts.append(time.perf_counter() - t0)
            return min(ts)

        t100, t400 = timed(100), timed(400)
        dt = t400 - t100
        rate = 300.0 / dt if dt > 0 else float("nan")
        print(json.dumps({"variant": name,
                          "iters_per_sec": round(rate, 1),
                          "t100": round(t100, 4), "t400": round(t400, 4)}))
        sys.stdout.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
