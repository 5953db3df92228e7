"""Set-up of a Matrix Market input partitioned over a mesh, as
``acg-tpu FILE.mtx --nparts P --partition-method graph --comm xla
--dtype f32 --manufactured-solution`` does it: the file (the one
``mtx_file`` writes and parses, so the one-chip and the partitioned
cells share it) is parsed into a host CSR, its rows are partitioned by
edge-cut minimisation (``partition_rows``, the built-in bisection with
partition seed 0), each part's rows are split into interior, border and
ghost rows (``partition_matrix``), the parts are stacked for the mesh
(``DistributedProblem.build``: local blocks in the format ``auto``
picks, the ghost block, the halo plan) and solved by ``DistCGSolver``
with the ``all_to_all`` halo, one part a chip.  The manufactured system
is the CLI's: ``numpy.random.default_rng(seed)`` draws xsol, ``b = A
xsol`` in float64 on the host, and the solver scatters and uploads b
itself at each solve, as it does for a CLI user.

A solve leaves x on the chips in the stacked (parts x rows) layout; the
reference reads it in the file's row order, gathered on the host when
``numpy.asarray`` is applied to the answer, after the window."""

from __future__ import annotations

import sys
import time
from pathlib import Path

import numpy as np

from benchmark import spec


def log(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)


class FileOrder:
    """A solve's stacked device answer; ``numpy.asarray`` gathers it
    into the file's row order (``DistributedProblem.gather``)."""

    def __init__(self, problem, stacked):
        self.problem, self.stacked = problem, stacked

    def __array__(self, dtype=None, copy=None):
        x = self.problem.gather(np.asarray(self.stacked))
        return x if dtype is None else x.astype(dtype)


class PartitionedSolver:
    """``DistCGSolver`` as the harness calls it: ``solve`` hands back a
    :class:`FileOrder`, and every other attribute (``stats``,
    ``problem``, ``comm``, ``kernels``, ``placement``) is the solver's
    own."""

    def __init__(self, solver):
        self.solver = solver

    def __getattr__(self, name):
        return getattr(self.solver, name)

    def solve(self, b, **kwargs):
        return FileOrder(self.solver.problem, self.solver.solve(b, **kwargs))


def build(config: dict, traffic: dict, *, cache, dtype=None, kernels=None):
    """``(solver, rhs)``; ``rhs(seed)`` gives ``(b, xsol)``, both on the
    host.  ``dtype`` and ``kernels`` override the configuration's
    precision and the ``auto`` kernel tier (the control runs the
    program's own bf16 path through the same partition)."""
    import jax.numpy as jnp

    from acg_tpu.graph import partition_matrix
    from acg_tpu.io.mtxfile import read_mtx
    from acg_tpu.matrix import SymCsrMatrix
    from acg_tpu.parallel.dist import DistCGSolver, DistributedProblem
    from acg_tpu.parallel.mesh import solve_mesh
    from acg_tpu.partition import partition_rows

    nparts = int(traffic["nparts"])
    dt = jnp.dtype(dtype or config["dtype"])
    mtx_file = spec.module(Path(__file__).with_name("mtx_file.py"))
    t0 = time.perf_counter()
    A = SymCsrMatrix.from_mtx(read_mtx(mtx_file.matrix_file(config, cache)))
    csr = A.to_csr()
    t1 = time.perf_counter()
    part = partition_rows(csr, nparts, seed=0, method="graph",
                          use_metis="never")
    t2 = time.perf_counter()
    subs = partition_matrix(csr, part, nparts)
    t3 = time.perf_counter()
    prob = DistributedProblem.build(csr, part, nparts, dtype=dt, subs=subs,
                                    vector_dtype=dt)
    t4 = time.perf_counter()
    solver = DistCGSolver(prob, comm="xla", kernels=kernels or "auto",
                          mesh=solve_mesh(nparts))
    rows = [s.nowned for s in prob.subs]
    log(f"partition: {nparts} parts, rows {min(rows)}-{max(rows)}, border "
        f"max {max(s.nborder for s in prob.subs)}, ghosts max "
        f"{max(s.nghost for s in prob.subs)}, halo entries "
        f"{sum(s.halo.total_send for s in prob.subs)}, local blocks "
        f"{prob.local.format}; host seconds: file {t1 - t0:.3f}, "
        f"partition_rows {t2 - t1:.3f}, partition_matrix {t3 - t2:.3f}, "
        f"build {t4 - t3:.3f}")

    def rhs(seed: int):
        rng = np.random.default_rng(seed)
        xsol = rng.standard_normal(A.nrows)
        xsol /= np.linalg.norm(xsol)
        return A.dsymv(xsol), xsol

    return PartitionedSolver(solver), rhs
