"""Model-problem generators: 2D/3D Poisson finite-difference matrices.

Rebuilds (and extends to 3D) the reference's ``matrices_generator/poisson.py``
(5-point 2D Poisson on an n x n grid).  Returns COO triplets of the FULL
symmetric matrix; callers needing one-triangle storage filter ``r <= c``.
The benchmark protocol (BASELINE.md) uses 2D n=2048 and 3D up to 512^3.
"""

from __future__ import annotations

import numpy as np

from acg_tpu.io.mtxfile import IDX_DTYPE, MtxFile


def poisson2d_coo(n: int, dtype=np.float64):
    """5-point 2D Poisson stencil on an n x n grid -> full COO (N = n*n)."""
    idx = np.arange(n * n, dtype=IDX_DTYPE)
    i, j = idx // n, idx % n
    rows = [idx]
    cols = [idx]
    vals = [np.full(n * n, 4.0, dtype=dtype)]
    for di, dj in ((-1, 0), (1, 0), (0, -1), (0, 1)):
        ii, jj = i + di, j + dj
        ok = (ii >= 0) & (ii < n) & (jj >= 0) & (jj < n)
        rows.append(idx[ok])
        cols.append((ii * n + jj)[ok])
        vals.append(np.full(ok.sum(), -1.0, dtype=dtype))
    return np.concatenate(rows), np.concatenate(cols), np.concatenate(vals), n * n


def aniso_poisson2d_coo(n: int, eps: float, dtype=np.float64):
    """Anisotropic/STRETCHED 2D Poisson on an n x n tensor grid -> full
    COO (N = n*n): the Laplacian assembled symmetrically (FV/FEM edge
    weights) on a grid whose y-spacings shrink geometrically by the
    stretch factor ``eps = h_min/h_max <= 1``.

    The ill-conditioned SPD family of the preconditioning tier
    (acg_tpu.precond): x-edge weights span ``[eps, 1]`` and y-edge
    weights ``[1, 1/eps]``, so the DIAGONAL varies by ~1/eps across the
    grid -- unlike the constant-diagonal uniform stencil, where Jacobi
    is a no-op scaling -- and the condition number grows ~1/eps beyond
    the uniform grid's.  Measured at n=256, eps=0.01 (f64, rtol 1e-6):
    CG 2956 iterations unpreconditioned, 992 with ``--precond jacobi``
    (3.0x), 718 with ``--precond cheby:4`` (4.1x).

    SPD by construction: a positively-weighted graph Laplacian plus
    Dirichlet boundary terms (symmetric, weakly diagonally dominant,
    strictly at the boundary rows, irreducible).
    """
    if not 0.0 < eps <= 1.0:
        raise ValueError(f"aniso stretch factor must be in (0, 1], "
                         f"got {eps}")
    j = np.arange(n)
    # x-edge weight in grid row j (the h_y(j)/h_x FEM factor) and
    # y-edge weight at horizontal edge e (1/h_y; e = 0 and n are the
    # Dirichlet boundary edges)
    wx = (eps ** ((j + 0.5) / n)).astype(dtype)
    e = np.arange(n + 1)
    wy = (eps ** (-(e / n))).astype(dtype)

    def idx(jj, ii):
        return (jj * n + ii).astype(IDX_DTYPE)

    J, I = np.meshgrid(j, j, indexing="ij")
    rows = [idx(J, I).ravel()]
    cols = [idx(J, I).ravel()]
    vals = [(2 * wx[J] + wy[J] + wy[J + 1]).ravel()]
    for di in (-1, 1):
        ok = (I + di >= 0) & (I + di < n)
        rows.append(idx(J, I)[ok])
        cols.append(idx(J, I + di)[ok])
        vals.append(-wx[J][ok])
    ok = J + 1 < n     # edge between grid rows j and j+1 weighs wy[j+1]
    rows.append(idx(J, I)[ok])
    cols.append(idx(J + 1, I)[ok])
    vals.append(-wy[J + 1][ok])
    rows.append(idx(J + 1, I)[ok])
    cols.append(idx(J, I)[ok])
    vals.append(-wy[J + 1][ok])
    return (np.concatenate(rows), np.concatenate(cols),
            np.concatenate(vals), n * n)


def poisson3d_coo(n: int, dtype=np.float64):
    """7-point 3D Poisson stencil on an n^3 grid -> full COO (N = n^3)."""
    N = n * n * n
    idx = np.arange(N, dtype=IDX_DTYPE)
    i, j, k = idx // (n * n), (idx // n) % n, idx % n
    rows = [idx]
    cols = [idx]
    vals = [np.full(N, 6.0, dtype=dtype)]
    for di, dj, dk in ((-1, 0, 0), (1, 0, 0), (0, -1, 0), (0, 1, 0), (0, 0, -1), (0, 0, 1)):
        ii, jj, kk = i + di, j + dj, k + dk
        ok = (ii >= 0) & (ii < n) & (jj >= 0) & (jj < n) & (kk >= 0) & (kk < n)
        rows.append(idx[ok])
        cols.append(((ii * n + jj) * n + kk)[ok])
        vals.append(np.full(ok.sum(), -1.0, dtype=dtype))
    return np.concatenate(rows), np.concatenate(cols), np.concatenate(vals), N


def poisson_dia(n: int, dim: int = 2, dtype=np.float64):
    """Poisson stencil assembled DIRECTLY as DIA planes -- no COO/CSR
    intermediate, no sort: O(ndiags * N) time and memory.

    This is how a stencil matrix should reach a TPU: the reference goes
    scipy COO -> .mtx file -> parse -> CSR (``matrices_generator/
    poisson.py``), which at N=512^3 (134M rows, ~0.9G nnz) costs tens of
    GB and minutes of preprocessing; the DIA planes ARE the device
    format, built here in one vectorised pass per diagonal.

    Returns ``(planes, offsets, N)`` with the package DIA convention
    ``planes[d][r] = A[r, r + offsets[d]]`` (``ops.spmv.DiaMatrix``).
    """
    N = n ** dim
    diag_val = float(2 * dim)
    offsets, planes = [], []
    # per-axis neighbour pairs: axis a (0 = fastest-varying) has stride
    # n^a and coordinate (r // n^a) % n; the entry A[r, r +- n^a] exists
    # unless the coordinate sits on that boundary.  Viewed as
    # (N/period, n, stride), the boundary rows are one slice of the
    # middle axis -- so each plane is a flat fill plus one strided zero
    # write of N/n entries, no index arithmetic over N at all
    for a in range(dim):
        stride = n ** a
        lo = np.full(N, -1.0, dtype=dtype)
        lo.reshape(-1, n, stride)[:, 0, :] = 0.0
        hi = np.full(N, -1.0, dtype=dtype)
        hi.reshape(-1, n, stride)[:, -1, :] = 0.0
        offsets += [-stride, stride]
        planes += [lo, hi]
    offsets.append(0)
    planes.append(np.full(N, diag_val, dtype=dtype))
    order = np.argsort(offsets)
    return ([planes[i] for i in order],
            tuple(int(offsets[i]) for i in order), N)


def poisson_dia_device(n: int, dim: int = 2, dtype=None):
    """Poisson DIA planes assembled ON DEVICE as one jitted program.

    Same output as :func:`poisson_dia` but with zero host->device
    transfer: the planes are fills plus boundary masks, which XLA
    computes from iotas directly in HBM.  At 512^3 this replaces a
    3.8 GB upload with a sub-second device computation -- the stencil
    analog of the reference generating its matrix on the host and
    shipping it to every GPU (``matrices_generator/poisson.py`` + scatter).

    Returns ``(planes, offsets, N)`` with planes as jax arrays.
    """
    import jax
    import jax.numpy as jnp

    if dtype is None:
        dtype = jnp.float32
    N = n ** dim

    @jax.jit
    def build():
        planes = []
        for a in range(dim):
            stride = n ** a
            coord = (jax.lax.iota(jnp.int32, N) // stride) % n
            planes.append(jnp.where(coord > 0, -1.0, 0.0).astype(dtype))
            planes.append(jnp.where(coord < n - 1, -1.0, 0.0).astype(dtype))
        planes.append(jnp.full((N,), float(2 * dim), dtype=dtype))
        return planes

    # build() order: [lo_a0, hi_a0, lo_a1, hi_a1, ..., diag]
    offsets = [s for a in range(dim) for s in (-(n ** a), n ** a)] + [0]
    order = np.argsort(offsets)
    planes = build()
    return ([planes[i] for i in order],
            tuple(int(offsets[i]) for i in order), N)


def batched_rhs(n: int, nrhs: int, seed: int = 42,
                dtype=np.float64) -> np.ndarray:
    """Default multi-RHS block for ``--nrhs B``: B random unit-norm
    columns (seeded).  Random, NOT replicated ones: parallel columns
    would collapse the block Krylov space to rank 1, making every
    batched/block measurement degenerate -- a serving fleet's requests
    differ, and so must the default benchmark block."""
    rng = np.random.default_rng(seed)
    B = rng.standard_normal((n, int(nrhs))).astype(dtype)
    B /= np.linalg.norm(B, axis=0, keepdims=True)
    return B


def irregular_spd_coo(n: int, avg_degree: float = 16.0, seed: int = 0,
                      dtype=np.float64):
    """Random irregular SPD matrix -> full COO.

    Stands in for the irregular SuiteSparse SPD workloads of the
    benchmark protocol (BASELINE.json configs 4-5: Flan_1565, Serena,
    Queen_4147 -- not redistributable here): a configuration-model graph
    whose degrees follow a truncated power law (so row lengths vary by
    orders of magnitude, defeating banded/DIA layouts and exercising the
    ELL/gather SpMV paths), with negative off-diagonal weights and a
    strictly diagonally dominant diagonal -> symmetric positive
    definite.

    Note: every row sums to exactly 1 (diag = 1 + sum|offdiag|), so
    ``b = ones`` is an eigenvector and CG converges on it in one
    iteration -- use a manufactured solution (random xsol, b = A xsol)
    for convergence behaviour; fixed-iteration timing is unaffected.
    """
    rng = np.random.default_rng(seed)
    # power-law-ish stub counts: most rows short, a heavy tail of hubs
    # pareto(2.2)+1 has mean ~1.83; scale so mean stubs/row ~ avg_degree
    # (each stub becomes one off-diagonal entry in its own row)
    deg = np.minimum((rng.pareto(2.2, n) + 1.0) * (avg_degree * 0.546),
                     n // 4).astype(np.int64)
    stubs = np.repeat(np.arange(n, dtype=IDX_DTYPE), deg)
    rng.shuffle(stubs)
    if stubs.size % 2:
        stubs = stubs[:-1]
    u, v = stubs[0::2], stubs[1::2]
    keep = u != v
    u, v = u[keep], v[keep]
    lo, hi = np.minimum(u, v), np.maximum(u, v)
    edges = np.unique(lo.astype(np.int64) * n + hi)
    lo, hi = (edges // n).astype(IDX_DTYPE), (edges % n).astype(IDX_DTYPE)
    w = -(0.1 + rng.random(lo.size)).astype(dtype)
    # diagonal = 1 + sum of |offdiag| per row -> strict dominance
    diag = np.ones(n, dtype=dtype)
    np.add.at(diag, lo, -w)
    np.add.at(diag, hi, -w)
    idx = np.arange(n, dtype=IDX_DTYPE)
    rows = np.concatenate([idx, lo, hi])
    cols = np.concatenate([idx, hi, lo])
    vals = np.concatenate([diag, w, w])
    return rows, cols, vals, n


def irregular_mtx(n: int, avg_degree: float = 16.0, seed: int = 0) -> MtxFile:
    """Irregular SPD matrix as a symmetric (lower-triangle) MtxFile."""
    r, c, v, N = irregular_spd_coo(n, avg_degree, seed)
    keep = r >= c
    order = np.lexsort((c[keep], r[keep]))
    return MtxFile(object="matrix", format="coordinate", field="real",
                   symmetry="symmetric", nrows=N, ncols=N, nnz=int(keep.sum()),
                   rowidx=r[keep][order], colidx=c[keep][order],
                   vals=v[keep][order])


def poisson_mtx(n: int, dim: int = 2) -> MtxFile:
    """Poisson matrix as a symmetric (lower-triangle) MtxFile."""
    if dim == 2:
        r, c, v, N = poisson2d_coo(n)
    elif dim == 3:
        r, c, v, N = poisson3d_coo(n)
    else:
        raise ValueError(f"dim must be 2 or 3, got {dim}")
    keep = r >= c  # store lower triangle once, symmetry declared in header
    order = np.lexsort((c[keep], r[keep]))
    return MtxFile(object="matrix", format="coordinate", field="real",
                   symmetry="symmetric", nrows=N, ncols=N, nnz=int(keep.sum()),
                   rowidx=r[keep][order], colidx=c[keep][order],
                   vals=v[keep][order],
                   comments=[f"% acg-tpu poisson{dim}d n={n}"])
