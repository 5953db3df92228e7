"""Device-resident sparse matrix formats and SpMV for TPU.

The reference's device SpMV is a merge-based CSR kernel tuned for GPU warp
semantics (``cg-kernels-cuda.cu:340-441``).  That idiom does not map to a
vector architecture; its *goal* -- load balance across irregular rows --
maps on TPU to row padding / binning (SURVEY.md section 7 "hard parts").
Two formats are provided:

* :class:`EllMatrix` -- ELLPACK: row-padded (n, K) value/column planes.
  For stencil-like matrices (Poisson: K<=5 in 2D, K<=7 in 3D) padding waste
  is tiny and SpMV becomes K fused gather-multiply-accumulates.
* :class:`CooMatrix` -- sorted COO + segment-sum: the general fallback for
  matrices with skewed row lengths where ELL padding would blow up memory.
* :class:`DiaMatrix` -- diagonal storage: y = sum_d data[d] * shift(x, d)
  with *static* offsets.  For banded matrices (stencils in natural order,
  or anything after RCM reordering) SpMV becomes pure VPU multiply-adds on
  statically-sliced vectors -- NO gathers at all.  Measured on TPU this is
  ~30x faster than the ELL gather path on poisson2d n=2048; XLA gathers
  with arbitrary indices do not vectorise on TPU.  A hand-written Pallas
  kernel (:func:`acg_tpu.ops.pallas_kernels.dia_spmv`) shaves a further
  ~1.2x off the DIA path on TPU by reading x through VMEM once instead of
  once per diagonal (solver flag ``kernels="pallas"``).

Format choice is automatic in :func:`device_matrix_from_csr` from the
sparsity structure (diagonal count, then row-length histogram), computed at
load time (same decision the reference makes statically by choosing its
merge-CSR kernel).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Union

import jax
import jax.numpy as jnp
import numpy as np


@functools.partial(jax.tree_util.register_dataclass,
                   data_fields=["data", "cols"], meta_fields=["nrows", "ncols_padded"])
@dataclasses.dataclass
class EllMatrix:
    """ELLPACK storage: data[i, k] * x[cols[i, k]] summed over k.

    Padding entries have data == 0 and cols == 0 (a harmless gather).
    ``ncols_padded`` is the length of the x vector this matrix multiplies
    (owned + ghost entries for partitioned off-diagonal blocks).
    """

    data: jax.Array  # (nrows, K) float
    cols: jax.Array  # (nrows, K) int32
    nrows: int
    ncols_padded: int

    @property
    def K(self) -> int:
        return self.data.shape[1]


@functools.partial(jax.tree_util.register_dataclass,
                   data_fields=["rows", "cols", "vals"],
                   meta_fields=["nrows", "ncols_padded"])
@dataclasses.dataclass
class CooMatrix:
    """Row-sorted COO; SpMV via segment_sum (general irregular fallback)."""

    rows: jax.Array  # (nnz,) int32, sorted ascending
    cols: jax.Array  # (nnz,) int32
    vals: jax.Array  # (nnz,) float
    nrows: int
    ncols_padded: int


@functools.partial(jax.tree_util.register_dataclass,
                   data_fields=["data"],
                   meta_fields=["offsets", "nrows", "ncols_padded"])
@dataclasses.dataclass
class DiaMatrix:
    """Diagonal (DIA) storage: ``data[d][i] = A[i, i + offsets[d]]``.

    SpMV is a sum of elementwise products against statically-shifted views
    of x -- fully vectorised on the VPU, no gathers.  ``offsets`` is a
    static tuple so each shift compiles to a static slice.

    ``data`` is a tuple of separate (nrows,) planes rather than one
    (ndiags, nrows) array: 1-D jit parameters keep their trivial layout,
    while a 2-D parameter was measured 2-3x slower inside the solve loop
    on TPU (XLA cannot re-lay-out runtime parameters the way it does
    compile-time constants).
    """

    data: tuple            # ndiags x (nrows,) float planes
    offsets: tuple         # (ndiags,) static ints, ascending
    nrows: int
    ncols_padded: int

    @property
    def dtype(self):
        return self.data[0].dtype


@functools.partial(jax.tree_util.register_dataclass,
                   data_fields=["bin_rows", "bin_data", "bin_cols",
                                "tail_rows", "tail_cols", "tail_vals"],
                   meta_fields=["bin_ks", "nrows", "ncols_padded"])
@dataclasses.dataclass
class BinnedEllMatrix:
    """Length-binned ELL: rows grouped by nnz into near-tight width
    bins, each bin a dense (m_b, K_b) gather-multiply-reduce, plus a
    sorted-COO tail for hub rows wider than the largest bin.

    The TPU answer to the reference's merge-based CSR kernel
    (``cg-kernels-cuda.cu:340-441``): its goal -- load balance across
    wildly skewed row lengths -- maps on a vector architecture to
    eliminating both the padding waste of plain ELL (power-law tails
    make K_max huge) and the per-nnz ``segment_sum`` machinery of COO,
    which costs as much as the gather itself (measured 177 ms vs 130 ms
    per 8.3M-nnz pass on v5e).  Each bin reduces over a STATIC K_b axis
    (no segment ids), and per-bin results scatter-add into y at unique
    row positions (~n ops, not ~nnz).  Geometric bin boundaries bound
    padding at ~1.33x.
    """

    bin_rows: tuple   # per bin: (m_b,) int32 original row ids
    bin_data: tuple   # per bin: (m_b, K_b) values
    bin_cols: tuple   # per bin: (m_b, K_b) int32 (padding -> col 0, val 0)
    tail_rows: jax.Array  # (t,) int32 sorted; hub-row leftovers
    tail_cols: jax.Array  # (t,) int32
    tail_vals: jax.Array  # (t,)
    bin_ks: tuple     # static K_b per bin
    nrows: int
    ncols_padded: int

    @property
    def dtype(self):
        if self.bin_data:
            return self.bin_data[0].dtype
        return self.tail_vals.dtype


DeviceMatrix = Union[EllMatrix, CooMatrix, DiaMatrix, BinnedEllMatrix]

# A fifth member by protocol rather than by type: matrix-free operators
# (acg_tpu.ops.operator) expose ``matfree_apply``/``matfree_diagonal``/
# ``matfree_nnz`` and are accepted everywhere a DeviceMatrix is -- the
# dispatchers below check the protocol FIRST, so an operator never
# falls through to a stored-plane path that does not exist for it.


def _is_matfree(A) -> bool:
    return hasattr(A, "matfree_apply")

# geometric (x1.5) bin widths: padding bounded at ~1.33x, ~18 bins max
BELL_WIDTHS = (1, 2, 3, 4, 6, 8, 12, 16, 24, 32, 48, 64, 96, 128, 192,
               256, 384, 512)


def binned_ell_from_csr(csr, dtype=jnp.float32,
                        widths=BELL_WIDTHS) -> BinnedEllMatrix:
    """Host-side CSR -> length-binned ELL (+ sorted-COO hub tail)."""
    nrows, ncols = csr.shape
    indptr = np.asarray(csr.indptr)
    row_nnz = np.diff(indptr)
    widths = np.asarray(widths)
    # bin index per row: first width >= nnz; hubs (> max width) -> tail
    bidx = np.searchsorted(widths, row_nnz)
    bin_rows, bin_data, bin_cols, bin_ks = [], [], [], []
    for b, K in enumerate(widths):
        rows = np.flatnonzero(bidx == b).astype(np.int32)
        if rows.size == 0:
            continue
        m = rows.size
        data = np.zeros((m, K), dtype=np.float64)
        cols = np.zeros((m, K), dtype=np.int32)
        nnz_b = row_nnz[rows]
        flat_r = np.repeat(np.arange(m), nnz_b)
        flat_p = (np.arange(nnz_b.sum())
                  - np.repeat(np.cumsum(nnz_b) - nnz_b, nnz_b))
        src = (np.repeat(indptr[rows], nnz_b)
               + flat_p).astype(np.int64)
        data[flat_r, flat_p] = np.asarray(csr.data)[src]
        cols[flat_r, flat_p] = np.asarray(csr.indices)[src]
        bin_rows.append(jnp.asarray(rows))
        bin_data.append(jnp.asarray(data, dtype=dtype))
        bin_cols.append(jnp.asarray(cols))
        bin_ks.append(int(K))
    hub = np.flatnonzero(bidx >= widths.size)
    t_rows = np.repeat(hub, row_nnz[hub]).astype(np.int32)
    t_src = np.concatenate([np.arange(indptr[r], indptr[r + 1])
                            for r in hub]) if hub.size else np.zeros(0, np.int64)
    return BinnedEllMatrix(
        bin_rows=tuple(bin_rows), bin_data=tuple(bin_data),
        bin_cols=tuple(bin_cols),
        tail_rows=jnp.asarray(t_rows),
        tail_cols=jnp.asarray(np.asarray(csr.indices)[t_src], dtype=jnp.int32),
        tail_vals=jnp.asarray(np.asarray(csr.data)[t_src], dtype=dtype),
        bin_ks=tuple(bin_ks), nrows=nrows, ncols_padded=ncols)


def csr_diag_offsets(csr) -> np.ndarray:
    """Distinct diagonal offsets (col - row) of a scipy sparse matrix,
    ascending.  Works for rectangular blocks (e.g. owned x ghost)."""
    coo = csr.tocoo()
    return np.unique(coo.col.astype(np.int64) - coo.row.astype(np.int64))


def dia_planes_fixed(csr, offsets, nrows_pad: int) -> np.ndarray:
    """Host-side CSR -> (ndiags, nrows_pad) DIA planes for a *given* offset
    set (used for mesh-uniform stacking: every part stores the union of all
    parts' offsets, missing diagonals as zero planes)."""
    offsets = np.asarray(offsets, dtype=np.int64)
    coo = csr.tocoo()
    diag = coo.col.astype(np.int64) - coo.row.astype(np.int64)
    dmap = np.searchsorted(offsets, diag)
    if diag.size and ((dmap >= offsets.size) | (offsets[dmap % offsets.size] != diag)).any():
        raise ValueError("matrix has diagonals outside the given offset set")
    data = np.zeros((offsets.size, nrows_pad), dtype=np.float64)
    data[dmap, coo.row] = coo.data
    return data


def acc_dtype(dtype):
    """Accumulation dtype for reductions over ``dtype`` storage: sub-f32
    storage (bf16) accumulates in f32 -- the converts ride the VPU for
    free while HBM traffic stays half-width -- wider dtypes accumulate
    natively.  The storage/compute split of the mixed-precision tier
    (the designed deviation from the reference's all-f64 arithmetic,
    ``comm.h:180-183``; SURVEY.md section 7 "hard parts")."""
    return jnp.promote_types(dtype, jnp.float32)


def dia_mv(planes, offsets, nrows: int, x: jax.Array) -> jax.Array:
    """y = A @ x for DIA planes (each (nrows,)) with static ``offsets``:
    ``y[i] = sum_d planes[d][i] * x[i + offsets[d]]``.  Pure VPU
    multiply-adds on statically-sliced views -- no gathers.  ``x`` may be
    shorter or longer than ``nrows`` (rectangular blocks); out-of-range
    entries read padded zeros.  Sub-f32 storage accumulates in f32 and
    rounds once on the final store (:func:`acc_dtype`)."""
    L = max(0, -min(offsets))
    R = max(0, max(offsets) + nrows - x.shape[0])
    adt = acc_dtype(x.dtype)
    xp = jnp.pad(x, (L, R))
    y = jnp.zeros((nrows,), dtype=adt)
    for plane, off in zip(planes, offsets):
        y = y + (plane.astype(adt)
                 * jax.lax.dynamic_slice(xp, (L + off,), (nrows,)).astype(adt))
    return y.astype(x.dtype)


def dia_mv_roll(planes, offsets, x: jax.Array) -> jax.Array:
    """``y = A @ x`` for square DIA planes via CYCLIC shifts:
    ``y = sum_d planes[d] * roll(x, -offsets[d])``.

    Equivalent to :func:`dia_mv` when every plane is zero at positions
    whose column would fall outside ``[0, n)`` -- true by construction
    for planes built by :func:`dia_from_csr` / :func:`dia_planes_fixed`
    / the stencil generators, since no matrix entry exists off the end
    of a diagonal: the wrapped values multiply structural zeros.

    This is the SPMD-native formulation of the distributed stencil SpMV:
    under ``jit`` over a sharded ``x``, XLA compiles each roll into
    boundary ``collective-permute``s -- the halo exchange of the
    reference's ``acghalo`` engine (``halo.c``), *derived by the
    partitioner* instead of hand-planned (verified: the 8-way sharded
    3D-Poisson program contains collective-permutes and zero
    all-gathers).  Padding-based shifts (:func:`dia_mv`) would instead
    break the even sharding and force gathers.
    """
    adt = acc_dtype(x.dtype)
    y = jnp.zeros_like(x, dtype=adt)
    for plane, off in zip(planes, offsets):
        y = y + plane.astype(adt) * jnp.roll(x, -off).astype(adt)
    return y.astype(x.dtype)


def dia_from_csr(csr, dtype=jnp.float32) -> DiaMatrix:
    """Convert a scipy CSR matrix to DIA planes (host-side)."""
    nrows, ncols = csr.shape
    coo = csr.tocoo()
    diag = coo.col.astype(np.int64) - coo.row.astype(np.int64)
    offsets = np.unique(diag)
    data = np.zeros((offsets.size, nrows), dtype=np.float64)
    dmap = np.searchsorted(offsets, diag)
    data[dmap, coo.row] = coo.data
    return DiaMatrix(data=tuple(jnp.asarray(data[d], dtype=dtype)
                                for d in range(offsets.size)),
                     offsets=tuple(int(o) for o in offsets),
                     nrows=nrows, ncols_padded=ncols)


def ell_planes_from_csr(rowptr, colidx, vals, nrows_pad: int,
                        pad_k: int | None = None):
    """Host-side CSR -> zero-padded ELL planes (numpy), rows padded to
    ``nrows_pad`` and width to ``pad_k`` (used for mesh-uniform stacking)."""
    rowptr = np.asarray(rowptr)
    colidx = np.asarray(colidx)
    vals = np.asarray(vals)
    nrows = len(rowptr) - 1
    row_nnz = np.diff(rowptr)
    K = int(row_nnz.max()) if row_nnz.size else 0
    if pad_k is not None:
        K = max(K, pad_k)
    K = max(K, 1)
    data = np.zeros((nrows_pad, K), dtype=np.float64)
    cols = np.zeros((nrows_pad, K), dtype=np.int32)
    # vectorised fill: position of each nz within its row
    rows = np.repeat(np.arange(nrows), row_nnz)
    pos = np.arange(len(colidx)) - np.repeat(rowptr[:-1], row_nnz)
    data[rows, pos] = vals
    cols[rows, pos] = colidx
    return data, cols


def ell_from_csr(rowptr, colidx, vals, nrows: int, ncols: int,
                 dtype=jnp.float32, pad_k: int | None = None) -> EllMatrix:
    """Convert host CSR arrays to a device EllMatrix."""
    data, cols = ell_planes_from_csr(rowptr, colidx, vals, nrows, pad_k)
    return EllMatrix(data=jnp.asarray(data, dtype=dtype),
                     cols=jnp.asarray(cols), nrows=nrows, ncols_padded=ncols)


def coo_from_csr(rowptr, colidx, vals, nrows: int, ncols: int,
                 dtype=jnp.float32) -> CooMatrix:
    rowptr = np.asarray(rowptr)
    row_nnz = np.diff(rowptr)
    rows = np.repeat(np.arange(nrows, dtype=np.int32), row_nnz)
    return CooMatrix(rows=jnp.asarray(rows),
                     cols=jnp.asarray(np.asarray(colidx), dtype=jnp.int32),
                     vals=jnp.asarray(np.asarray(vals), dtype=dtype),
                     nrows=nrows, ncols_padded=ncols)


# shared DIA-eligibility thresholds (device_matrix_from_csr, CLI partition
# auto-method; dist._stack_local_blocks keeps headroom over MAX_DIAGS
# because the union of per-part offset sets can exceed any one count)
MAX_DIAGS = 64
DIA_WASTE_LIMIT = 3.0


def count_diagonals(csr) -> int:
    return int(csr_diag_offsets(csr).size)


def prefers_dia(csr, max_diags: int = MAX_DIAGS,
                waste_limit: float = DIA_WASTE_LIMIT) -> bool:
    """True when the matrix is banded enough that gather-free DIA storage
    (and hence a contiguous band partition) is the right TPU choice."""
    if not csr.nnz:
        return False
    ndiags = count_diagonals(csr)
    return ndiags <= max_diags and ndiags * csr.shape[0] / csr.nnz <= waste_limit


def device_matrix_from_csr(csr, dtype=jnp.float32, format: str = "auto",
                           ell_waste_limit: float = 3.0,
                           dia_waste_limit: float = DIA_WASTE_LIMIT,
                           max_diags: int = MAX_DIAGS) -> DeviceMatrix:
    """Pick DIA, ELL or COO from the sparsity structure of a scipy CSR.

    DIA wins when the matrix is banded (few distinct diagonals, bounded
    fill waste) -- the common case for stencil/FEM matrices in natural or
    RCM order, and by far the fastest SpMV on TPU (no gathers).  Otherwise
    ELL when padding waste (K_max * n / nnz) stays below
    ``ell_waste_limit``, else segment-sum COO.
    """
    nrows, ncols = csr.shape
    row_nnz = np.diff(csr.indptr)
    K = int(row_nnz.max()) if nrows else 0
    nnz = csr.nnz
    if format == "auto":
        ndiags = count_diagonals(csr)
        if (ndiags <= max_diags and nnz
                and ndiags * nrows / nnz <= dia_waste_limit):
            format = "dia"
        else:
            waste = (K * nrows / nnz) if nnz else 1.0
            # skewed row lengths: binned ELL beats COO by replacing the
            # per-nnz segment_sum (as expensive as the gather itself)
            # with static per-bin reductions (measured ~2x -- BASELINE)
            format = "ell" if waste <= ell_waste_limit else "bell"
    if format == "dia":
        return dia_from_csr(csr, dtype)
    if format == "ell":
        return ell_from_csr(csr.indptr, csr.indices, csr.data, nrows, ncols, dtype)
    if format == "bell":
        return binned_ell_from_csr(csr, dtype)
    if format == "coo":
        return coo_from_csr(csr.indptr, csr.indices, csr.data, nrows, ncols, dtype)
    raise ValueError(f"unknown device matrix format {format!r}")


def matrix_dtype(A: DeviceMatrix):
    """Value-storage dtype of any device matrix format."""
    if hasattr(A, "dtype"):
        return A.dtype
    if hasattr(A, "data"):
        return A.data.dtype
    return A.vals.dtype


def matrix_index_bytes(A: DeviceMatrix) -> float:
    """Index bytes read per stored nonzero during SpMV (DIA: none;
    ELL-family: one int32 column; COO: row + column; binned ELL: the
    nnz-weighted mix of its 4 B bins and 8 B hub tail; matrix-free
    operators: none -- no stored nonzeros exist)."""
    if _is_matfree(A) or isinstance(A, DiaMatrix):
        return 0.0
    if isinstance(A, CooMatrix):
        return 8.0
    if isinstance(A, BinnedEllMatrix):
        bins = sum(int(d.size) for d in A.bin_data)  # padded entries read too
        tail = int(A.tail_vals.size)
        total = bins + tail
        return (4.0 * bins + 8.0 * tail) / total if total else 4.0
    return 4.0


def spmv(A: DeviceMatrix, x: jax.Array) -> jax.Array:
    """y = A @ x for a device sparse matrix (jit-safe, differentiable).

    Wrapped in a `jax.named_scope` so profiler traces show the SpMV as a
    labelled range (the reference's NVTX tier, ``cgcuda.c:771-801``).
    """
    with jax.named_scope(f"spmv_{type(A).__name__}"):
        return _spmv(A, x)


def _binned_ell_mv(A: BinnedEllMatrix, x: jax.Array) -> jax.Array:
    adt = acc_dtype(x.dtype)
    y = jnp.zeros((A.nrows,), dtype=adt)
    for rows, data, cols in zip(A.bin_rows, A.bin_data, A.bin_cols):
        contrib = jnp.einsum("mk,mk->m", data, x[cols],
                             preferred_element_type=adt)
        # each row lives in exactly one bin: unique scatter positions
        y = y.at[rows].add(contrib, unique_indices=True)
    if A.tail_rows.size:
        prod = A.tail_vals.astype(adt) * x[A.tail_cols].astype(adt)
        y = y + jax.ops.segment_sum(prod, A.tail_rows,
                                    num_segments=A.nrows,
                                    indices_are_sorted=True)
    return y.astype(x.dtype)


def _spmv(A: DeviceMatrix, x: jax.Array) -> jax.Array:
    adt = acc_dtype(x.dtype)
    if _is_matfree(A):
        # matrix-free operator tier (ops.operator): plane values are
        # GENERATED inside the apply -- zero matrix HBM traffic
        return A.matfree_apply(x)
    if isinstance(A, BinnedEllMatrix):
        return _binned_ell_mv(A, x)
    if isinstance(A, DiaMatrix):
        # static shifted views of x; XLA fuses into one VPU loop
        return dia_mv(A.data, A.offsets, A.nrows, x)
    if isinstance(A, EllMatrix):
        # K gathers of n elements each; XLA fuses the multiply-accumulate.
        return jnp.einsum("nk,nk->n", A.data, x[A.cols],
                          preferred_element_type=adt).astype(x.dtype)
    if isinstance(A, CooMatrix):
        prod = A.vals.astype(adt) * x[A.cols].astype(adt)
        return jax.ops.segment_sum(prod, A.rows, num_segments=A.nrows,
                                   indices_are_sorted=True).astype(x.dtype)
    raise TypeError(f"unsupported device matrix {type(A)}")


def matrix_diagonal(A: DeviceMatrix) -> jax.Array:
    """``diag(A)`` as an (nrows,) device array, jit-safe -- the
    preconditioning tier's setup primitive (acg_tpu.precond): extracted
    once per solver, zero host transfers.  Rows without a stored
    diagonal entry (structural padding of the stacked layouts) come
    back exactly 0, which the Jacobi state builder turns into a 0
    inverse (padded residual entries are exactly 0 by construction)."""
    adt = acc_dtype(matrix_dtype(A))
    if _is_matfree(A):
        # the operator-path twin: analytic diagonal through the
        # operator's own hook (typed refusal for user operators
        # registered without one) -- what makes --precond jacobi work
        # matrix-free
        return A.matfree_diagonal().astype(adt)
    if isinstance(A, DiaMatrix):
        if 0 in A.offsets:
            return A.data[A.offsets.index(0)][: A.nrows].astype(adt)
        return jnp.zeros((A.nrows,), dtype=adt)
    if isinstance(A, EllMatrix):
        rows = jnp.arange(A.nrows)[:, None]
        return jnp.sum(jnp.where(A.cols == rows, A.data, 0),
                       axis=1).astype(adt)
    if isinstance(A, CooMatrix):
        on = A.rows == A.cols
        return jax.ops.segment_sum(
            jnp.where(on, A.vals, 0).astype(adt), A.rows,
            num_segments=A.nrows, indices_are_sorted=True)
    if isinstance(A, BinnedEllMatrix):
        d = jnp.zeros((A.nrows,), dtype=adt)
        for rows, data, cols in zip(A.bin_rows, A.bin_data, A.bin_cols):
            contrib = jnp.sum(jnp.where(cols == rows[:, None], data, 0),
                              axis=1).astype(adt)
            d = d.at[rows].add(contrib, unique_indices=True)
        if A.tail_rows.size:
            on = A.tail_rows == A.tail_cols
            d = d + jax.ops.segment_sum(
                jnp.where(on, A.tail_vals, 0).astype(adt), A.tail_rows,
                num_segments=A.nrows, indices_are_sorted=True)
        return d
    raise TypeError(f"unsupported device matrix {type(A)}")


@jax.jit
def _count_nonzero_on_device(arrays):
    """Total nonzeros across a pytree of arrays, as ONE compiled device
    reduction returning a scalar."""
    leaves = jax.tree_util.tree_leaves(arrays)
    return sum(jnp.count_nonzero(a) for a in leaves)


def spmv_flops(A: DeviceMatrix) -> float:
    """Analytic flops per SpMV, reference convention (3 per stored nz).

    nnz is counted ON DEVICE: pulling the planes to the host for a numpy
    count would be an O(matrix) device->host copy -- ~3.8 GB for the
    512^3 DIA planes -- for a flop statistic.  Only one scalar crosses
    the wire here."""
    if _is_matfree(A):
        # analytic count: no planes exist to scan, on device or off
        return 3.0 * float(A.matfree_nnz())
    if isinstance(A, DiaMatrix):
        nnz = float(_count_nonzero_on_device(tuple(A.data)))
    elif isinstance(A, EllMatrix):
        nnz = float(_count_nonzero_on_device((A.data,)))
    elif isinstance(A, BinnedEllMatrix):
        nnz = float(_count_nonzero_on_device(tuple(A.bin_data))
                    + A.tail_vals.size)
    else:
        nnz = float(A.vals.size)
    return 3.0 * nnz
