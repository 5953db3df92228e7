"""Distributed CG over a TPU device mesh.

The multi-device counterpart of :mod:`acg_tpu.solvers.jax_cg`, rebuilding
the reference's distributed solve paths (``acgsolvercuda_solvempi``,
``_solve_pipelined``, ``cgcuda.c:403-1917``; device-initiated variants
``cg-kernels-cuda.cu:627-1688``) in the execution model XLA natively
provides: ONE compiled SPMD program containing the whole solve loop --
which is precisely the reference's monolithic persistent-kernel design,
with `lax.psum` in place of NVSHMEM allreduce and an `all_to_all` halo in
place of put-with-signal neighbour messaging.

Data layout (host-built by :class:`DistributedProblem`):
  * every per-part array is padded to the max size across parts (XLA needs
    identical shapes per shard; the reference does the same max-sizing for
    NVSHMEM symmetric buffers, ``halo.c:883-887``), stacked on a leading
    ``parts`` axis, and sharded over the 1-D solve mesh;
  * vectors are `[owned | padding]`; padding rows of the matrix blocks are
    all-zero so padded entries stay exactly zero through every update and
    reduction -- no masks needed anywhere in the loop;
  * the local (owned x owned) and off-diagonal (owned x ghost) blocks are
    separate (the reference's ``f*``/``o*`` split), so XLA can overlap the
    halo all_to_all with the local-block SpMV -- the same communication/
    computation overlap the reference schedules by hand with streams and
    events (``cgcuda.c:855-899``);
  * the local block is stored as gather-free DIA planes whenever the
    partition keeps it banded (:class:`StackedLocalBlock`; owned rows are
    re-sorted into natural order for this -- ``graph.reorder_owned_
    natural``), with ELL gather planes as the general fallback; the ghost
    block is compressed to the coupled (border) rows only
    (:class:`StackedGhostBlock`).
"""

from __future__ import annotations

import dataclasses
import functools
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from acg_tpu.errors import (AcgError, BreakdownError, ErrorCode,
                            NotConvergedError)
from acg_tpu.graph import (Subdomain, partition_matrix, reorder_owned_natural,
                           scatter_vector)
from acg_tpu.ops.spmv import (acc_dtype, csr_diag_offsets, dia_mv,
                              dia_planes_fixed, ell_planes_from_csr)
from acg_tpu.parallel.halo import DeviceHaloPlan, build_device_halo, halo_exchange
from acg_tpu.parallel.halo_dma import halo_exchange_dma
from acg_tpu.parallel.mesh import PARTS_AXIS, shard_devices, solve_mesh
from acg_tpu.parallel.reductions import make_pdot, make_pdotk
from acg_tpu.parallel.multihost import get_global, put_global
from acg_tpu.solvers.jax_cg import _breakdown_guard, _iterate
from acg_tpu.solvers.stats import (SolverStats, StoppingCriteria,
                                   cg_flops_per_iteration)

# the reference's --comm spellings mapped onto our two transports
# (cuda/acg-cuda.c:321-377): ONE copy, shared by the CLI, the explain
# tier and the commbench observatory
COMM_ALIASES = {"mpi": "xla", "nccl": "xla", "nvshmem": "dma"}


def resolve_comm(name: str) -> str:
    """Transport for a dist solver from a --comm spelling; ``none``
    (the CLI's single-device selector) resolves to the xla transport
    for analysis passes that build a mesh tier regardless."""
    c = COMM_ALIASES.get(str(name), str(name))
    return "xla" if c == "none" else c


def _ell_mv(data: jax.Array, cols: jax.Array, x: jax.Array) -> jax.Array:
    return jnp.einsum("nk,nk->n", data, x[cols],
                      preferred_element_type=acc_dtype(x.dtype)
                      ).astype(x.dtype)


@dataclasses.dataclass
class StackedLocalBlock:
    """Per-part owned x owned blocks, stacked over the mesh (leading axis
    = parts) in the fastest eligible device format.

    ``"dia"``: gather-free diagonal planes (one (P, nrows) array per
    offset; the union of all parts' offsets is stored so shapes are
    mesh-uniform).  Chosen when the partition keeps local blocks banded --
    contiguous parts of a banded matrix (``partition_rows_band``) with
    owned rows in natural order.  ``"ell"``: row-padded gather planes
    ``(data, cols)``, the general fallback (scattered partitions).
    ``"binnedell"``: the length-binned layout of
    :class:`acg_tpu.ops.spmv.BinnedEllMatrix` stacked per part
    (mesh-uniform per-bin row maxima + a padded COO hub tail) -- chosen
    by the same histogram rule as the single-device ``auto`` when
    plain-ELL padding waste blows past its limit (power-law /
    SuiteSparse-class workloads; the reference's merge-CSR load-balance
    goal, ``cg-kernels-cuda.cu:340-441``, round-4 verdict item 3).
    """

    format: str      # "dia" | "ell" | "binnedell" | "matfree"
    arrays: tuple    # dia: ndiags x (P, nrows); ell: (data (P,nrows,K), cols)
    #                  binnedell: (bin_rows, bin_data, bin_cols tuples,
    #                              tail_rows, tail_cols, tail_vals)
    #                  matfree: (row0 (P,1), nowned (P,1), *tables (P,L))
    offsets: tuple   # dia/matfree: static diagonal offsets, ascending
    nrows: int
    bin_ks: tuple = ()   # binnedell only: static K_b per bin
    # matfree only (acg_tpu.ops.operator / arm_matfree): the stencil
    # operator TEMPLATE -- static metadata (kind, grid, dtype) keying
    # the in-shard plane generation; its coefficient tables ride
    # ``arrays`` stacked per part so they shard like every other block
    operator: object = None

    def gen_planes(self, arrays):
        """Matfree: this shard's LOCAL-block DIA planes, generated from
        the stacked (row0, nowned, *tables) arrays -- global stencil
        values at rows [row0, row0 + nrows) masked to the owned x owned
        window, bitwise-equal to what ``dia_planes_fixed`` would have
        assembled (out-of-part couplings live in the ghost block,
        padding rows are zero)."""
        from acg_tpu.ops.operator import stencil_planes
        op = self.operator
        row0 = arrays[0].reshape(-1)[0]
        nown = arrays[1].reshape(-1)[0]
        return stencil_planes(op.kind, op.grid, self.offsets,
                              tuple(arrays[2:]), self.nrows, op.dtype,
                              row0=row0, nowned=nown)

    def shard_mv(self, arrays, x):
        """y = A_local @ x for one shard (arrays = leading axis stripped)."""
        if self.format == "matfree":
            # the matrix-free stencil tier: plane values generated in
            # the shard (fused by XLA into the accumulate), then the
            # SAME dia_mv accumulation as the assembled DIA path --
            # zero matrix HBM traffic, bitwise-equal trajectories
            return dia_mv(self.gen_planes(arrays), self.offsets,
                          self.nrows, x)
        if self.format == "dia":
            return dia_mv(arrays, self.offsets, self.nrows, x)
        if self.format == "binnedell":
            bin_rows, bin_data, bin_cols, t_rows, t_cols, t_vals = arrays
            adt = acc_dtype(x.dtype)
            y = jnp.zeros((self.nrows,), dtype=adt)
            for rows, data, cols in zip(bin_rows, bin_data, bin_cols):
                contrib = jnp.einsum("mk,mk->m", data, x[cols],
                                     preferred_element_type=adt)
                # padding rows index nrows -> dropped by the jit
                # scatter's OOB mode (NOT unique_indices: every padding
                # row shares that id)
                y = y.at[rows].add(contrib)
            if t_vals.shape[-1]:
                prod = t_vals.astype(adt) * x[t_cols].astype(adt)
                y = y.at[t_rows].add(prod)
            return y.astype(x.dtype)
        data, cols = arrays
        return _ell_mv(data, cols, x)


@dataclasses.dataclass
class StackedGhostBlock:
    """Per-part owned x ghost off-diagonal blocks, compressed to the rows
    that actually touch ghosts (the reference stores its ``o*`` block over
    border rows only, ``symcsrmatrix.h:249-292``; here the coupled-row
    list replaces the contiguous border range).  SpMV gathers ghost values
    for ``bmax`` coupled rows and scatter-adds their contributions --
    O(border) work instead of O(owned)."""

    rows: jax.Array   # (P, bmax) int32, ascending; padding = nrows (dropped)
    data: jax.Array   # (P, bmax, Kg)
    cols: jax.Array   # (P, bmax, Kg) int32 into the ghost vector
    nrows: int
    bmax: int

    def shard_mv(self, arrays, xg):
        rows, data, cols = arrays
        contrib = jnp.einsum("bk,bk->b", data, xg[cols],
                             preferred_element_type=acc_dtype(xg.dtype)
                             ).astype(xg.dtype)
        # padding rows index nrows: out of bounds -> dropped by scatter
        return jnp.zeros((self.nrows,), xg.dtype).at[rows].add(
            contrib, indices_are_sorted=True)


@dataclasses.dataclass(frozen=True)
class UniformShapes:
    """Mesh-uniform sizing agreed across controllers for the LOCAL-READ
    flow (each controller sees only its own parts): the union DIA offset
    set (or None -> ELL), padded widths, and halo maxima.  The analog of
    the reference's max-allreduce symmetric-buffer sizing
    (``halo.c:883-887``), computed by one small allgather."""

    offsets: tuple | None   # DIA offsets union, or None for the ELL path
    Kl: int                 # max local-block row width
    bmax: int               # max coupled (border) rows per part
    Kg: int                 # max ghost-block row width
    maxcnt: int             # max per-neighbour halo send count
    nmax_ghost: int         # max ghost count per part
    nnz_total: int
    halo_send_total: int = 0   # sum of per-part halo send entries
    # binned-ELL sizing (round-4 verdict item 3): per-BELL_WIDTHS-bin
    # max row count over all parts, and the max hub-tail nnz; None when
    # the plain-ELL waste rule keeps the ell layout
    bell_ms: tuple | None = None
    bell_tail: int = 0


def _agree_uniform_shapes(subs_owned, nparts: int,
                          max_diags: int = 80,
                          dia_waste_limit: float = 3.0,
                          ell_waste_limit: float = 3.0,
                          nmax_owned: int = 0) -> UniformShapes:
    """Compute this controller's local stats and allgather-max/union them
    so every controller derives the IDENTICAL stacked shapes.  The
    payload is one fixed-size int64 vector per process."""
    import jax

    from acg_tpu.ops.spmv import BELL_WIDTHS

    offs = np.unique(np.concatenate(
        [csr_diag_offsets(s.A_local) for s in subs_owned]
        or [np.zeros(0, np.int64)]))
    Kl = max((int(np.diff(s.A_local.indptr).max(initial=0))
              for s in subs_owned), default=0)
    bmax = max((int(np.count_nonzero(np.diff(s.A_ghost.indptr)))
                for s in subs_owned), default=0)
    Kg = max((int(np.diff(s.A_ghost.indptr).max(initial=0))
              for s in subs_owned), default=0)
    maxcnt = max((int(c) for s in subs_owned for c in s.halo.send_counts),
                 default=0)
    nmax_ghost = max((s.nghost for s in subs_owned), default=0)
    nnz = sum(int(s.A_local.nnz + s.A_ghost.nnz) for s in subs_owned)
    # LOCAL-block-only nnz, agreed separately: the ELL/binned-ELL waste
    # ratio concerns the local block's padding against its own nnz, and
    # the full-view flow (_stack_local_blocks) computes it that way --
    # using the ghost-inclusive total here made borderline matrices pick
    # plain ELL in the local-read flow while the full-view flow binned
    # them (ADVICE r5)
    nnz_local = sum(int(s.A_local.nnz) for s in subs_owned)
    send_total = sum(int(s.halo.total_send) for s in subs_owned)
    # binned-ELL sizing: per-bin row-count max and hub-tail nnz max over
    # this controller's parts (the bin histogram of each local block)
    nbins = len(BELL_WIDTHS)
    bell = _bell_histogram([s.A_local for s in subs_owned])
    cap = 2 * max_diags
    too_many = offs.size > cap
    payload = np.full(cap + 9 + nbins + 1, np.iinfo(np.int64).min,
                      dtype=np.int64)
    payload[:min(offs.size, cap)] = offs[:cap]
    payload[cap:cap + 9] = (offs.size if not too_many else cap + 1,
                            Kl, bmax, Kg, maxcnt, nmax_ghost, nnz,
                            send_total, nnz_local)
    payload[cap + 9:] = bell
    if jax.process_count() > 1:
        from jax.experimental import multihost_utils

        gathered = np.asarray(
            multihost_utils.process_allgather(payload, tiled=False))
    else:
        gathered = payload[None]
    all_offs = np.unique(np.concatenate(
        [g[:cap][g[:cap] != np.iinfo(np.int64).min] for g in gathered]
        or [np.zeros(0, np.int64)]))
    counts = gathered[:, cap]
    Kl = int(gathered[:, cap + 1].max())
    bmax = int(gathered[:, cap + 2].max())
    Kg = int(gathered[:, cap + 3].max())
    maxcnt = int(gathered[:, cap + 4].max())
    nmax_ghost = int(gathered[:, cap + 5].max())
    nnz_total = int(gathered[:, cap + 6].sum())
    halo_send_total = int(gathered[:, cap + 7].sum())
    nnz_local_total = int(gathered[:, cap + 8].sum())
    bell_all = gathered[:, cap + 9:].max(axis=0)
    dia_ok = (not (counts > cap).any() and all_offs.size <= max_diags
              and nnz_total
              and (all_offs.size * nmax_owned * nparts
                   <= dia_waste_limit * nnz_total))
    # the single-device auto histogram rule (ops.spmv.device_matrix_
    # from_csr): when plain-ELL padding waste blows its limit, take the
    # binned layout.  Every controller computes this from the same
    # agreed scalars, so the format decision is mesh-uniform -- and the
    # waste ratio uses the LOCAL-block nnz, the same definition the
    # full-view flow applies, so borderline matrices pick the same
    # format on both ingest paths (ADVICE r5)
    bell_ok = (not dia_ok and nnz_local_total
               and Kl * nmax_owned * nparts
               > ell_waste_limit * nnz_local_total)
    return UniformShapes(
        offsets=tuple(int(o) for o in all_offs) if dia_ok else None,
        Kl=Kl, bmax=bmax, Kg=Kg, maxcnt=maxcnt, nmax_ghost=nmax_ghost,
        nnz_total=nnz_total, halo_send_total=halo_send_total,
        bell_ms=tuple(int(m) for m in bell_all[:nbins]) if bell_ok
        else None,
        bell_tail=int(bell_all[nbins]) if bell_ok else 0)


def _bell_histogram(blocks) -> np.ndarray:
    """``(len(BELL_WIDTHS) + 1,)`` int64: per-bin MAX row count over the
    given local blocks, hub-tail max nnz last.  The one binning rule
    shared by the uniform-shape agreement and the stacking itself --
    they must stay bit-identical or the agreed bin sizes overflow on
    the local-read flow."""
    from acg_tpu.ops.spmv import BELL_WIDTHS

    nbins = len(BELL_WIDTHS)
    out = np.zeros(nbins + 1, dtype=np.int64)
    widths = np.asarray(BELL_WIDTHS)
    for b in blocks:
        if b is None:
            continue
        row_nnz = np.diff(b.indptr)
        bidx = np.searchsorted(widths, row_nnz)
        cnt = np.bincount(np.minimum(bidx, nbins), minlength=nbins + 1)
        out[:nbins] = np.maximum(out[:nbins], cnt[:nbins])
        out[nbins] = max(out[nbins], int(row_nnz[bidx >= nbins].sum()))
    return out


def _stack_bell_blocks(blocks, nrows_pad: int, dtype,
                       bin_ms, tail_max: int) -> StackedLocalBlock:
    """Stack per-part local blocks in the length-binned ELL layout with
    MESH-UNIFORM shapes: bin b holds ``bin_ms[b]`` row slots per part
    (the max over parts; absent rows pad with row id ``nrows_pad`` ->
    dropped by the scatter), the hub tail ``tail_max`` COO slots.  The
    distributed restatement of :func:`acg_tpu.ops.spmv.
    binned_ell_from_csr` (round-4 verdict item 3; ref
    ``cg-kernels-cuda.cu:340-441``)."""
    from acg_tpu.ops.spmv import BELL_WIDTHS

    P = len(blocks)
    npdtype = np.dtype(dtype)
    widths = np.asarray(BELL_WIDTHS)
    live = [b for b in range(widths.size) if bin_ms[b]]
    bin_rows = [np.full((P, bin_ms[b]), nrows_pad, np.int32) for b in live]
    bin_data = [np.zeros((P, bin_ms[b], widths[b]), npdtype) for b in live]
    bin_cols = [np.zeros((P, bin_ms[b], widths[b]), np.int32) for b in live]
    T = int(tail_max)
    t_rows = np.full((P, T), nrows_pad, np.int32)
    t_cols = np.zeros((P, T), np.int32)
    t_vals = np.zeros((P, T), npdtype)
    for p, blk in enumerate(blocks):
        if blk is None:
            continue
        indptr = np.asarray(blk.indptr)
        vals = np.asarray(blk.data)
        colidx = np.asarray(blk.indices)
        row_nnz = np.diff(indptr)
        bidx = np.searchsorted(widths, row_nnz)
        for i, b in enumerate(live):
            rows_b = np.flatnonzero(bidx == b).astype(np.int32)
            if rows_b.size == 0:
                continue
            nnz_b = row_nnz[rows_b]
            flat_r = np.repeat(np.arange(rows_b.size), nnz_b)
            flat_p = (np.arange(nnz_b.sum())
                      - np.repeat(np.cumsum(nnz_b) - nnz_b, nnz_b))
            src = (np.repeat(indptr[rows_b], nnz_b) + flat_p).astype(np.int64)
            bin_rows[i][p, : rows_b.size] = rows_b
            bin_data[i][p][flat_r, flat_p] = vals[src]
            bin_cols[i][p][flat_r, flat_p] = colidx[src]
        hub = np.flatnonzero(bidx >= widths.size)
        if hub.size:
            t_r = np.repeat(hub, row_nnz[hub]).astype(np.int32)
            t_src = np.concatenate(
                [np.arange(indptr[r], indptr[r + 1]) for r in hub])
            t_rows[p, : t_r.size] = t_r
            t_cols[p, : t_r.size] = colidx[t_src]
            t_vals[p, : t_r.size] = vals[t_src]
    return StackedLocalBlock(
        format="binnedell",
        arrays=(tuple(bin_rows), tuple(bin_data), tuple(bin_cols),
                t_rows, t_cols, t_vals),
        offsets=(), nrows=nrows_pad,
        bin_ks=tuple(int(widths[b]) for b in live))


def _stack_local_blocks(subs, nmax_owned: int, dtype,
                        max_diags: int = 80,  # headroom over spmv.MAX_DIAGS:
                        # the union of per-part offset sets can exceed any
                        # single part's diagonal count
                        dia_waste_limit: float = 3.0,
                        ell_waste_limit: float = 3.0,
                        global_csr=None,
                        uniform: UniformShapes | None = None
                        ) -> StackedLocalBlock:
    """Stacked arrays are HOST numpy (calloc-backed zeros, filled only
    for parts whose blocks exist): non-owned parts of a multi-controller
    build never touch their pages, so host RSS is O(owned/P); the device
    placement happens later through ``put_global``'s per-shard slicing
    (``DistCGSolver.device_args``).

    With restricted builds (some ``A_local is None``) the mesh-uniform
    format decision and shape bounds come from ``global_csr`` -- every
    controller must pick identical offsets/K."""
    blocks = [s.A_local for s in subs]
    built = [b for b in blocks if b is not None]
    npdtype = np.dtype(dtype)
    if uniform is not None:
        # local-read flow: shapes (and the format decision) pre-agreed
        # across controllers
        if uniform.offsets is not None:
            offs = np.asarray(uniform.offsets, dtype=np.int64)
            nnz = uniform.nnz_total
        else:
            if uniform.bell_ms is not None:
                return _stack_bell_blocks(blocks, nmax_owned, dtype,
                                          uniform.bell_ms,
                                          uniform.bell_tail)
            offs = np.zeros(0, np.int64)
            nnz = 0  # force the ELL path
        Kl = uniform.Kl
    elif global_csr is not None:
        # restricted build: the local blocks of OTHER controllers are
        # invisible, so the mesh-uniform offset set must be derivable
        # from global structure alone.  That is only sound when every
        # part's owned rows form a contiguous natural-order range (band
        # partitions): then local diagonals are a subset of the global
        # ones.  Scattered (graph/metis) partitions have local-index
        # diagonals unrelated to the global set -> ELL path.
        contiguous = all(
            s.owned_order == "natural" and (s.nowned == 0 or (
                int(s.global_ids[s.nowned - 1]) - int(s.global_ids[0]) + 1
                == s.nowned))
            for s in subs)
        offs = (csr_diag_offsets(global_csr) if contiguous
                else np.zeros(0, np.int64))
        nnz = int(global_csr.nnz) if contiguous else 0
        Kl = int(np.diff(global_csr.indptr).max(initial=0))
    else:
        offs = np.unique(np.concatenate(
            [csr_diag_offsets(b) for b in built] or [np.zeros(1, np.int64)]))
        nnz = sum(int(b.nnz) for b in built)
        Kl = max((int(np.diff(b.indptr).max(initial=0)) for b in built),
                 default=0)
    if (nnz and offs.size <= max_diags
            and offs.size * nmax_owned * len(blocks) <= dia_waste_limit * nnz):
        planes = np.zeros((offs.size, len(blocks), nmax_owned),
                          dtype=npdtype)
        for p, b in enumerate(blocks):
            if b is not None:
                planes[:, p, :] = dia_planes_fixed(b, offs, nmax_owned)
        return StackedLocalBlock(format="dia",
                                 arrays=tuple(planes[d]
                                              for d in range(offs.size)),
                                 offsets=tuple(int(o) for o in offs),
                                 nrows=nmax_owned)
    if (uniform is None and global_csr is None and nnz
            and Kl * nmax_owned * len(blocks) > ell_waste_limit * nnz):
        # the single-device auto histogram rule: plain-ELL padding waste
        # past its limit -> length-binned layout.  (Restricted builds --
        # global_csr set -- keep ELL: per-part LOCAL row widths are not
        # derivable from global structure on the controllers that cannot
        # see the blocks, so a mesh-uniform bin sizing does not exist
        # there; the local-read flow agrees bins via its allgather.)
        bell = _bell_histogram(built)
        return _stack_bell_blocks(blocks, nmax_owned, dtype,
                                  tuple(int(m) for m in bell[:-1]),
                                  int(bell[-1]))
    Kl = max(Kl, 1)
    ld = np.zeros((len(blocks), nmax_owned, Kl), dtype=npdtype)
    lc = np.zeros((len(blocks), nmax_owned, Kl), dtype=np.int32)
    for p, b in enumerate(blocks):
        if b is None:
            continue
        d, c = ell_planes_from_csr(b.indptr, b.indices, b.data, nmax_owned,
                                   pad_k=Kl)
        ld[p], lc[p] = d.astype(npdtype), c
    return StackedLocalBlock(format="ell", arrays=(ld, lc),
                             offsets=(), nrows=nmax_owned)


def _stack_ghost_blocks(subs, nmax_owned: int, dtype,
                        global_csr=None,
                        uniform: UniformShapes | None = None
                        ) -> StackedGhostBlock:
    """Host-numpy ghost blocks (see ``_stack_local_blocks``); with
    restricted builds the uniform bmax/Kg bounds come from the global
    structure (border counts are known for every part; the global max
    row length bounds any ghost row's length) or the pre-agreed
    ``uniform`` shapes (local-read flow)."""
    npdtype = np.dtype(dtype)
    coupled = [None if s.A_ghost is None
               else np.flatnonzero(np.diff(s.A_ghost.indptr)) for s in subs]
    if uniform is not None:
        bmax = uniform.bmax or 1
        Kg = uniform.Kg or 1
    elif global_csr is not None:
        bmax = max((s.nborder for s in subs), default=0) or 1
        Kg = int(np.diff(global_csr.indptr).max(initial=0)) or 1
    else:
        bmax = max((r.size for r in coupled if r is not None), default=0) or 1
        Kg = max((int(np.diff(s.A_ghost.indptr).max(initial=0))
                  for s in subs if s.A_ghost is not None), default=0) or 1
    P = len(subs)
    rows = np.full((P, bmax), nmax_owned, dtype=np.int32)  # pad = OOB drop
    data = np.zeros((P, bmax, Kg), dtype=npdtype)
    cols = np.zeros((P, bmax, Kg), dtype=np.int32)
    for p, (s, ri) in enumerate(zip(subs, coupled)):
        if ri is None or ri.size == 0:
            continue
        sub = s.A_ghost[ri]
        d, c = ell_planes_from_csr(sub.indptr, sub.indices, sub.data,
                                   ri.size, pad_k=Kg)
        rows[p, : ri.size] = ri
        data[p, : ri.size] = d.astype(npdtype)
        cols[p, : ri.size] = c
    return StackedGhostBlock(rows=rows, data=data, cols=cols,
                             nrows=nmax_owned, bmax=bmax)


@dataclasses.dataclass
class DistributedProblem:
    """Host-side compilation of a partitioned matrix into mesh-ready arrays.

    The role of ``acgsolvercuda_init`` (``cgcuda.c:143-332``): upload the
    local + off-diagonal blocks and the halo plan, sized for the mesh.
    """

    nparts: int
    n: int
    subs: list[Subdomain]
    nmax_owned: int
    halo: DeviceHaloPlan
    local: StackedLocalBlock
    ghost: StackedGhostBlock
    nnz_total: int
    dtype: object
    # vector storage dtype; None = same as the matrix blocks.  The
    # supported split is bf16 blocks + f32 vectors ("--dtype mixed",
    # jax_cg.JaxCGSolver.vector_dtype rationale)
    vector_dtype: object = None

    @property
    def vdtype(self):
        return self.dtype if self.vector_dtype is None else self.vector_dtype

    # the matrix-free stencil operator armed over this problem
    # (arm_matfree; None = assembled local blocks).  The halo plan and
    # ghost block stay assembled either way -- the operator replaces
    # only the O(ndiags * N) local-plane HBM traffic
    operator: object = None

    # parts whose matrix blocks this controller built (None = all);
    # scatter() only fills these, matching the device shards this
    # process can address
    owned_parts: tuple | None = None
    # contiguous band boundaries (nparts+1) in the local-read flow:
    # lets gather()/scatter() use analytic global ids where non-owned
    # parts are stubs without them
    band_bounds: tuple | None = None

    @classmethod
    def build(cls, full_csr, part, nparts: int, dtype=jnp.float32,
              subs: list[Subdomain] | None = None,
              reorder: str = "natural",
              vector_dtype=None,
              owned_parts=None) -> "DistributedProblem":
        """``reorder="natural"`` (default) re-sorts each part's owned rows
        by global id (in place when ``subs`` is passed) so contiguous
        partitions of banded matrices keep gather-free DIA local blocks;
        ``"ibg"`` preserves the interior|border|ghost layout.

        ``owned_parts`` (multi-controller): assemble matrix blocks and
        host arrays only for the listed parts -- the rest stay as
        untouched calloc pages, so per-controller host RSS for the
        stacked problem is O(N * owned/nparts) instead of O(N).  Shape
        and format decisions then derive from the GLOBAL matrix so every
        controller compiles the identical program."""
        restricted = owned_parts is not None
        if subs is None or (not restricted and subs[0].A_local is None):
            subs = partition_matrix(full_csr, part, nparts,
                                    owned_parts=owned_parts)
        if reorder == "natural":
            reorder_owned_natural(subs)
        nmax_owned = max(s.nowned for s in subs)
        halo = build_device_halo(subs)
        gcsr = full_csr if restricted else None
        local = _stack_local_blocks(subs, nmax_owned, dtype, global_csr=gcsr)
        ghost = _stack_ghost_blocks(subs, nmax_owned, dtype, global_csr=gcsr)
        return cls(nparts=nparts, n=full_csr.shape[0], subs=subs,
                   nmax_owned=nmax_owned, halo=halo, local=local,
                   ghost=ghost, nnz_total=int(full_csr.nnz), dtype=dtype,
                   vector_dtype=vector_dtype,
                   owned_parts=None if owned_parts is None
                   else tuple(int(p) for p in owned_parts))

    @staticmethod
    def read_local_subdomains(path, nparts: int, mesh=None, bounds=None):
        """Phase 1 of the local-read flow: the HOST-LOCAL part (header
        read, per-part range reads, subdomain construction) with NO
        collectives -- so a one-sided I/O failure can be error-agreed at
        a checkpoint before any controller enters the shape allgather of
        :meth:`assemble_local` (mismatched collectives would otherwise
        cross-match and hang).  Returns ``(subs, bounds, n, owned)``."""
        from acg_tpu.errors import AcgError, ErrorCode
        from acg_tpu.graph import BandStub, subdomain_from_row_slice
        from acg_tpu.io.mtxfile import read_mtx_row_range, read_mtx_sizes

        n, _, _ = read_mtx_sizes(path)
        if bounds is None:
            bounds = np.linspace(0, n, nparts + 1).astype(np.int64)
        bounds = np.asarray(bounds, dtype=np.int64)
        if mesh is None:
            mesh = solve_mesh(nparts)
        pi = jax.process_index()
        owned = tuple(p for p in range(nparts)
                      if mesh.devices.flat[p].process_index == pi)
        subs: list = [None] * nparts
        for p in range(nparts):
            if p in owned:
                sl = read_mtx_row_range(path, int(bounds[p]),
                                        int(bounds[p + 1]))
                if sl.symmetry != "general":
                    raise AcgError(
                        ErrorCode.NOT_SUPPORTED,
                        f"{path}: range reads need FULL storage "
                        f"(symmetry 'general'); this file declares "
                        f"{sl.symmetry!r} -- regenerate with "
                        f"mtx2bin --expand")
                r, c, v = sl.to_coo()
                subs[p] = subdomain_from_row_slice(r, c, v, bounds, p)
            else:
                subs[p] = BandStub(part=p,
                                   nowned_=int(bounds[p + 1] - bounds[p]))
        return subs, bounds, n, owned

    @classmethod
    def assemble_local(cls, subs, bounds, n: int, nparts: int,
                       owned, dtype=jnp.float32,
                       vector_dtype=None) -> "DistributedProblem":
        """Phase 2 of the local-read flow: the COLLECTIVE part (uniform-
        shape allgather) plus stacking.  Call only after all controllers
        passed phase 1 (checkpointed)."""
        bounds = np.asarray(bounds, dtype=np.int64)
        nmax_owned = int(np.max(np.diff(bounds)))
        uniform = _agree_uniform_shapes([subs[p] for p in owned], nparts,
                                        nmax_owned=nmax_owned)
        halo = build_device_halo(subs, maxcnt=uniform.maxcnt,
                                 nmax_ghost=uniform.nmax_ghost)
        local = _stack_local_blocks(subs, nmax_owned, dtype, uniform=uniform)
        ghost = _stack_ghost_blocks(subs, nmax_owned, dtype, uniform=uniform)
        prob = cls(nparts=nparts, n=n, subs=subs, nmax_owned=nmax_owned,
                   halo=halo, local=local, ghost=ghost,
                   nnz_total=uniform.nnz_total, dtype=dtype,
                   vector_dtype=vector_dtype, owned_parts=owned,
                   band_bounds=tuple(int(b) for b in bounds))
        prob.halo_send_total = uniform.halo_send_total
        return prob

    @classmethod
    def build_local_read(cls, path, nparts: int, dtype=jnp.float32,
                         vector_dtype=None, mesh=None,
                         bounds=None) -> "DistributedProblem":
        """Pod-scale ingest: each controller RANGE-READS only its own
        rows from a row-sorted full-storage binary file (``mtx2bin
        --expand`` output) and builds only its own subdomains -- no
        controller ever holds the full matrix, its COO triplets, or any
        other part's blocks.  The role of the reference's root-rank read
        + subgraph scatter (``graph.c:1529-1897``,
        ``mtxfile.h:997-1087``) with the root removed: I/O, host memory
        and preprocessing are all O(local nnz).

        Uses a contiguous band partition (``bounds`` or equal rows);
        mesh-uniform shapes come from one small allgather
        (:func:`_agree_uniform_shapes`).  Structural symmetry of the
        matrix is assumed (SPD inputs) -- it is what makes the halo
        send side locally derivable (``graph.subdomain_from_row_slice``).

        Multi-controller callers that want clean one-sided-failure
        semantics should run :meth:`read_local_subdomains`, checkpoint,
        then :meth:`assemble_local` (the CLI does).
        """
        subs, bounds, n, owned = cls.read_local_subdomains(
            path, nparts, mesh=mesh, bounds=bounds)
        return cls.assemble_local(subs, bounds, n, nparts, owned,
                                  dtype=dtype, vector_dtype=vector_dtype)

    # -- vector scatter/gather to the stacked padded layout ---------------

    def scatter(self, x_global: np.ndarray, dtype=None) -> np.ndarray:
        out = np.zeros((self.nparts, self.nmax_owned),
                       dtype=np.dtype(dtype if dtype is not None
                                      else self.vdtype))
        owned = (range(self.nparts) if self.owned_parts is None
                 else self.owned_parts)
        x_global = np.asarray(x_global)
        for p in owned:
            s = self.subs[p]
            out[p, : s.nowned] = x_global[s.global_ids[: s.nowned]]
        return out

    def neighbor_counts(self):
        """(send_counts, recv_counts), each (nparts, nparts) int32:
        ``send_counts[p, q]`` = entries p sends to q.  Gates the puts in
        the DMA transport (the reference's per-neighbour sendcounts,
        ``halo.h:72-186``).

        In the local-read flow only owned parts carry plans; their rows
        are filled from local info (recv side directly from the owned
        recv windows -- the transpose shortcut would need other
        controllers' send rows), and non-owned rows stay zero: each
        controller's device shards only ever read its own rows."""
        scnt = np.zeros((self.nparts, self.nparts), dtype=np.int32)
        rcnt = np.zeros((self.nparts, self.nparts), dtype=np.int32)
        for p, s in enumerate(self.subs):
            h = s.halo
            if h is None:
                continue
            for q, cnt in zip(h.send_parts, h.send_counts):
                scnt[p, int(q)] = int(cnt)
            for q, cnt in zip(h.recv_parts, h.recv_counts):
                rcnt[p, int(q)] = int(cnt)
        if self.owned_parts is None:
            # full-information build: keep the exact transpose (identical
            # to the recv fill, but bit-for-bit the historical behavior)
            rcnt = scnt.T.copy()
        return scnt, rcnt

    def part_rows(self) -> list:
        """Owned row count per part, in part order -- half of the
        snapshot repartition sidecar (acg_tpu.checkpoint)."""
        if self.band_bounds is not None:
            return [int(self.band_bounds[p + 1] - self.band_bounds[p])
                    for p in range(self.nparts)]
        return [int(s.nowned) for s in self.subs]

    def row_permutation(self) -> np.ndarray | None:
        """Concatenated global row ids in stacked slot order (part 0's
        owned rows, then part 1's, ...): the permutation half of the
        snapshot repartition sidecar.  None when this controller
        cannot derive it (restricted multi-controller builds whose
        non-owned parts are stubs without band bounds) -- snapshots
        then omit the sidecar and repartition resume refuses
        self-describingly."""
        if self.band_bounds is not None:
            return np.concatenate([
                np.arange(self.band_bounds[p], self.band_bounds[p + 1],
                          dtype=np.int64)
                for p in range(self.nparts)]) if self.nparts else \
                np.zeros(0, np.int64)
        if self.owned_parts is not None:
            return None
        return np.concatenate([
            np.asarray(s.global_ids[: s.nowned], dtype=np.int64)
            for s in self.subs]) if self.subs else np.zeros(0, np.int64)

    def gather(self, stacked: np.ndarray) -> np.ndarray:
        out = np.zeros(self.n, dtype=np.asarray(stacked).dtype)
        if self.band_bounds is not None:
            # analytic global ids: non-owned parts are stubs here
            for p in range(self.nparts):
                lo, hi = self.band_bounds[p], self.band_bounds[p + 1]
                out[lo:hi] = stacked[p, : hi - lo]
            return out
        for p, s in enumerate(self.subs):
            out[s.global_ids[: s.nowned]] = stacked[p, : s.nowned]
        return out


def make_dist_spmv(prob: "DistributedProblem", comm: str, interpret: bool,
                   kernels: str = "xla", axis: str = PARTS_AXIS,
                   fault=None):
    """Shard-level distributed SpMV: halo(x) || local SpMV, then
    off-diagonal SpMV -- call stack 3.2's overlap pattern
    (``cgcuda.c:855-899``), scheduled by XLA instead of streams.

    ``kernels="pallas*"`` runs the hand-written single-x-pass DIA kernel
    for the local block (the role of the reference's device SpMV inside
    ``solvempi``, ``cgcuda.c:871``); non-DIA local blocks and the small
    ghost block stay on the XLA path.

    Returns ``f(x_loc, la, ga, sidx, gsrc, gval, scnt, rcnt, k=None,
    pidx=None)`` for use inside ``shard_map`` (shared by the solve
    program and the per-op profiling tier).  ``fault`` (a static
    acg_tpu.faults.FaultSpec) arms in-loop injection: ``k`` is the
    iteration index and ``pidx`` the shard's part index, so a
    ``halo:*``/``spmv:*`` spec poisons exactly one part's payload at
    exactly one iteration -- callers that never pass ``k`` (setup SpMVs,
    the profiler) are injection-free."""
    halo = prob.halo
    local_block = prob.local
    ghost_block = prob.ghost
    use_pallas = kernels.startswith("pallas") and local_block.format == "dia"
    pallas_interpret = kernels.endswith("interpret")
    if use_pallas:
        from acg_tpu.ops.pallas_kernels import dia_spmv

    def dist_spmv(x_loc, la, ga, sidx, gsrc, gval, scnt, rcnt,
                  k=None, pidx=None):
        if use_pallas:
            y = dia_spmv(la, local_block.offsets, x_loc,
                         interpret=pallas_interpret)
        else:
            y = local_block.shard_mv(la, x_loc)
        if halo.has_ghosts:
            if comm == "dma":
                ghost = halo_exchange_dma(x_loc, sidx, gsrc, gval,
                                          scnt, rcnt,
                                          axis, interpret=interpret)
            else:
                ghost = halo_exchange(x_loc, sidx, gsrc, axis)
            if fault is not None and k is not None:
                ghost = fault.apply_halo(ghost, k, pidx)
            y = y + ghost_block.shard_mv(ga, ghost)
        if fault is not None and k is not None:
            y = fault.apply_spmv(y, k, pidx)
        return y

    return dist_spmv


def interior_border_split(prob: "DistributedProblem") -> np.ndarray:
    """``(nparts, imax)`` int32 interior row ids per part, ascending,
    padded with ``nmax_owned`` (dropped by the jit scatter's OOB mode).

    A row is *border* when it couples to ghost values (it has entries in
    the off-diagonal block -- exactly the coupled-row list the stacked
    ghost block stores, ``StackedGhostBlock.rows``); every other owned
    row is *interior* and its SpMV result needs nothing from the halo
    exchange.  This is the reference's L1 interior/border graph split
    (``graph.c``: the rows whose update can start before any neighbour
    data lands), recomputed here from the halo plans instead of METIS
    metadata so every partition method gets it."""
    nrows = prob.nmax_owned
    interiors = []
    for s in prob.subs:
        if s is None or getattr(s, "A_ghost", None) is None:
            raise AcgError(
                ErrorCode.NOT_SUPPORTED,
                "interior/border split needs the full-information "
                "build (restricted multi-controller builds hold other "
                "controllers' coupled-row lists as stubs)")
        mask = np.ones(s.nowned, dtype=bool)
        coupled = np.flatnonzero(np.diff(s.A_ghost.indptr))
        mask[coupled[coupled < s.nowned]] = False
        interiors.append(np.flatnonzero(mask).astype(np.int32))
    imax = max((r.size for r in interiors), default=0) or 1
    out = np.full((prob.nparts, imax), nrows, dtype=np.int32)
    for p, r in enumerate(interiors):
        out[p, : r.size] = r
    return out


def make_dist_spmv_overlapped(prob: "DistributedProblem", comm: str,
                              interpret: bool, axis: str = PARTS_AXIS):
    """Interior|border OVERLAPPED distributed SpMV -- the fused tier's
    twin of :func:`make_dist_spmv` (``kernels='fused'`` on the mesh).

    The reference's device-initiated solver starts its one-sided halo
    puts, runs the interior SpMV while they are in flight, then waits
    the receive signals and finishes the border rows
    (``cg-kernels-cuda.cu:713-899``).  Restated as a DEPENDENCY split
    for XLA's scheduler: the exchange is issued first and nothing
    depends on it until the border finish, so the interior rows' work
    (a per-row gather SpMV over the interior row list) is free to
    overlap the puts; the border rows' local contribution plus the
    ghost contribution land after the recv wait.  Per-row arithmetic is
    bit-identical to the unsplit SpMV (same per-row multiply-add order
    over the same plane/ELL-slot sequence), so the split program's
    trajectory equals the unsplit one exactly (pinned in
    tests/test_fused_dist.py).

    ``ga`` arrives EXTENDED by the split: ``(rows, data, cols,
    interior_rows)`` -- the coupled-row list doubles as the border set,
    and :meth:`DistCGSolver.device_args` appends the interior list
    (:func:`interior_border_split`) when the fused tier is armed.
    Supports the ``dia`` and ``ell`` stacked local formats (the two
    with a per-row gather form); ``binnedell`` is refused at solver
    setup.  No fault hook: the fused tier refuses armed injectors at
    solve time (its base program carries no breakdown flag), so the
    signature keeps the ``k``/``pidx`` slots for call compatibility
    and nothing else."""
    halo = prob.halo
    local_block = prob.local
    ghost_block = prob.ghost
    if local_block.format not in ("dia", "ell", "matfree"):
        raise ValueError(f"overlapped SpMV needs DIA, ELL or matrix-"
                         f"free local blocks (got "
                         f"{local_block.format!r})")
    nrows = local_block.nrows
    offs = local_block.offsets

    def local_rows_mv(la, x, rows):
        """The local block's SpMV restricted to ``rows`` (padding ids
        == nrows gather clamped garbage that the caller's scatter
        drops).  Bit-identical per row to ``shard_mv``: the DIA form
        accumulates plane products in the same plane order over the
        same padded-x values (:func:`acg_tpu.ops.spmv.dia_mv`), the ELL
        form is the same row-independent einsum reduction, and the
        matrix-free form runs the DIA accumulation over GENERATED
        plane values (the interior/border split applied to the stencil
        apply -- the same split PR 13 gave the assembled SpMV)."""
        adt = acc_dtype(x.dtype)
        if local_block.format in ("dia", "matfree"):
            planes = (local_block.gen_planes(la)
                      if local_block.format == "matfree" else la)
            L = max(0, -min(offs))
            R = max(0, max(offs))
            xp = jnp.pad(x, (L, R))
            acc = jnp.zeros(rows.shape, adt)
            for plane, off in zip(planes, offs):
                acc = acc + (plane[rows].astype(adt)
                             * xp[rows + (L + off)].astype(adt))
            return acc.astype(x.dtype)
        data, cols = la
        return jnp.einsum("bk,bk->b", data[rows], x[cols[rows]],
                          preferred_element_type=adt).astype(x.dtype)

    def dist_spmv(x_loc, la, ga, sidx, gsrc, gval, scnt, rcnt,
                  k=None, pidx=None):
        grows, gdata, gcols, irows = ga
        # 1. issue the halo exchange FIRST: nothing below depends on it
        #    until the border finish, so the scheduler can run the
        #    interior SpMV while the one-sided puts (comm='dma') or the
        #    all_to_all are in flight -- the reference's stream overlap
        #    (cgcuda.c:855-899) as a data-dependency statement
        ghost = None
        if halo.has_ghosts:
            if comm == "dma":
                ghost = halo_exchange_dma(x_loc, sidx, gsrc, gval,
                                          scnt, rcnt, axis,
                                          interpret=interpret)
            else:
                ghost = halo_exchange(x_loc, sidx, gsrc, axis)
        # 2. interior rows: zero ghost dependencies, free to overlap
        with jax.named_scope("spmv_interior"):
            y_int = local_rows_mv(la, x_loc, irows)
        # 3+4. border finish: the border rows' local contribution plus
        #      the ghost contribution (which waits the recv side)
        with jax.named_scope("spmv_border"):
            y_bor = local_rows_mv(la, x_loc, grows)
            y = jnp.zeros((nrows,), x_loc.dtype)
            y = y.at[irows].add(y_int, indices_are_sorted=True)
            y = y.at[grows].add(y_bor, indices_are_sorted=True)
            if ghost is not None:
                contrib = jnp.einsum(
                    "bk,bk->b", gdata, ghost[gcols],
                    preferred_element_type=acc_dtype(x_loc.dtype)
                ).astype(x_loc.dtype)
                y = y.at[grows].add(contrib, indices_are_sorted=True)
        return y

    return dist_spmv


def arm_matfree(prob: "DistributedProblem", op) -> "DistributedProblem":
    """Arm the matrix-free operator tier over a built distributed
    problem: replace the assembled LOCAL planes with a ``matfree``
    stacked block whose shard-level SpMV GENERATES the stencil values
    (ops.operator.stencil_planes over per-part ``(row0, nowned)`` and
    the operator's O(grid-side) tables), while the halo plan and the
    ghost block -- the O(border) boundary-strip coupling -- stay
    assembled and ride the existing exchange machinery (all_to_all or
    one-sided DMA) unchanged.  In-place on ``prob``; returns it.

    Needs the full-information build over a CONTIGUOUS natural-order
    band partition (each part's local rows are then a global row range,
    so the generated global planes masked to the owned window equal the
    assembled ``dia_planes_fixed`` stacking bitwise); anything else
    refuses self-describingly rather than silently answering a
    different system."""
    from acg_tpu.ops.operator import StencilOperator

    if not isinstance(op, StencilOperator):
        raise AcgError(
            ErrorCode.NOT_SUPPORTED,
            "the distributed matrix-free tier runs the built-in "
            "stencil operators (their local structure is derivable per "
            "part); user-registered operators ride the single-device "
            "tiers")
    if prob.owned_parts is not None:
        raise AcgError(
            ErrorCode.NOT_SUPPORTED,
            "matrix-free arming needs the full-information build "
            "(restricted multi-controller builds hold other "
            "controllers' subdomains as stubs)")
    if int(op.nrows) != int(prob.n):
        raise AcgError(
            ErrorCode.INVALID_VALUE,
            f"operator computes a {op.nrows}-row system; this problem "
            f"has {prob.n} rows")
    if np.dtype(str(op.dtype)) != np.dtype(prob.dtype):
        raise AcgError(
            ErrorCode.INVALID_VALUE,
            f"operator dtype {op.dtype} != problem dtype "
            f"{np.dtype(prob.dtype)}")
    rows0, nowns = [], []
    for s in prob.subs:
        gids = np.asarray(s.global_ids[: s.nowned], dtype=np.int64)
        if s.nowned and (s.owned_order != "natural"
                         or int(gids[-1]) - int(gids[0]) + 1 != s.nowned):
            raise AcgError(
                ErrorCode.NOT_SUPPORTED,
                f"matrix-free stencils need a contiguous natural-order "
                f"band partition (part {s.part} owns a scattered row "
                f"set); use --partition-method band")
        rows0.append(int(gids[0]) if s.nowned else 0)
        nowns.append(int(s.nowned))
    P = prob.nparts
    arrays = (np.asarray(rows0, np.int32).reshape(P, 1),
              np.asarray(nowns, np.int32).reshape(P, 1))
    for t in op.tables:
        arrays = arrays + (np.broadcast_to(
            np.asarray(t), (P,) + np.shape(t)).copy(),)
    prob.local = StackedLocalBlock(format="matfree", arrays=arrays,
                                   offsets=op.offsets,
                                   nrows=prob.nmax_owned, operator=op)
    prob.operator = op
    return prob


class DistCGSolver:
    """Whole-solve SPMD CG program over a 1-D mesh of ``nparts`` devices.

    ``comm`` selects the halo transport (the reference's ``--comm``
    choice, ``cuda/acg-cuda.c:321-377``): ``"xla"`` = `lax.all_to_all`
    collectives (the NCCL/MPI analog), ``"dma"`` = Pallas one-sided
    remote copies (the NVSHMEM analog, halo_dma.py).
    """

    def __init__(self, problem: DistributedProblem, pipelined: bool = False,
                 mesh: Mesh | None = None, comm: str = "xla",
                 precise_dots: bool = False, kernels: str = "auto",
                 replace_every: int = 0, replace_restart: bool = True,
                 recovery=None, trace: int = 0, progress: int = 0,
                 precond=None, health=None, ckpt=None, algorithm=None):
        """``recovery`` (acg_tpu.solvers.resilience.RecoveryPolicy) arms
        in-loop breakdown detection plus the host-side restart ladder:
        bounded restarts from the recomputed true residual, the
        dma -> xla halo-transport fallback, and (full single-controller
        builds) the distributed host solver -- with every restart/abort
        decision error-agreed across controllers.

        ``trace``/``progress`` (acg_tpu.telemetry, 0 = off) arm the
        in-loop convergence ring buffer / the heartbeat in the SPMD
        loop.  Every recorded scalar is already psum'd, so the buffer
        is replicated across shards and leaves the mesh as ONE
        rank-independent fetch per solve; the heartbeat fires on part 0
        only.

        ``precond`` (acg_tpu.precond: spec / spec string / None) arms
        PCG / pipelined-PCG over the mesh: Jacobi and block-Jacobi
        state comes from each part's LOCAL block (stacked host-side,
        sharded like the matrix -- zero extra communication per apply),
        Chebyshev's lambda_max from a power iteration compiled over the
        same halo'd SpMV the solve uses.  The classic loop keeps 2
        allreduces per iteration (the second fuses (r, z) with (r, r))
        and the pipelined loop keeps its SINGLE fused allreduce (3
        scalars).

        ``health`` (acg_tpu.health.HealthSpec or None) arms the
        numerical-health tier over the mesh: the in-loop audit
        recomputes ``b - A x`` through the SAME halo'd distributed
        SpMV the solve runs (inside a ``lax.cond`` whose predicate --
        the iteration index -- is identical on every shard, so the
        conditional collectives stay mesh-uniform), the gap psums, and
        the carried audit vector is replicated like the telemetry
        ring.  ``None`` compiles the byte-identical unaudited
        program."""
        if comm not in ("xla", "dma"):
            raise ValueError(f"unknown halo transport {comm!r}")
        # multi-controller comm='dma': a CAPABILITY PROBE (the conftest
        # two-process-probe pattern, library-side) decides whether the
        # one-sided transport can run in this topology; an incapable
        # topology DOWNGRADES to the xla collectives with a
        # self-describing event instead of the old hard refusal --
        # single-controller runs (where the transport is proven:
        # scripts/dma_probe.py on silicon, interpret-mode parity in CI)
        # pass through without any stale validation caveat
        self._comm_downgrade = None
        if comm == "dma" and jax.process_count() > 1:
            from acg_tpu.parallel.halo_dma import dma_transport_status
            ok, why = dma_transport_status()
            if not ok:
                comm = "xla"
                self._comm_downgrade = why
                sys.stderr.write(
                    f"acg-tpu: halo transport dma -> xla: {why}\n")
        self.problem = problem
        self.pipelined = pipelined
        self.precise_dots = precise_dots
        self.comm = comm
        # recurrence selection (acg_tpu.recurrence): classic/pipelined
        # stay on the hand-built shard_body (builder emission pinned
        # byte-identical in tests/test_hlo_structure.py); sstep:S /
        # pipelined:L compose the builder recurrences with this tier's
        # halo'd SpMV + fused psum machinery (_compile_ca)
        from acg_tpu.recurrence import parse_algorithm
        self.algo = parse_algorithm(algorithm)
        if self.algo is not None and not self.algo.communication_avoiding:
            self.pipelined = pipelined = (self.algo.kind == "pipelined")
            self.algo = None
        self._lam = None
        self.mesh = mesh if mesh is not None else solve_mesh(problem.nparts)
        self.stats = SolverStats(unknowns=problem.n)
        self._sharding = NamedSharding(self.mesh, P(PARTS_AXIS))
        self._interpret = self.mesh.devices.flat[0].platform != "tpu"
        # kernel-tier resolution mirrors JaxCGSolver: pallas on TPU
        # hardware for f32/bf16 DIA local blocks, interpret mode when
        # explicitly requested off-TPU (tests), XLA otherwise
        itemsize = np.dtype(problem.dtype).itemsize
        if kernels == "auto":
            kernels = ("pallas" if not self._interpret
                       and itemsize in (2, 4)
                       and problem.local.format == "dia" else "xla")
        elif kernels == "pallas" and self._interpret:
            kernels = "pallas-interpret"
        elif kernels.startswith("fused"):
            # the distributed fused-iteration tier (ROADMAP item 4):
            # builder-emitted classic/pipelined recurrences over the
            # interior|border split SpMV with the halo exchange in
            # flight (make_dist_spmv_overlapped).  Needs a per-row
            # gather form of the local block and the full-information
            # build (the split derives from every part's coupled-row
            # list)
            if problem.local.format not in ("dia", "ell", "matfree"):
                raise ValueError(
                    "kernels='fused' needs DIA, ELL or matrix-free "
                    f"local blocks (this problem stacked "
                    f"{problem.local.format!r}, which has no per-row "
                    f"gather form); use kernels='auto'")
            if problem.owned_parts is not None:
                raise ValueError(
                    "kernels='fused' needs the full-information build: "
                    "restricted multi-controller builds hold other "
                    "controllers' coupled-row lists as stubs, so the "
                    "interior/border split is not derivable")
            kernels = "fused"
        if kernels not in ("xla", "pallas", "pallas-interpret", "fused"):
            raise ValueError(f"unknown kernels choice {kernels!r}")
        self.kernels = kernels
        self.replace_every = int(replace_every)
        self.replace_restart = bool(replace_restart)
        if self.replace_every < 0:
            raise ValueError("replace_every must be >= 0")
        if self.replace_every:
            # same contract as the single-device solver (jax_cg): the
            # bf16 tier's periodic-f32-residual-replacement soundness
            # mechanism, distributed
            if np.dtype(problem.vdtype) != np.dtype(jnp.bfloat16):
                raise ValueError(
                    "replace_every is the bf16 tier's accuracy contract; "
                    "build the problem with vector_dtype=bf16 (f32/f64 "
                    "storage has no replacement drift to correct)")
            if pipelined:
                raise ValueError("replace_every implements classic CG")
            if precise_dots:
                raise ValueError("replace_every computes scalars in "
                                 "plain f32; precise_dots needs the "
                                 "direct programs")
        from acg_tpu.precond import parse_precond
        self.precond_spec = parse_precond(precond)
        if self.precond_spec is not None and self.replace_every:
            raise ValueError(
                "precond does not compose with replace_every: the "
                "replacement segments restructure the recurrences the "
                "preconditioner threads through")
        # preconditioner state: host-stacked (jacobi/bjacobi) or device
        # scalars (cheby), built lazily at first solve/lower
        self._mstate = None
        # numerical-health tier (acg_tpu.health): static spec baked
        # into the compiled SPMD program; refusals mirror JaxCGSolver's
        if health is not None:
            from acg_tpu.health import HealthSpec
            if not isinstance(health, HealthSpec):
                raise ValueError("health must be an "
                                 "acg_tpu.health.HealthSpec or None")
            if not health.armed:
                health = None
        if health is not None and self.replace_every:
            raise ValueError(
                "the true-residual audit (health) does not compose "
                "with replace_every: the replacement segments already "
                "recompute b - A x every K iterations")
        self.health_spec = health
        # survivability tier (acg_tpu.checkpoint): an armed
        # CheckpointConfig turns solve() into the host-chunked snapshot
        # driver (the JaxCGSolver discipline; same refusals)
        if ckpt is not None:
            from acg_tpu.checkpoint import CheckpointConfig
            if not isinstance(ckpt, CheckpointConfig):
                raise ValueError("ckpt must be an acg_tpu.checkpoint."
                                 "CheckpointConfig or None")
            if self.replace_every:
                raise ValueError(
                    "checkpointing (ckpt) does not compose with "
                    "replace_every: the replacement segments' inner "
                    "state never leaves the program (use the direct "
                    "classic/pipelined programs)")
        self.ckpt = ckpt
        if self.algo is not None:
            # the CA refusal set mirrors JaxCGSolver's (the
            # could-never-fire discipline)
            ca = str(self.algo)
            if pipelined:
                raise ValueError(
                    f"--algorithm {ca} selects its own recurrence; it "
                    f"does not compose with the pipelined flag")
            if self.replace_every:
                raise ValueError(
                    f"{ca} does not compose with replace_every")
            if self.precise_dots:
                raise ValueError(
                    f"{ca} accumulates its fused Gram/window reductions "
                    f"in the scalar dtype; precise_dots composes with "
                    f"the classic/pipelined programs")
            if self.precond_spec is not None:
                raise ValueError(
                    f"{ca} runs unpreconditioned: the s-step basis and "
                    f"the p(l) auxiliary basis have no M^-1 hook yet")
            if np.dtype(problem.vdtype) == np.dtype(jnp.bfloat16):
                raise ValueError(
                    f"{ca} amplifies storage rounding through its basis "
                    f"products; bf16 vectors need the classic/pipelined "
                    f"tiers")
            if ckpt is not None:
                raise ValueError(
                    f"{ca} checkpoints on the single-device tier only "
                    f"(checkpoint.ca_carry_names); on the mesh, "
                    f"--ckpt/--resume need --algorithm "
                    f"classic|pipelined")
            if self.health_spec is not None:
                if self.algo.kind == "pl":
                    raise ValueError(
                        f"{ca} has no in-loop audit hook; --audit-every "
                        f"needs classic/pipelined/sstep")
                if self.health_spec.abft:
                    raise ValueError(
                        f"{ca} has no checksum hook for its basis "
                        f"products; --abft needs classic/pipelined")
        if (self.algo is not None and self.algo.kind == "pl"
                and recovery is None):
            # restarted p(l)-CG (the jax_cg rationale): sqrt breakdown
            # is algorithmic; arm the restart ladder by default
            from acg_tpu.recurrence import pl_restart_policy
            recovery = pl_restart_policy()
        self.recovery = recovery
        self.trace = int(trace)
        self.progress = int(progress)
        if self.trace < 0 or self.progress < 0:
            raise ValueError("trace/progress must be >= 0 (iteration "
                             "counts; 0 disables)")
        if self.replace_every and (self.trace or self.progress):
            # the replacement segments' inner fori threads no global
            # iteration index: the telemetry hooks would silently
            # record nothing (the fault-injector refusal rationale)
            raise ValueError(
                "convergence telemetry (trace/progress) does not reach "
                "the replacement-segment program (replace_every); use "
                "the direct classic/pipelined programs")
        self.last_trace = None
        if self.kernels == "fused":
            # the fused tier dispatches the BUILDER base program
            # (recurrence.build_dist_program over the overlapped SpMV):
            # every cross-cutting feature it does not thread refuses
            # here rather than silently dropping (the could-never-fire
            # discipline, mirroring the single-device fused tier)
            for on, what in (
                    (self.replace_every,
                     "replace_every (the replacement segments "
                     "restructure the loop)"),
                    (self.precise_dots,
                     "precise_dots (the fused tier accumulates its "
                     "dots in the plain scalar dtype)"),
                    (self.precond_spec is not None,
                     "precond (no preconditioner hook in the fused "
                     "base program)"),
                    (self.health_spec is not None,
                     "the health audit (no audit hook in the fused "
                     "base program)"),
                    (self.ckpt is not None,
                     "checkpointing (the fused base program exposes "
                     "no loop carry)"),
                    (self.algo is not None,
                     f"--algorithm {self.algo} (the CA recurrences "
                     f"keep the unsplit SpMV; fused covers "
                     f"classic/pipelined)"),
                    (self.recovery is not None,
                     "recovery (the fused base program carries no "
                     "breakdown flag, so a policy could never fire)"),
                    (bool(self.trace or self.progress),
                     "convergence telemetry (trace/progress)")):
                if on:
                    raise ValueError(
                        f"kernels='fused' (dist) does not compose with "
                        f"{what}; use kernels='auto'/'xla'/'pallas'")
        self._program = self._compile()

    def _program_for(self, fault):
        """The solve program matching the current comm + fault state:
        armed faults always get a solve-local compile; the pristine
        program is cached (and lazily rebuilt after a transport
        fallback invalidates it)."""
        if fault is not None:
            return self._compile(fault=fault)
        if self._program is None:
            self._program = self._compile()
        return self._program

    # -- program construction ---------------------------------------------

    def _compile(self, fault=None, state_io: bool = False):
        """Build the whole-solve program.  ``fault`` (a static
        acg_tpu.faults.FaultSpec) bakes the injector into the loop --
        the armed program is a solve-local temporary, never cached on
        ``self``, so clean solves keep the pristine compilation.

        ``state_io`` (the survivability tier, acg_tpu.checkpoint) makes
        the program ALSO return the final loop carry -- per-part vector
        leaves sharded like x, psum'd scalars replicated -- and accept
        an optional ``carry``/``k_offset`` pair that re-enters the
        recurrence exactly where a previous chunk left it (the
        checkpoint chunk driver's plumbing).  Disarmed programs never
        name any of it and lower byte-identical code (pinned in
        tests/test_checkpoint.py)."""
        if self.algo is not None:
            # communication-avoiding recurrences: the builder program
            # (recurrence.run_sstep_loop / run_pl_loop) composed with
            # this tier's machinery
            return self._compile_ca(fault=fault)
        if self.kernels == "fused":
            # the distributed fused-iteration tier: the recurrence
            # builder's base emission (classic_recurrence /
            # pipelined_recurrence over TierOps) composed with the
            # interior|border OVERLAPPED SpMV -- no hand-built loop
            # (the PR 12 one-recurrence-per-feature discipline).
            # Faults/state_io never reach here: both are refused at
            # setup/solve for this tier
            from acg_tpu.recurrence import build_dist_program
            return build_dist_program(self)
        prob = self.problem
        pipelined = self.pipelined
        replace_every = self.replace_every
        replace_restart = self.replace_restart
        axis = PARTS_AXIS

        comm = self.comm
        interpret = self._interpret
        precise = self.precise_dots
        trace = self.trace
        progress = self.progress
        precond_spec = self.precond_spec
        health = self.health_spec
        if trace or progress:
            from acg_tpu import telemetry
        if precond_spec is not None:
            from acg_tpu.precond import make_apply
        if health is not None:
            from acg_tpu import health as _health

        dist_spmv = make_dist_spmv(prob, comm, interpret,
                                   kernels=self.kernels, fault=fault)

        # commsize==1 parity (the reference's explicit special case,
        # ``cgcuda.c:403``): on a 1-shard mesh every psum is an identity
        # -- but XLA does NOT elide a 1-device all-reduce, and on this
        # runtime each one costs a fixed per-op launch overhead INSIDE
        # the iteration loop (measured round 5: 2 all-reduces/iteration
        # made the nparts=1 program 27x slower than the single-chip
        # solver, the `cg_dist1` ladder row's collapse).  The whole
        # shard_map wrapper is bypassed below for the same reason.
        single_shard = self.mesh.devices.size == 1

        def psum(v):
            return v if single_shard else lax.psum(v, axis)

        # the loop-carry leaf layout a snapshot stores (acg_tpu.
        # checkpoint): vector leaves shard per-part, the psum'd scalars
        # replicate -- shared by shard_body's state_io outputs, the
        # shard_map specs, and the chunk driver's snapshot writer
        from acg_tpu.checkpoint import SCALAR_LEAVES, carry_names
        c_names = carry_names(pipelined, precond_spec is not None)[1:]
        # the GLOBAL unknown count (the ABFT mismatch scale; local
        # shapes would understate the rounding headroom)
        nglobal = int(prob.n)

        def shard_body(la, ga, sidx, gsrc, gval, scnt, rcnt, b, x0,
                       tols, maxits, mstate=None, unbounded=False,
                       needs_diff=False, detect=False, carry=None,
                       k_offset=None):
            # shard_map keeps the sharded parts axis as a leading size-1 dim
            la, ga = (jax.tree.map(lambda a: a[0], t) for t in (la, ga))
            sidx, gsrc, gval, scnt, rcnt, b, x0 = (
                a[0] for a in (sidx, gsrc, gval, scnt, rcnt, b, x0))
            if precond_spec is not None:
                mstate = jax.tree.map(lambda a: a[0], mstate)
            if carry is not None:
                # vector leaves arrive stacked like b; psum'd scalars
                # arrive replicated (shape ()) and pass through
                carry = tuple(a[0] if a.ndim == 2 else a for a in carry)
            maxits = maxits.astype(jnp.int32)
            dtype = b.dtype
            # bf16 storage keeps every scalar in f32 (jax_cg._scalar_setup
            # rationale): dots accumulate in f32, updated vectors round
            # once on store, only half-width bytes cross HBM and the ICI
            sdt = acc_dtype(dtype)
            store = ((lambda v: v.astype(dtype)) if sdt != dtype
                     else (lambda v: v))
            res_atol, res_rtol, diff_atol, diff_rtol = tols
            # the part index a vector fault targets; only derivable from
            # the mesh axis inside shard_map (the plain-jit bypass below
            # is single-part by construction)
            pidx = None
            if fault is not None:
                pidx = (jnp.int32(0) if single_shard
                        else lax.axis_index(axis))

            def spmv(x, k=None):
                return dist_spmv(x, la, ga, sidx, gsrc, gval, scnt, rcnt,
                                 k=k, pidx=pidx)

            def ldot(a, c):
                return jnp.dot(a, c, preferred_element_type=sdt)

            # the fused-reduction family (parallel.reductions): ONE
            # psum carries k scalars -- compensated mode psums hi/lo
            # pairs so local summation error stays out of the global
            # scalar, and the pipelined/PCG single-allreduce property
            # (cgcuda.c:1730-1737) is the k=2/k=3 member.  The builders
            # emit exactly the op sequence the hand-written ladders
            # traced, so these programs lower byte-identically to the
            # pre-refactor ones (pinned in tests/test_hlo_structure.py)
            pdot = make_pdot(psum, ldot, sdt, precise)
            _pdotk = make_pdotk(psum, ldot, sdt, precise)

            def pdot2_fused(a1, c1, a2, c2):
                return _pdotk((a1, c1), (a2, c2))

            def pdot3_fused(a1, c1, a2, c2, a3, c3):
                return _pdotk((a1, c1), (a2, c2), (a3, c3))

            bnrm2 = jnp.sqrt(pdot(b, b))
            x0nrm2 = jnp.sqrt(pdot(x0, x0))
            if precond_spec is not None:
                # papply reuses the tier's halo'd SpMV closure: the
                # cheby apply's communication is exactly K extra SpMVs
                _papply = make_apply(precond_spec, lambda _A, x: spmv(x))

                def papply(vec, k=None):
                    z = _papply(mstate, None, vec)
                    if fault is not None and k is not None:
                        z = fault.apply_precond(z, k, pidx)
                    return z

            if carry is not None:
                # resume (the survivability tier): the provided carry IS
                # the loop state -- nothing is recomputed, the Krylov
                # recurrence continues exactly where the snapshot left
                # it (x0 holds the snapshot iterate).  The setup SpMV
                # and its collectives are skipped on every shard alike
                # (carry is a static python branch, mesh-uniform)
                r = carry[0]
                if precond_spec is not None:
                    r0nrm2 = jnp.sqrt(carry[-1])
                elif pipelined:
                    r0nrm2 = jnp.sqrt(jnp.maximum(carry[-2], 0))
                else:
                    r0nrm2 = jnp.sqrt(carry[-1])
            elif precond_spec is not None:
                r = b - spmv(x0)
                u0 = store(papply(r))
                gamma0, rr0 = pdot2_fused(r, u0, r, r)
                gamma = rr0
                r0nrm2 = jnp.sqrt(rr0)
            else:
                r = b - spmv(x0)
                gamma = pdot(r, r)
                r0nrm2 = jnp.sqrt(gamma)
            res_tol = jnp.maximum(res_atol, res_rtol * r0nrm2)
            diff_tol = jnp.maximum(diff_atol, diff_rtol * x0nrm2)
            inf = jnp.asarray(jnp.inf, sdt)
            if health is not None and health.abft:
                # the column checksum c = A^T 1 (= A 1: symmetric
                # systems) through the tier's own halo'd SpMV -- one
                # extra exchange per solve.  The in-loop test rides the
                # FUSED 3-dot psum (pdot3_fused), so the armed delta is
                # exactly +1 all_reduce per audit and ZERO extra SpMVs
                cvec = spmv(jnp.ones_like(b)).astype(sdt)

            # Loop structure and convergence logic shared with the
            # single-device solver (jax_cg._iterate / _converged): gamma is
            # psum'd, so `done` is identical on every shard and the while
            # predicates agree across the mesh.
            def run_iter(iter_body, init_state, gamma_of, dx_of,
                         init_gamma=None, bad_of=None):
                return _iterate(iter_body, init_state, gamma_of, maxits,
                                res_tol, diff_tol, dx_of, unbounded,
                                init_gamma=init_gamma, bad_of=bad_of)

            if replace_every and not pipelined:
                # the sound-bf16 contract, distributed: inner bf16 CG
                # segments over the mesh with a per-segment f32
                # true-residual replacement (mixed-precision dist SpMV
                # -- bf16 blocks x f32 vector).  Mirrors
                # jax_cg._cg_replaced_program; b/x0 arrive in f32
                # (solve scatters them wide), and every psum'd scalar
                # is f32, so the convergence test per segment is
                # grounded in the true residual on every shard.
                vdt = jnp.bfloat16

                def segment(x32, r32, p, its):
                    r16 = r32.astype(vdt)
                    seg_gamma = pdot(r16, r16)
                    if replace_restart:
                        p = r16
                    else:
                        pn = pdot(p, p)
                        bad = ((~jnp.isfinite(pn))
                               | (pn > jnp.asarray(1e24, sdt) * seg_gamma))
                        p = jnp.where(bad, r16, p)
                    nin = jnp.minimum(jnp.int32(replace_every), maxits - its)

                    def ibody(j, st):
                        d, rr, pp, g = st
                        live = j < nin
                        t = spmv(pp)
                        pdott = pdot(pp, t)
                        num = g if replace_restart else pdot(rr, pp)
                        alpha = jnp.where(live & (pdott > 0), num / pdott,
                                          jnp.zeros_like(g))
                        d = (d.astype(sdt)
                             + alpha * pp.astype(sdt)).astype(vdt)
                        r_new = (rr.astype(sdt)
                                 - alpha * t.astype(sdt)).astype(vdt)
                        g_next = pdot(r_new, r_new)
                        beta = jnp.where(g > 0, g_next / g,
                                         jnp.zeros_like(g))
                        pp = jnp.where(live,
                                       (r_new.astype(sdt)
                                        + beta * pp.astype(sdt)).astype(vdt),
                                       pp)
                        return (d, r_new, pp, g_next)

                    d, _, p, _ = jax.lax.fori_loop(
                        0, replace_every, ibody,
                        (jnp.zeros_like(r16), r16, p, seg_gamma))
                    x32 = x32 + d.astype(sdt)
                    r32 = b - spmv(x32)
                    return x32, r32, p, its + nin, pdot(r32, r32)

                p0 = r.astype(vdt)
                if unbounded:
                    nouter = ((maxits + jnp.int32(replace_every) - 1)
                              // jnp.int32(replace_every))

                    def obody(_, carry):
                        x32, r32, p, its, _ = carry
                        return segment(x32, r32, p, its)

                    x32, _, _, k, gamma_f = jax.lax.fori_loop(
                        0, nouter, obody,
                        (x0, r, p0, jnp.int32(0), gamma))
                    done = jnp.isfinite(gamma_f)
                else:
                    def wcond(c):
                        # NaN >= x is False: a non-finite recomputed
                        # residual exits here -- the segment boundary
                        # doubles as the breakdown detector for free
                        return (c[4] >= res_tol * res_tol) & (c[3] < maxits)

                    def wbody(c):
                        return segment(*c[:4])

                    x32, _, _, k, gamma_f = jax.lax.while_loop(
                        wcond, wbody, (x0, r, p0, jnp.int32(0), gamma))
                    done = gamma_f < res_tol * res_tol
                return (x32[None], k, jnp.sqrt(gamma_f), r0nrm2, bnrm2,
                        x0nrm2, inf, done, ~jnp.isfinite(gamma_f))

            # heartbeat emits from part 0 only: every recorded scalar is
            # psum'd (mesh-uniform), so one part speaks for the mesh
            leader = None
            if progress and not single_shard:
                leader = lax.axis_index(axis) == jnp.int32(0)

            if not pipelined:
                # carry layout mirrors jax_cg._cg_program: rr (the true
                # residual the convergence test reads) joins only under
                # precond, dx only under a diff criterion
                dx_i = 5 if precond_spec is not None else 4

                # dxsqr joins the carry only under a diff criterion (extra
                # loop-carried scalars measurably slow the TPU loop)
                def body(k, state):
                    if trace:
                        buf, state = state[-1], state[:-1]
                    if health is not None:
                        aud, state = state[-1], state[:-1]
                    x, r, p, gamma = state[:4]
                    t = spmv(p, k)
                    pdott = pdot(p, t)
                    if fault is not None:
                        pdott = fault.apply_dot(pdott, k)
                    if detect:
                        # breakdown detection mirrors jax_cg._cg_program
                        # (shared predicate; the deferred gamma_next
                        # term below too): every flagged scalar is
                        # psum'd, so `bad` is identical on all shards
                        # and the early exit is mesh-uniform
                        bad, alpha = _breakdown_guard(gamma, pdott)
                        x = store(jnp.where(bad, x, x + alpha * p))
                        r = store(jnp.where(bad, r, r - alpha * t))
                    else:
                        alpha = gamma / pdott
                        x = store(x + alpha * p)
                        r = store(r - alpha * t)
                    if precond_spec is not None:
                        z = papply(r, k)
                        # ONE fused psum for both scalars: the classic
                        # PCG loop keeps 2 allreduces per iteration
                        gamma_next, rr_next = pdot2_fused(r, z, r, r)
                        beta = gamma_next / gamma
                        p_next = store(z + beta * p)
                        out = (x, r, p_next, gamma_next, rr_next)
                    else:
                        gamma_next = pdot(r, r)
                        beta = gamma_next / gamma
                        p_next = store(r + beta * p)
                        out = (x, r, p_next, gamma_next)
                    if needs_diff:
                        dx = alpha * alpha * psum(ldot(p, p))
                        if detect:
                            # freeze dx on breakdown (jax_cg rationale):
                            # alpha = 0 must not fake the diff criterion
                            dx = jnp.where(bad, state[dx_i], dx)
                        out = out + (dx,)
                    fire = None
                    if health is not None:
                        # cadence phased to TRAJECTORY iterations: the
                        # checkpoint chunk driver passes the chunk's
                        # starting iteration (mesh-uniform, like k)
                        kk = k if k_offset is None else k + k_offset

                        # in-loop audit through the SAME halo'd SpMV:
                        # the cond predicate (k) is mesh-uniform, so
                        # the conditional collectives fire on every
                        # shard together; the psum'd gap replicates
                        def compute_gap():
                            return _health.relative_gap(b - spmv(x), r,
                                                                                pdot, bnrm2, sdt)

                        aud, fire = _health.audit_update(
                            aud, health, kk, compute_gap)
                        prog_now = (out[4] if precond_spec is not None
                                    else gamma_next)
                        prog_prev = (state[4] if precond_spec is not None
                                     else gamma)
                        aud = _health.stall_update(aud, health,
                                                   prog_now < prog_prev)
                        if health.abft:
                            # Huang-Abraham checksum test of this
                            # iteration's t = A p: sum(t) vs (c, p),
                            # all three scalars in ONE fused psum
                            aud = _health.abft_update(
                                aud, health, kk, t, p, cvec,
                                pdot3_fused, sdt, nglobal)
                    if detect:
                        deferred = bad | (~jnp.isfinite(gamma_next))
                        if precond_spec is not None:
                            # negative (r, z): the non-SPD-M signal
                            deferred = deferred | (gamma_next < 0)
                        if health is not None:
                            if precond_spec is None:
                                # sign anomaly (jax_cg rationale)
                                deferred = deferred | (gamma_next < 0)
                            deferred = deferred | _health.trip(aud,
                                                               health)
                        out = out + (deferred,)
                    if health is not None:
                        out = out + (aud,)
                    if trace:
                        # psum'd scalars: the ring is replicated, one
                        # rank-independent fetch per solve (gamma IS the
                        # preconditioned residual norm^2 under precond)
                        audit_col = (_health.ring_gap(aud, fire, sdt)
                                     if health is not None else None)
                        out = out + (telemetry.ring_record(
                            buf, k, gamma_next, alpha, beta, pdott,
                            audit=audit_col),)
                    if progress:
                        telemetry.heartbeat(k, gamma_next, progress,
                                            leader=leader, what="dist-cg")
                    return out

                if carry is not None:
                    init_state = (x0,) + tuple(carry)
                elif precond_spec is not None:
                    init_state = (x0, r, u0, gamma0, rr0)
                else:
                    init_state = (x0, r, r, gamma)
                init_state = init_state + ((inf,) if needs_diff else ())
                if detect:
                    init_state = init_state + (jnp.asarray(False),)
                if health is not None:
                    init_state = init_state + (_health.audit_init(sdt,
                                                                  health),)
                if trace:
                    init_state = init_state + (telemetry.ring_init(
                        trace, sdt, audit=health is not None),)
                bad_i = -1 - (1 if trace else 0) - (
                    1 if health is not None else 0)
                conv_i = 4 if precond_spec is not None else 3
                k, state, done = run_iter(
                    body, init_state, lambda s: s[conv_i],
                    (lambda s: s[dx_i]) if needs_diff else (lambda s: inf),
                    bad_of=(lambda s: s[bad_i]) if detect else None)
                x, r_fin, gamma_fin = state[0], state[1], state[conv_i]
                dxsqr = state[dx_i] if needs_diff else inf
                breakdown = state[bad_i] if detect else jnp.asarray(False)
                tbuf = state[-1] if trace else None
                aud_out = (state[-2] if trace else state[-1]) \
                    if health is not None else None
                rnrm2 = jnp.sqrt(gamma_fin)
            elif precond_spec is not None:
                # preconditioned Ghysels-Vanroose (jax_cg pbody, psum'd):
                # ONE fused 3-scalar allreduce per iteration, the
                # preconditioner apply + its SpMV overlapping it
                if carry is None:
                    w = spmv(u0)
                zeros = jnp.zeros_like(b)

                def pbody(k, state):
                    if trace:
                        buf, state = state[-1], state[:-1]
                    if health is not None:
                        aud, state = state[-1], state[:-1]
                    x, r, u, w, p, s, q, z, gamma_prev, alpha_prev = \
                        state[:10]
                    rr_prev = state[10]
                    gamma, delta, rr = pdot3_fused(r, u, w, u, r, r)
                    if fault is not None:
                        delta = fault.apply_dot(delta, k)
                    m = papply(w, k)
                    nvec = spmv(m, k)
                    beta = gamma / gamma_prev
                    denom = delta - beta * (gamma / alpha_prev)
                    if detect:
                        bad, alpha = _breakdown_guard(gamma, denom)
                        bad = bad | (gamma < 0)
                        alpha = jnp.where(bad, jnp.zeros_like(alpha),
                                          alpha)
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
                        dx = alpha * alpha * psum(ldot(p, p))
                        if detect:
                            dx = jnp.where(bad, state[11], dx)
                        out = out + (dx,)
                    fire = None
                    if health is not None:
                        kk = k if k_offset is None else k + k_offset

                        def compute_gap():
                            return _health.relative_gap(b - spmv(x), r,
                                                                                pdot, bnrm2, sdt)

                        aud, fire = _health.audit_update(
                            aud, health, kk, compute_gap)
                        aud = _health.stall_update(aud, health,
                                                   rr < rr_prev)
                        if health.abft:
                            # checksum test of this iteration's n = A m
                            aud = _health.abft_update(
                                aud, health, kk, nvec, m, cvec,
                                pdot3_fused, sdt, nglobal)
                    if detect:
                        flag = bad
                        if health is not None:
                            flag = flag | _health.trip(aud, health)
                        out = out + (flag,)
                    if health is not None:
                        out = out + (aud,)
                    if trace:
                        audit_col = (_health.ring_gap(aud, fire, sdt)
                                     if health is not None else None)
                        out = out + (telemetry.ring_record(
                            buf, k, gamma, alpha, beta, denom,
                            audit=audit_col),)
                    if progress:
                        telemetry.heartbeat(k, gamma, progress,
                                            leader=leader,
                                            what="dist-cg")
                    return out

                if carry is not None:
                    init_state = (x0,) + tuple(carry)
                    rr0 = carry[9]
                else:
                    init_state = (x0, r, u0, w, zeros, zeros, zeros,
                                  zeros, inf, inf, rr0)
                init_state = init_state + ((inf,) if needs_diff else ())
                if detect:
                    init_state = init_state + (jnp.asarray(False),)
                if health is not None:
                    init_state = init_state + (_health.audit_init(sdt,
                                                                  health),)
                if trace:
                    init_state = init_state + (telemetry.ring_init(
                        trace, sdt, audit=health is not None),)
                bad_i = -1 - (1 if trace else 0) - (
                    1 if health is not None else 0)
                k, state, done = run_iter(
                    pbody, init_state, lambda s: s[10],
                    (lambda s: s[11]) if needs_diff else (lambda s: inf),
                    init_gamma=rr0,
                    bad_of=(lambda s: s[bad_i]) if detect else None)
                x, r_fin = state[0], state[1]
                dxsqr = state[11] if needs_diff else inf
                breakdown = state[bad_i] if detect else jnp.asarray(False)
                tbuf = state[-1] if trace else None
                aud_out = (state[-2] if trace else state[-1]) \
                    if health is not None else None
                rnrm2 = jnp.sqrt(pdot(r_fin, r_fin))
                # stale-test consistency: see jax_cg._cg_pipelined_program
                done = jnp.logical_or(done, rnrm2 <= res_tol)
            else:
                if carry is None:
                    w = spmv(r)
                zeros = jnp.zeros_like(b)

                def body(k, state):
                    if trace:
                        buf, state = state[-1], state[:-1]
                    if health is not None:
                        aud, state = state[-1], state[:-1]
                    x, r, w, p, t, z, gamma_prev, alpha_prev = state[:8]
                    # the pipelined variant's single fused allreduce:
                    # both scalars in one psum (cgcuda.c:1730-1737)
                    # single fused allreduce of both scalars
                    gamma, delta = pdot2_fused(r, r, w, r)
                    if fault is not None:
                        delta = fault.apply_dot(delta, k)
                    q = spmv(w, k)  # overlaps the psum under XLA's scheduler
                    # the SpMV input, before the update rebinds w (the
                    # ABFT check verifies q against THIS vector)
                    w_in = w
                    beta = gamma / gamma_prev
                    denom = delta - beta * (gamma / alpha_prev)
                    if detect:
                        # jax_cg._cg_pipelined_program's guard: the
                        # flag is NOT gamma_next-deferred here (the
                        # pipelined poison surfaces in the next
                        # iteration's (w, r) reduction instead)
                        bad, alpha = _breakdown_guard(gamma, denom)
                        if health is not None:
                            # sign anomaly (jax_cg rationale)
                            bad = bad | (gamma < 0)
                            alpha = jnp.where(bad, jnp.zeros_like(alpha),
                                              alpha)
                    else:
                        alpha = gamma / denom
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
                        dx = alpha * alpha * psum(ldot(p, p))
                        if detect:
                            dx = jnp.where(bad, state[8], dx)
                        out = out + (dx,)
                    fire = None
                    if health is not None:
                        kk = k if k_offset is None else k + k_offset

                        def compute_gap():
                            return _health.relative_gap(b - spmv(x), r,
                                                                                pdot, bnrm2, sdt)

                        aud, fire = _health.audit_update(
                            aud, health, kk, compute_gap)
                        aud = _health.stall_update(aud, health,
                                                   gamma < gamma_prev)
                        if health.abft:
                            # checksum test of this iteration's q = A w
                            # (w_in: the pre-update input)
                            aud = _health.abft_update(
                                aud, health, kk, q, w_in, cvec,
                                pdot3_fused, sdt, nglobal)
                    if detect:
                        flag = bad
                        if health is not None:
                            flag = flag | _health.trip(aud, health)
                        out = out + (flag,)
                    if health is not None:
                        out = out + (aud,)
                    if trace:
                        # carried gamma (stale by one, like the
                        # convergence test); alpha denominator in the
                        # pAp slot (jax_cg._cg_pipelined_program)
                        audit_col = (_health.ring_gap(aud, fire, sdt)
                                     if health is not None else None)
                        out = out + (telemetry.ring_record(
                            buf, k, gamma, alpha, beta, denom,
                            audit=audit_col),)
                    if progress:
                        telemetry.heartbeat(k, gamma, progress,
                                            leader=leader, what="dist-cg")
                    return out

                # stale-gamma convergence test (see jax_cg): s[6] is the
                # psum'd ||r||^2 from before the update
                if carry is not None:
                    init_state = (x0,) + tuple(carry)
                    init_gamma = carry[5]
                else:
                    init_state = (x0, r, w, zeros, zeros, zeros, inf, inf)
                    init_gamma = gamma
                init_state = init_state + ((inf,) if needs_diff else ())
                if detect:
                    init_state = init_state + (jnp.asarray(False),)
                if health is not None:
                    init_state = init_state + (_health.audit_init(sdt,
                                                                  health),)
                if trace:
                    init_state = init_state + (telemetry.ring_init(
                        trace, sdt, audit=health is not None),)
                bad_i = -1 - (1 if trace else 0) - (
                    1 if health is not None else 0)
                k, state, done = run_iter(
                    body, init_state, lambda s: s[6],
                    (lambda s: s[8]) if needs_diff else (lambda s: inf),
                    init_gamma=init_gamma,
                    bad_of=(lambda s: s[bad_i]) if detect else None)
                x, r_fin = state[0], state[1]
                dxsqr = state[8] if needs_diff else inf
                breakdown = state[bad_i] if detect else jnp.asarray(False)
                tbuf = state[-1] if trace else None
                aud_out = (state[-2] if trace else state[-1]) \
                    if health is not None else None
                rnrm2 = jnp.sqrt(pdot(r_fin, r_fin))
                # stale-test consistency: see jax_cg._cg_pipelined_program
                done = jnp.logical_or(done, rnrm2 <= res_tol)

            # breakdown-at-the-floor consistency (jax_cg rationale): a
            # flagged exit whose residual already meets tolerance is
            # convergence, not breakdown
            breakdown = breakdown & ~done
            dxnrm2 = jnp.sqrt(dxsqr)
            out = (x[None], k, rnrm2, r0nrm2, bnrm2, x0nrm2, dxnrm2,
                   done, breakdown)
            out = out + ((tbuf,) if trace else ())
            # the audit vector rides after the ring so the existing
            # out[9] = ring fetch in solve() is untouched
            out = out + ((aud_out,) if health is not None else ())
            if state_io:
                # the final loop carry, strictly last (checkpoint.
                # carry_names order minus x, which rides the result):
                # vector leaves re-stack the parts axis, psum'd scalars
                # stay replicated
                core = state[1:1 + len(c_names)]
                out = out + tuple(v[None] if v.ndim else v
                                  for v in core)
            return out

        with_precond = precond_spec is not None
        if single_shard and not prob.halo.has_ghosts:
            # one shard, no halo: shard_body runs as a PLAIN jit program
            # (the stacked (1, ...) leading axes are stripped inside it
            # either way).  Skipping shard_map avoids its manual-
            # sharding boundary entirely, so XLA optimises the loop
            # exactly like the single-chip solver's.
            @functools.partial(jax.jit,
                               static_argnames=("unbounded", "needs_diff",
                                                "detect"))
            def program(la, ga, sidx, gsrc, gval, scnt, rcnt, b, x0,
                        tols, maxits, unbounded, needs_diff,
                        detect=False, mstate=None, carry=None,
                        k_offset=None):
                return shard_body(la, ga, sidx, gsrc, gval, scnt, rcnt,
                                  b, x0, tols, maxits, mstate=mstate,
                                  unbounded=unbounded,
                                  needs_diff=needs_diff, detect=detect,
                                  carry=carry, k_offset=k_offset)

            return program

        pspec = P(PARTS_AXIS)
        rspec = P()
        # pspec acts as a pytree prefix for the la/ga tuples (and the
        # mstate pytree when a preconditioner is armed: every state
        # leaf carries a leading parts axis, scalars tiled)
        in_specs = (pspec, pspec,                              # blocks
                    pspec, pspec, pspec, pspec, pspec,         # halo, counts
                    pspec, pspec,                              # b, x0
                    rspec, rspec)                              # tols, maxits
        if with_precond:
            in_specs = in_specs + (pspec,)                     # mstate
        # the telemetry ring (psum'd scalars) and the audit vector
        # (psum'd gap) are replicated
        out_specs = (pspec,) + (rspec,) * (
            8 + (1 if trace else 0)
            + (1 if self.health_spec is not None else 0))
        # the state_io carry: vector leaves shard like x, psum'd
        # scalars replicate (checkpoint.carry_names order)
        carry_specs = tuple(rspec if nm in SCALAR_LEAVES else pspec
                            for nm in c_names)
        if state_io:
            out_specs = out_specs + carry_specs

        @functools.partial(jax.jit,
                           static_argnames=("unbounded", "needs_diff",
                                            "detect"))
        def program(la, ga, sidx, gsrc, gval, scnt, rcnt, b, x0,
                    tols, maxits, unbounded, needs_diff, detect=False,
                    mstate=None, carry=None, k_offset=None):
            extra = (mstate,) if with_precond else ()
            specs = in_specs
            if carry is not None:
                extra = extra + (tuple(carry),)
                specs = specs + (carry_specs,)
            if k_offset is not None:
                extra = extra + (k_offset,)
                specs = specs + (rspec,)

            def smb(la, ga, sidx, gsrc, gval, scnt, rcnt, b, x0, tols,
                    maxits, *rest):
                i = 0
                ms = cr = ko = None
                if with_precond:
                    ms, i = rest[i], i + 1
                if carry is not None:
                    cr, i = rest[i], i + 1
                if k_offset is not None:
                    ko, i = rest[i], i + 1
                return shard_body(la, ga, sidx, gsrc, gval, scnt, rcnt,
                                  b, x0, tols, maxits, mstate=ms,
                                  unbounded=unbounded,
                                  needs_diff=needs_diff, detect=detect,
                                  carry=cr, k_offset=ko)

            return jax.shard_map(
                smb,
                mesh=self.mesh, in_specs=specs, out_specs=out_specs,
                check_vma=False,
            )(la, ga, sidx, gsrc, gval, scnt, rcnt, b, x0, tols, maxits,
              *extra)

        return program

    def _compile_ca(self, fault=None):
        """Communication-avoiding recurrence programs: s-step CG (one
        Gram allreduce per s-iteration block) and deep-pipelined
        p(l)-CG (one fused 2l+2-scalar window allreduce per iteration),
        shard_map'd over the SAME halo'd SpMV / psum plumbing as the
        hand-built programs.  The recurrence math itself -- basis
        construction, coefficient updates, the stream-Cholesky window
        bookkeeping -- is the same code the single-device tier runs
        (recurrence.run_sstep_loop / run_pl_loop): a recurrence lands
        once in the builder and rides every tier."""
        from acg_tpu.recurrence import (TierOps, run_pl_loop,
                                        run_sstep_loop)
        prob = self.problem
        algo = self.algo
        axis = PARTS_AXIS
        comm = self.comm
        interpret = self._interpret
        trace = self.trace
        progress = self.progress
        health = self.health_spec
        dist_spmv = make_dist_spmv(prob, comm, interpret,
                                   kernels=self.kernels, fault=fault)
        single_shard = self.mesh.devices.size == 1

        def psum(v):
            return v if single_shard else lax.psum(v, axis)

        def shard_body(la, ga, sidx, gsrc, gval, scnt, rcnt, b, x0,
                       tols, maxits, lam, unbounded=False):
            la, ga = (jax.tree.map(lambda a: a[0], t) for t in (la, ga))
            sidx, gsrc, gval, scnt, rcnt, b, x0 = (
                a[0] for a in (sidx, gsrc, gval, scnt, rcnt, b, x0))
            maxits = maxits.astype(jnp.int32)
            dtype = b.dtype
            sdt = acc_dtype(dtype)
            store = ((lambda v: v.astype(dtype)) if sdt != dtype
                     else (lambda v: v))
            res_atol, res_rtol = tols[0], tols[1]
            pidx = None
            if fault is not None:
                pidx = (jnp.int32(0) if single_shard
                        else lax.axis_index(axis))

            def spmv(x, k=None):
                return dist_spmv(x, la, ga, sidx, gsrc, gval, scnt,
                                 rcnt, k=k, pidx=pidx)

            def ldot(a, c):
                return jnp.dot(a, c, preferred_element_type=sdt)

            pdot = make_pdot(psum, ldot, sdt, False)
            ops = TierOps(spmv=spmv, dot=pdot, psum_stack=psum,
                          store=store, sdt=sdt)
            leader = None
            if progress and not single_shard:
                leader = lax.axis_index(axis) == jnp.int32(0)
            bnrm2 = jnp.sqrt(pdot(b, b))
            x0nrm2 = jnp.sqrt(pdot(x0, x0))
            r = b - spmv(x0)
            gamma = pdot(r, r)
            r0nrm2 = jnp.sqrt(gamma)
            res_tol = jnp.maximum(res_atol, res_rtol * r0nrm2)
            inf = jnp.asarray(jnp.inf, sdt)
            lam_t = (lam[0].astype(sdt), lam[1].astype(sdt))
            what = algo.solver_name("dist-cg")
            if algo.kind == "sstep":
                x, k, gamma_f, bad, done, extras = run_sstep_loop(
                    ops, algo.param, algo.basis, lam_t, b, x0, r,
                    gamma, res_tol, maxits, unbounded, fault=fault,
                    trace=trace, progress=progress, health=health,
                    what=what, leader=leader, bnrm2=bnrm2)
                rnrm2 = jnp.sqrt(jnp.maximum(gamma_f, 0.0))
            else:
                eta = r0nrm2
                z0 = store(r / jnp.where(eta == 0, 1.0, eta))
                x, k, q, conv, bad, extras = run_pl_loop(
                    ops, algo.param, lam_t, x0, z0, eta, gamma,
                    res_tol, maxits, unbounded, fault=fault,
                    trace=trace, progress=progress, what=what,
                    leader=leader)
                x = store(x)
                rnrm2 = jnp.abs(q)
                done = (~bad) if unbounded else conv
            breakdown = bad & ~done
            out = (x[None], k, rnrm2, r0nrm2, bnrm2, x0nrm2, inf,
                   done, breakdown)
            return out + extras

        pspec = P(PARTS_AXIS)
        rspec = P()
        in_specs = (pspec, pspec, pspec, pspec, pspec, pspec, pspec,
                    pspec, pspec, rspec, rspec, rspec)
        out_specs = (pspec,) + (rspec,) * (
            8 + (1 if trace else 0)
            + (1 if health is not None else 0))

        @functools.partial(jax.jit,
                           static_argnames=("unbounded", "needs_diff",
                                            "detect"))
        def program(la, ga, sidx, gsrc, gval, scnt, rcnt, b, x0, tols,
                    maxits, lam, unbounded, needs_diff, detect=False):
            # needs_diff / detect ride the signature for dispatch
            # compatibility: diff criteria are refused at solve time,
            # and the CA programs always carry their breakdown flag
            if single_shard and not prob.halo.has_ghosts:
                return shard_body(la, ga, sidx, gsrc, gval, scnt, rcnt,
                                  b, x0, tols, maxits, lam,
                                  unbounded=unbounded)

            def smb(la, ga, sidx, gsrc, gval, scnt, rcnt, b, x0, tols,
                    maxits, lam):
                return shard_body(la, ga, sidx, gsrc, gval, scnt, rcnt,
                                  b, x0, tols, maxits, lam,
                                  unbounded=unbounded)

            return jax.shard_map(
                smb, mesh=self.mesh, in_specs=in_specs,
                out_specs=out_specs,
                check_vma=False,
            )(la, ga, sidx, gsrc, gval, scnt, rcnt, b, x0, tols,
              maxits, lam)

        return program

    def _ensure_lam(self, dev_args):
        """Cached (lmin, lmax) interval for the CA recurrences: the
        mesh power iteration (_power_lmax) through this tier's own
        halo'd SpMV, with the recurrence module's spectral headroom."""
        if self._lam is None:
            from acg_tpu.recurrence import LAM_SAFETY
            if self.algo is not None and self.algo.needs_lam:
                self._lam = (0.0,
                             self._power_lmax(dev_args) * LAM_SAFETY)
            else:
                self._lam = (0.0, 0.0)
        return self._lam

    def _solver_name(self) -> str:
        if self.algo is not None:
            return self.algo.solver_name("dist-cg")
        return "dist-cg-pipelined" if self.pipelined else "dist-cg"

    def _interior_rows(self) -> np.ndarray:
        """Cached stacked interior row lists (the fused tier's split;
        host numpy, placed by device_args like the halo plan)."""
        if getattr(self, "_irows", None) is None:
            self._irows = interior_border_split(self.problem)
        return self._irows

    # -- preconditioner state ---------------------------------------------

    def _power_lmax(self, dev_args, iters=None) -> float:
        """Power-iteration lambda_max over the SAME halo'd distributed
        SpMV the solve programs run, compiled once at setup (the
        Chebyshev tier's spectral estimate).  Norms psum across the
        mesh, so every shard (and controller) derives the identical
        scalar."""
        from acg_tpu.precond import POWER_ITERS
        iters = POWER_ITERS if iters is None else int(iters)
        b, x0, la, ga, sidx, gsrc, gval, scnt, rcnt = dev_args
        prob = self.problem
        axis = PARTS_AXIS
        dist_spmv = make_dist_spmv(prob, self.comm, self._interpret,
                                   kernels=self.kernels)
        single_shard = self.mesh.devices.size == 1
        sdt = acc_dtype(np.dtype(prob.vdtype))

        def shard(la, ga, sidx, gsrc, gval, scnt, rcnt, v):
            la, ga = (jax.tree.map(lambda a: a[0], t) for t in (la, ga))
            sidx, gsrc, gval, scnt, rcnt, v = (
                a[0] for a in (sidx, gsrc, gval, scnt, rcnt, v))

            def psum(s):
                return s if single_shard else lax.psum(s, axis)

            def spmv(x):
                return dist_spmv(x, la, ga, sidx, gsrc, gval, scnt, rcnt)

            def ldot(a, c):
                return jnp.dot(a, c, preferred_element_type=sdt)

            def it(_, v):
                w = spmv(v)
                return (w.astype(sdt)
                        / jnp.sqrt(psum(ldot(w, w)))).astype(v.dtype)

            v = jax.lax.fori_loop(0, iters, it, v)
            w = spmv(v)
            return psum(ldot(v, w)) / psum(ldot(v, v))

        rng = np.random.default_rng(0)
        v0 = put_global(prob.scatter(rng.standard_normal(prob.n)),
                        sharding=self._sharding)
        if single_shard and not prob.halo.has_ghosts:
            out = jax.jit(shard)(la, ga, sidx, gsrc, gval, scnt, rcnt, v0)
        else:
            pspec = P(PARTS_AXIS)
            out = jax.jit(jax.shard_map(
                shard, mesh=self.mesh,
                in_specs=(pspec,) * 8, out_specs=P(),
                check_vma=False,
            ))(la, ga, sidx, gsrc, gval, scnt, rcnt, v0)
        return float(out)

    def _ensure_precond_state(self, dev_args=None):
        """Build (once) the stacked preconditioner state and place it on
        the mesh: jacobi/bjacobi from each part's LOCAL host blocks (no
        communication -- diagonal entries are owned x owned by
        construction), cheby from the power iteration above.  Every
        leaf carries a leading parts axis (scalars tiled), so ONE
        pytree-prefix spec shards the whole state."""
        if self.precond_spec is None or self._mstate is not None:
            return self._mstate
        from acg_tpu import precond as precond_mod
        prob = self.problem
        sdt = np.dtype(acc_dtype(np.dtype(prob.vdtype)))
        spec = self.precond_spec
        if spec.kind == "jacobi":
            host = precond_mod.stacked_jacobi_state(prob, sdt)
        elif spec.kind == "bjacobi":
            host = precond_mod.stacked_bjacobi_state(prob, spec.block, sdt)
        else:
            if dev_args is None:
                dev_args = getattr(self, "_last_dev_args", None)
            if dev_args is None:
                raise RuntimeError("cheby state needs the placed device "
                                   "arguments (solve/lower build them)")
            lmax = self._power_lmax(dev_args) * precond_mod.CHEBY_SAFETY
            lmin = lmax / precond_mod.CHEBY_RATIO
            self._precond_lams = (lmin, lmax)
            host = (np.full((prob.nparts,), lmin, sdt),
                    np.full((prob.nparts,), lmax, sdt))
        put = functools.partial(put_global, sharding=self._sharding)
        self._mstate = jax.tree.map(put, host)
        return self._mstate

    # -- public solve ------------------------------------------------------

    def _solve_dtype(self):
        """The dtype solve inputs scatter to (the JaxCGSolver hook's
        twin, shared with the perfmodel tier): the problem's vector
        dtype, except the replacement tier's outer iteration owns b/x0
        in f32."""
        return np.dtype(np.float32 if self.replace_every
                        else self.problem.vdtype)

    def device_args(self, b_global: np.ndarray,
                    x0: np.ndarray | None = None):
        """Scatter + place every solve input on the mesh (the upload
        stage of ``acgsolvercuda_init``, ``cgcuda.c:143-332``); shared
        by :meth:`solve` and the per-op profiler.

        Under ``replace_every`` the outer iteration owns b/x0 in f32
        (scattering them to bf16 would bake a u_bf16 backward error
        into every replaced residual)."""
        prob = self.problem
        dtype = self._solve_dtype()
        put = functools.partial(put_global, sharding=self._sharding)
        b = put(prob.scatter(np.asarray(b_global), dtype=dtype))
        x0 = put(prob.scatter(np.asarray(x0), dtype=dtype)
                 if x0 is not None
                 else np.zeros((prob.nparts, prob.nmax_owned), dtype=dtype))
        la = jax.tree.map(put, prob.local.arrays)
        ga = jax.tree.map(put, (prob.ghost.rows, prob.ghost.data,
                                prob.ghost.cols))
        if self.kernels == "fused":
            # the interior row lists ride the ghost-block tuple (the
            # split SpMV consumes both row sets together); the pytree-
            # prefix shard specs cover the longer tuple unchanged
            ga = ga + (put(self._interior_rows()),)
        sidx = put(prob.halo.send_idx)
        gsrc = put(prob.halo.ghost_src)
        gval = put(prob.halo.ghost_valid)
        scnt_np, rcnt_np = prob.neighbor_counts()
        return (b, x0, la, ga, sidx, gsrc, gval,
                put(scnt_np), put(rcnt_np))

    def lower_solve(self, b_global, x0=None, criteria=None):
        """Lower (but do not run) the EXACT whole-solve SPMD program this
        configuration dispatches for ``(b, x0, criteria)`` and return
        the ``jax.stages.Lowered`` handle -- the observability hook the
        perfmodel tier (:mod:`acg_tpu.perfmodel`) compiles to extract
        the compiler's cost/memory analysis.  Same program object, same
        static arguments and same input avals as :meth:`solve`, so the
        lowered text is byte-identical to a clean solve's (asserted in
        tests/test_hlo_structure.py); detection mirrors a clean solve
        (armed iff a recovery policy is set -- never the fault
        injector)."""
        crit = criteria or StoppingCriteria()
        if self.replace_every and crit.needs_diff:
            raise ValueError("replace_every supports residual criteria "
                             "only")
        if self.algo is not None and crit.needs_diff:
            raise ValueError(f"{self.algo} supports residual criteria "
                             f"only")
        if self.kernels == "fused" and crit.needs_diff:
            raise ValueError("kernels='fused' supports residual "
                             "criteria only")
        sdt = acc_dtype(np.dtype(self.problem.vdtype))
        dev = self.device_args(np.asarray(b_global), x0)
        b, x0, la, ga, sidx, gsrc, gval, scnt, rcnt = dev
        tols = jnp.asarray([crit.residual_atol, crit.residual_rtol,
                            crit.diff_atol, crit.diff_rtol], dtype=sdt)
        program = self._program_for(None)
        kwargs = dict(unbounded=crit.unbounded,
                      needs_diff=crit.needs_diff,
                      detect=self._detect(None))
        if self.precond_spec is not None:
            self._last_dev_args = dev
            kwargs["mstate"] = self._ensure_precond_state(dev)
        args = (la, ga, sidx, gsrc, gval, scnt, rcnt, b, x0,
                tols, jnp.int32(crit.maxits))
        if self.algo is not None:
            lam = self._ensure_lam(dev)
            args = args + ((jnp.asarray(lam[0], sdt),
                            jnp.asarray(lam[1], sdt)),)
        return program.lower(*args, **kwargs)

    def _detect(self, fault) -> bool:
        """Breakdown-flag arming shared by solve() and lower_solve (the
        jax_cg._detect twin): recovery, an active injector, or a health
        spec whose detectors trip the breakdown path."""
        return (self.recovery is not None or fault is not None
                or (self.health_spec is not None
                    and self.health_spec.arms_detect))

    def comm_profile(self) -> dict:
        """Static per-iteration communication ledger (the perfmodel
        tier): per-neighbour halo payload bytes from the halo plans,
        psum/allreduce scalar counts and bytes, and ring-hop estimates
        from the 1-D mesh shape.  Pure host arithmetic -- nothing here
        touches the device or the compiled programs.

        Counts describe the direct classic/pipelined loop: one halo'd
        SpMV per iteration, classic = 2 psums of 1 scalar each,
        pipelined = 1 FUSED psum of 2 scalars (the communication-
        avoiding property tests/test_hlo_structure.py pins in the HLO);
        compensated dots double each payload (hi+lo pairs).  The
        replacement tier runs the same pattern per inner iteration plus
        one f32 exchange per segment."""
        prob = self.problem
        P = int(prob.nparts)
        dbl = int(np.dtype(prob.vdtype).itemsize)
        sdl = int(np.dtype(acc_dtype(np.dtype(prob.vdtype))).itemsize)
        scnt, _rcnt = prob.neighbor_counts()
        neighbors = []
        total = 0
        max_hops = 0
        for p in range(P):
            for q in range(P):
                c = int(scnt[p, q])
                if c == 0 or p == q:
                    continue
                # ring distance over the 1-D parts axis: the ICI-hop
                # estimate for a torus-linked pod slice
                hops = min(abs(p - q), P - abs(p - q))
                max_hops = max(max_hops, hops)
                total += c * dbl
                neighbors.append({"src": p, "dst": q, "bytes": c * dbl,
                                  "hops": hops})
        nred = 1 if self.pipelined else 2
        scal = ((2 if self.pipelined else 1)
                * (2 if self.precise_dots else 1))
        led = {
            "transport": self.comm,
            "nparts": P,
            "mesh_shape": {str(k): int(v)
                           for k, v in dict(self.mesh.shape).items()},
            "halo_exchanges_per_iteration": 1,
            # local-read multi-controller builds hold plans only for
            # this controller's parts (neighbor_counts leaves the rest
            # zero): the halo totals then cover the OWNED rows only --
            # marked so a consumer never mistakes a per-controller
            # partial for the pod-global volume
            **({"owned_parts_only": True,
                "owned_parts": [int(p) for p in prob.owned_parts]}
               if prob.owned_parts is not None else {}),
            "halo_bytes_per_iteration": int(total),
            "allreduce_per_iteration": int(nred),
            "allreduce_scalars": int(scal),
            "allreduce_bytes_per_iteration": int(nred * scal * sdl),
            "max_hops": int(max_hops),
            # what the transport ACTUALLY moves per exchange and shard:
            # windows are padded to the mesh-wide maximum count (the
            # NVSHMEM symmetric-buffer trick), so the wire sees the
            # padded plane -- (P-1) windows for the dma rotation
            # schedule, P for the all_to_all plane.  The commbench
            # calibration prices halo time over these bytes (its
            # sweeps use the same convention); the unpadded neighbour
            # totals above stay the VOLUME accounting
            "halo_plane_bytes_per_exchange": int(
                ((P - 1) if self.comm == "dma" else P)
                * int(getattr(prob.halo, "maxcnt", 0)) * dbl),
            # the ring distances this partition's edges span -- the key
            # that matches a commbench per-edge put/wait row to an
            # actual edge of this halo plan
            "ring_distances": sorted({n["hops"] for n in neighbors}),
        }
        if prob.operator is not None:
            # the matrix-free stencil ledger: who the operator is, and
            # what the "matrix read" actually costs per apply -- the
            # O(grid-side) coefficient tables (0 for constant
            # stencils), NOT nnz * itemsize.  --explain prices the
            # roofline's matrix-bytes term from this
            led["operator"] = prob.operator.identity()
            led["matrix_free"] = True
            led["matrix_bytes_per_spmv"] = int(
                prob.operator.table_bytes())
        if self.kernels == "fused":
            # the overlap declaration of the fused tier: how much
            # interior-SpMV work is available to hide the halo latency
            # behind.  perfmodel's --explain verdict prices it as
            # predicted exposed halo seconds = max(0, t_halo -
            # t_interior_spmv), confronted with the measured
            # solve-windowed overlap score when a --trace capture
            # exists
            irows = self._interior_rows()
            nint = int((irows < prob.nmax_owned).sum())
            nbor = int((np.asarray(prob.ghost.rows)
                        < prob.nmax_owned).sum())
            mat_b = int(np.dtype(prob.dtype).itemsize)
            matfree = prob.local.format == "matfree"
            idx_b = 0 if prob.local.format in ("dia", "matfree") else 4
            nnz_int = 0
            for p, s in enumerate(prob.subs):
                if s.A_local is None:
                    continue
                rnnz = np.diff(s.A_local.indptr)
                ir = irows[p]
                ir = ir[ir < s.nowned]
                nnz_int += int(rnnz[ir].sum())
            led["overlap"] = {
                "split": "interior|border",
                "interior_rows": nint,
                "border_rows": nbor,
                "interior_nnz": nnz_int,
                # HBM traffic of the interior SpMV phase: matrix reads
                # plus the x gather + y write over the interior rows
                # (matrix-free: the planes are generated, not read --
                # only the vector traffic remains)
                "interior_matrix_bytes": (
                    (0 if matfree else nnz_int * (mat_b + idx_b))
                    + 2 * nint * dbl),
            }
        if self.algo is not None:
            # communication-avoiding recurrences: the reduction
            # schedule is the recurrence's own declaration
            # (recurrence.reduction_schedule) -- fractional values are
            # exact per-iteration averages of per-block events (the
            # whole point: s-step's 1/s allreduce per iteration vs
            # classic's 2)
            from acg_tpu.recurrence import reduction_schedule
            sched = reduction_schedule(self.algo, False)
            led["algorithm"] = str(self.algo)
            led["allreduce_per_iteration"] = \
                sched["allreduce_per_iteration"]
            led["allreduce_scalars"] = sched["allreduce_scalars"]
            led["allreduce_bytes_per_iteration"] = (
                sched["allreduce_per_iteration"]
                * sched["allreduce_scalars"] * sdl)
            led["halo_exchanges_per_iteration"] = \
                sched["spmv_per_iteration"]
            led["halo_bytes_per_iteration"] = (
                total * sched["spmv_per_iteration"])
            for extra_key in ("iterations_per_reduction",
                              "reduction_latency_hidden"):
                if extra_key in sched:
                    led[extra_key] = sched[extra_key]
        if self.precond_spec is not None:
            # reclassify for PCG: cheby multiplies the halo pattern by
            # its degree (K extra SpMV-shaped exchanges per iteration);
            # jacobi/bjacobi move nothing.  The scalar fused into the
            # existing reductions ((r,z) / the 3-scalar pipelined psum)
            # widens payloads without adding collectives
            from acg_tpu.precond import comm_contribution
            pc = comm_contribution(self.precond_spec)
            extra = int(pc.get("halo_spmv_equivalents_per_apply", 0))
            led["halo_exchanges_per_iteration"] = 1 + extra
            led["halo_bytes_per_iteration"] = int(total) * (1 + extra)
            # widest reduction payload: pipelined PCG fuses 3 scalars,
            # classic PCG's second psum fuses 2 (doubled compensated)
            led["allreduce_scalars"] = ((3 if self.pipelined else 2)
                                        * (2 if self.precise_dots else 1))
            # TOTAL scalars per iteration, not nred x widest: both PCG
            # loops move 3 (classic: 1 + the 2-scalar fusion)
            led["allreduce_bytes_per_iteration"] = (
                3 * (2 if self.precise_dots else 1) * sdl)
            led["precond"] = pc
        if len(neighbors) > 64:
            led["neighbors_truncated"] = len(neighbors) - 64
            neighbors = neighbors[:64]
        led["neighbors"] = neighbors
        return led

    def solve(self, b_global: np.ndarray, x0: np.ndarray | None = None,
              criteria: StoppingCriteria | None = None,
              raise_on_divergence: bool = True, warmup: int = 0,
              host_result: bool = True) -> np.ndarray:
        """``host_result=False`` skips the global gather and returns the
        STACKED device array ((nparts, nmax_owned), sharded over the
        mesh) -- callers that stream per-part windows to disk
        (``--output`` distributed write) or feed another device
        computation never materialise the full vector anywhere, the
        point of the reference's rank-ordered distributed output
        (``mtxfile_fwrite_mpi_double``)."""
        if self.ckpt is not None:
            return self._solve_ckpt(b_global, x0=x0, criteria=criteria,
                                    raise_on_divergence=raise_on_divergence,
                                    warmup=warmup,
                                    host_result=host_result)
        crit = criteria or StoppingCriteria()
        st = self.stats
        st.criteria = crit
        prob = self.problem
        dtype = np.dtype(prob.vdtype)
        if self.replace_every and crit.needs_diff:
            raise ValueError("replace_every supports residual criteria "
                             "only")
        if self.kernels == "fused" and crit.needs_diff:
            raise ValueError("kernels='fused' supports residual "
                             "criteria only (the builder base program "
                             "carries no dx scalar)")

        from acg_tpu import faults
        self._crash_refusal()
        fault = faults.device_fault()
        if fault is not None and self.kernels == "fused":
            # the fused base program carries no breakdown flag: an
            # armed injector would poison the solve with nothing
            # downstream ever noticing (the replace_every rationale)
            raise AcgError(
                ErrorCode.INVALID_VALUE,
                "fault injection does not reach the fused "
                "interior/border program (kernels='fused'); inject "
                "into the classic/pipelined programs instead")
        if (fault is not None and fault.site == "halo"
                and not prob.halo.has_ghosts):
            # this topology performs no halo exchange: the armed
            # injector could never fire (the replace_every rationale)
            raise AcgError(
                ErrorCode.INVALID_VALUE,
                "halo fault injection needs a topology with ghost "
                "exchange; this problem has no halo (single part or "
                "fully decoupled partition)")
        if fault is not None and fault.part >= prob.nparts:
            raise AcgError(
                ErrorCode.INVALID_VALUE,
                f"fault spec targets part {fault.part}, but this mesh "
                f"has {prob.nparts} parts -- the fault could never "
                f"fire")
        if fault is not None and self.replace_every:
            # the replacement segments call the dist SpMV without the
            # global iteration index: an armed injector would silently
            # never fire (jax_cg rationale) -- refuse instead
            raise AcgError(
                ErrorCode.INVALID_VALUE,
                "fault injection does not reach the replacement-segment "
                "program (replace_every); inject into the direct "
                "classic/pipelined programs instead")
        if (self.algo is not None and fault is not None
                and self.algo.kind == "sstep"
                and fault.site in ("spmv", "sdc", "halo")
                and fault.iteration % self.algo.param != 0):
            # the s-step basis products carry the BLOCK-START iteration
            # index (jax_cg rationale): mid-block arming never fires
            raise AcgError(
                ErrorCode.INVALID_VALUE,
                f"sstep:{self.algo.param} applies SpMV/halo faults at "
                f"block boundaries; arm an iteration that is a "
                f"multiple of {self.algo.param} (got "
                f"{fault.iteration})")
        if (self.algo is not None and fault is not None
                and self.algo.kind == "pl" and fault.site == "dot"):
            raise AcgError(
                ErrorCode.INVALID_VALUE,
                "dot fault injection has no site in the p(l) "
                "recurrence (its reductions are fused window matvecs); "
                "use spmv:, or the classic/pipelined/sstep programs")
        if (fault is not None and fault.site == "precond"
                and self.precond_spec is None):
            # no preconditioner armed: the apply the fault poisons
            # never runs (the replace_every rationale)
            raise AcgError(
                ErrorCode.INVALID_VALUE,
                "precond fault injection needs an armed preconditioner "
                "(--precond jacobi|bjacobi|cheby:K); this solve runs "
                "unpreconditioned CG")
        detect = self._detect(fault)
        from acg_tpu import telemetry
        if self._comm_downgrade is not None:
            # the capability-probe downgrade, recorded once as a
            # structured event so stats/metrics consumers see WHY this
            # solve ran the xla transport
            telemetry.record_event(st, "transport-downgrade",
                                   f"dma -> xla: {self._comm_downgrade}")
            self._comm_downgrade = None
        if fault is not None:
            telemetry.record_event(st, "fault-armed",
                                   f"{fault.site}:{fault.mode}"
                                   f"@{fault.iteration}")
        # an armed injector bakes into a solve-local program; the cached
        # pristine program serves every clean solve
        program = self._program_for(fault)

        t_xfer = time.perf_counter()
        with telemetry.annotate("transfer"):
            b, x0, la, ga, sidx, gsrc, gval, scnt, rcnt = \
                self.device_args(b_global, x0)
        telemetry.add_timing(st, "transfer", time.perf_counter() - t_xfer)
        self.placement = shard_devices(la)
        # tolerances in the scalar dtype (f32 for bf16 storage) so a 1e-9
        # rtol is not pre-rounded to 8 mantissa bits
        sdt = acc_dtype(dtype)
        tols = jnp.asarray([crit.residual_atol, crit.residual_rtol,
                            crit.diff_atol, crit.diff_rtol], dtype=sdt)
        kwargs = dict(unbounded=crit.unbounded, needs_diff=crit.needs_diff,
                      detect=detect)
        if self.precond_spec is not None:
            self._last_dev_args = (b, x0, la, ga, sidx, gsrc, gval,
                                   scnt, rcnt)
            kwargs["mstate"] = self._ensure_precond_state(
                self._last_dev_args)
        args = (la, ga, sidx, gsrc, gval, scnt, rcnt, b, x0, tols,
                jnp.int32(crit.maxits))
        if self.algo is not None:
            if crit.needs_diff:
                raise ValueError(f"{self.algo} supports residual "
                                 f"criteria only")
            lam = self._ensure_lam((b, x0, la, ga, sidx, gsrc, gval,
                                    scnt, rcnt))
            args = args + ((jnp.asarray(lam[0], sdt),
                            jnp.asarray(lam[1], sdt)),)
        t_warm = time.perf_counter()
        with telemetry.annotate("compile"):
            for _ in range(max(warmup, 0)):
                jax.block_until_ready(program(*args, **kwargs)[0])
        if warmup > 0:
            telemetry.add_timing(st, "compile",
                                 time.perf_counter() - t_warm)

        def attempt_trace(out):
            """The ONE extra host fetch of a traced solve: the ring is
            replicated (psum'd scalars), so any controller's copy is
            the mesh's."""
            if not self.trace:
                return None
            # rspec output -> fully replicated: every process holds a
            # complete copy, np.asarray reads the local one
            return telemetry.ConvergenceTrace.from_ring(
                np.asarray(out[9]), int(out[1]),
                solver=self._solver_name())

        hl = self.health_spec is not None

        def attempt_aud(out):
            """The replicated audit vector (rides LAST, after the
            ring); one tiny rank-independent fetch per attempt."""
            return np.asarray(out[-1]) if hl else None

        t0 = time.perf_counter()
        with telemetry.annotate("solve"):
            out = program(*args, **kwargs)
            jax.block_until_ready(out[0])
        niter = int(out[1])
        first_norms = None
        # first note_audit resets the summary, later attempts merge
        # (the jax_cg rationale: a recovered solve must still show the
        # worst gap of the whole solve); gap_tripped marks the latest
        # attempt's exit as an accuracy gate for the raise below
        aud_fresh = True
        gap_tripped = False
        if detect and bool(out[8]):
            # the recovery ladder (solvers.resilience): bounded restarts
            # from the recomputed true residual; a recurring breakdown
            # under the dma transport retires it for the xla
            # collectives; the final rung re-solves on the distributed
            # host oracle.  Multi-controller, every restart/abort
            # decision is error-agreed (erragree.agree_status inside the
            # driver), so the pod acts in unison.
            from acg_tpu.solvers.resilience import RecoveryDriver
            driver = RecoveryDriver(self.recovery, st, "dist-cg")
            pol = self.recovery
            x0_dev = args[8]
            # stats report the ORIGINAL solve's norms (jax_cg rationale)
            first_norms = (float(out[4]), float(out[5]), float(out[3]))
            abs_tol = max(crit.residual_atol,
                          crit.residual_rtol * float(out[3]))
            rtols = jnp.asarray([abs_tol, 0.0, crit.diff_atol,
                                 crit.diff_rtol], dtype=sdt)
            def restart_args(x_next):
                if not bool(jnp.isfinite(x_next).all()):
                    driver.record("iterate non-finite; restarting "
                                  "from the initial guess")
                    x_next = x0_dev
                remaining = max(crit.maxits - niter, 1)
                return (args[:8] + (x_next, rtols)
                        + (jnp.int32(remaining),) + args[11:])

            while bool(out[8]):
                k_done = int(out[1])
                if hl:
                    # audit evidence before the restart decision: the
                    # accuracy_degraded event marks a gap trip apart
                    # from an arithmetic breakdown (jax_cg rationale)
                    from acg_tpu import health as health_mod
                    gap_tripped = health_mod.note_audit(
                        st, attempt_aud(out), self.health_spec,
                        "dist-cg", fresh=aud_fresh)
                    aud_fresh = False
                if self.trace:
                    # the trajectory that led INTO the breakdown
                    st.trace = self.last_trace = attempt_trace(out)
                    driver.log_trace_window(st.trace)
                if gap_tripped and self.health_spec.action == "abort":
                    # host-tier parity (the jax_cg rationale): abort is
                    # a hard stop, the restart budget and the transport
                    # fallback belong to replace.  The predicate comes
                    # from the psum'd (replicated) audit vector, so
                    # every controller raises in unison
                    st.tsolve += time.perf_counter() - t0
                    st.converged = False
                    raise BreakdownError(
                        f"dist-cg: true-residual gap "
                        f"{st.health.get('gap_max', 0.0):.3e} exceeds "
                        f"threshold {self.health_spec.threshold:g} at "
                        f"iteration {niter} (--on-gap abort)")
                if (self.comm == "dma" and driver.restarts >= 1
                        and pol is not None and pol.fallback_comm):
                    # a restart did not cure it: suspect the one-sided
                    # transport and retire it for this solver.  The
                    # fallback is its OWN rung -- it gets an attempt on
                    # the new transport without consuming the restart
                    # budget (otherwise max_restarts=1 would retire the
                    # transport and give up before ever trying it).
                    # The pristine program is invalidated and rebuilt
                    # LAZILY -- eagerly compiling it here alongside the
                    # fault-armed one would waste a whole multi-second
                    # XLA compile inside the recovery path
                    st.nbreakdowns += 1
                    driver.on_fallback("fallback: halo transport "
                                       "dma -> xla")
                    self.comm = "xla"
                    self._program = None
                    self._ckpt_program = None
                    if fault is not None:
                        fault = fault.shift(k_done)
                    program = self._program_for(fault)
                    args = restart_args(out[0])
                    out = program(*args, **kwargs)
                    jax.block_until_ready(out[0])
                    niter += int(out[1])
                    continue
                if driver.on_breakdown(k_done):
                    x_next = out[0]
                    if fault is not None:
                        if (self.algo is not None
                                and self.algo.kind == "sstep"
                                and fault.device_site
                                and fault.iteration <= k_done):
                            # fired inside a frozen basis block: vanish,
                            # never rebase (jax_cg rationale)
                            fault = None
                        elif (self.algo is not None
                              and self.algo.kind == "pl"
                              and fault.device_site):
                            # shift in the z-counter frame (j = adv + l
                            # at breakdown -- jax_cg rationale): a
                            # fired fault vanishes instead of
                            # re-triggering forever
                            fault = fault.shift(
                                k_done + self.algo.param + 1)
                        else:
                            fault = fault.shift(k_done)
                        program = self._program_for(fault)
                    if self.precond_spec is not None:
                        # preserve finite preconditioner state across
                        # the restart, rebuild it when poisoned
                        from acg_tpu.precond import refresh_state
                        if refresh_state(self, driver):
                            kwargs["mstate"] = self._mstate
                    args = restart_args(x_next)
                    out = program(*args, **kwargs)
                    jax.block_until_ready(out[0])
                    niter += int(out[1])
                    continue
                can_host = (pol is not None and pol.fallback_host
                            and prob.owned_parts is None
                            and all(s.A_local is not None
                                    for s in prob.subs))
                if can_host:
                    driver.on_fallback("fallback: distributed host "
                                       "reference solver")
                    st.tsolve += time.perf_counter() - t0
                    return self._host_fallback(b_global, crit,
                                               raise_on_divergence,
                                               host_result)
                st.tsolve += time.perf_counter() - t0
                st.converged = False
                if gap_tripped:
                    # the jax_cg parity: a gap-gated exit names the
                    # accuracy gate, not the arithmetic diagnosis
                    raise BreakdownError(
                        f"dist-cg: true-residual gap "
                        f"{st.health.get('gap_max', 0.0):.3e} exceeds "
                        f"threshold {self.health_spec.threshold:g} at "
                        f"iteration {niter} (--on-gap "
                        f"{self.health_spec.action}); "
                        f"{st.nrestarts} restart(s) exhausted and no "
                        f"fallback available")
                raise driver.give_up(niter, float(out[2]))
        t_solve = time.perf_counter() - t0
        st.tsolve += t_solve
        telemetry.add_timing(st, "solve", t_solve)
        if self.trace:
            st.trace = self.last_trace = attempt_trace(out)

        x_st, k, rnrm2, r0nrm2, bnrm2, x0nrm2, dxnrm2, done = out[:8]
        st.nsolves += 1
        st.niterations = niter
        st.ntotaliterations += niter
        st.bnrm2, st.x0nrm2, st.r0nrm2 = (
            first_norms if first_norms is not None
            else (float(bnrm2), float(x0nrm2), float(r0nrm2)))
        st.rnrm2 = float(rnrm2)
        st.dxnrm2 = float(dxnrm2)
        st.converged = bool(done) or crit.unbounded
        if hl:
            from acg_tpu import health as health_mod
            health_mod.note_audit(st, attempt_aud(out),
                                  self.health_spec, "dist-cg",
                                  fresh=aud_fresh)
        # service-metrics tier (no-op disarmed): one completed solve,
        # plus this solve's halo/psum traffic folded out of the static
        # comm ledger (comm_profile, the perfmodel tier's hook)
        from acg_tpu import metrics
        metrics.record_solve(t_solve, niter, st.converged,
                             solver=self._solver_name())
        metrics.observe_solver_comm(self, niter)
        self._account_ops(st, niter)

        if host_result:
            x = prob.gather(get_global(x_st))
            st.fexcept_arrays = [x]
        else:
            x = x_st
            # device-side scans; only two bools cross the wire (the
            # JaxCGSolver host_result=False convention)
            has_nan = bool(jnp.isnan(x_st).any())
            has_inf = bool(jnp.isinf(x_st).any())
            st.fexcept_arrays = [np.asarray([np.nan if has_nan else 0.0,
                                             np.inf if has_inf else 0.0])]
        if not st.converged and raise_on_divergence:
            raise NotConvergedError(
                f"{niter} iterations, residual {st.rnrm2:.3e}")
        return x

    def _crash_refusal(self) -> None:
        """``crash:exit`` fires from the checkpoint chunk driver between
        snapshots: armed without --ckpt it could never fire -- refuse
        instead of reporting a clean 'fault-tested' solve (the
        fault-injector discipline)."""
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

    def _account_ops(self, st, niter: int) -> None:
        """Analytic flop/byte census of ``niter`` iterations on this
        configuration -- shared by the plain and checkpoint-chunked
        solve paths so their stats blocks cannot drift apart."""
        prob = self.problem
        dtype = np.dtype(prob.vdtype)
        n = prob.n
        # CA recurrences run spmv_per_iteration SpMV-equivalents (the
        # s-step matrix-powers basis: (2s-1)/s), declared once by
        # recurrence.reduction_schedule -- the same number the jax_cg
        # tier's census and the comm ledger report, so the two tiers'
        # stats for the identical algorithm cannot drift apart
        spmv_eq = 1.0
        if self.algo is not None:
            from acg_tpu.recurrence import reduction_schedule
            spmv_eq = reduction_schedule(
                self.algo, False)["spmv_per_iteration"]
        st.nflops += (cg_flops_per_iteration(prob.nnz_total, n, self.pipelined)
                      * niter + 3.0 * prob.nnz_total + 2.0 * n
                      + 3.0 * prob.nnz_total * (spmv_eq - 1.0) * niter)
        dbl = dtype.itemsize
        # matrix bytes in the matrix dtype (differs from vectors under
        # mixed); DIA local blocks read no index arrays, ELL reads 4 B
        mat_dbl = np.dtype(prob.dtype).itemsize
        idx_b = 0 if prob.local.format in ("dia", "matfree") else 4
        # matrix-free local blocks read no planes at all -- the gemv
        # row's bytes are the generated-operand vector traffic plus
        # the O(grid-side) coefficient tables
        mat_read = (prob.operator.table_bytes()
                    if prob.operator is not None
                    else prob.nnz_total * (mat_dbl + idx_b))
        ngemv = int(niter * spmv_eq) + 1
        st.ops["gemv"].add(ngemv, 0.0,
                           (mat_read + 2 * n * dbl) * ngemv)
        # op census matching the single-device/eager accounting
        # (jax_cg.solve / host_cg.solve): the convergence test's (r, r)
        # is the nrm2 class, classic CG's p = r setup the one copy --
        # these were the permanently-zero stats rows (the reference
        # fills both, cgcuda.c:1942-1957)
        st.ops["dot"].add(niter, 0.0, 2 * n * dbl * niter)
        st.ops["nrm2"].add(niter + 1, 0.0, n * dbl * (niter + 1))
        st.ops["axpy"].add(3 * niter, 0.0, 3 * n * dbl * 3 * niter)
        if not self.pipelined:
            st.ops["copy"].add(1, 0.0, 2 * n * dbl)
        if self.algo is not None:
            # CA recurrences: the schedule is the single source
            # (recurrence.reduction_schedule) -- fractional per-
            # iteration averages rounded to whole events
            from acg_tpu.recurrence import reduction_schedule
            sched = reduction_schedule(self.algo, False)
            nred = max(int(round(sched["allreduce_per_iteration"]
                                 * niter)), 1)
            st.ops["allreduce"].add(
                nred, 0.0, 8 * sched["allreduce_scalars"] * nred)
        else:
            st.ops["allreduce"].add(
                (1 if self.pipelined else 2) * niter, 0.0,
                8 * (1 if self.pipelined else 2) * niter)
        # local-read problems carry the allgathered total (summing subs
        # here would count only this controller's parts)
        halo_total = getattr(prob, "halo_send_total", None)
        if halo_total is None:
            halo_total = sum(int(s.halo.total_send) for s in prob.subs
                             if s.halo is not None)
        halo_bytes = halo_total * dbl
        nhalo = int(niter * spmv_eq) + 1
        st.ops["halo"].add(nhalo, 0.0, halo_bytes * nhalo)
        if self.precond_spec is not None:
            # the precond_apply census (jax_cg._account_precond's dist
            # twin): one apply per iteration + setup, cheby billing its
            # per-apply SpMVs -- and their halo exchanges, which are the
            # only preconditioner communication on this tier
            from acg_tpu import metrics as _metrics
            from acg_tpu import precond as precond_mod
            spec = self.precond_spec
            nappl = niter + 1
            per_fl = precond_mod.flops_per_apply(spec, n,
                                                 3.0 * prob.nnz_total)
            st.nflops += per_fl * nappl
            sb = precond_mod.state_bytes(self._mstate)
            per_b = precond_mod.bytes_per_apply(
                spec, n, dbl,
                prob.nnz_total * (mat_dbl + idx_b) + 2 * n * dbl, sb)
            nops = nappl * (spec.degree if spec.kind == "cheby" else 1)
            st.ops["precond"].add(nops, 0.0, int(per_b * nappl))
            st.ops["dot"].add(nappl, 0.0, 2 * n * dbl * nappl)
            if spec.kind == "cheby":
                st.ops["halo"].add(spec.degree * nappl, 0.0,
                                   halo_bytes * spec.degree * nappl)
            st.precond.update({"kind": str(spec), "applies": nappl,
                               "flops_per_apply": per_fl,
                               "state_bytes": sb})
            lams = getattr(self, "_precond_lams", None)
            if lams is not None:
                st.precond["lambda_min"] = lams[0]
                st.precond["lambda_max"] = lams[1]
            _metrics.record_precond(spec.kind, nops)

    def _host_fallback(self, b_global, crit, raise_on_divergence: bool,
                       host_result: bool):
        """The last recovery rung: re-solve on the distributed host
        oracle (HostDistCGSolver, same subdomain layout, f64 numpy) from
        the original b.  Only reachable on full single-controller builds
        -- restricted (multi-controller) problems hold other
        controllers' blocks as stubs, so the ladder ends at the raise
        there."""
        from acg_tpu import faults
        from acg_tpu.solvers.host_cg import HostDistCGSolver
        from acg_tpu.solvers.resilience import adopt_host_stats

        hs = HostDistCGSolver(self.problem.subs)
        with faults.suppressed():
            x = hs.solve(np.asarray(b_global, np.float64), criteria=crit,
                         raise_on_divergence=raise_on_divergence)
        adopt_host_stats(self.stats, hs.stats)
        if host_result:
            return x
        # callers expecting the stacked device layout still get it
        from acg_tpu.parallel.multihost import put_global
        return put_global(self.problem.scatter(x), sharding=self._sharding)

    # -- survivability tier: checkpoint-chunked solve ---------------------

    _ckpt_tier = "dist-cg"

    def _ckpt_program_for(self, fault):
        """The state_io chunk program: fault-armed compiles are
        solve-local (static spec changes per chunk as the injector
        shifts); the pristine one is cached."""
        if fault is not None:
            return self._compile(fault=fault, state_io=True)
        prog = getattr(self, "_ckpt_program", None)
        if prog is None:
            prog = self._ckpt_program = self._compile(state_io=True)
        return prog

    def _solve_ckpt(self, b_global, x0=None, criteria=None,
                    raise_on_divergence: bool = True, warmup: int = 0,
                    host_result: bool = True):
        """Checkpoint-armed solve over the mesh (acg_tpu.checkpoint):
        the UNCHANGED SPMD recurrence dispatched in host chunks of at
        most ``ckpt.every`` iterations with the full loop carry
        threaded through (``state_io``), every per-part leaf gathered
        host-side and committed under ONE agreed sequence number
        (checkpoint.agree_seq) so all ranks hold the same iteration,
        and breakdowns answered by the rollback rung before the
        restart/fallback ladder.  The carry continues the Krylov
        recurrence exactly, so the chunked trajectory is
        iteration-identical to solve()'s (tests/test_checkpoint.py);
        snapshot time is billed to its own ``ckpt`` phase."""
        from acg_tpu import checkpoint as ckpt_mod
        from acg_tpu import faults, metrics, observatory, telemetry, \
            tracing
        from acg_tpu import health as health_mod
        from acg_tpu.solvers.resilience import RecoveryDriver

        cfg = self.ckpt
        crit = criteria or StoppingCriteria()
        st = self.stats
        st.criteria = crit
        prob = self.problem
        dtype = self._solve_dtype()
        sdt = acc_dtype(np.dtype(prob.vdtype))
        if crit.needs_diff:
            raise AcgError(
                ErrorCode.INVALID_VALUE,
                "checkpointing supports residual criteria only: the "
                "diff criterion's dx scalar is not part of the "
                "snapshot carry")
        fault0 = faults.device_fault()
        if (fault0 is not None and fault0.site == "halo"
                and not prob.halo.has_ghosts):
            raise AcgError(
                ErrorCode.INVALID_VALUE,
                "halo fault injection needs a topology with ghost "
                "exchange; this problem has no halo (single part or "
                "fully decoupled partition)")
        if (fault0 is not None and fault0.site == "precond"
                and self.precond_spec is None):
            raise AcgError(
                ErrorCode.INVALID_VALUE,
                "precond fault injection needs an armed preconditioner "
                "(--precond jacobi|bjacobi|cheby:K); this solve runs "
                "unpreconditioned CG")
        detect = self._detect(fault0)
        if fault0 is not None:
            telemetry.record_event(st, "fault-armed",
                                   f"{fault0.site}:{fault0.mode}"
                                   f"@{fault0.iteration}")
        t_xfer = time.perf_counter()
        with telemetry.annotate("transfer"):
            dev = self.device_args(b_global, x0)
            b, x0_dev, la, ga, sidx, gsrc, gval, scnt, rcnt = dev
        telemetry.add_timing(st, "transfer", time.perf_counter() - t_xfer)
        b_crc = ckpt_mod.vector_checksum(np.asarray(b_global))
        kwargs = dict(unbounded=crit.unbounded, needs_diff=False,
                      detect=detect)
        if self.precond_spec is not None:
            self._last_dev_args = dev
            kwargs["mstate"] = self._ensure_precond_state(dev)
        fixed = (la, ga, sidx, gsrc, gval, scnt, rcnt, b)
        hl = self.health_spec is not None
        tr = self.trace
        pc_kind = (str(self.precond_spec)
                   if self.precond_spec is not None else None)
        names = ckpt_mod.carry_names(self.pipelined,
                                     self.precond_spec is not None)
        ncore = len(names) - 1
        scalar = ckpt_mod.SCALAR_LEAVES
        put = functools.partial(put_global, sharding=self._sharding)
        solver_name = ("dist-cg-pipelined" if self.pipelined
                       else "dist-cg")

        def to_dev(arrs):
            """Host snapshot arrays -> placed carry leaves (vectors
            scattered over the mesh, scalars as plain device scalars)."""
            return tuple(
                jnp.asarray(arrs[nm], dtype=sdt) if nm in scalar
                else put(np.asarray(arrs[nm], dtype=dtype))
                for nm in names[1:])

        def to_host(x_st, core):
            arrs = {"x": np.asarray(get_global(x_st))}
            for nm, leaf in zip(names[1:], core):
                arrs[nm] = np.asarray(get_global(leaf) if nm not in scalar
                                      else leaf)
            return arrs

        def run(program, x_cur, atol, rtol, m, carry, k0):
            tols = jnp.asarray([atol, rtol, 0.0, 0.0], dtype=sdt)
            koff = jnp.int32(k0) if hl else None
            out = program(*fixed, x_cur, tols, jnp.int32(m),
                          carry=carry, k_offset=koff, **kwargs)
            core = out[-ncore:]
            ring = out[9] if tr else None
            aud = out[9 + (1 if tr else 0)] if hl else None
            return out[:9], ring, aud, core

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
                precond=pc_kind, n=int(prob.n), dtype=dtype,
                b_crc=b_crc, nparts=int(prob.nparts),
                repartition=cfg.repartition)
            ckpt_mod.check_resume_env(snap, st)
            if cfg.repartition:
                # shape-portable resume: reassemble the stored carry
                # into global row order via the permutation sidecar,
                # then RE-SLICE it onto THIS problem's partition (the
                # halo plans and preconditioner state were already
                # rebuilt for this mesh at solver setup) -- the Krylov
                # recurrence continues with the same global state, up
                # to dot-product re-association across the new layout
                snap, repartitioned = ckpt_mod.apply_repartition(
                    snap, tier=self._ckpt_tier,
                    nparts=int(prob.nparts), stats=st,
                    precond_spec=self.precond_spec)
                arrs_g = {}
                for nm, a in snap.arrays.items():
                    a = np.asarray(a)
                    arrs_g[nm] = (a if nm in scalar or a.ndim == 0
                                  else prob.scatter(a, dtype=a.dtype))
                snap = ckpt_mod.SolverSnapshot(meta=snap.meta,
                                               arrays=arrs_g)
            consumed = snap.iteration
            resumed_from = consumed
            sm = snap.meta
            abs_tol = float(sm["abs_tol"])
            first_norms = (float(sm["bnrm2"]), float(sm["x0nrm2"]),
                           float(sm["r0nrm2"]))
            x_cur = put(np.asarray(snap.arrays["x"], dtype=dtype))
            carry = to_dev(snap.arrays)
            metrics.record_resume()
            telemetry.record_event(
                st, "resume",
                f"resumed from snapshot at iteration {consumed}")
            sys.stderr.write(f"acg-tpu: {self._ckpt_tier}: resumed "
                             f"from snapshot at iteration {consumed}\n")
        last_snap = ((consumed, dict(snap.arrays))
                     if snap is not None else None)

        driver = RecoveryDriver(self.recovery, st, self._ckpt_tier)
        program = self._ckpt_program_for(fault0)
        if warmup > 0:
            t_w = time.perf_counter()
            with telemetry.annotate("compile"):
                jax.block_until_ready(run(program, x_cur, 0.0, 0.0, 0, carry,
                                          consumed)[0][0])
            telemetry.add_timing(st, "compile",
                                 time.perf_counter() - t_w)

        def agreed_chunk(m: int) -> int:
            """The wall-clock cadence sizes chunks from a LOCALLY
            measured s/iteration; multi-controller, every rank must
            dispatch the SPMD program with the SAME iteration cap (a
            mismatched ``m`` desynchronises the in-loop collectives
            and agree_seq's iteration agreement).  All ranks gather
            their proposals and take the minimum -- the slowest
            rank's loss window stays the bound.  --ckpt-every is
            static and identical everywhere: no gather."""
            if cfg.secs <= 0 or jax.process_count() == 1:
                return m
            from acg_tpu.parallel.erragree import allgather_blobs
            got = allgather_blobs(str(int(m)), tag="ckpt-chunk")
            return max(1, min(int(g) for g in got))

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
                m = agreed_chunk(min(cfg.chunk_for(rate), remaining))
                chunk_fault = (fault.shift(executed)
                               if fault is not None else None)
                program = self._ckpt_program_for(chunk_fault)
                t_chunk = time.time()
                if abs_tol is None:
                    res, tbuf, aud, core = run(
                        program, x_cur, crit.residual_atol,
                        crit.residual_rtol, m, carry, consumed)
                else:
                    # later chunks keep the FIRST attempt's absolute
                    # target (never re-baseline rtol)
                    res, tbuf, aud, core = run(
                        program, x_cur, abs_tol, 0.0, m, carry,
                        consumed)
                jax.block_until_ready(res[0])
                t_end = time.time()
                k_chunk = int(res[1])
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
                    first_norms = (float(res[4]), float(res[5]),
                                   float(res[3]))
                    abs_tol = max(crit.residual_atol,
                                  crit.residual_rtol * first_norms[2])
                if tr:
                    st.trace = self.last_trace = \
                        telemetry.ConvergenceTrace.from_ring(
                            np.asarray(tbuf), k_chunk,
                            solver=solver_name,
                            offset=consumed - k_chunk)
                # live-observatory tier: real mid-solve sample from the
                # per-chunk carry return (no-op disarmed; host-side)
                observatory.note_chunk(
                    self._ckpt_tier, consumed, float(res[2]),
                    abs_tol=abs_tol,
                    trace=(st.trace if tr else None),
                    rtol=crit.residual_rtol)
                if hl and aud is not None:
                    gap_tripped = health_mod.note_audit(
                        st, np.asarray(aud), self.health_spec,
                        self._ckpt_tier, fresh=aud_fresh)
                    aud_fresh = False
                if detect and bool(res[8]):
                    if tr:
                        driver.log_trace_window(st.trace)
                    if (gap_tripped
                            and self.health_spec.action == "abort"):
                        st.tsolve += time.perf_counter() - t0 - ck_secs
                        st.converged = False
                        raise BreakdownError(
                            f"{self._ckpt_tier}: true-residual gap "
                            f"{st.health.get('gap_max', 0.0):.3e} "
                            f"exceeds threshold "
                            f"{self.health_spec.threshold:g} at "
                            f"iteration {consumed} (--on-gap abort)")
                    driver.note_breakdown(consumed)
                    # `fault` stays in the TRAJECTORY frame (the
                    # per-dispatch shift rebases it): vanish a fired
                    # fault instead of rebasing, which would make the
                    # dispatch shift double-subtract a pending one
                    if (fault is not None and fault.device_site
                            and fault.iteration <= executed):
                        fault = None
                    # FIRST RUNG: roll the carry back to the last
                    # agreed snapshot (exact pre-corruption Krylov
                    # state; the restart budget is untouched)
                    if (last_snap is not None
                            and driver.on_rollback(consumed,
                                                   last_snap[0])):
                        arrs = last_snap[1]
                        x_cur = put(np.asarray(arrs["x"], dtype=dtype))
                        carry = to_dev(arrs)
                        consumed = last_snap[0]
                        continue
                    # second rung: restart from the recomputed true
                    # residual (carry=None re-enters the setup path)
                    if driver.on_breakdown(consumed, noted=True):
                        x_next = res[0]
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
                    can_host = (pol is not None and pol.fallback_host
                                and prob.owned_parts is None
                                and all(s.A_local is not None
                                        for s in prob.subs))
                    if can_host:
                        driver.on_fallback("fallback: distributed host "
                                           "reference solver")
                        st.tsolve += time.perf_counter() - t0 - ck_secs
                        return self._host_fallback(
                            b_global, crit, raise_on_divergence,
                            host_result)
                    st.tsolve += time.perf_counter() - t0 - ck_secs
                    st.converged = False
                    raise driver.give_up(
                        consumed, float(res[2]),
                        snapshot=cfg.path if nsnaps else None)
                finished = (consumed >= crit.maxits if unbounded
                            else bool(res[7]))
                x_cur = res[0]
                carry = core
                if cfg.path is not None and not finished:
                    t_ck = time.perf_counter()
                    arrs = to_host(x_cur, core)
                    seq += 1
                    meta = {
                        "tier": self._ckpt_tier,
                        "pipelined": bool(self.pipelined),
                        "precond": pc_kind,
                        "n": int(prob.n),
                        "nparts": int(prob.nparts),
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
                    rp = prob.row_permutation()
                    if rp is not None:
                        # the shape-portable sidecar: global row ids
                        # in stacked slot order + per-part row counts
                        # let --resume-repartition reassemble this
                        # carry onto ANY partition (or the single-
                        # device/host tiers)
                        arrs["_rowperm"] = rp
                        meta["part_rows"] = prob.part_rows()
                    # ONE agreed sequence number across controllers
                    # before anything touches disk; the primary writes
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
                    last_snap = (consumed, arrs)
                    # crash:exit models preemption BETWEEN iterations,
                    # after the snapshot committed
                    faults.maybe_crash(consumed - k_chunk, consumed)
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
        st.nsolves += 1
        st.niterations = executed
        st.ntotaliterations += executed
        st.bnrm2, st.x0nrm2, st.r0nrm2 = first_norms
        st.rnrm2 = float(res[2])
        st.dxnrm2 = float(res[6])
        st.converged = bool(res[7]) or crit.unbounded
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
        self._account_ops(st, executed)
        x_st = res[0]
        if host_result:
            x = prob.gather(get_global(x_st))
            st.fexcept_arrays = [x]
        else:
            x = x_st
            has_nan = bool(jnp.isnan(x_st).any())
            has_inf = bool(jnp.isinf(x_st).any())
            st.fexcept_arrays = [np.asarray([np.nan if has_nan else 0.0,
                                             np.inf if has_inf
                                             else 0.0])]
        if not st.converged and raise_on_divergence:
            raise NotConvergedError(
                f"{executed} iterations, residual {st.rnrm2:.3e}")
        return x
