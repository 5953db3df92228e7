"""Hand-written Pallas TPU kernels for the CG hot loop.

The reference's device-kernel tier (``acg/cg-kernels-cuda.cu``): merge-CSR
SpMV (``:340-441``), fused BLAS-1 with device scalars (``:78-303``), and
the 6-vector pipelined update (``:187-269``).  On TPU the XLA compiler
already fuses elementwise chains well, so each kernel here exists to beat
a *specific* HBM-traffic bound the fusion cannot reach:

* :func:`dia_spmv` -- DIA SpMV with a single pass over ``x``: the XLA
  formulation (``ops/spmv.py:dia_mv``) reads one shifted copy of ``x``
  per diagonal (D+1 vector reads + 1 write for D diagonals); this kernel
  DMAs each x tile into VMEM once and applies all D statically-shifted
  multiplies from VMEM, for D + 2 vectors of HBM traffic a call: the D
  planes and x read, y written (x's band halos re-read per window on
  the "fast" route, ``dia_spmv_x_reads``) -- the same traffic argument
  as the reference's merge-CSR kernel, restated for a vector
  architecture.
* :func:`tiledia_spmv` -- the row-tiled DIA local block of a graph
  partition with no gather of x: each (tile, offset) segment reads its x
  window from a VMEM-resident x by unaligned row loads and lane rotates,
  where the XLA formulation (``ops/spmv.py:tiledia_mv``) gathers every
  window element.
* :func:`fused_pipelined_update` -- the Ghysels-Vanroose 6-vector update
  (z,t,p,x,r,w) in one pass with alpha/beta in SMEM, the analog of
  ``acgsolvercuda_pipelined_update_kernel`` (``cg-kernels-cuda.cu:
  187-269``).

All run in interpret mode on CPU (tests) and compiled on TPU.  Whether
they actually beat XLA fusion is *measured* (``scripts/bench_pallas.py``,
BASELINE.md) -- the solvers select per measurement via
``kernels="pallas"``.

Every ``pallas_call`` here passes a stable ``name=`` (``acg_dia_spmv``,
``acg_dia_spmv_dot``, ``acg_dia_spmv_padded``, ``acg_tiledia_spmv``,
``acg_fused_cg_a``/``_b``, ``acg_fused_pipelined_update``,
``acg_stencil_spmv``): the compiled
custom call, and so its event in a profiler capture, carries that name
(``acg_dia_spmv.7``), whatever XLA numbers around it.

The DISTRIBUTED fused tier (``kernels='fused'`` with ``--nparts``) does
not use these single-device kernels: it is the recurrence builder's
emission over the interior|border OVERLAPPED SpMV
(``parallel.dist.make_dist_spmv_overlapped`` -- one-sided halo DMA in
flight behind the interior rows' work).  Folding that tier's axpy/dot
updates into true per-iteration Pallas mega-kernels on the split row
sets is the remaining rung of ROADMAP item 4; the ``_window_copies``
machinery here is the intended substrate.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from acg_tpu.ops.spmv import TILEDIA_TILE, acc_dtype, tiledia_mv

# row-tile length for the SpMV kernel; multiple of the f32 (8,128) tile
TILE = 16384
LANE = 128


def _pad_to(x, m):
    r = (-x.shape[0]) % m
    return jnp.pad(x, (0, r)) if r else x


@functools.partial(jax.jit, static_argnames=("offsets", "interpret"))
def dia_spmv(planes, offsets: tuple, x, interpret: bool = False):
    """y = A @ x for DIA ``planes`` (tuple of (n,) arrays, one per static
    diagonal offset), reading ``x`` through VMEM once per row tile.

    Equivalent to :func:`acg_tpu.ops.spmv.dia_mv` with x-length == n
    (square blocks); see that function for the semantics.

    Fast path (n divisible by the row tile, band within one tile): each
    tile issues three static-size DMAs straight out of x -- body, left
    halo, right halo -- with edge tiles zero-filling the out-of-range
    halo instead of reading it, so no padded copy of x is ever
    materialised.  Out-of-range x positions only ever multiply plane
    entries that are structurally zero (no matrix entry has a column off
    the end), so the zero fill is correctness-neutral; it exists to keep
    NaN-free garbage out of uninitialised VMEM.  Ragged shapes take a
    jnp.pad fallback.
    """
    n = x.shape[0]
    route = dia_spmv_route(offsets, n, x.dtype, ndiags=len(planes))
    if route[0] in ("fast", "clustered"):
        return _dia_spmv_clustered(planes, offsets, x,
                                   *_kernel_args(route), interpret)
    if route[0] == "xla":
        from acg_tpu.ops.spmv import dia_mv

        return dia_mv(planes, offsets, n, x)
    L = max(0, -min(offsets))
    R = max(0, max(offsets))
    return _dia_spmv_padded(planes, offsets, x, L, R, interpret)


@functools.partial(jax.jit, static_argnames=("offsets", "interpret"))
def dia_spmv_dot(planes, offsets: tuple, x, interpret: bool = False):
    """``(y, dot(x, y))`` with the dot fused into the SpMV pass.

    The classic CG step needs ``t = A p`` immediately followed by
    ``(p, t)`` (``cgcuda.c:913``: cusparseSpMV then cublasDdot).  Fusing
    the scalar into the kernel saves the dot's two full vector re-reads
    (~13%% of the iteration's HBM traffic on the flagship).  Falls back
    to kernel-then-``jnp.dot`` on routes without the fused variant.
    """
    n = x.shape[0]
    route = dia_spmv_route(offsets, n, x.dtype, ndiags=len(planes))
    if route[0] in ("fast", "clustered"):
        y, d = _dia_spmv_clustered(planes, offsets, x, *_kernel_args(route),
                                   interpret, with_dot=True)
        return y, d[0].astype(x.dtype)
    y = dia_spmv(planes, offsets, x, interpret=interpret)
    return y, jnp.dot(x, y)


def _kernel_args(route) -> tuple:
    """``(far, Lpad, Rpad, tile, align)`` of ``_dia_spmv_clustered`` for
    a "fast" or "clustered" route: the fast path IS that kernel with no
    far windows."""
    if route[0] == "fast":
        return ((),) + route[1:]
    return route[2:]


def dia_spmv_route(offsets: tuple, n: int, dtype, ndiags: int | None = None):
    """Which implementation :func:`dia_spmv` will take for this shape:
    ``("fast", Lpad, Rpad, tile, align)`` (single-window kernel),
    ``("clustered", central, far, Lpad, Rpad, tile, align)``
    (ring kernel for clustered diagonals), ``("padded",)``, or
    ``("xla",)``.  Exposed so callers reporting a kernel tier (bench)
    can record what actually ran instead of what was requested."""
    ndiags = len(offsets) if ndiags is None else ndiags
    L = max(0, -min(offsets))
    R = max(0, max(offsets))
    itemsize = jnp.dtype(dtype).itemsize
    # scoped-VMEM budget per grid step: the x window plus the
    # double-buffered BlockSpec tiles (D planes + y), under the ~16 MB
    # scoped limit with margin.  A band too wide for this budget has no
    # x-reuse win anyway (each tile's window would mostly be halo), so
    # those matrices go to XLA's shifted-views formulation instead.
    budget = 12 * 2 ** 20

    def vmem_bytes(tile, halo):
        return (tile + 2 * halo + 2 * (ndiags + 1) * tile) * itemsize

    # Mosaic must prove DMA slice offsets divisible by the flattened
    # (sublane x lane) tile; round the halo sizes up to that quantum so
    # every HBM/VMEM DMA offset is a multiple of it
    align = {4: 1024, 2: 2048}.get(itemsize)
    if align is not None:
        Lpad = L + (-L) % align
        Rpad = R + (-R) % align
        band = max(Lpad, Rpad)
        tile = TILE
        while tile < band and vmem_bytes(2 * tile, band) <= budget:
            tile *= 2
        if (band <= tile and n % tile == 0 and n >= tile
                and vmem_bytes(tile, band) <= budget):
            return ("fast", Lpad, Rpad, tile, align)
        clustered = _cluster_route(offsets, n, itemsize, align, budget,
                                   ndiags)
        if clustered is not None:
            return clustered
    if L + R >= TILE:
        # wide band: the window is mostly halo, so the single-x-pass
        # traffic argument is void -- D+1 passes from XLA win
        return ("xla",)
    return ("padded",)


def dia_spmv_x_reads(route) -> float | None:
    """HBM bytes of x one :func:`dia_spmv` call reads over x's own, on a
    ``route`` of :func:`dia_spmv_route`: 1.0 on the clustered route
    (each tile enters the ring once; halos come from the ring), ``(tile
    + Lpad + Rpad) / tile`` on the fast route (each window re-reads its
    band halos); None on the fallbacks."""
    if route[0] == "clustered":
        return 1.0
    if route[0] == "fast":
        _, Lpad, Rpad, tile, _ = route
        return (tile + Lpad + Rpad) / tile
    return None


def _cluster_route(offsets, n, itemsize, align, budget, ndiags):
    """Route for stencils whose diagonals CLUSTER (3D Poisson: {-n^2},
    {-n..n}, {+n^2}) in a band far too wide for one window: the kernel's
    ring of x tiles (``_dia_spmv_clustered``) still reads x from HBM
    once a call.  Far clusters must be single offsets on tile boundaries
    (each then reads one whole tile of the ring); the cluster containing
    0 reads its tile's slot with halos, like the fast path."""
    if n % TILE or n < TILE:
        return None
    sorted_offs = sorted(offsets)
    clusters: list[list[int]] = [[sorted_offs[0]]]
    for o in sorted_offs[1:]:
        if o - clusters[-1][-1] > TILE // 2:
            clusters.append([o])
        else:
            clusters[-1].append(o)
    if len(clusters) < 2:
        return None
    central = min(clusters, key=lambda c: min(abs(o) for o in c))
    far_clusters = [c for c in clusters if c is not central]
    if any(len(c) != 1 or c[0] % TILE or abs(c[0]) >= n
           for c in far_clusters):
        return None
    far = tuple(c[0] for c in far_clusters)
    L = max(0, -min(central))
    R = max(0, max(central))
    Lpad = L + (-L) % align
    Rpad = R + (-R) % align
    if max(Lpad, Rpad) > TILE:
        return None

    def vmem(tile):
        # the kernel's x ring (one window-sized slot per tile a step
        # reads, plus the tile in flight) and the double-buffered
        # BlockSpec tiles (D planes + y)
        lo, hi = _ring_span(far, Lpad, Rpad, tile)
        return ((hi - lo + 2) * (tile + Lpad + Rpad)
                + 2 * (ndiags + 1) * tile) * itemsize

    # grow the tile while the far offsets stay tile-multiples and VMEM
    # fits: fewer grid steps amortise the per-step DMA round-trips
    # (8192 steps of overhead measurably beat the traffic saving at
    # 512^3 with the base tile)
    tile = TILE
    while (n % (2 * tile) == 0 and vmem(2 * tile) <= budget
           and all(o % (2 * tile) == 0 for o in far)):
        tile *= 2
    if vmem(tile) > budget:
        return None
    return ("clustered", tuple(central), far, Lpad, Rpad, tile, align)


def _window_copies(hbm, wref, sems, s0: int, i, grid: int, tile: int,
                   Lpad: int, Rpad: int, align: int, dtype):
    """(start, wait) callables streaming HBM tile ``i`` plus its left/
    right band halos into a ``(Lpad + tile + Rpad,)`` VMEM window, edge
    tiles zero-filling the out-of-range halo (correctness-neutral: those
    positions only multiply structural zeros).  Uses semaphores
    ``sems[s0:s0+3]``.  Shared by the single-x-pass SpMV kernels and the
    fused CG phase A, so the subtle Mosaic DMA logic (alignment proofs,
    edge fills) lives once."""
    # int32-explicit semaphore indices: under jax_enable_x64 a Python
    # int traces as an i64 constant, which tpu.memref_slice rejects
    sem = [jnp.int32(s0 + k) for k in range(3)]
    body_cp = pltpu.make_async_copy(
        hbm.at[pl.ds(pl.multiple_of(i * tile, align), tile)],
        wref.at[pl.ds(Lpad, tile)], sems.at[sem[0]])

    def _left_cp():
        return pltpu.make_async_copy(
            hbm.at[pl.ds(pl.multiple_of(i * tile - Lpad, align), Lpad)],
            wref.at[pl.ds(0, Lpad)], sems.at[sem[1]])

    def _right_cp():
        return pltpu.make_async_copy(
            hbm.at[pl.ds(pl.multiple_of((i + 1) * tile, align), Rpad)],
            wref.at[pl.ds(Lpad + tile, Rpad)], sems.at[sem[2]])

    def start():
        body_cp.start()
        if Lpad:
            @pl.when(i > 0)
            def _():
                _left_cp().start()

            @pl.when(i == 0)
            def _():
                wref[pl.ds(0, Lpad)] = jnp.zeros((Lpad,), dtype)
        if Rpad:
            @pl.when(i < grid - 1)
            def _():
                _right_cp().start()

            @pl.when(i == grid - 1)
            def _():
                wref[pl.ds(Lpad + tile, Rpad)] = jnp.zeros((Rpad,), dtype)

    def wait():
        if Lpad:
            @pl.when(i > 0)
            def _():
                _left_cp().wait()
        if Rpad:
            @pl.when(i < grid - 1)
            def _():
                _right_cp().wait()
        body_cp.wait()

    return start, wait


def _ring_span(far, Lpad: int, Rpad: int, tile: int) -> tuple[int, int]:
    """``(lo, hi)``: grid step ``i`` of the ring kernel reads x tiles
    ``i+lo .. i+hi`` -- each far offset's whole-tile shift, the body,
    and the neighbours whose edges are the central cluster's halos."""
    shifts = [o // tile for o in far] + [0, -1 if Lpad else 0,
                                         1 if Rpad else 0]
    return min(shifts), max(shifts)


def _dia_spmv_clustered(planes, offsets, x, far, Lpad, Rpad, tile, align,
                        interpret, with_dot=False):
    """Single-x-pass SpMV over VMEM windows of x.

    ``far == ()`` (the single-window "fast" route): each grid step
    fetches tile ``i`` plus its band halos (``_window_copies``) and
    waits for it before computing.

    Otherwise (the "clustered" route, see ``_cluster_route``): a RING of
    x tiles carried across the grid steps, which TPU runs in order on
    one core.  Step ``i`` reads tiles ``i+lo .. i+hi`` (``_ring_span``):
    each far offset is exactly one whole tile ``i + offset/tile``, and
    the central cluster reads tile ``i``'s slot, whose margins receive
    the edges of tiles ``i-1`` and ``i+1`` by VMEM copies.  Tile ``t``
    lives in slot ``t mod K`` (K = hi - lo + 2: the span plus the tile
    in flight), enters it once by DMA one step ahead of its first use,
    and tiles off either end are zero-filled slots (correctness-neutral:
    those positions only multiply structural zeros).  So x is read from
    HBM once per call, and its fetch overlaps the previous step's work.
    The branch on ``i mod K`` makes every slot index static.

    Both accumulate the planes in ``offsets`` order, one multiply-add
    each in :func:`acc_dtype` (sub-f32 storage accumulates in f32: the
    converts are free on the VPU, VMEM/HBM stay half-width), so the two
    routes compute the same ``y`` bit for bit.

    ``with_dot=True`` additionally returns ``dot(x, y)`` accumulated in
    SMEM across the (sequential) grid -- the CG step's (p, Ap) scalar
    for free, saving the separate dot's two full vector re-reads."""
    n = x.shape[0]
    grid = n // tile
    win = tile + Lpad + Rpad
    nplanes = len(planes)
    kadt = acc_dtype(x.dtype)

    def accumulate(plane_refs, window_of):
        acc = jnp.zeros((tile,), kadt)
        for pr, off in zip(plane_refs, offsets):
            acc = acc + (pr[:].astype(kadt) * window_of(off).astype(kadt))
        return acc

    def finish(i, acc, xbody, y_ref, dot_ref):
        """Store y; with the dot, add (x, y) over the tile, ``xbody()``
        giving x's tile."""
        y_ref[:] = acc.astype(x.dtype)
        if with_dot:
            # TPU grids run sequentially, so accumulating the partial
            # into the (1,)-SMEM output across steps is safe; products
            # are widened to the accumulation dtype before the reduction
            # so bf16 inputs don't collapse the scalar
            partial = jnp.sum(acc * xbody().astype(kadt))

            @pl.when(i == 0)
            def _():
                dot_ref[0] = partial

            @pl.when(i > 0)
            def _():
                dot_ref[0] += partial

    def outputs(refs):
        return refs[nplanes], (refs[nplanes + 1] if with_dot else None)

    scratch, params = (), None
    if not far:
        def kernel(x_hbm, *refs):
            plane_refs = refs[:nplanes]
            y_ref, dot_ref = outputs(refs)
            i = pl.program_id(0)

            def body(xwin, sems):
                start, wait = _window_copies(x_hbm, xwin, sems, 0, i, grid,
                                             tile, Lpad, Rpad, align,
                                             x.dtype)
                start()
                wait()
                acc = accumulate(plane_refs,
                                 lambda off: xwin[pl.ds(Lpad + off, tile)])
                finish(i, acc, lambda: xwin[pl.ds(Lpad, tile)], y_ref,
                       dot_ref)

            pl.run_scoped(body, pltpu.VMEM((win,), x.dtype),
                          pltpu.SemaphoreType.DMA((3,)))
    else:
        lo, hi = _ring_span(far, Lpad, Rpad, tile)
        K = hi - lo + 2
        scratch = ([pltpu.VMEM((win,), x.dtype) for _ in range(K)]
                   + [pltpu.SemaphoreType.DMA((K,))])
        # the ring is state carried from step to step: the grid must
        # stay sequential
        params = pltpu.CompilerParams(dimension_semantics=("arbitrary",))

        def kernel(x_hbm, *refs):
            plane_refs = refs[:nplanes]
            y_ref, dot_ref = outputs(refs)
            ring, sems = refs[-K - 1:-1], refs[-1]
            i = pl.program_id(0)

            def fetch(t, slot):
                # int32-explicit semaphore index: under jax_enable_x64 a
                # Python int traces as an i64 constant
                return pltpu.make_async_copy(
                    x_hbm.at[pl.ds(pl.multiple_of(t * tile, align), tile)],
                    ring[slot].at[pl.ds(Lpad, tile)],
                    sems.at[jnp.int32(slot)])

            def zero(slot):
                ring[slot][pl.ds(Lpad, tile)] = jnp.zeros((tile,), x.dtype)

            def step(r):
                """Grid step ``i`` with ``i mod K == r``."""
                def slot(d):  # the slot of tile i + d
                    return (r + d) % K

                if r == 0:
                    @pl.when(i == 0)
                    def _():
                        for t in range(lo, hi + 1):
                            if 0 <= t < grid:
                                fetch(t, slot(t)).start()
                            else:
                                zero(slot(t))

                # the tile step i+1 reads first: in flight behind this
                # step's work, into the slot of tile i+lo-1, now unread
                @pl.when(i + hi + 1 < grid)
                def _():
                    fetch(i + hi + 1, slot(hi + 1)).start()

                if r == 0:
                    @pl.when(i == 0)
                    def _():
                        for t in range(0, min(hi, grid - 1) + 1):
                            fetch(t, slot(t)).wait()

                @pl.when((i > 0) & (i + hi < grid))
                def _():
                    fetch(i + hi, slot(hi)).wait()

                @pl.when((i > 0) & (i + hi >= grid))
                def _():
                    zero(slot(hi))

                body = ring[slot(0)]
                if Lpad:
                    body[pl.ds(0, Lpad)] = ring[slot(-1)][pl.ds(tile, Lpad)]
                if Rpad:
                    body[pl.ds(Lpad + tile, Rpad)] = \
                        ring[slot(1)][pl.ds(Lpad, Rpad)]

                def window_of(off):
                    if off in far:
                        return ring[slot(off // tile)][pl.ds(Lpad, tile)]
                    return body[pl.ds(Lpad + off, tile)]

                acc = accumulate(plane_refs, window_of)
                finish(i, acc, lambda: body[pl.ds(Lpad, tile)], y_ref,
                       dot_ref)

            # int32-explicit modulo: under jax_enable_x64 a plain `i % K`
            # promotes through int64, which Mosaic cannot lower
            phase = jax.lax.rem(i, jnp.int32(K))
            for r in range(K):
                pl.when(phase == jnp.int32(r))(functools.partial(step, r))

    tile_spec = pl.BlockSpec((tile,), lambda i: (i,),
                             memory_space=pltpu.VMEM)
    out_specs = tile_spec
    out_shape = jax.ShapeDtypeStruct((n,), x.dtype)
    if with_dot:
        out_specs = (tile_spec,
                     pl.BlockSpec((1,), lambda i: (0,),
                                  memory_space=pltpu.SMEM))
        out_shape = (out_shape, jax.ShapeDtypeStruct((1,), kadt))
    return pl.pallas_call(
        kernel,
        grid=(grid,),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)] + [
            pl.BlockSpec((tile,), lambda i: (i,), memory_space=pltpu.VMEM)
            for _ in planes],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=scratch,
        compiler_params=params,
        interpret=interpret,
        name="acg_dia_spmv_dot" if with_dot else "acg_dia_spmv",
    )(x, *planes)


def _dia_spmv_padded(planes, offsets, x, L, R, interpret):
    """Ragged-shape fallback: one padded x copy, one DMA per tile."""
    n = x.shape[0]
    tile = TILE if n >= TILE else (n + (-n) % LANE)
    planes = tuple(_pad_to(p, tile) for p in planes)
    npad = planes[0].shape[0]
    grid = npad // tile
    win = tile + L + R
    win = win + (-win) % 4096  # DMA-offset alignment, any dtype
    # sized so the last tile's window slice stays in range
    xp = jnp.pad(x, (L, (grid - 1) * tile + win - L - n))

    def kernel(xp_ref, *plane_refs_and_out):
        plane_refs = plane_refs_and_out[:-1]
        y_ref = plane_refs_and_out[-1]
        i = pl.program_id(0)

        def body(xwin, sem):
            cp = pltpu.make_async_copy(
                xp_ref.at[pl.ds(i * tile, win)], xwin, sem)
            cp.start()
            cp.wait()
            kadt = acc_dtype(x.dtype)
            acc = jnp.zeros((tile,), kadt)
            for pr, off in zip(plane_refs, offsets):
                acc = acc + (pr[:].astype(kadt)
                             * xwin[pl.ds(L + off, tile)].astype(kadt))
            y_ref[:] = acc.astype(x.dtype)

        pl.run_scoped(body, pltpu.VMEM((win,), x.dtype),
                      pltpu.SemaphoreType.DMA)

    y = pl.pallas_call(
        kernel,
        grid=(grid,),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)] + [
            pl.BlockSpec((tile,), lambda i: (i,), memory_space=pltpu.VMEM)
            for _ in planes],
        out_specs=pl.BlockSpec((tile,), lambda i: (i,),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((npad,), x.dtype),
        interpret=interpret,
        name="acg_dia_spmv_padded",
    )(xp, *planes)
    return y[:n]


# row-tiled DIA: one tile of rows (TILEDIA_TILE) is one (8, 128) f32
# vreg; the kernel walks the segments in chunks of TILEDIA_CHUNK per grid
# step (the SMEM blocks of per-segment scalars must match XLA's T(1024)
# layout)
TILEDIA_CHUNK = 1024
# segments whose x windows are loaded and rotated before their products
# join the tiles' running sums: loads issued ahead of the stores that
# would otherwise order them (3.6x on the 2D n=2048 part, TPU v5e)
TILEDIA_UNROLL = 8
_TILEDIA_VMEM = 96 * 2 ** 20


def _tiledia_layout(n: int, reach: tuple):
    """``(ntiles, L, xlen, vmem)`` of the kernel for ``n`` rows: x is
    held whole in VMEM as rows of 128 lanes, padded by ``L`` (the lowest
    offset, rounded up to a tile) in front and enough behind that every
    segment's window, the dummy tile's included, stays inside."""
    T = TILEDIA_TILE
    ntiles = -(-n // T)
    L = -(-max(0, -reach[0]) // T) * T
    R = -(-max(0, reach[1]) // T) * T
    xlen = L + (ntiles + 2) * T + R
    vmem = 4 * (xlen + (ntiles + 1) * T + 2 * TILEDIA_CHUNK * T)
    return ntiles, L, xlen, vmem


def tiledia_fits(n: int, tile: int, reach: tuple, dtype) -> bool:
    """Whether :func:`tiledia_spmv` runs the kernel for this block (its
    tile is the kernel's, the storage at most f32 wide, and x, y and two
    plane chunks fit VMEM), rather than
    :func:`acg_tpu.ops.spmv.tiledia_mv`."""
    return (tile == TILEDIA_TILE and jnp.dtype(dtype).itemsize <= 4
            and _tiledia_layout(n, reach)[3] <= _TILEDIA_VMEM)


@functools.partial(jax.jit, static_argnames=("reach", "interpret"))
def tiledia_spmv(planes, seg_off, seg_tile, x, reach: tuple,
                 interpret: bool = False):
    """y = A @ x for a row-tiled DIA block, gather-free: the same
    operator and per-row summation order as
    :func:`acg_tpu.ops.spmv.tiledia_mv`, in f32 (sub-f32 storage is
    widened first, the :func:`acc_dtype` rule).

    x sits whole in VMEM as rows of 128 lanes; y is one VMEM-resident
    output, written back once.  The grid walks the segments in order,
    ``TILEDIA_CHUNK`` a step, their planes streamed by BlockSpec and
    their scalars (window start, output row, first-of-tile) in SMEM.  A
    segment's x window ``[w, w + 1024)`` is the eight rows from row ``w
    // 128`` and the eight from the next, each rotated left by ``w mod
    128`` lanes, the first's lanes kept where they do not wrap.  Its
    product adds to a running tile sum, reset at a tile's first segment
    and stored after every segment, so a tile's sum is exact in segment
    order across chunk boundaries."""
    n = x.shape[0]
    T = TILEDIA_TILE
    if not tiledia_fits(n, planes.shape[-1], reach, x.dtype):
        return tiledia_mv(planes, seg_off, seg_tile, reach, x)
    ntiles, L, xlen, vmem = _tiledia_layout(n, reach)
    S = planes.shape[0]
    C = TILEDIA_CHUNK
    spad = -(-S // C) * C
    i32 = jnp.int32
    first = jnp.concatenate([jnp.ones((1,), bool),
                             seg_tile[1:] != seg_tile[:-1]])

    def padded(v, fill):
        return jnp.pad(v.astype(i32), (0, spad - S), constant_values=fill)

    # padding segments: the dummy tile ntiles, each its own first, over
    # a window inside x's padding
    start = padded(L + seg_tile.astype(i32) * T + seg_off.astype(i32),
                   L + ntiles * T)
    trow = padded(seg_tile * 8, ntiles * 8)
    first = padded(first, 1)
    xp = jnp.pad(x.astype(jnp.float32), (L, xlen - L - n)).reshape(-1, LANE)
    planes = planes.astype(jnp.float32).reshape(S, 8, LANE)

    def kernel(start_ref, trow_ref, first_ref, x_ref, p_ref, y_ref):
        @pl.when(pl.program_id(0) == 0)
        def _():
            y_ref[...] = jnp.zeros(y_ref.shape, y_ref.dtype)

        lane = jax.lax.broadcasted_iota(i32, (8, LANE), 1)

        def window(j):
            q = start_ref[j] >> 7
            r = start_ref[j] & (LANE - 1)
            sh = (LANE - r) & (LANE - 1)
            return jnp.where(lane < LANE - r,
                             pltpu.roll(x_ref[pl.ds(q, 8), :], sh, 1),
                             pltpu.roll(x_ref[pl.ds(q + 1, 8), :], sh, 1))

        def segments(u, acc):
            js = [u * TILEDIA_UNROLL + j for j in range(TILEDIA_UNROLL)]
            prods = [p_ref[j] * window(j) for j in js]
            for j, prod in zip(js, prods):
                acc = jnp.where(first_ref[j] != 0, 0.0, acc) + prod
                y_ref[pl.ds(pl.multiple_of(trow_ref[j], 8), 8), :] = acc
            return acc

        acc0 = y_ref[pl.ds(pl.multiple_of(trow_ref[0], 8), 8), :]
        jax.lax.fori_loop(0, C // TILEDIA_UNROLL, segments, acc0)

    def smem():
        return pl.BlockSpec((C,), lambda i: (i,), memory_space=pltpu.SMEM)

    y = pl.pallas_call(
        kernel,
        grid=(spad // C,),
        in_specs=[smem(), smem(), smem(),
                  pl.BlockSpec(memory_space=pltpu.VMEM),
                  pl.BlockSpec((C, 8, LANE), lambda i: (i, 0, 0))],
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct(((ntiles + 1) * 8, LANE),
                                       jnp.float32),
        # y and the running sum are carried from step to step
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=vmem + 8 * 2 ** 20),
        interpret=interpret,
        name="acg_tiledia_spmv",
    )(start, trow, first, xp, planes)
    return y[: ntiles * 8].reshape(-1)[:n].astype(x.dtype)


def fused_cg_route(offsets: tuple, n: int, dtype) -> tuple | None:
    """(Lpad, Rpad, tile, align) when the two-phase fused CG iteration
    supports this shape (square DIA, single-window band, n divisible by
    the tile), else None.

    The tile is grown beyond the SpMV route's choice while VMEM allows:
    even with the cross-step double-buffered windows, fewer/larger
    steps amortise the per-step fixed costs (slot bookkeeping, output
    tile turnover); the pre-double-buffering measurement (base tile
    losing ~30% to synchronous DMAs) established the direction and the
    growth stays beneficial-or-neutral after it."""
    route = dia_spmv_route(offsets, n, dtype)
    if route[0] != "fast":
        return None
    Lpad, Rpad, tile, align = route[1:]
    ndiags = len(offsets)
    itemsize = jnp.dtype(dtype).itemsize
    budget = 12 * 2 ** 20

    def vmem(t):
        # 2x double-buffered windows + double-buffered BlockSpec tiles
        # (planes, p, t)
        return (4 * (t + Lpad + Rpad) + 2 * (ndiags + 2) * t) * itemsize

    while n % (2 * tile) == 0 and vmem(2 * tile) <= budget:
        tile *= 2
    return Lpad, Rpad, tile, align


def cg_phase_a(planes, offsets: tuple, r, p_old, gamma, gamma_prev,
               interpret: bool = False):
    """Phase A of the fused classic-CG iteration: one streamed pass that
    computes ``p = r + beta p_old`` (beta = gamma/gamma_prev, inf -> 0
    on the first iteration), ``t = A p``, and ``(p, t)``.

    The p-update is folded INTO the SpMV's halo windows: p values at
    shifted positions are recomputed from the r/p_old windows already in
    VMEM, so p_old's deferred update costs one extra streamed window
    instead of a separate full pass.  HBM traffic: D plane reads + r
    window + p_old window + p write + t write (~D+4 passes) vs the
    XLA formulation's ~D+7 for the same ops.

    This is the reference's monolithic device-kernel concept
    (``acgsolvercuda_cg_kernel``, ``cg-kernels-cuda.cu:627-970``)
    restated for TPU: the whole iteration as two kernels with scalars
    riding SMEM, leaving nothing for XLA to fuse (the failure mode that
    retired the single fused kernels in round 2 -- BASELINE.md).

    Returns ``(p, t, pdott)``; pdott is a () f32 scalar.
    """
    n = r.shape[0]
    route = fused_cg_route(offsets, n, r.dtype)
    if route is None:
        raise ValueError("shape not supported by the fused CG kernels")
    Lpad, Rpad, tile, align = route
    grid = n // tile
    win = tile + Lpad + Rpad
    kadt = acc_dtype(r.dtype)

    ndiags = len(planes)

    def kernel(scal_ref, r_hbm, p_hbm, *rest):
        plane_refs = rest[:ndiags]
        p_ref, t_ref, dot_ref = rest[ndiags:ndiags + 3]
        rwin_a, rwin_b, pwin_a, pwin_b, sems = rest[ndiags + 3:]
        rwins, pwins = (rwin_a, rwin_b), (pwin_a, pwin_b)
        i = pl.program_id(0)
        beta = (scal_ref[0, 0] / scal_ref[0, 1]).astype(r.dtype)

        # DOUBLE-BUFFERED windows: scratch_shapes persist across the
        # (strictly sequential) TPU grid steps, so step i's compute
        # overlaps step i+1's window DMAs -- the cross-step prefetch
        # Mosaic gives BlockSpec operands, hand-rolled for the halo
        # windows.  Slot selection is static via even/odd duplication;
        # slot s uses semaphores sems[s*6 : s*6+6].
        def starts(step, slot):
            for hbm, wref, s0 in ((r_hbm, rwins[slot], slot * 6),
                                  (p_hbm, pwins[slot], slot * 6 + 3)):
                st, _ = _window_copies(hbm, wref, sems, s0, step, grid,
                                       tile, Lpad, Rpad, align, r.dtype)
                st()

        def waits(step, slot):
            for hbm, wref, s0 in ((r_hbm, rwins[slot], slot * 6),
                                  (p_hbm, pwins[slot], slot * 6 + 3)):
                _, wt = _window_copies(hbm, wref, sems, s0, step, grid,
                                       tile, Lpad, Rpad, align, r.dtype)
                wt()

        def compute(rwin, pwin):
            # p over the whole window (halo positions recomputed from
            # the r/p_old windows -- the deferred-p-update trick).
            # pw is a VALUE; offsets are static, so plain slices compile
            pw = rwin[...] + beta * pwin[...]
            acc = jnp.zeros((tile,), kadt)
            for pr, off in zip(plane_refs, offsets):
                acc = acc + (pr[:].astype(kadt)
                             * pw[Lpad + off:Lpad + off + tile]
                             .astype(kadt))
            p_body = pw[Lpad:Lpad + tile]
            p_ref[:] = p_body
            t_ref[:] = acc.astype(r.dtype)
            return jnp.sum(acc * p_body.astype(kadt))

        # int32-explicit modulo: under jax_enable_x64 a plain `i % 2`
        # promotes through int64, which Mosaic cannot lower
        par = jax.lax.rem(i, jnp.int32(2))

        @pl.when(i == 0)
        def _():
            starts(i, 0)

        for parity in (0, 1):
            @pl.when((par == jnp.int32(parity)) & (i < grid - 1))
            def _(parity=parity):
                starts(i + 1, 1 - parity)

        for parity in (0, 1):
            @pl.when(par == jnp.int32(parity))
            def _(parity=parity):
                waits(i, parity)
                partial = compute(rwins[parity], pwins[parity])

                @pl.when(i == 0)
                def _():
                    dot_ref[0] = partial

                @pl.when(i > 0)
                def _():
                    dot_ref[0] += partial

    tile_spec = pl.BlockSpec((tile,), lambda i: (i,),
                             memory_space=pltpu.VMEM)
    scal = jnp.stack([gamma.astype(jnp.float32),
                      gamma_prev.astype(jnp.float32)]).reshape(1, 2)
    p, t, d = pl.pallas_call(
        kernel,
        grid=(grid,),
        in_specs=[pl.BlockSpec((1, 2), lambda i: (0, 0),
                               memory_space=pltpu.SMEM),
                  pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY)] + [
            tile_spec for _ in planes],
        out_specs=(tile_spec, tile_spec,
                   pl.BlockSpec((1,), lambda i: (0,),
                                memory_space=pltpu.SMEM)),
        out_shape=(jax.ShapeDtypeStruct((n,), r.dtype),
                   jax.ShapeDtypeStruct((n,), r.dtype),
                   jax.ShapeDtypeStruct((1,), jnp.float32)),
        scratch_shapes=[pltpu.VMEM((win,), r.dtype),
                        pltpu.VMEM((win,), r.dtype),
                        pltpu.VMEM((win,), r.dtype),
                        pltpu.VMEM((win,), r.dtype),
                        pltpu.SemaphoreType.DMA((12,))],
        interpret=interpret,
        name="acg_fused_cg_a",
    )(scal, r, p_old, *planes)
    return p, t, d[0]


def cg_phase_b(x, p, r, t, gamma, pdott, interpret: bool = False):
    """Phase B of the fused classic-CG iteration: one streamed pass for
    ``alpha = gamma/(p,t); x += alpha p; r -= alpha t`` and the next
    ``gamma = (r, r)`` accumulated in SMEM.  Returns (x, r, gamma)."""
    n = x.shape[0]
    tile = TILE if n % TILE == 0 and n >= TILE else None
    if tile is None:
        raise ValueError("shape not supported by the fused CG kernels")
    grid = n // tile
    kadt = acc_dtype(x.dtype)

    def kernel(scal_ref, x_ref, p_ref, r_ref, t_ref, xo, ro, go):
        i = pl.program_id(0)
        alpha = (scal_ref[0, 0] / scal_ref[0, 1]).astype(x.dtype)
        xo[:] = x_ref[:] + alpha * p_ref[:]
        rn = r_ref[:] - alpha * t_ref[:]
        ro[:] = rn
        partial = jnp.sum(rn.astype(kadt) * rn.astype(kadt))

        @pl.when(i == 0)
        def _():
            go[0] = partial

        @pl.when(i > 0)
        def _():
            go[0] += partial

    tile_spec = pl.BlockSpec((tile,), lambda i: (i,),
                             memory_space=pltpu.VMEM)
    scal = jnp.stack([gamma.astype(jnp.float32),
                      pdott.astype(jnp.float32)]).reshape(1, 2)
    xn, rn, g = pl.pallas_call(
        kernel,
        grid=(grid,),
        in_specs=[pl.BlockSpec((1, 2), lambda i: (0, 0),
                               memory_space=pltpu.SMEM)] + [tile_spec] * 4,
        out_specs=(tile_spec, tile_spec,
                   pl.BlockSpec((1,), lambda i: (0,),
                                memory_space=pltpu.SMEM)),
        out_shape=(jax.ShapeDtypeStruct((n,), x.dtype),
                   jax.ShapeDtypeStruct((n,), x.dtype),
                   jax.ShapeDtypeStruct((1,), jnp.float32)),
        interpret=interpret,
        name="acg_fused_cg_b",
    )(scal, x, p, r, t)
    return xn, rn, g[0]


@functools.partial(jax.jit, static_argnames=("interpret",))
def fused_pipelined_update(x, r, w, p, t, z, q, alpha, beta,
                           interpret: bool = False):
    """One-pass Ghysels-Vanroose update (``cg-kernels-cuda.cu:187-269``):

        z = q + beta z;  t = w + beta t;  p = r + beta p
        x = x + alpha p; r = r - alpha t; w = w - alpha z

    Returns (x, r, w, p, t, z).  alpha/beta ride in SMEM (the reference
    reads them from device memory to avoid host syncs; same idea).
    """
    n = x.shape[0]
    ab = jnp.stack([alpha.astype(x.dtype), beta.astype(x.dtype)]).reshape(1, 2)
    vecs = [_pad_to(v, TILE) for v in (x, r, w, p, t, z, q)]
    npad = vecs[0].shape[0]
    grid = npad // TILE

    def kernel(ab_ref, x_ref, r_ref, w_ref, p_ref, t_ref, z_ref, q_ref,
               xo, ro, wo, po, to, zo):
        a = ab_ref[0, 0]
        b = ab_ref[0, 1]
        zn = q_ref[:] + b * z_ref[:]
        tn = w_ref[:] + b * t_ref[:]
        pn = r_ref[:] + b * p_ref[:]
        xo[:] = x_ref[:] + a * pn
        ro[:] = r_ref[:] - a * tn
        wo[:] = w_ref[:] - a * zn
        po[:] = pn
        to[:] = tn
        zo[:] = zn

    tile_spec = pl.BlockSpec((TILE,), lambda i: (i,),
                             memory_space=pltpu.VMEM)
    outs = pl.pallas_call(
        kernel,
        grid=(grid,),
        in_specs=[pl.BlockSpec((1, 2), lambda i: (0, 0),
                               memory_space=pltpu.SMEM)] + [tile_spec] * 7,
        out_specs=(tile_spec,) * 6,
        out_shape=tuple(jax.ShapeDtypeStruct((npad,), x.dtype)
                        for _ in range(6)),
        interpret=interpret,
        name="acg_fused_pipelined_update",
    )(ab, *vecs)
    return tuple(o[:n] for o in outs)


# -- matrix-free stencil SpMV (the operator tier's Pallas path) -----------

def stencil_spmv_route(op, n_total: int, dtype):
    """``(Lpad, Rpad, tile, align)`` when the in-kernel-generated
    stencil SpMV supports this operator/shape, else None.  Constant-
    coefficient Poisson on the single-window band (the ``dia_spmv``
    "fast" shape): the whole point of the kernel is that NO plane
    inputs exist -- x streams through VMEM once and the coefficient
    masks are computed from iotas in-register -- so the VMEM budget is
    looser than the assembled kernel's, but the band/divisibility
    constraints are the same."""
    if getattr(op, "kind", None) != "poisson":
        return None
    route = dia_spmv_route(op.offsets, n_total, dtype,
                           ndiags=len(op.offsets))
    if route[0] != "fast":
        return None
    Lpad, Rpad, tile, align = route[1:]
    if tile % LANE:
        return None
    return Lpad, Rpad, tile, align


@functools.partial(jax.jit,
                   static_argnames=("n", "dim", "offsets", "Lpad",
                                    "Rpad", "tile", "align", "interpret"))
def _stencil_poisson_call(x, n: int, dim: int, offsets: tuple,
                          Lpad: int, Rpad: int, tile: int, align: int,
                          interpret: bool):
    N = x.shape[0]
    grid = N // tile
    win = tile + Lpad + Rpad
    sub = tile // LANE

    def kernel(x_hbm, y_ref):
        i = pl.program_id(0)

        def body(xwin, sems):
            start, wait = _window_copies(x_hbm, xwin, sems, 0, i, grid,
                                         tile, Lpad, Rpad, align,
                                         x.dtype)
            start()
            wait()
            kadt = acc_dtype(x.dtype)
            # global row indices of this tile, as a native 2-D tile
            # (TPU iotas want >= 2 dims); masks derive from the grid
            # coordinate exactly like ops.operator.stencil_planes
            r2 = jax.lax.broadcasted_iota(jnp.int32, (sub, LANE), 0)
            c2 = jax.lax.broadcasted_iota(jnp.int32, (sub, LANE), 1)
            gidx = i * tile + r2 * LANE + c2
            acc = jnp.zeros((sub, LANE), kadt)
            for off in offsets:
                xs = xwin[pl.ds(Lpad + off, tile)].reshape(
                    sub, LANE).astype(kadt)
                # the generated plane VALUE, in exactly dia_mv's
                # ``y + plane * x`` expression shape so XLA forms the
                # same multiply-add chain as the assembled/XLA path
                if off == 0:
                    plane = jnp.full((sub, LANE), float(2 * dim), kadt)
                else:
                    stride = abs(int(off))
                    coord = (gidx // stride) % n
                    mask = coord > 0 if off < 0 else coord < n - 1
                    plane = jnp.where(mask, -1.0, 0.0).astype(kadt)
                acc = acc + plane * xs
            y_ref[:] = acc.reshape(tile).astype(x.dtype)

        pl.run_scoped(body, pltpu.VMEM((win,), x.dtype),
                      pltpu.SemaphoreType.DMA((3,)))

    return pl.pallas_call(
        kernel,
        grid=(grid,),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((tile,), lambda i: (i,),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((N,), x.dtype),
        interpret=interpret,
        name="acg_stencil_spmv",
    )(x)


def stencil_spmv(op, x, interpret: bool = False, tile: int | None = None,
                 align: int | None = None):
    """y = A @ x for a matrix-free :class:`~acg_tpu.ops.operator.
    StencilOperator` with the coefficient masks generated IN-KERNEL:
    x streams through VMEM once per row tile (``_window_copies``, the
    single-x-pass machinery the assembled DIA kernel uses) and the
    plane values never exist anywhere -- not in HBM, not in VMEM.
    This is the matrix-free restatement of :func:`dia_spmv`'s traffic
    argument: the assembled kernel still reads D planes per tile; this
    one reads x and writes y, full stop.

    Values are bitwise-equal to the XLA matfree apply (-1 * x == -x;
    masked positions add a zero, exactly like the structural-zero
    plane entries).  Shapes outside the single-window route -- or
    non-Poisson kinds -- fall back to the operator's own XLA apply
    (``op.matfree_apply``), the same degrade discipline as
    ``dia_spmv``'s "xla" route.  ``tile``/``align`` override the route
    for interpret-mode tests at small sizes."""
    n_total = x.shape[0]
    if tile is not None:
        n, dim = op.grid
        band = n ** (dim - 1)
        Lpad = Rpad = band + (-band) % (align or 1)
        if (tile % LANE or n_total % tile or band > tile
                or op.kind != "poisson"):
            return op.matfree_apply(x)
        return _stencil_poisson_call(x, n, dim, op.offsets, Lpad, Rpad,
                                     tile, align or 1, interpret)
    route = stencil_spmv_route(op, n_total, x.dtype)
    if route is None:
        return op.matfree_apply(x)
    Lpad, Rpad, rtile, ralign = route
    n, dim = op.grid
    return _stencil_poisson_call(x, n, dim, op.offsets, Lpad, Rpad,
                                 rtile, ralign, interpret)
