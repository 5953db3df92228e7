"""Device-initiated halo exchange: Pallas one-sided remote DMA over ICI.

The TPU-native analog of the reference's NVSHMEM transport (SURVEY.md
components #11/#14): where the reference's monolithic kernel issues
``nvshmemx_double_put_signal_nbi_block`` per neighbour and spins on
``signal_wait_until`` flags (``cg-kernels-cuda.cu:713-776``,
``halo.cu:181-242``), this kernel issues ``pltpu.make_async_remote_copy``
per neighbour (a put that signals the receiver's DMA semaphore) and waits
on the matching semaphores.

Structure: nparts-1 rotation rounds; in round s every shard puts its
window for shard ``me+s`` and receives from shard ``me-s`` -- the
systolic all-to-all schedule that keeps traffic on ICI neighbours first.
Pallas interpret mode (CPU meshes, tests) additionally *requires* this
uniformity: it emulates remote DMA with collectives that pair DMA ops
across devices in issue order, so any per-shard divergence in the op
sequence -- different ordering, or count-gated skips that are not
globally uniform per round -- deadlocks or mis-routes.

Synchronisation details:
  * One scalar send and one scalar recv DMA semaphore are shared by all
    rounds.  Every put moves exactly ``maxcnt`` elements (windows are
    padded to the mesh-wide maximum, like the reference's NVSHMEM
    symmetric buffers, ``halo.c:883-887``), so the shared-semaphore
    waits are exact regardless of completion order.
  * On real TPUs, puts and waits are gated by the per-neighbour counts
    (only real neighbours communicate -- the reference's per-neighbour
    ``sendcounts``, ``halo.h:72-186``).  Interpret mode must issue a
    globally uniform op sequence, so there the exchange is dense; the
    gating arithmetic itself is still covered on CPU by a
    ring-structured test whose gate pattern is uniform per round.
  * On real TPUs a neighbourhood barrier at kernel entry reproduces the
    reference's ``readytoreceive`` handshake (``halo.c:957-967``): a TPU
    core runs its program in order, so a neighbour entering this kernel
    proves it has consumed the previous exchange's buffers.  Interpret
    mode has no barrier primitive and skips it (its DMA emulation
    rendezvouses on fresh buffers, so the hazard does not exist there).
  * Receive-plane rows of non-neighbours are never written; the unpack
    masks padding ghost slots (``ghost_valid``) so those uninitialised
    rows are never observed.

Selected by ``--comm dma`` (the reference's ``--comm nvshmem``); the
default ``--comm xla`` transport is the `lax.all_to_all` in
:mod:`acg_tpu.parallel.halo`.  Pack/unpack stay XLA gathers outside the
kernel, exactly as the reference keeps its pack kernels separate from the
transport (``halo.cu:41-107``).

Validation status: the gating, routing, and barrier-count logic are all
exercised in CI (interpret mode, uniform-gate rings plus randomized
star/line/clustered topologies vs the xla transport); the compiled
kernel compiles for a described v5e:2x2 (``tests/test_tpu_compile.py``)
and has run over real ICI on a single-controller 2x2 v5e host, matching
the xla transport's iterations and error norms on a graph-partitioned
2M-row solve (``chip_smoke.py --four-chips``, PR 21).  Multi-controller
(one process per host) has not run on TPU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from acg_tpu.parallel.mesh import PARTS_AXIS


_dma_status: tuple | None = None


def dma_transport_status(refresh: bool = False) -> tuple:
    """Cached ``(available, why)`` capability verdict for the one-sided
    transport in THIS process topology -- the conftest two-process-probe
    pattern, library-side.

    Single-controller: available (the compiled path ran over ICI on
    four v5e chips, ``chip_smoke.py --four-chips``; interpret mode is
    CI-covered).  Multi-controller on TPU: unavailable -- that layout
    has never run, and a wrong guess deadlocks a pod, so the verdict is
    a self-describing downgrade, not a probe.  Multi-controller
    off-TPU: the interpret emulation pairs DMA ops with collectives, so
    the probe ATTEMPTS one tiny
    cross-process psum over a mesh with ONE DEVICE PER PROCESS (every
    controller reaches solver setup together, so the collective is
    matched) and then AGREES the verdict across controllers over the
    erragree blob allgather -- a locally-divergent verdict would arm
    mismatched transports (DMA puts on one controller, all_to_all on
    another) and deadlock the very first halo exchange, the failure
    mode the old hard refusal protected against.  ``DistCGSolver``
    downgrades ``comm='dma'`` to the xla transport with a recorded
    event when this says no."""
    global _dma_status
    if _dma_status is not None and not refresh:
        return _dma_status
    if jax.process_count() == 1:
        _dma_status = (True, "")
        return _dma_status
    if jax.devices()[0].platform == "tpu":
        _dma_status = (
            False,
            "the put-with-signal path has run single-controller "
            "only (chip_smoke.py --four-chips); multi-controller "
            "pods are unverified")
        return _dma_status
    try:
        import numpy as np
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        from acg_tpu.parallel.multihost import put_global
        # one device per PROCESS: jax.devices()[:n] can be all local,
        # which would probe nothing cross-process
        by_proc: dict = {}
        for d in jax.devices():
            by_proc.setdefault(d.process_index, d)
        devs = np.asarray([by_proc[p] for p in sorted(by_proc)])
        mesh = Mesh(devs, ("probe",))
        f = jax.jit(jax.shard_map(lambda a: lax.psum(a, "probe"),
                                  mesh=mesh, in_specs=P("probe"),
                                  out_specs=P(), check_vma=False))
        a = put_global(np.ones((devs.size,), np.float32),
                       sharding=NamedSharding(mesh, P("probe")))
        np.asarray(f(a))
        mine = (True, "")
    except Exception as e:  # noqa: BLE001 -- the probe must conclude
        mine = (False, "cross-process collectives unavailable on this "
                f"backend ({type(e).__name__})")
    # ONE agreed verdict: any controller failing downgrades them all
    # (the erragree every-controller-calls-here contract holds -- all
    # controllers construct the solver at the same program point)
    try:
        from acg_tpu.parallel.erragree import allgather_blobs
        got = allgather_blobs("ok" if mine[0] else "no",
                              tag="dma-probe")
        if all(g == "ok" for g in got):
            _dma_status = (True, "")
        else:
            _dma_status = (False, mine[1] if not mine[0] else
                           "a peer controller's transport probe failed")
    except Exception as e:  # noqa: BLE001 -- no agreement, no arming
        _dma_status = (False, "transport-probe verdict agreement "
                       f"failed ({type(e).__name__})")
    return _dma_status


def _exchange_kernel(axis, use_barrier, gate_by_counts, scnt_ref, rcnt_ref,
                     sendbuf_ref, recvbuf_ref, send_sem, recv_sem):
    """Per-shard kernel: neighbourhood barrier, start every gated put
    (nbi-style, all in flight at once), then wait for sends and
    receives."""
    me = lax.axis_index(axis)
    nparts = lax.axis_size(axis)

    def want_send(q):
        if gate_by_counts:
            return scnt_ref[q] > 0
        return jnp.asarray(True)

    def want_recv(q):
        if gate_by_counts:
            return rcnt_ref[q] > 0
        return jnp.asarray(True)

    if use_barrier:
        # readytoreceive handshake with the neighbourhood (halo.c:957-967)
        barrier = pltpu.get_barrier_semaphore()
        nneighbors = jnp.int32(0)
        for s in range(1, nparts):
            q = (me + s) % nparts
            is_neighbor = want_send(q) | want_recv(q)

            @pl.when(is_neighbor)
            def _(q=q):
                pltpu.semaphore_signal(
                    barrier, inc=1, device_id=q,
                    device_id_type=pltpu.DeviceIdType.LOGICAL)
            nneighbors = nneighbors + jnp.where(is_neighbor, 1, 0)
        pltpu.semaphore_wait(barrier, nneighbors)

    def put_descriptor(peer, src_row, dst_row):
        # put-with-signal (cg-kernels-cuda.cu:734-746): the window lands
        # in the peer's recvbuf row and signals the peer's recv semaphore
        return pltpu.make_async_remote_copy(
            src_ref=sendbuf_ref.at[src_row],
            dst_ref=recvbuf_ref.at[dst_row],
            send_sem=send_sem,
            recv_sem=recv_sem,
            device_id=peer,
            device_id_type=pltpu.DeviceIdType.LOGICAL)

    # start all puts before waiting on any (the reference's _nbi puts,
    # cg-kernels-cuda.cu:734-746): distinct source and destination rows,
    # so every transfer is independent and overlaps on the wire
    for s in range(1, nparts):
        dst = (me + s) % nparts

        @pl.when(want_send(dst))
        def _(dst=dst):
            put_descriptor(dst, dst, me).start()

    for s in range(1, nparts):
        dst = (me + s) % nparts
        src = (me - s + nparts) % nparts

        @pl.when(want_send(dst))
        def _(dst=dst):
            put_descriptor(dst, dst, me).wait_send()

        @pl.when(want_recv(src))
        def _(src=src):
            # signal_wait_until analog: src's put into my row `src`
            put_descriptor(src, src, src).wait_recv()


@functools.partial(jax.jit,
                   static_argnames=("axis", "interpret", "gate_by_counts"))
def _exchange(sendbuf, send_counts, recv_counts, axis: str, interpret: bool,
              gate_by_counts: bool | None = None):
    nparts, maxcnt = sendbuf.shape
    if nparts == 1:
        # no neighbours, no puts: the receive plane is never written and
        # every ghost gather is masked by ghost_valid.  Short-circuit
        # instead of compiling the degenerate kernel -- measured on real
        # hardware (2026-07-30): Mosaic SIGABRTs compiling the empty-put
        # barrier kernel, while a 1-device kernel with an actual
        # self-put + barrier compiles and runs correctly
        # (scripts/dma_probe.py holds the repro of both).
        return jnp.zeros_like(sendbuf)
    if gate_by_counts is None:
        gate_by_counts = not interpret
    kernel = functools.partial(_exchange_kernel, axis, not interpret,
                               gate_by_counts)
    # one (1, width) plane per peer behind an UNTILED leading dim, width
    # padded to the 128-lane tile: Mosaic refuses a DMA whose slice cuts
    # a tile, and a row of a 2-D (nparts, maxcnt) HBM buffer is a 1-row
    # slice of its (4, 128) tiling
    width = maxcnt + (-maxcnt) % 128
    planes = jnp.pad(sendbuf, ((0, 0), (0, width - maxcnt)))
    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((nparts, 1, width), sendbuf.dtype),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),   # send_counts
            pl.BlockSpec(memory_space=pltpu.SMEM),   # recv_counts
            pl.BlockSpec(memory_space=pl.ANY),       # sendbuf
        ],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[
            pltpu.SemaphoreType.DMA(()),             # send (shared)
            pltpu.SemaphoreType.DMA(()),             # recv (shared)
        ],
        compiler_params=pltpu.CompilerParams(has_side_effects=True,
                                             collective_id=0),
        interpret=interpret,
    )(send_counts, recv_counts, planes.reshape(nparts, 1, width))
    return out.reshape(nparts, width)[:, :maxcnt]


def dma_exchange(sendbuf: jax.Array, send_counts: jax.Array,
                 recv_counts: jax.Array, axis: str = PARTS_AXIS,
                 interpret: bool | None = None,
                 gate_by_counts: bool | None = None) -> jax.Array:
    """The raw systolic put-with-signal exchange without pack/unpack --
    the communication observatory's probe entry (acg_tpu.commbench:
    dense window sweeps and the per-edge put/wait timing rows, whose
    distance gates are globally uniform per rotation round and so are
    safe under the interpret emulation's op pairing).  Same contract as
    the :func:`_exchange` kernel the solve-path transport rides, same
    interpret default as :func:`halo_exchange_dma`."""
    if interpret is None:
        interpret = jax.devices()[0].platform != "tpu"
    return _exchange(sendbuf, send_counts, recv_counts, axis, interpret,
                     gate_by_counts)


def halo_exchange_dma(x_loc: jax.Array, send_idx: jax.Array,
                      ghost_src: jax.Array, ghost_valid: jax.Array,
                      send_counts: jax.Array, recv_counts: jax.Array,
                      axis: str = PARTS_AXIS,
                      interpret: bool | None = None) -> jax.Array:
    """Exchange ghost values by one-sided remote DMA; call inside
    `shard_map` over ``axis``.

    Same contract as :func:`acg_tpu.parallel.halo.halo_exchange` plus the
    per-neighbour counts (``send_counts[q]`` = entries this shard sends to
    shard q), which gate the puts so only real neighbours communicate,
    and ``ghost_valid``, which masks padding ghost slots whose gathers
    would otherwise read receive-plane rows no neighbour ever wrote
    (uninitialised device memory on real TPUs).
    """
    if interpret is None:
        interpret = jax.devices()[0].platform != "tpu"
    sendbuf = x_loc[send_idx]                    # pack (halo.cu:41-54)
    recvbuf = _exchange(sendbuf, send_counts, recv_counts, axis,
                        interpret)
    ghost = recvbuf.reshape(-1)[ghost_src]       # unpack (halo.cu:94-107)
    return jnp.where(ghost_valid, ghost, 0)
