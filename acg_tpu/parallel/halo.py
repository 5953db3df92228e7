"""Device-side halo exchange over the mesh.

Rebuilds the reference's halo engine (``acg/halo.c``, ``acg/halo.cu``,
SURVEY.md components #13-14) in XLA-collective form: the host-side plan
(per-neighbour index lists, :class:`acg_tpu.graph.HaloPlan`) is compiled
into static padded gather/scatter index arrays, and the transport is a
single `lax.all_to_all` over the ``parts`` mesh axis inside `shard_map`.

Mapping of the reference's mechanisms:
  * pack kernel (``halo.cu:41-54``: ``sendbuf[i] = src[sendbufidx[i]]``)
    -> one gather ``x[send_idx]`` producing the (nparts, maxcnt) send plane;
  * MPI persistent-request / NCCL grouped send-recv transport
    (``halo.c:1077-1090,1272-1330``) -> `lax.all_to_all` over ICI;
  * unpack kernel (``halo.cu:94-107``) -> one gather from the received
    plane into the ghost slots (``ghost_src``);
  * NVSHMEM max-size symmetric buffers (``halo.c:883-887``) -> the same
    pad-to-max trick, required here by XLA's static shapes: every
    (src, dst) window is padded to the mesh-wide maximum count.

A Pallas remote-DMA transport (the device-initiated put-with-signal analog)
lives in ``acg_tpu.parallel.halo_dma`` and is selected by ``--comm dma``;
the hand-written compute kernels live in ``acg_tpu.ops.pallas_kernels``.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import numpy as np
from jax import lax

from acg_tpu.graph import Subdomain
from acg_tpu.parallel.mesh import PARTS_AXIS


@functools.partial(jax.tree_util.register_dataclass,
                   data_fields=["send_idx", "ghost_src", "ghost_valid"],
                   meta_fields=["maxcnt", "nmax_ghost", "nparts"])
@dataclasses.dataclass
class DeviceHaloPlan:
    """Static padded halo plan, stacked over parts (leading axis sharded).

    ``send_idx[p, q, :]`` gathers from part p's owned vector the window it
    sends to part q (padded with index 0; padding values are never read on
    the receive side).  ``ghost_src[p, g]`` indexes the flattened received
    plane (nparts * maxcnt) to fill ghost slot g of part p.
    ``ghost_valid[p, g]`` is 0 for padding slots beyond part p's real
    ghost count: their ghost_src of 0 would read a receive-plane row that
    the DMA transport may never have written (uninitialised device
    memory), so the unpack masks them to zero.
    """

    send_idx: jax.Array     # (nparts, nparts, maxcnt) int32
    ghost_src: jax.Array    # (nparts, nmax_ghost) int32
    ghost_valid: jax.Array  # (nparts, nmax_ghost) bool
    maxcnt: int
    nmax_ghost: int
    nparts: int

    @property
    def has_ghosts(self) -> bool:
        return self.nmax_ghost > 0 and self.maxcnt > 0


def build_device_halo(subs: list[Subdomain], maxcnt: int | None = None,
                      nmax_ghost: int | None = None) -> DeviceHaloPlan:
    """Compile host halo plans into padded device index arrays.

    ``maxcnt``/``nmax_ghost`` override the locally-derived maxima in the
    local-read flow, where this controller only holds its own parts'
    plans (parts with ``halo is None`` are skipped; their rows stay as
    untouched calloc pages and their device shards are filled by the
    owning controller)."""
    nparts = len(subs)
    if maxcnt is None:
        maxcnt = max((int(c) for s in subs if s.halo is not None
                      for c in s.halo.send_counts), default=0)
    if nmax_ghost is None:
        nmax_ghost = max((s.nghost for s in subs), default=0)
    send_idx = np.zeros((nparts, nparts, max(maxcnt, 1)), dtype=np.int32)
    ghost_src = np.zeros((nparts, max(nmax_ghost, 1)), dtype=np.int32)
    ghost_valid = np.zeros((nparts, max(nmax_ghost, 1)), dtype=bool)
    for p, s in enumerate(subs):
        if s.halo is None:
            continue
        ghost_valid[p, : s.nghost] = True
        h = s.halo
        for j, q in enumerate(h.send_parts):
            w = h.send_idx[h.send_ptr[j]:h.send_ptr[j + 1]]
            send_idx[p, int(q), : w.size] = w
        # ghost slot g of part p comes from owner q's send window to p, at
        # the slot's rank within its (contiguous, global-id-sorted) window
        for j, q in enumerate(h.recv_parts):
            lo, hi = int(h.recv_ptr[j]), int(h.recv_ptr[j + 1])
            ghost_src[p, lo:hi] = int(q) * max(maxcnt, 1) + np.arange(hi - lo)
    # arrays stay HOST numpy: device placement goes through put_global's
    # per-shard slicing (multi-controller processes must not materialise
    # full device copies of other processes' shards)
    return DeviceHaloPlan(send_idx=send_idx, ghost_src=ghost_src,
                          ghost_valid=ghost_valid,
                          maxcnt=maxcnt, nmax_ghost=nmax_ghost, nparts=nparts)


def halo_exchange(x_loc: jax.Array, send_idx: jax.Array,
                  ghost_src: jax.Array, axis: str = PARTS_AXIS) -> jax.Array:
    """Exchange ghost values; call inside `shard_map` over ``axis``.

    Per shard: ``x_loc`` (nmax_owned,), ``send_idx`` (nparts, maxcnt),
    ``ghost_src`` (nmax_ghost,).  Returns the ghost vector (nmax_ghost,).
    """
    with jax.named_scope("acg.halo"):
        sendbuf = x_loc[send_idx]                   # pack: (nparts, maxcnt)
        recvbuf = lax.all_to_all(sendbuf, axis, split_axis=0, concat_axis=0,
                                 tiled=True)        # transport over ICI
        return recvbuf.reshape(-1)[ghost_src]       # unpack into ghost slots
