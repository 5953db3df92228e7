"""Multi-controller (multi-host) runtime glue.

The reference boots one MPI rank per GPU and derives every communicator
from ``MPI_COMM_WORLD`` (``cuda/acg-cuda.c:891-1203``; NCCL unique-id
broadcast ``:1110-1121``; NVSHMEM bootstrap ``comm-nvshmem.cu:84-100``).
The TPU-native analog is JAX's multi-controller runtime: one Python
process per host, :func:`jax.distributed.initialize` playing the role of
``MPI_Init``, and the *global* device list playing the role of the
communicator.  The jitted SPMD solve program is unchanged -- each process
traces the identical program over the global mesh and XLA runs the
collectives over ICI/DCN; only array ingress/egress differ, because each
process can address only its local shards.

Entry points:

* :func:`initialize` -- idempotent ``jax.distributed.initialize``; on TPU
  pods all arguments are auto-detected from the environment, elsewhere
  (and in the CPU smoke test) coordinator/process counts are explicit.
* :func:`put_global` / :func:`get_global` -- host-array placement onto a
  possibly multi-process sharding and back.  Single-process these reduce
  to ``device_put`` / ``device_get``.
* :func:`is_primary` -- "rank 0" predicate for stdout/stderr output (the
  reference prints stats and the solution from rank 0 only,
  ``mtxfile_fwrite_mpi_double``).
"""

from __future__ import annotations

import numpy as np


def initialize(coordinator: str | None = None,
               num_processes: int | None = None,
               process_id: int | None = None,
               local_device_ids=None) -> None:
    """Start the multi-controller runtime (the ``MPI_Init`` analog).

    Idempotent: a second call (or a call in an already-initialised
    process) is a no-op, so library code may call this unconditionally.
    With no arguments, JAX auto-detects cluster configuration from the
    TPU pod metadata / cluster-scheduler environment; the explicit
    arguments exist for manual launches and the CPU-based smoke test.
    """
    import jax

    if jax.distributed.is_initialized():
        return
    kwargs = {}
    if coordinator is not None:
        kwargs["coordinator_address"] = coordinator
    if num_processes is not None:
        kwargs["num_processes"] = num_processes
    if process_id is not None:
        kwargs["process_id"] = process_id
    if local_device_ids is not None:
        kwargs["local_device_ids"] = local_device_ids
    jax.distributed.initialize(**kwargs)


def is_primary() -> bool:
    """True on the process that should write user-facing output."""
    import jax

    return jax.process_index() == 0


def put_global(arr, sharding):
    """Place a host array, identically present on every process, onto
    ``sharding`` (which may span devices of other processes).

    Single-process this is ``jax.device_put``.  Multi-process it builds
    the global array from per-process local shards -- every process holds
    the full host array (the driver reads/partitions the matrix on every
    controller, the analog of the reference's root-rank read + scatter,
    ``acggraph_scatter``), so the callback just slices it.
    """
    import jax

    if jax.process_count() == 1:
        return jax.device_put(arr, sharding)
    arr = np.asarray(arr)
    # dtype must be explicit: a process whose devices are all outside the
    # mesh holds no addressable shards to infer it from
    try:
        return jax.make_array_from_callback(arr.shape, sharding,
                                            lambda idx: arr[idx],
                                            dtype=arr.dtype)
    except TypeError:
        # older jax: no dtype kwarg -- inference from the local shards
        # still covers every process that addresses part of the mesh
        return jax.make_array_from_callback(arr.shape, sharding,
                                            lambda idx: arr[idx])


def get_global(x) -> np.ndarray:
    """Fetch a (possibly non-fully-addressable) device array to every
    host as a numpy array -- the ``MPI_Allgatherv`` of the solution
    vector in reverse (`mtxfile.h:1087` writes rank-by-rank instead; on
    a single-controller the assembled array is the natural form)."""
    import jax

    if jax.process_count() == 1 or x.is_fully_addressable:
        return np.asarray(jax.device_get(x))
    from jax.experimental import multihost_utils

    return np.asarray(multihost_utils.process_allgather(x, tiled=True))
