"""Device mesh construction for the solve.

The reference's communicator setup (``acgcomm_init_*``, rank = part id,
``cuda/acg-cuda.c:1036``) maps on TPU to a 1-D `jax.sharding.Mesh` whose
single axis enumerates subdomains: part p lives on mesh coordinate p.  The
mesh takes the role of the communicator; XLA inserts the collectives
(SURVEY.md section 2, "Distributed communication backend").

Multi-host topologies (the ICI/DCN split): after
`acg_tpu.parallel.multihost.initialize` (the MPI_Init analog),
``jax.devices()`` is the *global* device list, so the default mesh below
already spans all hosts; array ingress/egress go through
``multihost.put_global`` / ``get_global``.  Validated by a 2-process
gloo-backed CPU smoke test (``tests/test_multihost.py``).
"""

from __future__ import annotations

import jax
import numpy as np
from jax.sharding import Mesh

PARTS_AXIS = "parts"


def solve_mesh(nparts: int | None = None, devices=None) -> Mesh:
    """A 1-D mesh of ``nparts`` devices with axis name ``parts``.

    With ``nparts`` greater than the device count this raises -- the
    reference equivalent is launching more MPI ranks than GPUs, which it
    also treats as a configuration error.

    Multi-controller with ``nparts`` below the global device count:
    devices are drawn round-robin across processes (not ``devices[:n]``,
    which would leave later hosts outside the mesh entirely), so every
    controller keeps at least one mesh device as long as
    ``nparts >= process_count``.  Below that there is no valid layout --
    the reference analog is launching MPI on fewer hosts, so we say so.
    """
    if devices is None:
        devices = jax.devices()
        if jax.process_count() > 1:
            by_proc: dict[int, list] = {}
            for d in devices:
                by_proc.setdefault(d.process_index, []).append(d)
            groups = [by_proc[p] for p in sorted(by_proc)]
            devices = [g[i] for i in range(max(map(len, groups)))
                       for g in groups if i < len(g)]
    if nparts is None:
        nparts = len(devices)
    if nparts > len(devices):
        raise ValueError(
            f"need {nparts} devices for {nparts} parts, have {len(devices)}")
    chosen = list(devices[:nparts])
    procs = {getattr(d, "process_index", 0) for d in chosen}
    import jax as _jax
    if len(procs) < _jax.process_count():
        raise ValueError(
            f"{nparts} parts cannot span all {_jax.process_count()} "
            f"controller processes; launch at most {nparts} controllers "
            f"(the MPI analog: fewer ranks than hosts)")
    return Mesh(np.array(chosen), (PARTS_AXIS,))


def shard_devices(tree) -> list[int]:
    """Sorted ids of the devices that hold a shard of the first array in
    ``tree`` -- where the data actually lives, read from the array's
    addressable shards rather than from the mesh it was meant for."""
    leaf = jax.tree.leaves(tree)[0]
    return sorted({int(sh.device.id) for sh in leaf.addressable_shards})
