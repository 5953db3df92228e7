"""Real-hardware probe of the DMA transport's primitives (round-4
verdict item 6): pin the Mosaic lowering of ``make_async_remote_copy``
+ barrier/DMA semaphores on an actual TPU chip, even with only one chip
available (self-puts: device_id = own index).

Result record (one TPU v5 lite, 2026-07-30, before the multi-chip
path existed on hardware):

1. ``halo_dma._exchange`` compiled at nparts=1 (barrier present, put
   loops empty): remote compile helper dies with SIGABRT -- a Mosaic
   crash on the degenerate kernel.  The library now short-circuits
   nparts==1 before reaching Pallas.
2. Self-put WITHOUT a barrier but with collective_id=0: JAX rejects --
   "collective_id has to be unspecified or None when not using a
   custom barrier".
3. Self-put WITH the barrier handshake (the transport's actual
   structure): COMPILES AND RUNS, payload bit-exact.  This is the
   first on-silicon execution of the put-with-signal path.  The
   multi-chip case runs in ``chip_smoke.py --four-chips``.

Run: ``python scripts/dma_probe.py`` (needs a real TPU; CPU runs
interpret mode and proves nothing).
"""

from __future__ import annotations

import sys

import numpy as np

ROOT = __file__.rsplit("/", 2)[0]
sys.path.insert(0, ROOT)


def main() -> int:
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    from jax.sharding import Mesh, PartitionSpec as P


    d = jax.devices()[0]
    print(f"# platform: {d.platform} {d.device_kind}", file=sys.stderr)
    if d.platform != "tpu":
        print("not a TPU; nothing to probe", file=sys.stderr)
        return 2
    mesh = Mesh(np.array(jax.devices()[:1]), ("parts",))

    def kernel(src_ref, dst_ref, send_sem, recv_sem):
        me = lax.axis_index("parts")
        barrier = pltpu.get_barrier_semaphore()
        pltpu.semaphore_signal(barrier, inc=1, device_id=me,
                               device_id_type=pltpu.DeviceIdType.LOGICAL)
        pltpu.semaphore_wait(barrier, 1)
        copy = pltpu.make_async_remote_copy(
            src_ref=src_ref, dst_ref=dst_ref, send_sem=send_sem,
            recv_sem=recv_sem, device_id=me,
            device_id_type=pltpu.DeviceIdType.LOGICAL)
        copy.start()
        copy.wait_send()
        copy.wait_recv()

    def selfput(x):
        return pl.pallas_call(
            kernel,
            out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            scratch_shapes=[pltpu.SemaphoreType.DMA(()),
                            pltpu.SemaphoreType.DMA(())],
            compiler_params=pltpu.CompilerParams(has_side_effects=True,
                                             collective_id=1),
            interpret=False)(x)

    f = jax.shard_map(selfput, mesh=mesh, in_specs=P("parts"),
                      out_specs=P("parts"), check_vma=False)
    x = jnp.arange(8 * 128, dtype=jnp.float32).reshape(1, 8, 128)
    out = jax.jit(f)(x)
    out.block_until_ready()
    ok = np.array_equal(np.asarray(out), np.asarray(x))
    print(f"barrier + self-put make_async_remote_copy: compiled and ran; "
          f"payload correct: {ok}")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
