"""Compile-only checks of the main path's Pallas kernels for a TPU v5e.

Nothing runs: each case lowers a kernel at the width the chip smoke
(``chip_smoke.py``) drives and compiles it for a described, unattached
``v5e:2x2`` topology with the installed TPU compiler -- which refuses
what interpret mode accepts (DMA slices not aligned to the tiling, too
much scoped VMEM).  Every case asserts that a Mosaic kernel
(``tpu_custom_call``) is in the compiled program.

The topology is described inside a module-scoped fixture (never at
import time): only one process may load the TPU library, and it keeps
it until it exits.  Compiles run with x64 off (``tests/conftest.py``
turns it on; compiled Pallas needs it off) and with the persistent
compilation cache off (a compile for a described chip cannot be read
back without one).
"""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from acg_tpu.ops import pallas_kernels as pk
from acg_tpu.parallel.mesh import PARTS_AXIS


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 -- no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture
def tpu_compile(topo):
    """``compile(fn, *shapes)``: the compiled text of ``jax.jit(fn)`` for
    the described chip, with x64 and the persistent cache off."""
    from jax.experimental.compilation_cache import compilation_cache

    x64 = jax.config.jax_enable_x64
    cache = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_x64", False)
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()

    def compile_(fn, *shapes):
        return jax.jit(fn).lower(*shapes).compile().as_text()

    yield compile_
    jax.config.update("jax_enable_x64", x64)
    jax.config.update("jax_enable_compilation_cache", cache)
    compilation_cache.reset_cache()


def _stencil_offsets(n: int, dim: int) -> tuple:
    return tuple(sorted([0] + [s * n ** a for a in range(dim)
                               for s in (-1, 1)]))


@pytest.mark.parametrize("kernel", ["dia_spmv", "dia_spmv_dot"])
@pytest.mark.parametrize("n,dim,route", [(2048, 2, "fast"),
                                         (512, 3, "clustered")])
def test_dia_spmv_compiles(topo, tpu_compile, kernel, n, dim, route):
    N = n ** dim
    offsets = _stencil_offsets(n, dim)
    assert pk.dia_spmv_route(offsets, N, jnp.float32)[0] == route
    one = SingleDeviceSharding(topo.devices[0])
    vec = jax.ShapeDtypeStruct((N,), jnp.float32, sharding=one)
    fn = getattr(pk, kernel)
    text = tpu_compile(lambda planes, x: fn(planes, offsets, x),
                       (vec,) * len(offsets), vec)
    assert "tpu_custom_call" in text


def test_cg_phase_a_compiles(topo, tpu_compile):
    n = 2048
    N = n * n
    offsets = _stencil_offsets(n, 2)
    one = SingleDeviceSharding(topo.devices[0])
    vec = jax.ShapeDtypeStruct((N,), jnp.float32, sharding=one)
    scal = jax.ShapeDtypeStruct((), jnp.float32, sharding=one)
    text = tpu_compile(
        lambda planes, r, p, g, g0: pk.cg_phase_a(planes, offsets, r, p,
                                                  g, g0),
        (vec,) * len(offsets), vec, vec, scal, scal)
    assert "tpu_custom_call" in text


def test_fused_pipelined_update_compiles(topo, tpu_compile):
    N = 2048 * 2048
    one = SingleDeviceSharding(topo.devices[0])
    vec = jax.ShapeDtypeStruct((N,), jnp.float32, sharding=one)
    scal = jax.ShapeDtypeStruct((), jnp.float32, sharding=one)
    text = tpu_compile(pk.fused_pipelined_update, *(vec,) * 7, scal, scal)
    assert "tpu_custom_call" in text


def test_pallas_roll_spmv_compiles_on_four_chips(topo, tpu_compile):
    """The sharded 512^3 SpMV: the per-shard kernel plus its ppermute
    halo, over a described 4-chip mesh."""
    from acg_tpu.ops.spmv import DiaMatrix
    from acg_tpu.parallel.sharded_dia import PallasRollSpmv, _halo_sizes

    n = 512
    N = n ** 3
    offsets = _stencil_offsets(n, 3)
    mesh = Mesh(np.asarray(topo.devices), (PARTS_AXIS,))
    nloc = N // 4
    Lh, Rh = _halo_sizes(offsets, nloc)
    sh = NamedSharding(mesh, P(PARTS_AXIS))
    plane = jax.ShapeDtypeStruct((4 * (Lh + nloc + Rh),), jnp.float32,
                                 sharding=sh)
    vec = jax.ShapeDtypeStruct((N,), jnp.float32, sharding=sh)
    spmv = PallasRollSpmv(mesh, nloc, Lh, Rh, offsets)
    text = tpu_compile(
        lambda planes, x: spmv(DiaMatrix(data=planes, offsets=offsets,
                                         nrows=N, ncols_padded=N), x),
        (plane,) * len(offsets), vec)
    assert "tpu_custom_call" in text
    assert "collective-permute" in text


@pytest.mark.parametrize("maxcnt", [7, 1000])
def test_dma_exchange_compiles_on_four_chips(topo, tpu_compile, maxcnt):
    """The one-sided halo exchange (``--comm dma``) over four chips,
    at window widths below and off the 128-lane tile."""
    from acg_tpu.parallel.halo_dma import _exchange

    mesh = Mesh(np.asarray(topo.devices), (PARTS_AXIS,))
    sh = NamedSharding(mesh, P(PARTS_AXIS))

    def body(sendbuf, scnt, rcnt):
        return _exchange(sendbuf[0], scnt[0], rcnt[0], PARTS_AXIS,
                         False)[None]

    fn = jax.shard_map(body, mesh=mesh, in_specs=(P(PARTS_AXIS),) * 3,
                       out_specs=P(PARTS_AXIS), check_vma=False)
    text = tpu_compile(
        fn, jax.ShapeDtypeStruct((4, 4, maxcnt), jnp.float32, sharding=sh),
        *(jax.ShapeDtypeStruct((4, 4), jnp.int32, sharding=sh),) * 2)
    assert "tpu_custom_call" in text
