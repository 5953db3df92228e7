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
@pytest.mark.parametrize("n,dim,parts,route", [
    pytest.param(2048, 2, 1, "fast", id="2048-2-fast"),
    pytest.param(512, 3, 1, "clustered", id="512-3-clustered"),
    pytest.param(512, 3, 4, "clustered", id="512-3-shard4-clustered"),
])
def test_dia_spmv_compiles(topo, tpu_compile, kernel, n, dim, parts, route):
    """The kernel on one chip's whole vector, and on one shard's window
    of the four-chip pallas-roll SpMV (a quarter of the rows plus its
    halos: 34,078,720 at 512^3), under the kernel's VMEM budget."""
    from acg_tpu.parallel.sharded_dia import _halo_sizes

    offsets = _stencil_offsets(n, dim)
    N = n ** dim // parts
    if parts > 1:
        N += sum(_halo_sizes(offsets, N))
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


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_tiledia_spmv_compiles(topo, tpu_compile, dtype):
    """The row-tiled DIA kernel on the largest local block of the 2D
    n=2048 file graph-partitioned into four: 1,048,577 rows, 9,209
    segments of 1,024 rows, offsets within +-2,046."""
    n, S, reach = 1048577, 9209, (-2046, 2046)
    assert pk.tiledia_fits(n, pk.TILEDIA_TILE, reach, dtype)
    one = SingleDeviceSharding(topo.devices[0])
    text = tpu_compile(
        lambda planes, off, tile, x: pk.tiledia_spmv(planes, off, tile, x,
                                                     reach),
        jax.ShapeDtypeStruct((S, pk.TILEDIA_TILE), dtype, sharding=one),
        jax.ShapeDtypeStruct((S,), jnp.int32, sharding=one),
        jax.ShapeDtypeStruct((S,), jnp.int32, sharding=one),
        jax.ShapeDtypeStruct((n,), dtype, sharding=one))
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


def _exchange_order(text: str) -> dict:
    """Per computation of a compiled program, its one-sided halo
    kernels (``acg_halo_dma``) and all-reduces in schedule order."""
    import re

    order, comp = {}, None
    for line in text.splitlines():
        if line and not line.startswith(" ") and line.rstrip().endswith("{"):
            comp = line.split(" ")[0].lstrip("%")
            continue
        m = re.match(r"\s*(?:ROOT )?%(\S+) = .*? (all-reduce|custom-call)\(",
                     line)
        if m and (m[2] == "all-reduce" or m[1].startswith("acg_halo_dma")):
            order.setdefault(comp, []).append(m[2])
    return order


@pytest.mark.parametrize("pipelined", [False, True])
def test_dma_exchanges_are_separated_by_a_reduction(topo, tpu_compile,
                                                    pipelined):
    """The mesh solve over ``--comm dma`` compiled for four v5e chips: in
    every computation of the program (set-up, loop bodies, the
    pipelined recurrence's residual replacement) no two one-sided halo
    kernels run back to back -- an all-reduce lies between each pair,
    so no part enters an exchange while a neighbour is still inside the
    previous one (halo_exchange_dma's ``after``).  A computation's first
    exchange follows the reduction or the loop boundary before it."""
    from jax.sharding import Mesh

    from acg_tpu.graph import partition_matrix
    from acg_tpu.io.generators import poisson_mtx
    from acg_tpu.matrix import SymCsrMatrix
    from acg_tpu.parallel.dist import DistCGSolver, DistributedProblem
    from acg_tpu.parallel.mesh import solve_mesh
    from acg_tpu.partition import partition_rows

    csr = SymCsrMatrix.from_mtx(poisson_mtx(64, dim=2)).to_csr()
    part = partition_rows(csr, 4, seed=0, method="graph", use_metis="never")
    prob = DistributedProblem.build(csr, part, 4, dtype=jnp.float32,
                                    subs=partition_matrix(csr, part, 4),
                                    vector_dtype=jnp.float32)
    b = np.ones(csr.shape[0], np.float32)
    # the argument shapes from a CPU mesh, the program for the chips
    dev = DistCGSolver(prob, comm="dma", pipelined=pipelined,
                       mesh=solve_mesh(4)).device_args(b, None)
    mesh = Mesh(np.asarray(topo.devices), (PARTS_AXIS,))
    solver = DistCGSolver(prob, comm="dma", pipelined=pipelined, mesh=mesh)
    sh = NamedSharding(mesh, P(PARTS_AXIS))
    rep = NamedSharding(mesh, P())
    b_, x0, la, ga, sidx, gsrc, gval, scnt, rcnt = (
        jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                                    sharding=sh), a)
        for a in dev)
    program = solver._program_for(None)

    def fn(*args):
        return program(*args, unbounded=False, needs_diff=False)

    text = tpu_compile(
        fn, la, ga, sidx, gsrc, gval, scnt, rcnt, b_, x0,
        jax.ShapeDtypeStruct((4,), jnp.float32, sharding=rep),
        jax.ShapeDtypeStruct((), jnp.int32, sharding=rep))
    order = _exchange_order(text)
    kernels = sum(ops.count("custom-call") for ops in order.values())
    assert kernels >= (7 if pipelined else 2)
    for comp, ops in order.items():
        pairs = zip(ops, ops[1:])
        assert ("custom-call", "custom-call") not in pairs, (comp, ops)
