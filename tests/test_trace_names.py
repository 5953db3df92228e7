"""The names a profiler capture carries from inside a solve.

Device side: the solve programs put their ops under the scopes
``acg.setup``, ``acg.spmv``, ``acg.halo``, ``acg.dot`` and ``acg.update``
(``jax.named_scope``), which land in each compiled op's ``op_name``
metadata.  Host side: ``JaxCGSolver.solve`` and ``DistCGSolver.solve``
bracket their steps in ``acg:*`` spans (``telemetry.span``) on the
profiler's clock."""

import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from acg_tpu.io.generators import poisson2d_coo
from acg_tpu.matrix import SymCsrMatrix
from acg_tpu.ops.spmv import DiaMatrix, device_matrix_from_csr
from acg_tpu.solvers.jax_cg import JaxCGSolver
from acg_tpu.solvers.stats import StoppingCriteria

BODY = "while/body/"


def op_names(compiled_text: str) -> set[str]:
    return set(re.findall(r'op_name="([^"]*)"', compiled_text))


def scopes_under(names, prefix: str) -> set[str]:
    """The ``acg.*`` scopes that follow ``prefix`` in any op_name."""
    out = set()
    for n in names:
        if prefix in n:
            out.update(re.findall(r"acg\.[a-z]+", n.partition(prefix)[2]))
    return out


@pytest.fixture(scope="module")
def dia():
    r, c, v, n = poisson2d_coo(16)
    csr = SymCsrMatrix.from_coo(n, r, c, v).to_csr()
    A = device_matrix_from_csr(csr, dtype=jnp.float32)
    assert isinstance(A, DiaMatrix)
    return A


@pytest.mark.parametrize("kind", ["classic", "pipelined"])
def test_loop_ops_carry_acg_scopes(dia, kind):
    """The compiled single-device program (DIA, interpreted Pallas
    kernel): the loop body's ops sit under acg.spmv, acg.dot and
    acg.update, and the set-up SpMV under acg.setup/acg.spmv."""
    from acg_tpu.solvers import jax_cg as jc

    program = jc._cg_pipelined_program if kind == "pipelined" \
        else jc._cg_program
    b = jnp.ones(dia.nrows, jnp.float32)
    z = jnp.float32(0.0)
    text = program.lower(
        dia, b, jnp.zeros_like(b), z, jnp.float32(1e-6), z, z,
        jnp.int32(20), unbounded=False, needs_diff=False,
        kernels="pallas-interpret").compile().as_text()
    names = op_names(text)
    assert {"acg.spmv", "acg.dot", "acg.update"} <= scopes_under(names, BODY)
    assert any("acg.setup/acg.spmv/" in n for n in names), sorted(names)
    assert "acg.halo" not in scopes_under(names, "jit(")


def test_pipelined_replacement_carries_acg_replace_scope(dia):
    """The f32 pipelined program replaces residuals: the conditional
    and the four SpMVs of its replacement branch sit under acg.replace
    inside the loop body, the SpMVs under acg.replace/acg.spmv."""
    from acg_tpu.solvers import jax_cg as jc

    b = jnp.ones(dia.nrows, jnp.float32)
    z = jnp.float32(0.0)
    names = op_names(jc._cg_pipelined_program.lower(
        dia, b, jnp.zeros_like(b), z, jnp.float32(1e-6), z, z,
        jnp.int32(20), unbounded=False, needs_diff=False,
        kernels="xla").compile().as_text())
    assert "acg.replace" in scopes_under(names, BODY)
    assert "acg.spmv" in scopes_under(names, "acg.replace/")
    assert "acg.replace" not in scopes_under(names, "acg.setup/")


def test_sharded_pallas_roll_carries_halo_scope():
    """The four-shard pallas-roll program (shard_map, ppermute halo,
    interpreted kernel per shard): the permutes and the window
    concatenation sit under acg.halo, the kernel under acg.spmv, the
    all-reduced dots under acg.dot -- in the loop and in set-up."""
    from acg_tpu.parallel.sharded_dia import build_sharded_poisson_solver

    solver = build_sharded_poisson_solver(16, 2, nparts=4,
                                          kernels="pallas-roll")
    assert solver.kernels.__class__.__name__ == "PallasRollSpmv"
    text = solver.lower_solve(
        solver.ones_b(), criteria=StoppingCriteria(
            maxits=20, residual_rtol=1e-6)).compile().as_text()
    names = op_names(text)
    assert {"acg.spmv", "acg.halo", "acg.dot", "acg.update"} <= \
        scopes_under(names, BODY)
    assert {"acg.spmv", "acg.halo"} <= scopes_under(names, "acg.setup/")
    halo = [n for n in names if BODY in n and "acg.halo" in n]
    assert any(n.endswith("/ppermute") for n in halo), halo
    assert any(n.endswith("/concatenate") for n in halo), halo


def capture_spans(tmp_path, solve) -> list[tuple[str, int, int]]:
    """``(name, start_ns, end_ns)`` of every ``acg:*`` host span that a
    profiler capture around ``solve()`` holds, in start order."""
    from jax.profiler import ProfileData

    with jax.profiler.trace(str(tmp_path)):
        solve()
    path = next(tmp_path.rglob("*.xplane.pb"))
    spans = []
    for plane in ProfileData.from_file(str(path)).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("acg:"):
                        spans.append((ev.name, ev.start_ns,
                                      ev.start_ns + ev.duration_ns))
    return sorted(spans, key=lambda s: (s[1], -s[2]))


@pytest.fixture(scope="module")
def warm_solver(dia):
    solver = JaxCGSolver(dia, kernels="xla")
    b = jnp.ones(dia.nrows, jnp.float32)
    crit = StoppingCriteria(maxits=200, residual_rtol=1e-6)
    solver.solve(b, criteria=crit, host_result=False)  # compiles
    return solver, b, crit


def test_solve_host_spans_in_order(tmp_path, warm_solver):
    """One solve's host spans: prepare (holding transfer), then
    dispatch and wait inside solve, then fetch, check and account."""
    solver, b, crit = warm_solver
    spans = capture_spans(tmp_path, lambda: solver.solve(
        b, criteria=crit, host_result=False))
    first = {}
    for name, s, e in spans:
        first.setdefault(name, (s, e))
    order = ["acg:prepare", "acg:solve", "acg:fetch", "acg:check",
             "acg:account"]
    assert set(order) | {"acg:transfer", "acg:dispatch", "acg:wait"} == \
        set(first), sorted(first)
    starts = [first[n][0] for n in order]
    assert starts == sorted(starts)

    def inside(inner, outer):
        return (first[outer][0] <= first[inner][0]
                and first[inner][1] <= first[outer][1])

    assert inside("acg:transfer", "acg:prepare")
    assert inside("acg:dispatch", "acg:solve")
    assert inside("acg:wait", "acg:solve")
    assert first["acg:dispatch"][0] <= first["acg:wait"][0]
    assert first["acg:solve"][1] <= first["acg:fetch"][0]


def test_host_result_fetches_x_and_skips_device_check(tmp_path,
                                                      warm_solver):
    """With x copied to the host, the finiteness report reads that copy:
    no device scan, so no acg:check span."""
    solver, b, crit = warm_solver
    names = {n for n, _, _ in capture_spans(
        tmp_path, lambda: solver.solve(b, criteria=crit))}
    assert {"acg:fetch", "acg:account"} <= names
    assert "acg:check" not in names


@pytest.mark.parametrize("warmup,ncompile", [(0, 0), (1, 1)])
def test_compile_span_only_with_warmup(tmp_path, warm_solver, warmup,
                                       ncompile):
    """acg:compile brackets warm-up solves, and with warmup=0 none is
    opened."""
    solver, b, crit = warm_solver
    spans = capture_spans(tmp_path, lambda: solver.solve(
        b, criteria=crit, host_result=False, warmup=warmup))
    assert sum(n == "acg:compile" for n, _, _ in spans) == ncompile
    assert sum(n == "acg:solve" for n, _, _ in spans) == 1


@pytest.fixture(scope="module")
def mesh_prob():
    """A 16x16 Poisson grid graph-partitioned into four parts (the
    built-in bisection)."""
    from acg_tpu.graph import partition_matrix
    from acg_tpu.parallel.dist import DistributedProblem
    from acg_tpu.partition import partition_rows

    r, c, v, n = poisson2d_coo(16)
    csr = SymCsrMatrix.from_coo(n, r, c, v).to_csr()
    part = partition_rows(csr, 4, method="graph", use_metis="never")
    return DistributedProblem.build(
        csr, part, 4, dtype=jnp.float32,
        subs=partition_matrix(csr, part, 4), vector_dtype=jnp.float32)


def _mesh_solver(prob, pipelined=False):
    from acg_tpu.parallel.dist import DistCGSolver
    from acg_tpu.parallel.mesh import solve_mesh

    return DistCGSolver(prob, comm="xla", mesh=solve_mesh(4),
                        pipelined=pipelined)


@pytest.fixture(scope="module")
def mesh_solver(mesh_prob):
    """``DistCGSolver`` over four devices of the CPU mesh, the
    ``all_to_all`` halo; warmed so the captures hold no compile."""
    solver = _mesh_solver(mesh_prob)
    b = np.ones(mesh_prob.n)
    crit = StoppingCriteria(maxits=200, residual_rtol=1e-6)
    solver.solve(b, criteria=crit, host_result=False)
    return solver, b, crit


@pytest.mark.parametrize("pipelined", [False, True])
def test_mesh_loop_ops_carry_acg_scopes(mesh_prob, pipelined):
    """The four-part classic and pipelined programs (shard_map,
    all_to_all halo): the loop body's ops sit under acg.spmv, acg.halo,
    acg.dot and acg.update, and the set-up residual under acg.setup."""
    solver = _mesh_solver(mesh_prob, pipelined)
    b = np.ones(mesh_prob.n)
    crit = StoppingCriteria(maxits=200, residual_rtol=1e-6)
    names = op_names(solver.lower_solve(b, criteria=crit)
                     .compile().as_text())
    assert {"acg.spmv", "acg.halo", "acg.dot", "acg.update"} <= \
        scopes_under(names, BODY)
    assert {"acg.spmv", "acg.halo"} <= scopes_under(names, "acg.setup/")
    # the pre-loop norms' all-reduces sit under acg.setup itself
    assert any("acg.setup/" in n and BODY not in n and "psum" in n
               for n in names), sorted(names)
    halo = [n for n in names if BODY in n and "acg.halo" in n]
    assert any(n.endswith("/all_to_all") for n in halo), halo


def test_dma_mesh_pipelined_program_names_its_halo_puts(mesh_prob):
    """The four-part pipelined program over the one-sided DMA halo
    (interpreted on the CPU mesh): the puts are the ``acg_halo_dma``
    kernel under acg.halo in the loop, and the replacement branch runs
    its SpMVs, halo included, under acg.replace."""
    from acg_tpu.parallel.dist import DistCGSolver
    from acg_tpu.parallel.mesh import solve_mesh

    solver = DistCGSolver(mesh_prob, comm="dma", mesh=solve_mesh(4),
                          pipelined=True)
    names = op_names(solver.lower_solve(
        np.ones(mesh_prob.n), criteria=StoppingCriteria(
            maxits=200, residual_rtol=1e-6)).compile().as_text())
    assert {"acg.halo", "acg.replace"} <= scopes_under(names, BODY)
    halo = [n.partition(BODY)[2] for n in names if BODY in n]
    assert any(n.startswith("acg.halo/") and "/acg_halo_dma/" in n
               for n in halo), sorted(halo)
    assert any(n.startswith("acg.replace/") and "/acg.halo/" in n
               and "/acg_halo_dma/" in n for n in halo), sorted(halo)


@pytest.mark.parametrize("host_result", [False, True])
def test_mesh_solve_host_spans_in_order(tmp_path, mesh_solver, host_result):
    """``DistCGSolver.solve`` brackets its steps in the spans of
    ``JaxCGSolver.solve``: prepare (holding transfer), dispatch and
    wait inside solve, then fetch, check (the finiteness scans, or the
    gather of x with ``host_result``) and account."""
    solver, b, crit = mesh_solver
    spans = capture_spans(tmp_path, lambda: solver.solve(
        b, criteria=crit, host_result=host_result))
    first = {}
    for name, s, e in spans:
        first.setdefault(name, (s, e))
    order = ["acg:prepare", "acg:solve", "acg:fetch", "acg:check",
             "acg:account"]
    assert set(order) | {"acg:transfer", "acg:dispatch", "acg:wait"} == \
        set(first), sorted(first)
    starts = [first[n][0] for n in order]
    assert starts == sorted(starts)

    def inside(inner, outer):
        return (first[outer][0] <= first[inner][0]
                and first[inner][1] <= first[outer][1])

    assert inside("acg:transfer", "acg:prepare")
    assert inside("acg:dispatch", "acg:solve")
    assert inside("acg:wait", "acg:solve")
    assert first["acg:solve"][1] <= first["acg:fetch"][0]


def test_mesh_solver_places_operator_once(tmp_path, mesh_prob):
    """A fresh ``DistCGSolver``'s first solve places the operator under
    one ``acg:place`` span inside ``acg:prepare`` and before
    ``acg:transfer``; the next solve holds no ``acg:place``."""
    solver = _mesh_solver(mesh_prob)
    b = np.ones(mesh_prob.n)
    crit = StoppingCriteria(maxits=200, residual_rtol=1e-6)
    first = capture_spans(tmp_path / "first", lambda: solver.solve(
        b, criteria=crit, host_result=False))
    place = [(s, e) for name, s, e in first if name == "acg:place"]
    assert len(place) == 1, first
    (ps, pe), = place
    prep = [(s, e) for name, s, e in first if name == "acg:prepare"][0]
    xfer = [(s, e) for name, s, e in first if name == "acg:transfer"][0]
    assert prep[0] <= ps and pe <= prep[1]
    assert pe <= xfer[0]
    again = capture_spans(tmp_path / "again", lambda: solver.solve(
        b, criteria=crit, host_result=False))
    assert "acg:place" not in {name for name, _, _ in again}
    assert solver.stats.operator_placements == 1


def test_spans_keep_the_status_phase(warm_solver):
    """The solve's sub-spans do not move the status document's phase:
    only pipeline phases (transfer, solve) reach the observatory."""
    from acg_tpu import observatory, telemetry

    seen = []
    real = observatory.note_phase
    observatory.note_phase = seen.append
    try:
        solver, b, crit = warm_solver
        solver.solve(b, criteria=crit, host_result=False)
        with telemetry.span("fetch"):
            pass
    finally:
        observatory.note_phase = real
    assert seen == ["transfer", "solve"]
