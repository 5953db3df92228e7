"""Set-up of upstream's pipelined run of a Matrix Market input, as
``acg-tpu FILE.mtx --solver acg-pipelined --dtype f32
--manufactured-solution`` does it on one chip.

The file, the host CSR, the device format and the manufactured systems
are ``mtx_file``'s; only the solver differs: the Ghysels-Vanroose
recurrence (``algorithm="pipelined"``) in place of classic CG.

The configuration runs the recurrence with its residual replacement
(``acg_tpu.recurrence.replaces``); a program without it cannot reach
the configuration's answer in f32, and set-up stops there."""

from __future__ import annotations

from pathlib import Path

from benchmark import spec


def build(config: dict, traffic: dict, *, cache, dtype=None, kernels=None):
    """``(solver, rhs)`` as ``mtx_file`` gives them, with the pipelined
    solver.  ``dtype`` and ``kernels`` override the configuration's
    precision and the ``auto`` kernel tier (the control runs the
    program's own bf16 path)."""
    try:
        from acg_tpu.recurrence import replaces  # noqa: F401
    except ImportError:
        raise SystemExit("benchmark: this configuration runs pipelined CG "
                         "with residual replacement "
                         "(acg_tpu.recurrence.replaces), which this "
                         "program lacks") from None
    from acg_tpu.solvers.jax_cg import JaxCGSolver

    mtx_file = spec.module(Path(__file__).with_name("mtx_file.py"))
    classic, rhs = mtx_file.build(config, traffic, cache=cache, dtype=dtype,
                                  kernels=kernels)
    return JaxCGSolver(classic.A, kernels=kernels or "auto",
                       host_matrix=classic.host_matrix,
                       algorithm="pipelined"), rhs
