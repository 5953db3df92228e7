#!/usr/bin/env python3
"""Bring-up smoke of the acg-tpu CG solver on TPU chips.

``python chip_smoke.py`` (one chip) drives the ``acg-tpu`` CLI
(:func:`acg_tpu.cli.main`) through four phases at sizes users call real,
all ``--dtype f32 --manufactured-solution``:

1. ``gen:poisson3d:512`` (134M unknowns, DIA planes assembled on the
   device), classic CG, 1000 fixed iterations: ``--kernels auto`` must
   resolve to the compiled ``pallas`` tier, and its residual and error
   norms must agree with the same solve under ``--kernels xla``.
2. The same matrix under ``--solver acg-pipelined``, same checks.
3. A 128^3 Matrix Market file (2,097,152 rows) written by
   ``acg_tpu.tools.genmatrix``, solved to ``--residual-rtol 1e-6``
   through the file path with the native parser, against the f64
   ``--solver host-native`` oracle on the same file.
4. ``gen:irregular:500000`` (the gather / binned-ELL path) to
   ``--residual-rtol 1e-6``.

``python chip_smoke.py --four-chips`` runs only the multi-chip paths,
each against its one-chip control on device 0: the sharded 512^3 solve
(``--nparts 4``) under ``--kernels auto`` (pallas-roll) and ``xla``, and
the graph-partitioned 128^3 file under ``--comm xla`` and ``--comm dma``.
Each must hold a shard on four distinct devices, and the ``dma`` run
must report the ``dma`` transport.

Every phase runs in this one process, one after another, and reads its
results back from its ``--stats-json`` document.  Per phase a line gives
the device kind, the kernel tier and SpMV route taken, iterations,
residual and error norms, and compile and solve seconds (compile = the
one warmup solve minus the timed solve).  The last line of standard
output is ``{"ok": true, "device": {...}}``, printed only when every
phase passed; with no TPU the script fails at once.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import shutil
import sys

ROOT = os.path.dirname(os.path.abspath(__file__))
WORK = os.path.join(ROOT, ".chip_smoke")  # made and removed by main()

# agreement of two f32 solves of one system that differ only in kernels
# or layout (summation order): norms within F32_RTOL of each other, or
# both within a factor FLOOR_BAND of each other below the recurrence's
# f32 attainable-accuracy floor (a fraction of the initial norm), where
# rounding noise sets the digits.  Measured on v5e, 512^3 after 1000
# iterations, errors of a unit-norm xsol: classic 2.60e-6 / 2.48e-6
# (pallas / xla), pipelined 2.13e-2 / 1.38e-2 -- pipelined CG's residual
# gap in f32 (the same 1.6e-2 plateau shows at 96^3 on the CPU, and
# f64 reaches 5e-11 there).
F32_RTOL = 1e-2
F32_FLOOR = {"acg": 1e-5, "acg-pipelined": 1e-1}
FLOOR_BAND = 4.0


class PhaseFailed(Exception):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise PhaseFailed(what)


def solve(name: str, matrix: str, *flags: str) -> dict:
    """One ``acg-tpu`` CLI run in this process; returns the manifest and
    stats of its ``--stats-json`` document."""
    from acg_tpu import cli

    gc.collect()  # the previous phase's device arrays go before this one's
    path = os.path.join(WORK, f"{name}.json")
    argv = [matrix, "--quiet", "--warmup", "1", "--stats-json", path,
            *flags]
    try:
        rc = cli.main(argv)
    except SystemExit as e:
        raise PhaseFailed(f"{name}: acg-tpu exited: {e}") from None
    check(rc == 0, f"{name}: acg-tpu returned {rc}")
    with open(path) as f:
        doc = json.load(f)
    run = {**doc["manifest"], **doc["stats"], "name": name}
    run["rnrm2"] = float(run["rnrm2"])  # non-finite values come as text
    run["solve_s"] = run["tsolve"]
    run["compile_s"] = max(run["timings"].get("compile", 0.0)
                           - run["solve_s"], 0.0)
    err = run.get("error_2norm") or {}
    print(f"{name}: device={run['backend']['device_kind']} "
          f"kernels={run.get('kernels')} route={run.get('spmv_route')} "
          f"transport={run.get('transport')} "
          f"devices={run.get('devices')} "
          f"iterations={run['niterations']} "
          f"residual={run['rnrm2']:.6e} error={err.get('final', 0):.6e} "
          f"compile_s={run['compile_s']:.3f} "
          f"solve_s={run['solve_s']:.3f}", flush=True)
    check(run["backend"]["platform"] == "tpu",
          f"{name}: ran on {run['backend']['platform']}")
    check(err.get("final") is not None and err["final"] < err["initial"],
          f"{name}: error norm {err} did not fall")
    return run


def close(a: float, b: float, initial: float, floor: float) -> bool:
    hi, lo = max(abs(a), abs(b)), min(abs(a), abs(b))
    return (hi - lo <= F32_RTOL * hi
            or (hi <= floor * initial and hi <= FLOOR_BAND * lo))


def agree(run: dict, ref: dict, *, same_iterations: bool) -> None:
    """``run`` against a reference solve of the same system: equal
    iteration count and f32-close norms for a fixed-iteration pair, a
    few iterations apart and errors of the same order for a pair solved
    to a tolerance."""
    what = f"{run['name']} vs {ref['name']}"
    it, it0 = run["niterations"], ref["niterations"]
    e, e0 = run["error_2norm"]["final"], ref["error_2norm"]["final"]
    if same_iterations:
        check(it == it0, f"{what}: iterations {it} != {it0}")
        floor = F32_FLOOR[ref["solver"]]
        check(close(run["rnrm2"], ref["rnrm2"], ref["r0nrm2"], floor),
              f"{what}: residual {run['rnrm2']:.6e} vs {ref['rnrm2']:.6e}")
        check(close(e, e0, ref["error_2norm"]["initial"], floor),
              f"{what}: error {e:.6e} vs {e0:.6e}")
    else:
        check(abs(it - it0) <= max(3, 0.03 * it0),
              f"{what}: iterations {it} vs {it0}")
        check(0.1 <= e / e0 <= 10.0, f"{what}: error {e:.6e} vs {e0:.6e}")


def tier(run: dict, kernels: str, route_prefix: str = "") -> None:
    check(run.get("kernels") == kernels,
          f"{run['name']}: kernels {run.get('kernels')} != {kernels}")
    check(str(run.get("spmv_route")).startswith(route_prefix),
          f"{run['name']}: route {run.get('spmv_route')}")


def write_poisson_file() -> str:
    from acg_tpu.tools import genmatrix

    path = os.path.join(WORK, f"poisson3d_n{FILE_N}.mtx")
    check(genmatrix.main(["-n", str(FILE_N), "--dim", "3", "-o", path])
          == 0, "genmatrix failed")
    return path


# the configurations (module constants, so a rehearsal can shrink them)
BIG = "gen:poisson3d:512"
FILE_N = 128
IRREGULAR = "gen:irregular:500000"
FIXED = ("--dtype", "f32", "--manufactured-solution",
         "--max-iterations", "1000", "--residual-rtol", "0")
TOL = ("--dtype", "f32", "--manufactured-solution",
       "--max-iterations", "20000", "--residual-rtol", "1e-6")


def one_chip() -> None:
    for solver in ("acg", "acg-pipelined"):
        fast = solve(f"{solver}-stencil-auto", BIG, "--solver", solver,
                     "--nparts", "1", *FIXED)
        tier(fast, "pallas", "dia-")
        ref = solve(f"{solver}-stencil-xla", BIG, "--solver", solver,
                    "--nparts", "1", "--kernels", "xla", *FIXED)
        tier(ref, "xla-roll")
        agree(fast, ref, same_iterations=True)

    mtx = write_poisson_file()
    dev = solve("acg-file", mtx, "--nparts", "1", *TOL)
    check(dev.get("native") is True, "native host core was not used")
    check(dev["converged"], "acg-file did not converge")
    # the f64 oracle runs last: the device phases keep x64 off
    host = solve("host-native-file", mtx, "--solver", "host-native",
                 "--dtype", "f64", "--manufactured-solution",
                 "--max-iterations", "20000", "--residual-rtol", "1e-6")
    agree(dev, host, same_iterations=False)

    irr = solve("acg-irregular", IRREGULAR, "--nparts", "1", *TOL)
    check(irr["converged"], "acg-irregular did not converge")


def four_chips() -> None:
    ref = solve("acg-stencil-1chip", BIG, "--nparts", "1", *FIXED)
    for kernels, expect in (("auto", "pallas-roll"), ("xla", "xla-roll")):
        run = solve(f"acg-stencil-4chips-{kernels}", BIG, "--nparts", "4",
                    "--kernels", kernels, *FIXED)
        tier(run, expect)
        check(run.get("devices") == [0, 1, 2, 3],
              f"{run['name']}: shards on devices {run.get('devices')}")
        agree(run, ref, same_iterations=True)

    mtx = write_poisson_file()
    ref = solve("acg-file-1chip", mtx, "--nparts", "1", *TOL)
    for comm in ("xla", "dma"):
        run = solve(f"acg-file-4chips-{comm}", mtx, "--nparts", "4",
                    "--partition-method", "graph", "--comm", comm, *TOL)
        check(run["converged"], f"{run['name']} did not converge")
        check(run.get("transport") == comm,
              f"{run['name']}: transport {run.get('transport')}")
        check(run.get("devices") == [0, 1, 2, 3],
              f"{run['name']}: shards on devices {run.get('devices')}")
        agree(run, ref, same_iterations=False)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run the four-chip paths and their one-chip "
                         "controls only")
    args = ap.parse_args(argv)

    sys.path.insert(0, ROOT)
    import jax

    from acg_tpu._platform import enable_compile_cache

    devices = jax.devices()
    d = devices[0]
    if d.platform != "tpu":
        print(f"chip_smoke: no TPU (platform {d.platform}); nothing run",
              file=sys.stderr)
        return 2
    need = 4 if args.four_chips else 1
    if len(devices) < need:
        print(f"chip_smoke: needs {need} chips, found {len(devices)}",
              file=sys.stderr)
        return 2
    print(f"device: {d.device_kind} x{len(devices)}; compile cache: "
          f"{enable_compile_cache()}", flush=True)
    shutil.rmtree(WORK, ignore_errors=True)
    os.makedirs(WORK)
    try:
        (four_chips if args.four_chips else one_chip)()
    except PhaseFailed as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    finally:
        shutil.rmtree(WORK, ignore_errors=True)
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
