"""The pipelined cell's reader ``spmv_calls`` (read as its twin
``spmv_calls.short``) on made-up reduced traces: what it counts, what it
leaves out, and that it reads nothing where its ops are absent."""

import pytest

from benchmark import spec, trace_reduce as tr


def trace(*devices, window=(0.0, 10.0)):
    return tr.Trace([tr.Device(f"/device:TPU:{i}",
                               [tr.Op(n, s, e, k) for n, s, e, k in ops])
                     for i, ops in enumerate(devices)], [], window)


def run(iterations, chips=1):
    return {"chips": chips,
            "solves": [{"iterations": k} for k in iterations]}


def spmv(name, t):
    return (name, t, t + 0.1, "spmv")


@pytest.mark.parametrize("name", ["spmv_calls", "spmv_calls.short"])
def test_spmv_calls_counts_kernel_events_over_iterations(name):
    # two solves of 10 iterations: 2 set-up SpMVs and 10 loop SpMVs
    # each, one replacement (4 SpMVs) in the second
    ops = [spmv("acg_dia_spmv.7", 0.2 * i) for i in range(2 * 12 + 4)]
    ops.append(("multiply_add_fusion.3", 9.0, 9.1, "other"))
    got = spec.reader(name).read(run([10, 10]), trace(ops))
    assert got == pytest.approx(28 / 20)


def test_spmv_calls_reads_the_busiest_chip_inside_the_window():
    ops = [spmv("acg_tiledia_spmv.6", 0.2 * i) for i in range(12)]
    ops.append(spmv("acg_tiledia_spmv.6", 11.0))     # after the window
    busy = ops + [spmv("acg_tiledia_spmv.6", 5.0 + 0.2 * i)
                  for i in range(4)]
    got = spec.reader("spmv_calls").read(run([10], chips=2),
                                         trace(ops, busy))
    # the busiest chip: 12 + 4 kernel calls over 10 iterations
    assert got == pytest.approx(1.6)


def test_spmv_calls_reads_nothing_without_kernels_or_iterations():
    read = spec.reader("spmv_calls").read
    assert read(run([10]), None) is None
    assert read(run([0]), trace([spmv("acg_dia_spmv.7", 0.0)])) is None
    assert read(run([10]), trace([("fusion.1", 0.0, 1.0, "other")])) is None
