"""The capture's op metadata (``xspace``) and the host-path readers
(``host_path``), on the chip-recorded captures in ``data/``: two made
before the program named its scopes and spans, and two made since
(``*.scoped.*``, by ``record_trace.py``: the 2D cell's path on a 256^2
grid on one chip, the four-chip cell's path on a 64^3 grid)."""

import pytest

from benchmark import host_path, spec, trace_reduce as tr, xspace
from benchmark.tests.test_trace_reduce import DATA, capture

OLD2, OLD4 = DATA / "poisson2d-2048.cg-256", DATA / "poisson3d-512.cg.4chip-64"
NEW2 = DATA / "poisson2d-2048.cg-256.scoped"
NEW4 = DATA / "poisson3d-512.cg.4chip-64.scoped"
STEMS = {"old2": OLD2, "old4": OLD4, "new2": NEW2, "new4": NEW4}


class Captures(dict):
    """``caps[key]``: ``(trace, run, op_stats)`` of a recorded capture,
    unpacked and read on first use."""

    def __init__(self, tmp):
        super().__init__()
        self.tmp = tmp

    def __missing__(self, key):
        stem = STEMS[key]
        trace, run = capture(stem, self.tmp)
        self[key] = (trace, run, xspace.op_stats(
            str(self.tmp / (stem.name + ".xplane.pb"))))
        return self[key]


@pytest.fixture(scope="module")
def caps(tmp_path_factory):
    return Captures(tmp_path_factory.mktemp("caps"))


def tf_ops(stats, dev, prefix):
    return {name: s.get("tf_op", "") for name, s in stats[dev].items()
            if name.startswith(prefix)}


def test_wire_format_reader_matches_profile_data(caps):
    """Every device op the trace reduction names has its metadata, and
    each op carries a tf_op and an hlo_category."""
    for key in STEMS:
        trace, _, stats = caps[key]
        assert set(stats) >= {d.name for d in trace.devices}, key
        for d in trace.devices:
            names = {o.name for o in d.ops}
            assert names <= set(stats[d.name]), (key, names - set(stats[d.name]))
            assert all(stats[d.name][n].get("hlo_category") for n in names)
            # every op of a solve program carries its op_name
            assert all(stats[d.name][n].get("tf_op") for n in names
                       if n.startswith(("multiply", "acg_dia_spmv",
                                        "dia_spmv", "all-reduce")))


@pytest.mark.parametrize("key", ["old2", "old4"])
def test_old_captures_name_the_kernel_by_its_jit(caps, key):
    """Before the scopes: the kernel's tf_op is its jit's, and no op
    carries an acg.* scope."""
    _, _, stats = caps[key]
    for dev in stats:
        kern = tf_ops(stats, dev, "dia_spmv")
        assert kern and all("jit(dia_spmv)/pallas_call" in t
                            for t in kern.values())
        assert not any(xspace.layer(s.get("tf_op", ""))
                       for s in stats[dev].values())


@pytest.mark.parametrize("key", ["new2", "new4"])
def test_new_captures_name_every_loop_op(caps, key):
    """Since the scopes: the kernel is named acg_dia_spmv and sits under
    acg.spmv, and every op of the loop body under an acg.* scope."""
    trace, _, stats = caps[key]
    for d in trace.devices:
        tags = {o.name: stats[d.name][o.name].get("tf_op", "")
                for o in d.ops}
        kern = tf_ops(stats, d.name, "acg_dia_spmv")
        assert kern and all(xspace.layer(t) == "spmv" for t in kern.values())
        # beside the kernel, XLA's own zero-length custom calls (two a
        # solve, outside the program's ops: no tf_op)
        assert all(o.end == o.start and not tags[o.name] for o in d.ops
                   if o.kind == "spmv" and o.name not in kern)
        loop = {n for n, t in tags.items() if xspace.in_loop(t)}
        unnamed = {n for n in loop if not xspace.layer(tags[n])}
        # the loop counter's increment is the one op outside the scopes
        assert all(tags[n].endswith("/add") for n in unnamed), unnamed


def test_four_chip_halo_is_named(caps):
    """The window concatenation and the permutes read acg.halo; the
    all-reduced dots acg.dot -- on the new four-chip capture only."""
    trace, _, stats = caps["new4"]
    for d in trace.devices:
        tags = {o.name: stats[d.name][o.name].get("tf_op", "")
                for o in d.ops}
        halo = {n for n, t in tags.items()
                if xspace.in_loop(t) and xspace.layer(t) == "halo"}
        assert any("concatenate" in tags[n] for n in halo), halo
        assert any(n.startswith("collective-permute-done") for n in halo)
        assert all(xspace.layer(tags[n]) == "dot" for n in tags
                   if n.startswith("all-reduce") and xspace.in_loop(tags[n]))
    old = caps["old4"][2]
    for dev in old:
        cat = tf_ops(old, dev, "pad_maximum_fusion")
        assert cat and all(t.endswith("shard_map/concatenate")
                           for t in cat.values())


@pytest.mark.parametrize("key", ["new2", "new4"])
def test_kernel_events_in_loop_and_setup(caps, key):
    """One kernel event per iteration in the loop, one per solve in
    set-up, none elsewhere."""
    trace, run, stats = caps[key]
    its = sum(s["iterations"] for s in run["solves"])
    for dev, n in xspace.kernel_events(trace, stats).items():
        assert n == {"loop": its, "setup": len(run["solves"])}, (dev, n)


@pytest.mark.parametrize("key,halo", [("new2", False), ("new4", True)])
def test_layer_seconds(caps, key, halo):
    """Device time by scope adds up to the busy ops' time; halo time in
    the loop exists on the four-chip capture only."""
    trace, run, stats = caps[key]
    by = xspace.layer_seconds(trace, stats)
    for d in trace.devices:
        total = sum(tr.seconds_by(d, trace.window, lambda o: 0).values())
        assert sum(by[d.name].values()) == pytest.approx(total)
        assert by[d.name]["loop:spmv"] > 0 and by[d.name]["loop:update"] > 0
        assert (by[d.name].get("loop:halo", 0.0) > 0) is halo


@pytest.mark.parametrize("key", ["new2", "new4"])
def test_host_readers_on_new_captures(caps, key):
    """host_ms and host_gap_ms read the program's spans: both positive,
    host_gap_ms within the window's idle time per solve on the idlest
    chip, and no span of a solve left unnamed by the breakdown's rule."""
    trace, run, _ = caps[key]
    n = len(run["solves"])
    gap = spec.reader("host_gap_ms").read(run, trace)
    host = spec.reader("host_ms").read(run, trace)
    assert gap == spec.reader("host_gap_ms.short").read(run, trace)
    assert host == spec.reader("host_ms.short").read(run, trace)
    idle = max(trace.window_s - tr.busy_seconds(d, trace.window)
               for d in trace.devices[:run["chips"]])
    assert 0 < gap <= 1e3 * idle / n
    assert 0 < host < 1e3 * trace.window_s / n
    names = {name for name, _, _ in trace.spans}
    assert set(host_path.HOST) | {host_path.WAIT, "acg:solve"} <= names
    assert "acg:compile" not in names


def test_host_gap_matches_the_loop_rule(caps):
    """host_gap_ms (idle outside acg:wait) against the rule by scopes:
    idle in gaps not bounded on both sides by loop-body ops.  They
    differ by the solve program's own gaps before and after its loop."""
    for key in ("new2", "new4"):
        trace, run, stats = caps[key]
        by_scope = []
        for d in trace.devices[:run["chips"]]:
            tags = stats[d.name]
            ops = sorted(((max(o.start, trace.window[0]),
                           min(o.end, trace.window[1]),
                           xspace.in_loop(tags[o.name].get("tf_op", "")))
                          for o in d.ops if o.end > trace.window[0]
                          and o.start < trace.window[1]))
            idle, cur, cur_loop = 0.0, trace.window[0], False
            for s, e, loop in ops:
                if s > cur and not (cur_loop and loop):
                    idle += s - cur
                if e >= cur:
                    cur, cur_loop = e, loop
            by_scope.append(idle + max(0.0, trace.window[1] - cur))
        rule = 1e3 * max(by_scope) / len(run["solves"])
        gap = host_path.host_gap_ms(run, trace)
        # the loop rule also counts the program's gaps around its loop
        assert gap <= rule <= 1.25 * gap, (key, gap, rule)


@pytest.mark.parametrize("key", ["old2", "old4"])
def test_host_readers_silent_before_the_spans(caps, key):
    trace, run, _ = caps[key]
    assert host_path.host_ms(run, trace) is None
    assert host_path.host_gap_ms(run, trace) is None


@pytest.mark.parametrize("key,metric,value", [
    ("old2", "iterations", 332.0),
    ("old2", "iter_ms", 0.03312932078313181),
    ("old2", "spmv_roofline", 78.84888504171737),
    ("old2", "spmv_ms.short", 0.0008118774131271409),
    ("old2", "update_ms", 0.0011632014845095995),
    ("old2", "collective_ms", None),
    ("old2", "idle_share", 94.0430984411993),
    ("old4", "iterations", 106.0),
    ("old4", "iter_ms", 0.16242466194968624),
    ("old4", "spmv_roofline", 39.93963129028899),
    ("old4", "update_ms", 0.0012942376834375882),
    ("old4", "collective_ms", 0.014514975366876674),
    ("old4", "idle_share", 89.32669509030639),
])
def test_existing_readers_read_as_before(caps, key, metric, value):
    """Every reader the benchmark had reads the same number from the
    same capture as it did before this module and the host readers."""
    trace, run, _ = caps[key]
    got = spec.reader(metric).read(run, trace)
    if value is None:
        assert got is None
    else:
        assert got == pytest.approx(value, rel=1e-12)

