"""What a capture says of each device op beyond its name and its time:
the ``tf_op`` (the op's ``op_name``, the ``jax.named_scope`` path, such
as ``jit(_cg_program)/while/body/acg.spmv/jit(dia_spmv)/pallas_call``)
and the ``hlo_category``, from each plane's event metadata.
``jax.profiler.ProfileData`` exposes only the events' own stats, so this
module reads the ``.xplane.pb`` itself, an ``XSpace`` message
(``tsl/profiler/protobuf/xplane.proto``), in the protobuf wire format:

    XSpace          planes = 1
    XPlane          name = 2, lines = 3, event_metadata = 4 (map id ->
                    XEventMetadata), stat_metadata = 5 (map id ->
                    XStatMetadata)
    XEventMetadata  name = 2 (the op's HLO text), display_name = 4,
                    stats = 5
    XStat           metadata_id = 1, str_value = 5, ref_value = 7
    XStatMetadata   name = 2

A map entry is a message of ``key = 1, value = 2``.  The event lines,
most of a capture's bytes, are skipped by their length.

    python3 -m benchmark.xspace <capture> [<run.json>]

prints, for each device plane, the device milliseconds of each
``acg.*`` layer in the solve loop and outside it, and the loop's and
set-up's kernel events (with the run record: per iteration)."""

from __future__ import annotations

import json
import re
import sys
from collections import defaultdict
from pathlib import Path

from benchmark import trace_reduce

_ACG = re.compile(r"acg\.([a-z]+)")
LOOP = "/while/body/"


def _varint(buf, pos: int) -> tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[pos]
        pos += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, pos
        shift += 7


def _fields(buf):
    """``(field number, value)`` of one message: an int for a varint, a
    memoryview for a length-delimited field, raw bytes otherwise."""
    pos, end = 0, len(buf)
    while pos < end:
        tag, pos = _varint(buf, pos)
        num, wire = tag >> 3, tag & 7
        if wire == 0:
            val, pos = _varint(buf, pos)
        elif wire == 2:
            n, pos = _varint(buf, pos)
            val, pos = buf[pos:pos + n], pos + n
        elif wire == 1:
            val, pos = bytes(buf[pos:pos + 8]), pos + 8
        elif wire == 5:
            val, pos = bytes(buf[pos:pos + 4]), pos + 4
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield num, val


def _map(buf) -> tuple[int, memoryview]:
    key, val = 0, memoryview(b"")
    for num, v in _fields(buf):
        if num == 1:
            key = v
        elif num == 2:
            val = v
    return key, val


def _plane(buf) -> tuple[str, dict[str, dict[str, str]]]:
    name, metas, stat_names = "", [], {}
    for num, v in _fields(buf):
        if num == 2:
            name = bytes(v).decode()
        elif num == 4:
            metas.append(_map(v)[1])
        elif num == 5:
            key, val = _map(v)
            for n2, v2 in _fields(val):
                if n2 == 2:
                    stat_names[key] = bytes(v2).decode()
    ops = {}
    for meta in metas:
        text, stats = "", {}
        for num, v in _fields(meta):
            if num == 2:
                text = bytes(v).decode(errors="replace")
            elif num == 5:
                sid = ref = None
                sval = ""
                for n2, v2 in _fields(v):
                    if n2 == 1:
                        sid = v2
                    elif n2 == 5:
                        sval = bytes(v2).decode(errors="replace")
                    elif n2 == 7:
                        ref = v2
                key = stat_names.get(sid)
                if key in ("tf_op", "hlo_category"):
                    stats[key] = stat_names.get(ref, "") if ref else sval
        if text:
            ops[trace_reduce.parse_op(text)[0]] = stats
    return name, ops


def op_stats(path: str) -> dict[str, dict[str, dict[str, str]]]:
    """``{device plane: {op name: {"tf_op": ..., "hlo_category": ...}}}``
    for every device plane (``/device:TPU:<i>``) of the capture at
    ``path`` (a file or a directory holding one).  The op name is the
    one ``trace_reduce`` gives its ``Op`` (``acg_dia_spmv.7``);
    ``tf_op`` loses the profiler's ``:<type>`` suffix, and an op that
    XLA added itself (a copy, an async slice) has none."""
    with open(trace_reduce.find_xplane(path), "rb") as f:
        data = memoryview(f.read())
    out = {}
    for num, v in _fields(data):
        if num != 1:
            continue
        name, ops = _plane(v)
        if name.startswith(trace_reduce.DEVICE_PREFIX):
            for stats in ops.values():
                if "tf_op" in stats:
                    stats["tf_op"] = stats["tf_op"].rsplit(":", 1)[0]
            out[name] = ops
    return out


def layer(tf_op: str) -> str:
    """The innermost ``acg.<layer>`` scope of an op (``spmv``, ``halo``,
    ``dot``, ``update``, ``setup``), or ``""`` outside them all."""
    found = _ACG.findall(tf_op)
    return found[-1] if found else ""


def in_loop(tf_op: str) -> bool:
    """Whether the op runs inside a solve program's loop body."""
    return LOOP in tf_op


def layer_seconds(trace, stats) -> dict[str, dict[str, float]]:
    """Device seconds in the window by ``"loop"``/``"out"`` and layer,
    for each device of the trace (``trace_reduce.load``'s), its ops
    tagged from ``stats`` (:func:`op_stats` of the same capture)."""
    out = {}
    for dev in trace.devices:
        tags = stats.get(dev.name, {})

        def key(o, tags=tags):
            tf = tags.get(o.name, {}).get("tf_op", "")
            return ("loop" if in_loop(tf) else "out") + ":" + layer(tf)

        out[dev.name] = trace_reduce.seconds_by(dev, trace.window, key)
    return out


def kernel_events(trace, stats) -> dict[str, dict[str, int]]:
    """Count of custom-call events in the window on each device (what
    ``trace_reduce`` classes as the SpMV), split by where the op sits:
    ``loop``, ``setup`` (the solve program's r0 = b - A x0), ``other``,
    or ``unnamed`` (no ``tf_op``: a call XLA added itself)."""
    out = {}
    for dev in trace.devices:
        tags = stats.get(dev.name, {})
        n = defaultdict(int)
        for o in dev.ops:
            if o.kind != "spmv" or not (trace.window[0] <= o.start
                                        and o.end <= trace.window[1]):
                continue
            tf = tags.get(o.name, {}).get("tf_op", "")
            n["loop" if in_loop(tf) else "setup" if "acg.setup" in tf
              else "other" if tf else "unnamed"] += 1
        out[dev.name] = dict(n)
    return out


def main(argv) -> int:
    path = argv[0]
    run = json.loads(Path(argv[1]).read_text()) if len(argv) > 1 else None
    its = sum(s["iterations"] for s in run["solves"]) if run else 0
    trace = trace_reduce.load(path)
    stats = op_stats(path)
    kernels = kernel_events(trace, stats)
    for dev, by in layer_seconds(trace, stats).items():
        print(f"{dev}: kernel events {kernels[dev]}")
        for k, v in sorted(by.items(), key=lambda kv: -kv[1]):
            per = f"  {1e3 * v / its:.4f} ms/iter" if its else ""
            print(f"  {k:16s} {1e3 * v:10.3f} ms{per}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
