"""The program's own names in a JAX profiler trace: the layer of every device
operation, from the ``jax.named_scope`` in its ``op_name``, and the
program's ``osafl.*`` host spans.

The trace's "XLA Ops" events carry an operation's HLO text but not its
metadata. Its ``/host:metadata`` plane keeps each program's HLO as a
serialized ``HloProto``, keyed by the program id that the "XLA Modules" line
appends to the program's name in parentheses. ``load`` reads those protos
(``_fields`` walks the protobuf wire format; nothing beyond JAX is needed),
maps each instruction name to its ``op_name``, and gives each operation the
layer of the program execution it ran in:

* ``devices``: per TPU device, ``[start_ns, duration_ns, name, layer,
  program]``, ``name`` as ``trace.load`` keeps it; ``layer`` is the
  outermost of ``LAYERS`` among the components of the ``op_name``, else
  ``"unscoped"`` (layout copies XLA inserts, operations run eagerly outside
  any jit); ``program`` the name of the program the operation ran in;
* ``modules``: per TPU device, ``[start_ns, duration_ns, name, program]``;
* ``spans``: the program's ``osafl.*`` host spans and the benchmark's own
  (``trace.SPANS``) as ``[name, start_ns, duration_ns, step]``, ``step`` the
  round index an ``osafl.round`` span carries (``step_num``), else None.

``layer_own_times`` sums own time by layer with ``trace.own_times``; the
layers and ``unscoped`` partition the window's own time exactly.
``span_label`` names an idle gap by the innermost span covering it.

    python3 -m bench.yardstick.layers <trace.xplane.pb>

prints, per round over the window from the first ``osafl.round`` span to
the end of the last (else ``trace.window``, one round a ``segment``), the
own time of each layer, the largest unscoped operations with their
programs, the mean host time of each span and the longest idle gaps with
their labels.
"""
from __future__ import annotations

import re
import sys

from bench.yardstick import trace as tz

LAYERS = ("request_gen", "fifo_commit", "local_sgd", "aggregate", "eval",
          "resource_solve")
UNSCOPED = "unscoped"
SPAN_PREFIX = "osafl."
_PROGRAM = re.compile(r"^(.*)\((\d+)\)$")
_WRAPPER = re.compile(r"^(?:[\w.]+\()+|\)+$")


def _varint(buf: bytes, i: int) -> tuple:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf: bytes):
    """(field number, value) of one protobuf message: an int for varint and
    fixed-width fields, the bytes of a length-delimited one."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        num, wire = key >> 3, key & 7
        if wire == 0:
            val, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            val, i = buf[i:i + size], i + size
        elif wire == 1:
            val, i = int.from_bytes(buf[i:i + 8], "little"), i + 8
        elif wire == 5:
            val, i = int.from_bytes(buf[i:i + 4], "little"), i + 4
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield num, val


def _one(buf: bytes, num: int, default=b""):
    for k, v in _fields(buf):
        if k == num:
            return v
    return default


def op_names(hlo_proto: bytes) -> dict:
    """Instruction name -> ``op_name`` of every instruction of a serialized
    ``HloProto`` (hlo_module 1; computations 3; instructions 2; name 1,
    metadata 7; op_name 2)."""
    out = {}
    module = _one(hlo_proto, 1)
    for k, comp in _fields(module):
        if k != 3:
            continue
        for kk, inst in _fields(comp):
            if kk != 2:
                continue
            name = meta = b""
            for f, v in _fields(inst):
                if f == 1:
                    name = v
                elif f == 7:
                    meta = v
            out[name.decode()] = _one(meta, 2).decode() if meta else ""
    return out


def programs(path: str) -> dict:
    """Program id -> {instruction name: op_name}, from the HLO protos of the
    trace's ``/host:metadata`` plane (XSpace planes 1; XPlane name 2,
    event_metadata 4, stat_metadata 5; XEventMetadata stats 5; XStat
    metadata_id 1, bytes_value 6)."""
    with open(path, "rb") as f:
        space = f.read()
    out = {}
    for k, plane in _fields(space):
        if k != 1 or _one(plane, 2) != b"/host:metadata":
            continue
        stat_names, metas = {}, []
        for f, v in _fields(plane):
            if f == 5:
                meta = _one(v, 2)
                stat_names[_one(meta, 1, 0)] = _one(meta, 2)
            elif f == 4:
                metas.append(_one(v, 2))
        for meta in metas:
            pid = _one(meta, 1, 0)
            for f, st in _fields(meta):
                if f == 5 and stat_names.get(_one(st, 1, 0)) == b"Hlo Proto":
                    out[pid] = op_names(_one(st, 6))
    return out


def layer_of(op_name: str) -> str:
    """The outermost layer scope among the components of an ``op_name``; a
    scope under a transform keeps the transform's wrapper
    (``vmap(local_sgd)``)."""
    for part in op_name.split("/"):
        part = _WRAPPER.sub("", part)
        if part in LAYERS:
            return part
    return UNSCOPED


def load(path: str) -> dict:
    """Layered operations, programs and ``osafl.*`` spans of one trace."""
    from jax.profiler import ProfileData

    names = programs(path)
    pd = ProfileData.from_file(path)
    devices, modules, spans = [], [], []
    for plane in pd.planes:
        if tz._DEVICE.match(plane.name):
            ops, mods = [], []
            for line in plane.lines:
                if line.name == "XLA Modules":
                    for ev in line.events:
                        m = _PROGRAM.match(ev.name.strip())
                        name, pid = (m.group(1), int(m.group(2))) if m \
                            else (ev.name.strip(), None)
                        mods.append([float(ev.start_ns),
                                     float(ev.duration_ns), name, pid])
                elif line.name == "XLA Ops":
                    for ev in line.events:
                        name = ev.name
                        if tz.KERNEL_MARK not in name:
                            name = name.split(" = ")[0]
                        ops.append([float(ev.start_ns), float(ev.duration_ns),
                                    name])
            mods.sort(key=lambda m: m[0])
            devices.append(_assign_layers(sorted(ops, key=lambda o: o[0]),
                                          mods, names))
            modules.append(mods)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX) or ev.name in tz.SPANS:
                        step = dict(ev.stats).get("step_num")
                        spans.append([ev.name, float(ev.start_ns),
                                      float(ev.duration_ns),
                                      None if step is None else int(step)])
    spans.sort(key=lambda s: s[1])
    return {"devices": devices, "modules": modules, "spans": spans}


def _assign_layers(ops, mods, names) -> list:
    """Each operation with the layer of its instruction in the program
    execution whose interval holds the operation's start, and that
    program's name (``none`` outside every execution)."""
    out, j = [], 0
    for start, dur, name in ops:
        while j < len(mods) and mods[j][0] + mods[j][1] < start:
            j += 1
        prog, module = {}, "none"
        if j < len(mods) and mods[j][0] <= start:
            prog, module = names.get(mods[j][3], {}), mods[j][2]
        layer = layer_of(prog.get(tz._short(name), ""))
        out.append([start, dur, name, layer, module])
    return out


def layer_own_times(ops, lo: float, hi: float) -> dict:
    """layer -> summed own time (ns) of the operations inside [lo, hi]; the
    values sum to ``trace.own_times`` of the same operations."""
    return tz.own_times([o[:2] + [o[3]] for o in ops], lo, hi)


def span_label(spans, a: float, b: float) -> str:
    """The innermost span covering the middle of [a, b], else ``host``."""
    mid, best = 0.5 * (a + b), None
    for name, s, d, _ in spans:
        if s <= mid <= s + d and (best is None or d < best[1]):
            best = (name, d)
    return best[0] if best else "host"


def summary(tr: dict, lo: float, hi: float, rounds: int) -> dict:
    """Per round over [lo, hi], the first device: own ms by layer, the ten
    largest unscoped operations by own ms with their programs, mean host ms
    by span name, and the ten longest idle gaps labelled by
    ``span_label``."""
    ops = tr["devices"][0]
    per = 1e6 * rounds
    own = tz.own_times([o[:2] + [f"{o[3]}|{tz._short(o[2])}|{o[4]}"]
                        for o in ops], lo, hi)
    by_layer, loose = {}, []
    for key, ns in own.items():
        layer, name, module = key.split("|")
        by_layer[layer] = by_layer.get(layer, 0.0) + ns
        if layer == UNSCOPED:
            loose.append([name, module, ns / per])
    host = {}
    for name, s, d, _ in tr["spans"]:
        if lo <= s and s + d <= hi:
            host.setdefault(name, []).append(d)
    busy = tz.busy_intervals([o[:3] for o in ops], lo, hi)
    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    gaps = sorted(((span_label(tr["spans"], a, b), b - a)
                   for a, b in zip(edges[::2], edges[1::2]) if b > a),
                  key=lambda g: -g[1])
    return {
        "layer_ms": {k: v / per for k, v in sorted(by_layer.items())},
        "unscoped_ops_ms": sorted(loose, key=lambda x: -x[2])[:10],
        "span_ms": {k: sum(v) / len(v) / 1e6 for k, v in sorted(host.items())},
        "idle_gaps_ms": [[k, v / 1e6] for k, v in gaps[:10]]}


def main(argv) -> int:
    import json

    tr = load(argv[0])
    rounds = [s for s in tr["spans"] if s[0] == "osafl.round"]
    if rounds:
        lo, hi = rounds[0][1], rounds[-1][1] + rounds[-1][2]
    else:
        rounds = [s for s in tr["spans"] if s[0] == "segment"]
        try:
            lo, hi = tz.window({"spans": [s[:3] for s in tr["spans"]]})
        except ValueError as e:
            print(f"no osafl.round spans, and {e}", file=sys.stderr)
            return 1
    print(json.dumps(summary(tr, lo, hi, len(rounds)), indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
