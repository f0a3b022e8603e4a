"""Reduction of a JAX profiler trace to the numbers the per-layer metrics
read.

``load`` turns an ``.xplane.pb`` into plain lists: for each TPU device, in
the order of the devices' ids, the operations of its "XLA Ops" line and the
program executions of its "XLA Modules" line as ``[start_ns, duration_ns,
name]``, and the benchmark's host spans (``segment``, ``device_get``,
``check_outputs``, ``harness_run``) as ``[name, start_ns, duration_ns]``. A
program keeps its name without the execution id the trace appends in
parentheses. An operation keeps its short HLO name (``%while.43``), except
a Pallas kernel (``custom_call_target="tpu_custom_call"``), which keeps its
whole text, operand shapes included: the trace names the score kernel
``%branch_0_fun.N`` after the platform branch it is lowered in, so its
operands are what identify it. The same plain form is what
``bench/tests/data`` keeps of a recorded trace.

The XLA Ops line nests: a ``while`` operation spans the operations of its
body. Busy time is the union of the intervals, so nesting does not count
twice; an operation's own time is its duration less what its children
cover.
"""
from __future__ import annotations

import re

KERNEL_MARK = 'custom_call_target="tpu_custom_call"'
SPANS = ("segment", "device_get", "check_outputs", "harness_run")
_RUN_ID = re.compile(r"\(\d+\)$")
_DEVICE = re.compile(r"^/device:TPU:(\d+)$")


def load(path: str) -> dict:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices, modules, spans = [], [], []
    for plane in sorted(pd.planes, key=_device_id):
        if _DEVICE.match(plane.name):
            ops, mods = [], []
            for line in plane.lines:
                if line.name == "XLA Modules":
                    mods.extend([float(ev.start_ns), float(ev.duration_ns),
                                 _RUN_ID.sub("", ev.name.strip())]
                                for ev in line.events)
                if line.name != "XLA Ops":
                    continue
                for ev in line.events:
                    name = ev.name
                    if KERNEL_MARK not in name:
                        name = name.split(" = ")[0]
                    ops.append([float(ev.start_ns), float(ev.duration_ns),
                                name])
            devices.append(sorted(ops, key=lambda o: o[0]))
            modules.append(sorted(mods, key=lambda o: o[0]))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in SPANS:
                        spans.append([ev.name, float(ev.start_ns),
                                      float(ev.duration_ns)])
    spans.sort(key=lambda s: s[1])
    return {"devices": devices, "modules": modules, "spans": spans}


def _device_id(plane) -> int:
    m = _DEVICE.match(plane.name)
    return int(m.group(1)) if m else -1


def window(tr: dict) -> tuple:
    """(start_ns, end_ns) of the traced window: from the first ``segment``
    span to the end of the last ``check_outputs`` span."""
    seg = [s for s in tr["spans"] if s[0] == "segment"]
    chk = [s for s in tr["spans"] if s[0] == "check_outputs"]
    if not seg or not chk:
        raise ValueError("the trace holds no segment/check_outputs spans")
    return seg[0][1], chk[-1][1] + chk[-1][2]


def round_starts(modules, per_round_runs: int):
    """Start times of the program that runs ``per_round_runs`` times and
    takes the most device time among those that do, or None: in a traced
    call of that many rounds, the one program each round runs once whose
    work is largest (the local SGD of a round)."""
    runs = {}
    for start, dur, name in modules:
        runs.setdefault(name, []).append((start, dur))
    once = [v for v in runs.values() if len(v) == per_round_runs]
    if not once:
        return None
    best = max(once, key=lambda v: sum(d for _, d in v))
    return sorted(s for s, _ in best)


def busy_intervals(ops, lo: float, hi: float) -> list:
    """Union of the operations' intervals, clipped to [lo, hi]."""
    out = []
    for start, dur, _ in sorted(ops, key=lambda o: o[0]):
        a, b = max(start, lo), min(start + dur, hi)
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def busy_ns(ops, lo: float, hi: float) -> float:
    return sum(b - a for a, b in busy_intervals(ops, lo, hi))


def own_times(ops, lo: float, hi: float) -> dict:
    """name -> summed own time (ns) of the operations inside [lo, hi]; an
    operation's own time excludes the operations nested in it."""
    own = {}
    stack = []                     # [end, name, start, child_ns]

    def close(item):
        end, name, start, child = item
        own[name] = own.get(name, 0.0) + (end - start) - child
        if stack:
            stack[-1][3] += end - start

    for start, dur, name in sorted(ops, key=lambda o: (o[0], -o[1])):
        if start < lo or start + dur > hi:
            continue
        while stack and stack[-1][0] <= start:
            close(stack.pop())
        stack.append([start + dur, _short(name), start, 0.0])
    while stack:
        close(stack.pop())
    return own


def idle_gaps(tr: dict, ops, lo: float, hi: float) -> list:
    """[(label, ns)] of every gap in the device's busy union inside the
    window, labelled by the host span that covers the gap's middle."""
    busy = busy_intervals(ops, lo, hi)
    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    gaps = []
    for a, b in zip(edges[::2], edges[1::2]):
        if b > a:
            mid = 0.5 * (a + b)
            label = "host"
            for name, s, d in tr["spans"]:
                if s <= mid <= s + d:
                    label = name
            gaps.append((label, b - a))
    return gaps


def kernel_events(ops, rows: int, cols: int) -> list:
    """The score kernel's events: a Pallas call whose first operand is the
    (rows, N') update plane, N' being N padded to the kernel's block, and
    whose second is the (1, N') mean."""
    out = []
    for op in ops:
        if KERNEL_MARK not in op[2]:
            continue
        m = re.search(r"custom-call\(f32\[(\d+),(\d+)\]\S* \S+, "
                      r"f32\[1,(\d+)\]", op[2])
        if m and int(m.group(1)) == rows and int(m.group(2)) == \
                int(m.group(3)) and cols <= int(m.group(2)) < cols + 65536:
            out.append(op)
    return out


def _short(name: str) -> str:
    return name.split(" = ")[0].lstrip("%")
