"""The layer reduction (``yardstick/layers.py``) on hand-built protobufs and
intervals, and the readers of ``yardstick/trace.py`` on the recorded
excerpt ``fcn-d1.trace.json``, pinned to the values they gave before the
program named its layers."""
import json
from pathlib import Path

import pytest

from bench.yardstick import layers as L
from bench.yardstick import trace as tz
from bench.tests.test_trace import _ctx, _read

OLD = Path(__file__).parent / "data" / "fcn-d1.trace.json"


def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        b, n = n & 0x7F, n >> 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _msg(num: int, payload: bytes) -> bytes:
    return _varint(num << 3 | 2) + _varint(len(payload)) + payload


def _int(num: int, n: int) -> bytes:
    return _varint(num << 3) + _varint(n)


def _hlo(*insts) -> bytes:
    """A serialized HloProto of one computation holding ``insts``, each
    (name, op_name or None)."""
    body = b"".join(
        _msg(2, _msg(1, name.encode()) + _msg(2, b"add")
             + (_msg(7, _msg(1, b"add") + _msg(2, op.encode()))
                if op is not None else b""))
        for name, op in insts)
    return _msg(1, _msg(1, b"jit_f") + _msg(3, _msg(1, b"main") + body))


def _space(programs: dict) -> bytes:
    """An XSpace whose /host:metadata plane keeps each program's HloProto,
    beside a device plane the reader skips."""
    stat = _msg(5, _int(1, 7) + _msg(2, _int(1, 7) + _msg(2, b"Hlo Proto")))
    metas = b"".join(
        _msg(4, _int(1, pid) + _msg(2, _int(1, pid)
                                  + _msg(2, f"jit_f({pid})".encode())
                                  + _msg(5, _int(1, 7) + _msg(6, hlo))))
        for pid, hlo in programs.items())
    meta_plane = _msg(2, b"/host:metadata") + stat + metas
    device_plane = _msg(2, b"/device:TPU:0") + _msg(4, _int(1, 1))
    return _msg(1, device_plane) + _msg(1, meta_plane)


def test_instruction_names_map_to_their_op_names():
    hlo = _hlo(("add.1", "jit(f)/local_sgd/add"), ("copy.2", None),
               ("fusion.300", "jit(f)/while/body/aggregate/dot_general"))
    assert L.op_names(hlo) == {"add.1": "jit(f)/local_sgd/add", "copy.2": "",
                               "fusion.300": "jit(f)/while/body/aggregate/"
                                             "dot_general"}


def test_programs_are_read_from_the_metadata_plane(tmp_path):
    path = tmp_path / "t.xplane.pb"
    big = 2 ** 63 + 12345                    # program ids fill 64 bits
    path.write_bytes(_space({big: _hlo(("add.1", "jit(f)/eval/add")),
                             7: _hlo(("mul.3", "jit(g)/mul"))}))
    assert L.programs(str(path)) == {big: {"add.1": "jit(f)/eval/add"},
                                     7: {"mul.3": "jit(g)/mul"}}


@pytest.mark.parametrize("op_name,layer", [
    ("jit(segment)/while/body/closed_call/request_gen/jit(_draw_block)/"
     "request_gen/argmax", "request_gen"),
    ("jit(one_client)/vmap(local_sgd)/while/body/closed_call/dot_general",
     "local_sgd"),
    ("jit(segment)/while/body/aggregate/jit(scored_reduce)/cond/"
     "branch_0_fun/scored_reduce", "aggregate"),
    ("jit(segment)/while/body/eval/local_sgd/add", "eval"),
    ("jit(scan)/while/body/closed_call/tanh", "unscoped"),
    ("", "unscoped"),
])
def test_an_op_belongs_to_its_outermost_layer(op_name, layer):
    assert L.layer_of(op_name) == layer


def test_ops_take_the_layer_of_the_program_they_ran_in():
    mods = [[0, 10, "jit_a", 1], [20, 10, "jit_b", 2]]
    names = {1: {"add.1": "jit(a)/fifo_commit/add"},
             2: {"add.1": "jit(b)/eval/add"}}
    ops = [[1, 2, "%add.1"], [12, 1, "%add.1"], [21, 2, "%add.1"],
           [25, 1, "%copy.9"]]
    assert [o[3:] for o in L._assign_layers(ops, mods, names)] == [
        ["fifo_commit", "jit_a"], ["unscoped", "none"], ["eval", "jit_b"],
        ["unscoped", "jit_b"]]


def test_layer_own_times_partition_the_own_time():
    ops = [[0, 10, "%while.1", "unscoped", "jit_f"],
           [1, 3, "%a.2", "local_sgd", "jit_f"],
           [5, 4, "%b.3", "local_sgd", "jit_f"],
           [12, 6, "%c.4", "aggregate", "jit_f"],
           [20, 5, "%d.5", "eval", "jit_g"]]
    by_layer = L.layer_own_times(ops, 0, 30)
    assert by_layer == {"unscoped": 3, "local_sgd": 7, "aggregate": 6,
                        "eval": 5}
    own = tz.own_times([o[:3] for o in ops], 0, 30)
    assert sum(by_layer.values()) == sum(own.values())


def test_a_gap_takes_the_innermost_covering_span():
    spans = [["osafl.round", 0, 100, 3], ["osafl.eval", 40, 30, None],
             ["osafl.request_draw", 10, 20, None]]
    assert L.span_label(spans, 45, 55) == "osafl.eval"
    assert L.span_label(spans, 75, 95) == "osafl.round"
    assert L.span_label(spans, 110, 120) == "host"


def test_summary_per_round():
    tr = {"devices": [[[0, 4e6, "%a.1", "local_sgd", "jit_step"],
                       [5e6, 2e6, "%copy.2", "unscoped", "jit_step"],
                       [10e6, 4e6, "%a.1", "local_sgd", "jit_step"],
                       [15e6, 2e6, "%copy.2", "unscoped", "jit_step"]]],
          "modules": [[[0, 8e6, "jit_step", 1], [10e6, 8e6, "jit_step", 1]]],
          "spans": [["osafl.round", 0, 9e6, 0], ["osafl.round", 10e6, 9e6, 1],
                    ["osafl.eval", 7e6, 2e6, None],
                    ["osafl.eval", 17e6, 2e6, None]]}
    s = L.summary(tr, 0, 19e6, 2)
    assert s["layer_ms"] == {"local_sgd": 4.0, "unscoped": 2.0}
    assert s["unscoped_ops_ms"] == [["copy.2", "jit_step", 2.0]]
    assert s["span_ms"] == {"osafl.eval": 2.0, "osafl.round": 9.0}
    assert s["idle_gaps_ms"] == [["osafl.eval", 3.0], ["osafl.eval", 2.0],
                                 ["osafl.round", 1.0], ["osafl.round", 1.0]]


def test_old_excerpt_reads_as_before():
    """Every reader of ``trace.py`` gives the value it gave on the recorded
    excerpt before the program named its layers, bit for bit."""
    ex = json.loads(OLD.read_text())
    t = _ctx(ex, 2, 128, 3_821_156)
    assert _read("idle_share", t) == 4.023632470104211
    assert _read("scored_reduce_ms", t) == 2.5856675
    assert _read("scored_reduce_roofline", t) == 93.10819156307798
    assert _read("round_mfu", _ctx(ex, 2, 128, 3_821_156, flops=2.76e11)) \
        == 0.5742338421222389
    lo, hi = tz.window(ex)
    ops = ex["devices"][0]
    assert (lo, hi, tz.busy_ns(ops, lo, hi)) == (1000.0, 243980913.0,
                                                 234163058.0)
    own = tz.own_times(ops, lo, hi)
    assert own["subtract_select_fusion.19"] == 50157825.0
    assert tz.idle_gaps(ex, ops, lo, hi)[0] == ("segment", 364932.0)


NEW = Path(__file__).parent / "data" / "fcn-d1.layers.json"


@pytest.fixture(scope="module")
def layered():
    return json.loads(NEW.read_text())


def test_recorded_layers_partition_the_windows_own_time(layered):
    ops = layered["devices"][0]
    lo, hi = tz.window(layered)
    by_layer = L.layer_own_times(ops, lo, hi)
    assert sum(by_layer.values()) == \
        sum(tz.own_times([o[:3] for o in ops], lo, hi).values())
    assert {"request_gen", "fifo_commit", "local_sgd", "aggregate",
            "eval", "unscoped"} <= set(by_layer)
    assert max(by_layer, key=by_layer.get) == "local_sgd"


def test_recorded_ops_keep_their_layers(layered):
    layer = {o[2]: o[3] for o in layered["devices"][0]}
    assert layer["%subtract_select_fusion.19"] == "local_sgd"
    assert layer["%pad.2"] == "aggregate"
    assert layer["%copy.123"] == "unscoped"     # a layout copy XLA inserts


def test_recorded_kernel_is_named_and_found_by_its_operands(layered):
    ev = tz.kernel_events(layered["devices"][0], 128, 3_821_156)
    assert len(ev) == 2 and all(e[2].startswith("%scored_reduce.")
                                and e[3] == "aggregate" for e in ev)


def test_recorded_dispatch_spans_lie_in_their_segments(layered):
    segs = [s for s in layered["spans"] if s[0] == "segment"]
    disp = [s for s in layered["spans"] if s[0] == "osafl.dispatch"]
    assert len(segs) == len(disp) == 2
    assert all(g[1] <= d[1] and d[1] + d[2] <= g[1] + g[2]
               for g, d in zip(segs, disp))
