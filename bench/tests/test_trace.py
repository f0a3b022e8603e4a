"""The reduction from a trace to metrics, on hand-made intervals and on two
recorded rounds of ``fcn-d1`` on one TPU v5 lite."""
import json
from pathlib import Path
from types import SimpleNamespace

import pytest

from bench.run import BENCH, load_module, peaks_for
from bench.yardstick import trace as tz

DATA = Path(__file__).parent / "data" / "fcn-d1.trace.json"


def _ctx(tr, rounds, rows, n_params, flops=0.0, request_gen_s=None):
    lo, hi = tz.window(tr)
    return SimpleNamespace(trace=tr, ops=tr["devices"], lo=lo, hi=hi,
                           rounds=rounds, flops=flops, plane_rows=rows,
                           n_params=n_params, request_gen_s=request_gen_s,
                           chips=1, peaks=peaks_for("TPU v5 lite"), tz=tz)


def _read(name, t):
    return load_module(BENCH / "metrics" / f"{name}.py").read(t)


@pytest.fixture(scope="module")
def excerpt():
    return json.loads(DATA.read_text())


def test_union_of_nested_and_overlapping_intervals():
    ops = [[0, 10, "while"], [2, 3, "a"], [4, 2, "b"],   # nested in while
           [8, 6, "c"],                                   # overlaps its end
           [20, 5, "d"], [30, 10, "e"]]                   # e runs past hi
    assert tz.busy_intervals(ops, 0, 35) == [[0, 14], [20, 25], [30, 35]]
    assert tz.busy_ns(ops, 0, 35) == 24
    assert tz.busy_ns(ops, 5, 22) == 9 + 2


def test_own_time_leaves_out_nested_children():
    ops = [[0, 10, "%while.1"], [2, 3, "%a.2"], [6, 2, "%b.3"],
           [12, 4, "%c.4"]]
    own = tz.own_times(ops, 0, 20)
    assert own == {"while.1": 5, "a.2": 3, "b.3": 2, "c.4": 4}
    assert sum(own.values()) == tz.busy_ns(ops, 0, 20)


def test_idle_share_of_a_synthetic_window():
    tr = {"devices": [[[100, 400, "x"], [600, 300, "y"]]],
          "spans": [["segment", 0, 80], ["device_get", 80, 870],
                    ["check_outputs", 950, 50]]}
    t = _ctx(tr, 1, 4, 10)
    assert t.lo == 0 and t.hi == 1000
    assert _read("idle_share", t) == pytest.approx(30.0)
    gaps = tz.idle_gaps(tr, tr["devices"][0], 0, 1000)
    assert gaps == [("segment", 100), ("device_get", 100),
                    ("check_outputs", 100)]


def test_recorded_window_busy_share_and_own_times(excerpt):
    lo, hi = tz.window(excerpt)
    ops = excerpt["devices"][0]
    busy = tz.busy_ns(ops, lo, hi)
    assert 0.9 < busy / (hi - lo) < 1.0
    assert sum(tz.own_times(ops, lo, hi).values()) == pytest.approx(busy)
    assert sum(s[0] == "segment" for s in excerpt["spans"]) == 2


def test_score_kernel_found_by_its_operands(excerpt):
    """The trace names the kernel %branch_0_fun.N; its operands, the
    (128, N') plane and the (1, N') mean, are what find it."""
    ops = excerpt["devices"][0]
    ev = tz.kernel_events(ops, 128, 3_821_156)
    assert len(ev) == 2
    assert all(e[2].startswith("%branch_0_fun") for e in ev)
    assert tz.kernel_events(ops, 64, 3_821_156) == []
    t = _ctx(excerpt, 2, 128, 3_821_156)
    ms = _read("scored_reduce_ms", t)
    assert ms == pytest.approx(2.5857, rel=1e-3)
    share = _read("scored_reduce_roofline", t)
    assert 0 < share <= 100


def test_readers_find_nothing_without_a_kernel():
    tr = {"devices": [[[0, 10, "%fusion.1"]]],
          "spans": [["segment", 0, 1], ["check_outputs", 9, 1]]}
    t = _ctx(tr, 1, 4, 100)
    assert _read("scored_reduce_ms", t) is None
    assert _read("scored_reduce_roofline", t) is None
    assert _read("round_mfu", t) is None
    assert _read("host_draws_ms", t) is None


def test_host_draws_are_the_mean_of_the_runs_counter():
    tr = {"devices": [[[0, 10, "%fusion.1"]]],
          "spans": [["segment", 0, 1], ["check_outputs", 9, 1]]}
    t = _ctx(tr, 3, 4, 100, request_gen_s=[0.004, 0.005, 0.009])
    assert _read("host_draws_ms", t) == pytest.approx(6.0)


def test_round_starts_pick_the_largest_program_run_once_a_round():
    mods = [[0, 1, "jit_draw"], [1, 5, "jit_one_client"], [6, 1, "jit_rnd"],
            [10, 1, "jit_draw"], [11, 6, "jit_one_client"],
            [17, 1, "jit_rnd"], [20, 1, "jit_draw"], [21, 1, "jit_draw"],
            [22, 5, "jit_one_client"], [27, 1, "jit_rnd"]]
    assert tz.round_starts(mods, 3) == [1, 11, 22]
    assert tz.round_starts(mods, 5) is None
