"""FLOP counts, roofline bytes, the peaks table and the manifest."""
import re
from types import SimpleNamespace

import jax
import numpy as np
import pytest

from bench.run import BENCH, ROOT, cell_spec, load_json, load_module, \
    peaks_for
from bench.yardstick import trace as tz

MANIFEST = load_json(ROOT / "BENCHMARK.json")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def _model(name):
    return load_module(BENCH / "configs" / f"{name}.py")


def test_fcn_flops_by_hand():
    fl = _model("fcn-d1-kappa5").flops_per_sample()
    mats = 3168 * 1024 + 1024 * 512 + 512 * 100
    assert fl["forward"] == 2 * mats == 7_639_040
    # weight gradients of all three layers, input gradients of the last two
    assert fl["train"] == 2 * mats + 2 * mats + 2 * (1024 * 512 + 512 * 100)
    assert fl["train"] == 16_429_056


def test_lstm_flops_by_hand():
    fl = _model("lstm-d2-kappa5").flops_per_sample()
    l1 = 10 * 2 * (64 * 512 + 128 * 512)
    l23 = 10 * 2 * (128 * 512 + 128 * 512)
    head = 2 * 128 * 100
    assert fl["forward"] == l1 + 2 * l23 + head == 7_234_560
    assert fl["train"] == 3 * fl["forward"]


@pytest.mark.parametrize("name", ["fcn-d1-kappa5", "lstm-d2-kappa5"])
def test_reference_layout_matches_the_stated_size(name):
    cfg = load_json(BENCH / "configs" / f"{name}.json")
    params = jax.eval_shape(_model(name).init, jax.random.PRNGKey(0))
    n = sum(int(np.prod(v.shape)) for v in jax.tree.leaves(params))
    assert n == cfg["n_params"]


def test_roofline_bytes_of_the_score_kernel():
    """U N 4 + N 4 bytes at 819 GB/s: a kernel event of exactly that time
    reads 100%."""
    U, N = 128, 3_821_156
    floor_ns = 4.0 * (U * N + N) / 819e9 * 1e9
    name = ('%branch_0_fun.3 = (f32[128,1]) custom-call(f32[128,3821568]'
            '{1,0} %pad.2, f32[1,3821568]{1,0} %bitcast.209), '
            'custom_call_target="tpu_custom_call"')
    tr = {"devices": [[[10.0, floor_ns, name]]],
          "spans": [["segment", 0.0, 1.0], ["check_outputs", 5e7, 1.0]]}
    t = SimpleNamespace(trace=tr, ops=tr["devices"], lo=0.0, hi=5e7 + 1,
                        rounds=1, plane_rows=U, n_params=N,
                        chips=1, peaks=peaks_for("TPU v5 lite"), tz=tz)
    share = load_module(BENCH / "metrics" /
                        "scored_reduce_roofline.py").read(t)
    assert share == pytest.approx(100.0)


def test_peaks_refuse_an_unknown_device():
    assert peaks_for("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(SystemExit, match="no peaks"):
        peaks_for("TPU v9 imaginary")


def test_manifest_resolves():
    e2e = {m["name"] for m in MANIFEST["end_to_end"]}
    cells = {w["name"] for w in MANIFEST["workloads"]}
    assert "setup_s" in e2e
    for conf in MANIFEST["configs"]:
        assert (ROOT / conf["file"]).is_file()
        assert (ROOT / conf["file"]).with_suffix(".py").is_file()
        cfg = load_json(ROOT / conf["file"])
        assert set(conf["reduced"]) <= set(cfg)
    for w in MANIFEST["workloads"]:
        spec = cell_spec(w["name"])
        driver = load_module(BENCH / "drivers" /
                             f"{spec.workload['driver']}.py")
        assert set(spec.workload["limits"]) == driver.numbers(spec.workload)
        assert len(w["why"]) <= 200
    for m in MANIFEST["per_layer"]:
        assert m["moves"] in e2e
        assert set(m.get("workloads", cells)) <= cells
        assert (BENCH / "metrics" / f"{m['name']}.py").is_file()
    for m in MANIFEST["end_to_end"] + MANIFEST["per_layer"]:
        assert NAME.match(m["name"]) and m["better"] in ("lower", "higher")
    for path in BENCH.rglob("*"):
        assert re.match(r"^[A-Za-z0-9_./-]+$",
                        str(path.relative_to(ROOT))), path
