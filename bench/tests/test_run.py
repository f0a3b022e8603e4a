"""A whole run at a small size on the CPU: the harness's look for a chip is
skipped, the rest of the run is driven, and ``correct`` has to come out true
for the program as it is and false with the timed path broken underneath."""
import contextlib
import io
import json
import os
import shutil
import subprocess
import sys
from types import SimpleNamespace

import jax
import pytest

from bench.run import ROOT, cell_spec, peaks_for, run_cell

CELL = "lstm-d2.fused"
SEED = 2 ** 31 + 77


def _tiny_spec():
    spec = cell_spec(CELL)
    spec.cfg.update(num_clients=4, capacity=[12, 24])
    return spec


def _run(seed=SEED):
    spec = _tiny_spec()
    args = SimpleNamespace(seed=seed, seconds=0.5, trace=0)
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        assert run_cell(spec, args, jax.devices()[:1],
                        peaks_for("TPU v5 lite"), "cpu", "") == 0
    return json.loads(out.getvalue().strip().splitlines()[-1])


def test_sound_program_is_correct():
    res = _run()
    assert res["correct"] is True
    assert list(res)[-1] == "checks"
    assert set(res["metrics"]) == {"round_ms", "peak_hbm_gb", "setup_s"}
    assert res["attempted"] >= 1 and res["failed"] == 0


def test_state_left_unchanged_is_caught(monkeypatch):
    from repro.core.round_fused import FusedEngine
    real = FusedEngine.run_segment

    def stuck(self, carry, length):
        _, outs = real(self, carry, length)
        return carry._replace(t=carry.t + length), outs

    monkeypatch.setattr(FusedEngine, "run_segment", stuck)
    assert _run()["correct"] is False


def test_half_batch_is_caught(monkeypatch):
    import repro.core.round_fused as rf
    real = rf.make_local_train_body

    def half(grad_fn, lr, kappa_max, prox_mu=0.0):
        body = real(grad_fn, lr, kappa_max, prox_mu)

        def one_client(params, batch, kappa):
            b = jax.tree.leaves(batch)[0].shape[1] // 2
            return body(params, jax.tree.map(lambda v: v[:, :b], batch),
                        kappa)
        return one_client

    monkeypatch.setattr(rf, "make_local_train_body", half)
    assert _run()["correct"] is False


def test_altered_label_is_caught(monkeypatch):
    import repro.core.round_fused as rf
    real = rf._draw_block

    def off_by_one(*a, **kw):
        st, xs, ys = real(*a, **kw)
        return st, xs, (ys + 1) % 100

    monkeypatch.setattr(rf, "_draw_block", off_by_one)
    assert _run()["correct"] is False


@pytest.mark.parametrize("variant", ["bf16", "half_batch", "label"])
def test_control_and_faults_in_the_programs_place_fail(variant):
    from bench.control import readings
    from bench.yardstick.compare import judge
    spec = _tiny_spec()
    nums = readings(spec, variant, SEED)
    assert not judge(nums, spec.workload["limits"]), nums


def _bench_cmd(cwd, env):
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELL, "--seed", "1",
         "--seconds", "1", "--trace", "0"], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=300)


def test_exits_nonzero_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = _bench_cmd(ROOT, env)
    assert r.returncode != 0
    assert r.stdout.strip() == ""
    assert "TPU" in r.stderr


def test_exits_nonzero_with_only_the_benchmark(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    r = _bench_cmd(tmp_path, env)
    assert r.returncode != 0
    assert r.stdout.strip() == ""
