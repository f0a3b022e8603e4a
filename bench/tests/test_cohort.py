"""The host-driven cell at a small size on the CPU: the reference of the
slot-pool round against ``repro.harness.run``, a whole run with the
harness's look for a chip skipped, and the run with its timed path broken
underneath, which has to come out not correct."""
import contextlib
import io
import json
from types import SimpleNamespace

import jax
import pytest

from bench.run import cell_spec, peaks_for, run_cell

CELL = "lstm-d2.cohort"
SEED = 2 ** 31 + 91


def _tiny_spec():
    spec = cell_spec(CELL)
    spec.cfg.update(capacity=[12, 24])
    spec.workload["harness"].update(num_users=48, cohort_size=16)
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
    assert res["correct"] is True, res["checks"]
    assert res["checks"]["participants_gap"]["value"] == 0
    assert res["checks"]["loss_gap"]["value"] < 1e-5
    assert set(res["metrics"]) == {"round_ms", "peak_hbm_gb", "setup_s"}
    assert res["attempted"] >= 2 and res["failed"] == 0


def test_state_left_unchanged_is_caught(monkeypatch):
    from repro.core.osafl import StackedOSAFLServer
    real = StackedOSAFLServer.round_stacked

    def stuck(self, d_new, active):
        w = self.w
        real(self, d_new, active)
        self.w = w
        return w

    monkeypatch.setattr(StackedOSAFLServer, "round_stacked", stuck)
    assert _run()["correct"] is False


def test_half_batch_is_caught(monkeypatch):
    import repro.harness.experiments as ex
    real = ex.make_vmapped_local_train

    def half(grad_fn, lr, kappa_max, prox_mu=0.0):
        step = real(grad_fn, lr, kappa_max, prox_mu=prox_mu)

        def local_step(params, batches, kappas):
            b = jax.tree.leaves(batches)[0].shape[2] // 2
            return step(params, jax.tree.map(lambda v: v[:, :, :b], batches),
                        kappas)
        return local_step

    monkeypatch.setattr(ex, "make_vmapped_local_train", half)
    assert _run()["correct"] is False


def test_altered_label_is_caught(monkeypatch):
    import repro.data.video_caching_stacked as vs
    real = vs._draw_block

    def off_by_one(*a, **kw):
        st, xs, ys = real(*a, **kw)
        return st, xs, (ys + 1) % 100

    monkeypatch.setattr(vs, "_draw_block", off_by_one)
    assert _run()["correct"] is False


@pytest.mark.parametrize("variant", ["bf16", "half_batch", "label", "frozen"])
def test_control_and_faults_in_the_programs_place_fail(variant):
    from bench.control import readings
    from bench.yardstick.compare import judge
    spec = _tiny_spec()
    nums = readings(spec, variant, SEED)
    assert not judge(nums, spec.workload["limits"]), nums
