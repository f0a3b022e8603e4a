"""The host-driven round on a mesh of chips (the queued ``fcn-d1.pod4``
cell: its workload file is there, ``BENCHMARK.json`` does not list it yet):
the numbers each driver compares, the driver's ``repro.harness.run`` calls
with and without a mesh, the collectives' reader, and, in a subprocess on
four faked CPU devices at a small FCN size, a whole run that has to come out
correct, and not correct with the timed path broken underneath."""
import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

from bench.run import BENCH, ROOT, cell_spec, load_json, load_module
from bench.yardstick import trace as tz

CELL = "fcn-d1.pod4"
SEED = 2 ** 31 + 113
DATA = Path(__file__).parent / "data" / "pod4.collectives.json"
FAULTS = ("frozen", "half_batch", "label", "no_exchange")


def pod4_spec():
    """The queued cell as ``cell_spec`` would give it once ``BENCHMARK.json``
    lists it: its workload file on the ``fcn-d1-kappa5`` configuration."""
    conf = BENCH / "configs" / "fcn-d1-kappa5.json"
    return SimpleNamespace(
        manifest=load_json(ROOT / "BENCHMARK.json"),
        cell={"name": CELL, "config": "fcn-d1-kappa5", "traffic": "pod4",
              "chips": 4},
        cfg=load_json(conf), model=load_module(conf.with_suffix(".py")),
        workload=load_json(BENCH / "workloads" / f"{CELL}.json"))


def _driver(name):
    return load_module(BENCH / "drivers" / f"{name}.py")


def _tiny(spec):
    spec.cfg.update(capacity=[12, 24])
    spec.workload["harness"].update(num_users=8, eval_samples=32)
    return spec


def test_each_driver_states_the_numbers_it_compares():
    harness = _driver("harness")
    assert harness.numbers(cell_spec("lstm-d2.cohort").workload) == {
        "loss_gap", "participants_gap"}
    assert harness.numbers(pod4_spec().workload) == {
        "loss_gap", "participants_gap", "step_gap", "change_gap"}
    assert _driver("fused").numbers(cell_spec("fcn-d1.fused").workload) == {
        "loss_gap", "step_gap", "change_gap"}


def test_the_mesh_spans_the_cells_chips():
    spec = pod4_spec()
    h = spec.workload["harness"]
    shape = h["mesh"]["shape"]
    assert int(np.prod(shape)) == spec.cell["chips"] == 4
    assert h["num_users"] % int(np.prod(shape)) == 0
    assert h["pod_engine"] == "exact_tp"


def _recorded_calls(monkeypatch, spec, harness):
    """The ``harness`` driver's ``repro.harness.run`` calls for ``spec``,
    each as (args, keywords), against a stand-in that returns a history."""
    import repro.harness
    calls = []

    def fake_run(alg, xc, **kw):
        calls.append(((alg, xc), kw))
        return [{"round": t, "test_loss": 4.6, "participants": 1,
                 "request_gen_s": 0.001, "round_s": 0.05}
                for t in range(xc.rounds)]

    monkeypatch.setattr(repro.harness, "run", fake_run)
    ctx = SimpleNamespace(
        cfg=spec.cfg, workload=spec.workload, model=spec.model, seed=SEED,
        seconds=0.5, t_start=0.0, counter=SimpleNamespace(armed=False,
                                                          count=0),
        trace_on=lambda: None, trace_off=lambda: None)
    res = harness.run(ctx)
    return calls, res


def test_without_a_mesh_the_calls_are_unchanged(monkeypatch):
    spec = cell_spec("lstm-d2.cohort")
    calls, res = _recorded_calls(monkeypatch, spec, _driver("harness"))
    h = spec.workload["harness"]
    w = spec.workload["warmup_rounds"]
    # warm-up, one longer warm-up, call A, call B: nothing else
    assert [xc.rounds for (_, xc), _ in calls] == [w, w + 15, w, w + 10]
    for (alg, xc), kw in calls:
        assert alg == "osafl"
        assert kw == {"eval_samples": h["eval_samples"]}
        assert xc.engine == "stacked" and xc.num_clients == h["num_users"]
        assert (xc.cohort_size, xc.participation, xc.round_backend) == (
            h["cohort_size"], h["participation"], h["round_backend"])
    assert res.weights is None and res.rounds == 10


def test_mesh_cell_asks_for_the_pod_engine_and_a_check_call(monkeypatch):
    spec = pod4_spec()
    h = spec.workload["harness"]
    harness = _driver("harness")
    monkeypatch.setattr(harness, "run_kwargs", lambda h: {
        "mesh": "the mesh", "pod_engine": h["pod_engine"]})
    monkeypatch.setattr(harness, "read_weights",
                        lambda fl_run, xc, kw, ev: fl_run(
                            "osafl", xc, eval_samples=ev, **kw) and ["w"])
    calls, res = _recorded_calls(monkeypatch, spec, harness)
    assert [xc.rounds for (_, xc), _ in calls][-1] == \
        spec.workload["check_rounds"]
    for (alg, xc), kw in calls:
        assert xc.engine == "pod"
        assert kw == {"eval_samples": h["eval_samples"], "mesh": "the mesh",
                      "pod_engine": "exact_tp"}
    assert res.weights == ["w"]


@pytest.mark.parametrize("variant", ("bf16",) + FAULTS)
def test_control_and_faults_in_the_programs_place_fail(variant):
    from bench.control import readings
    from bench.yardstick.compare import judge
    spec = _tiny(pod4_spec())
    nums = readings(spec, variant, SEED)
    assert not judge(nums, spec.workload["limits"]), nums


def test_exchange_fault_needs_a_mesh():
    from bench.yardstick.cohort import run_cohort_reference
    spec = _tiny(pod4_spec())
    traffic = dict(spec.workload["harness"])
    del traffic["mesh"]
    with pytest.raises(ValueError, match="mesh"):
        run_cohort_reference(spec.cfg, traffic, spec.model, SEED, 1,
                             fault="no_exchange")


# ---------------------------------------------------------------------------
# the collectives' reader
# ---------------------------------------------------------------------------

def _tctx(tr, lo, hi, rounds):
    return SimpleNamespace(trace=tr, ops=tr["devices"], lo=lo, hi=hi,
                           rounds=rounds, tz=tz)


def test_collective_own_time_by_hand():
    """A plain-form excerpt of two chips (``data/pod4.collectives.json``),
    its value worked out by hand in the file's note."""
    ex = load_json(DATA)
    value = load_module(BENCH / "metrics" / "collective_ms.py").read(
        _tctx(ex, ex["lo"], ex["hi"], ex["rounds"]))
    assert value == pytest.approx(ex["expected_ms"])


def test_collective_reader_finds_nothing_on_one_chip():
    tr = {"devices": [[[0, 10, "%fusion.1"], [20, 5, "%all-reducer.1"]]]}
    assert load_module(BENCH / "metrics" / "collective_ms.py").read(
        _tctx(tr, 0, 100, 1)) is None


# ---------------------------------------------------------------------------
# a whole run on four faked CPU devices
# ---------------------------------------------------------------------------

_SUB = textwrap.dedent("""
    import contextlib, io, json, sys
    from types import SimpleNamespace
    sys.path[:0] = [{src!r}, {root!r}]
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    assert len(jax.devices()) == 4
    import repro.harness
    import repro.core.client as client
    import repro.core.osafl as osafl
    import repro.data.video_caching_stacked as vs
    from repro.core.shmap import client_axes, shard_map
    from bench.run import peaks_for, run_cell
    from bench.tests.test_pod4 import pod4_spec

    def tiny():
        spec = pod4_spec()
        spec.cfg.update(capacity=[12, 24])
        spec.workload["harness"].update(num_users=8, eval_samples=32)
        return spec

    seen = []
    real_run = repro.harness.run

    def recording_run(alg, xc, **kw):
        mesh = kw.get("mesh")
        seen.append([xc.engine, kw.get("pod_engine"),
                     None if mesh is None else mesh.devices.size])
        return real_run(alg, xc, **kw)

    repro.harness.run = recording_run

    def stuck(real):
        def round_stacked(self, d_new, active):
            w = self.w
            real(self, d_new, active)
            self.w = w
            return w
        return round_stacked

    def half(real):
        def make(grad_fn, lr, kappa_max, prox_mu=0.0):
            body = real(grad_fn, lr, kappa_max, prox_mu=prox_mu)
            def one_client(params, batch, kappa):
                b = jax.tree.leaves(batch)[0].shape[1] // 2
                return body(params, jax.tree.map(lambda v: v[:, :b], batch),
                            kappa)
            return one_client
        return make

    def off_by_one(real):
        def draw(*a, **kw):
            st, xs, ys = real(*a, **kw)
            return st, xs, (ys + 1) % 100
        return draw

    def local_only(real):
        # each chip aggregates its own rows: no reduction between chips
        def make(fl, mesh=None):
            body = real(fl, None)
            if mesh is None:
                return body
            rows = P(client_axes(mesh))
            return shard_map(body, mesh=mesh,
                             in_specs=(P(), rows, rows, rows, rows, rows,
                                       rows, P()),
                             out_specs=(P(), rows, rows, rows, rows))
        return make

    PLANT = {{
        "frozen": (osafl.StackedOSAFLServer, "round_stacked", stuck),
        "half_batch": (client, "make_local_train_body", half),
        "label": (vs, "_draw_block", off_by_one),
        "no_exchange": (osafl, "make_stacked_round_body", local_only),
    }}

    out = {{}}
    for case in ("sound",) + {faults!r}:
        if case in PLANT:
            where, name, wrap = PLANT[case]
            real = getattr(where, name)
            setattr(where, name, wrap(real))
        del seen[:]
        buf = io.StringIO()
        try:
            with contextlib.redirect_stdout(buf):
                rc = run_cell(tiny(), SimpleNamespace(
                    seed={seed}, seconds=0.5, trace=0), jax.devices(),
                    peaks_for("TPU v5 lite"), "cpu", "")
        finally:
            if case in PLANT:
                setattr(where, name, real)
        res = json.loads(buf.getvalue().strip().splitlines()[-1])
        out[case] = {{"rc": rc, "correct": res["correct"],
                     "checks": res["checks"], "calls": list(seen)}}
    print("RESULT " + json.dumps(out))
""")


@pytest.fixture(scope="module")
def four_device_runs():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = _SUB.format(src=str(ROOT / "src"), root=str(ROOT),
                       faults=FAULTS, seed=SEED)
    r = subprocess.run([sys.executable, "-c", code], env=env, cwd=ROOT,
                       capture_output=True, text=True, timeout=1500)
    assert r.returncode == 0, r.stderr[-4000:]
    line = [x for x in r.stdout.splitlines() if x.startswith("RESULT ")][-1]
    return json.loads(line[len("RESULT "):])


def test_sound_run_on_the_mesh_is_correct(four_device_runs):
    res = four_device_runs["sound"]
    assert res["rc"] == 0
    assert res["correct"] is True, res["checks"]
    assert set(res["checks"]) == _driver("harness").numbers(
        pod4_spec().workload)
    assert res["checks"]["participants_gap"]["value"] == 0
    # every call of the run, the weights' check call too, is on the pod
    # engine over all four devices
    assert len(res["calls"]) == 5
    assert all(c == ["pod", "exact_tp", 4] for c in res["calls"])


@pytest.mark.parametrize("fault", FAULTS)
def test_broken_timed_path_is_caught(four_device_runs, fault):
    res = four_device_runs[fault]
    assert res["rc"] == 0
    assert res["correct"] is False, res["checks"]
