"""The program's own trace names, on the CPU at tiny sizes.

* Device scopes: every layer of the round (``request_gen``, ``fifo_commit``,
  ``local_sgd``, ``aggregate``, ``eval``, ``resource_solve``) is a
  ``jax.named_scope`` in the traced bodies, so the optimized HLO of the
  one-dispatch segment and of each jitted body of the dispatch round names
  it in the ``op_name`` of its instructions, and no instruction names two.
* Host spans: a ``jax.profiler`` trace of ``repro.harness.run`` on the
  sparse-cohort dispatch round holds one ``osafl.round`` span a round,
  carrying its round index, with each phase span inside it, and one
  ``osafl.slot_reset`` span per admission, counting the slots it reset in a
  ``rows`` stat; the fused round's dispatches carry ``osafl.dispatch``.
* The score kernel's stable name.
"""
from __future__ import annotations

import dataclasses
import json
import os
import re
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import buffer_stacked
from repro.core.client import make_vmapped_local_train
from repro.core.resource_stacked import _make_solver
from repro.data.video_caching_stacked import _draw_block
from repro.harness import ExperimentConfig
from repro.harness.experiments import _stacked_setup, build_fused_engine
from repro.kernels.scored_reduce import scored_reduce

LAYERS = ("request_gen", "fifo_commit", "local_sgd", "aggregate", "eval",
          "resource_solve")
ROOT = Path(__file__).resolve().parents[1]
XC = ExperimentConfig(model="mlp", dataset=2, num_clients=8, rounds=2,
                      capacity=(12, 24), arrivals=4, batch=8, seed=5,
                      request_backend="stacked", resource_backend="f32")


def _op_names(hlo_text: str) -> list:
    return re.findall(r'op_name="([^"]*)"', hlo_text)


def _layers(op_name: str) -> set:
    """The layer scopes among the components of an ``op_name``, a scope
    under a transform keeping the transform's wrapper (``vmap(local_sgd)``)."""
    parts = (re.sub(r"^(?:[\w.]+\()+|\)+$", "", p)
             for p in op_name.split("/"))
    return {p for p in parts if p in LAYERS}


@pytest.fixture(scope="module")
def fused_text():
    engine, s = build_fused_engine(
        "osafl", dataclasses.replace(XC, round_backend="fused"))
    carry = engine.init_carry(s.server, s.sbuf, s.rstream, 0)
    engine.run_segment(carry, 1)
    return engine.compiled_text(1)


@pytest.mark.parametrize("layer", LAYERS)
def test_fused_segment_names_each_layer(fused_text, layer):
    assert any(layer in _layers(n) for n in _op_names(fused_text))


def test_fused_segment_ops_belong_to_one_layer_at_most(fused_text):
    names = _op_names(fused_text)
    assert names and all(len(_layers(n)) <= 1 for n in names)


def test_score_kernel_runs_in_the_aggregate_layer(fused_text):
    assert any("/scored_reduce/" in n and _layers(n) == {"aggregate"}
               for n in _op_names(fused_text))


@pytest.fixture(scope="module")
def setup():
    return _stacked_setup("osafl", XC, 64)


def _draw_block_text(s):
    counts = jnp.full((s.U,), 2, jnp.int32)
    return _draw_block.lower(s.rstream.consts, s.rstream.state, counts,
                             width=XC.arrivals, warmup=0, dataset=2,
                             topk=s.rstream.topk)


def _stage_text(s):
    xs, ys, cnt = s.rstream.draw(np.full(s.U, 2), 2, XC.arrivals)
    return buffer_stacked._stage.lower(s.sbuf.state, xs, ys,
                                       jnp.asarray(cnt, jnp.int32))


def _commit_text(s):
    return buffer_stacked._commit.lower(s.sbuf.state)


def _local_train_text(s):
    step = make_vmapped_local_train(s.grad_fn, s.fl.local_lr,
                                    s.fl.kappa_max)
    slots = s.sbuf.sample_slots(np.random.default_rng(0),
                                (s.fl.kappa_max, XC.batch))
    return step.lower(s.server.params, s.sbuf.gather(slots),
                      jnp.full((s.U,), 2, jnp.int32))


def _aggregate_text(s):
    srv = s.server
    return srv._round_fn.lower(
        srv.w, srv.d_buffer, srv.participated, srv._lam_prev,
        jnp.zeros_like(srv.d_buffer), jnp.ones((s.U,), bool), srv.alphas,
        srv._sketch_key)


def _solve_text(s):
    cols = [jnp.asarray(a, jnp.float32) for a in
            (s.sysb.c, s.sysb.s, s.sysb.f_max, s.sysb.p_max, s.sysb.e_bd)]
    xi = gamma = jnp.ones((s.U,), jnp.float32)
    return _make_solver(dataclasses.astuple(s.net), "f32").lower(
        *cols, xi, gamma, jnp.float32(s.n_params))


@pytest.mark.parametrize("layer,lower", [
    ("request_gen", _draw_block_text),
    ("fifo_commit", _stage_text),
    ("fifo_commit", _commit_text),
    ("local_sgd", _local_train_text),
    ("aggregate", _aggregate_text),
    ("resource_solve", _solve_text),
], ids=["draw_block", "stage", "commit", "local_train", "rnd", "solve"])
def test_dispatch_round_bodies_name_their_layer(setup, layer, lower):
    names = _op_names(lower(setup).compile().as_text())
    assert any(_layers(n) == {layer} for n in names)
    assert all(_layers(n) <= {layer} for n in names)


def test_score_kernel_carries_its_name():
    """The Pallas call is named ``scored_reduce`` (here interpreted, the
    name scopes the interpreter's ops; compiled for a TPU it names the
    Mosaic call, ``tests/test_tpu_compile.py``)."""
    text = scored_reduce.lower(
        jax.ShapeDtypeStruct((16, 4096), jnp.float32),
        jax.ShapeDtypeStruct((4096,), jnp.float32),
        interpret=True).compile().as_text()
    assert any("/scored_reduce/" in n for n in _op_names(text))


_TRACE_CHILD = textwrap.dedent("""
    import dataclasses, glob, json, sys
    import jax
    from jax.profiler import ProfileData
    from repro.core.cohort import SparseCohortServer
    from repro.harness import ExperimentConfig, run

    newly = []                       # users newly seated, per admit() call
    admit = SparseCohortServer.admit

    def counting_admit(self, users):
        res = admit(self, users)
        newly.append(int(res.newly.sum()))
        return res

    SparseCohortServer.admit = counting_admit
    xc = ExperimentConfig(**json.loads(sys.argv[2]))
    fused = dataclasses.replace(xc, cohort_size=0, participation=1.0,
                                round_backend="fused")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with jax.profiler.trace(sys.argv[1], profiler_options=opts):
        run("osafl", xc)
        run("osafl", fused)
    path = glob.glob(sys.argv[1] + "/**/*.xplane.pb", recursive=True)[0]
    spans, resets = [], []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("osafl."):
                        spans.append([ev.name, ev.start_ns, ev.duration_ns,
                                      dict(ev.stats).get("step_num")])
                    if ev.name == "osafl.slot_reset":
                        resets.append([ev.start_ns, ev.duration_ns,
                                       dict(ev.stats).get("rows")])
    print(json.dumps({"slot_resets": sorted(resets), "newly": newly}))
    print(json.dumps(spans))
""")

ROUND_PHASES = ("osafl.admit", "osafl.arrivals", "osafl.request_draw",
                "osafl.fifo_commit", "osafl.resource_solve",
                "osafl.slot_sample", "osafl.local_sgd", "osafl.aggregate",
                "osafl.eval", "osafl.sync")


@pytest.fixture(scope="module")
def traced_child(tmp_path_factory):
    """The output lines of a profiled 2-round cohort run and a 2-round
    fused run, traced in a child process of its own (the profiler is one
    per process) under a time limit."""
    xc = dataclasses.replace(XC, num_clients=16, cohort_size=8,
                             participation=0.5)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join(
                   [str(ROOT / "src"), os.environ.get("PYTHONPATH", "")]))
    proc = subprocess.run(
        [sys.executable, "-c", _TRACE_CHILD,
         str(tmp_path_factory.mktemp("trace")),
         json.dumps(dataclasses.asdict(xc))],
        capture_output=True, text=True, env=env, timeout=300)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return [json.loads(ln) for ln in proc.stdout.strip().splitlines()[-2:]]


@pytest.fixture(scope="module")
def traced_spans(traced_child):
    """The ``osafl.*`` host spans of the traced runs: name, start, duration
    and the ``step_num`` stat."""
    return traced_child[1]


@pytest.fixture(scope="module")
def traced_slot_resets(traced_child):
    """The ``osafl.slot_reset`` spans of the traced cohort run (start,
    duration, ``rows`` stat) in time order, and the number of users each
    ``SparseCohortServer.admit`` call seated anew, in call order."""
    return traced_child[0]


def test_trace_holds_a_round_span_per_round_with_its_index(traced_spans):
    rounds = [s for s in traced_spans if s[0] == "osafl.round"]
    assert [s[3] for s in rounds] == [0, 1]
    assert all("#" not in s[0] for s in traced_spans)


def test_each_phase_span_lies_inside_its_round(traced_spans):
    rounds = [s for s in traced_spans if s[0] == "osafl.round"]
    for _, start, dur, _ in rounds:
        inside = [s[0] for s in traced_spans if s[0] in ROUND_PHASES
                  and start <= s[1] and s[1] + s[2] <= start + dur]
        assert sorted(inside) == sorted(ROUND_PHASES)
    assert sum(s[0] in ROUND_PHASES for s in traced_spans) == \
        len(rounds) * len(ROUND_PHASES)


def test_fused_dispatches_carry_their_span(traced_spans):
    assert sum(s[0] == "osafl.dispatch" for s in traced_spans) == 2


def test_each_admission_resets_its_newly_seated_slots_in_one_span(
        traced_spans, traced_slot_resets):
    """One ``osafl.slot_reset`` span per admission: the set-up's seating of
    the first residents, then one inside each round's ``osafl.admit``, its
    ``rows`` stat the number of users that call seated anew."""
    resets = traced_slot_resets["slot_resets"]
    assert [r[2] for r in resets] == traced_slot_resets["newly"]
    rounds = [s for s in traced_spans if s[0] == "osafl.round"]
    admits = [s for s in traced_spans if s[0] == "osafl.admit"]
    assert len(resets) == len(rounds) + 1
    for _, start, dur, _ in rounds:
        (adm,) = [a for a in admits
                  if start <= a[1] and a[1] + a[2] <= start + dur]
        inside = [r for r in resets
                  if adm[1] <= r[0] and r[0] + r[1] <= adm[1] + adm[2]]
        assert len(inside) == 1
