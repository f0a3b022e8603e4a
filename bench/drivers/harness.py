"""Driver of the host-driven round: ``repro.harness.run`` whole, since the
dispatch round has no per-round step of its own to call.

* a first warm-up call of ``warmup_rounds`` rounds compiles the run's
  programs, or loads them from JAX's persistent cache;
* further warm-up calls run ``warmup_rounds`` + R' rounds, R' half as many
  again as the last per-round time says ``--seconds`` holds, until the
  median time of the rounds past the first ``warmup_rounds`` says that
  R <= R' rounds last ``--seconds``. Slot admission seats a different number
  of new users each round: the slot reset compiles once, but the FIFO's
  ``reset_rows`` runs eager scatters that compile for each new number of
  seated users. The run of a seed is the same in every call, so the last of
  these calls has met every shape the window will. Set-up ends with it;
* call A runs ``warmup_rounds`` rounds and call B ``warmup_rounds`` + R. The
  window is wall(B) - wall(A): exactly the R rounds that B runs beyond A,
  the harness's own loop and host draws included, each call's set-up
  cancelled by the other's. No per-round tail can be had this way.

The workload's ``harness`` block gives the traffic; with a ``mesh`` (shape
and axis names) and a ``pod_engine`` every call runs the pod engine on that
mesh over the cell's chips, and without them every call is
``run("osafl", xc, eval_samples=...)`` on the stacked engine.

Each call starts the run of the same seed afresh, so B's first rounds are
the run's first rounds: the comparison reads them from B's history. Where
the workload sets ``compare_weights``, a check call after the window runs
the first ``check_rounds`` rounds again and copies the global weights to
the host before the first round and after each server round: the weights
stay inside ``repro.harness.run``, and its snapshots would write the whole
contribution buffer and FIFO storage to disk each time. In a traced run the
profiler records B alone, under the host span ``harness_run``; the window
there runs from the start of the local-SGD program of B's first round past
``warmup_rounds`` to the start of that of B's last round, and the local-SGD
program is the one that runs once a round and takes the most device time.
On a mesh the programs are read from the first chip's trace, where every
program of the round runs.
"""
from __future__ import annotations

import dataclasses
import math
import statistics
import time
from types import SimpleNamespace

import jax
import numpy as np

from bench.yardstick import compare, program
from bench.yardstick.cohort import run_cohort_reference

HISTORY_NUMBERS = {"loss_gap", "participants_gap"}
WEIGHT_NUMBERS = {"step_gap", "change_gap"}


def numbers(workload: dict) -> set:
    """The names of the numbers this driver compares for ``workload``."""
    if workload.get("compare_weights"):
        return HISTORY_NUMBERS | WEIGHT_NUMBERS
    return HISTORY_NUMBERS


def experiment_config(ctx, rounds: int):
    h = ctx.workload["harness"]
    return program.experiment_config(
        ctx.cfg, ctx.seed, rounds, h["num_users"],
        engine="pod" if "mesh" in h else "stacked",
        round_backend=h["round_backend"], cohort_size=h["cohort_size"],
        participation=h["participation"])


def run_kwargs(h: dict) -> dict:
    """The keywords of every ``repro.harness.run`` call beyond the config:
    none, or the workload's mesh and pod engine."""
    if "mesh" not in h:
        return {}
    from repro.core.shmap import make_mesh
    m = h["mesh"]
    return {"mesh": make_mesh(tuple(m["shape"]), tuple(m["axes"])),
            "pod_engine": h["pod_engine"]}


def read_weights(fl_run, xc, kw: dict, ev: int) -> list:
    """The flat global weights of the run of ``xc`` before its first round
    and after each of its rounds, as the server holds them."""
    from repro.core.osafl import StackedOSAFLServer

    real = StackedOSAFLServer.round_stacked
    seen = []

    def reading(self, *a, **k):
        if not seen:
            seen.append(np.asarray(self.w, np.float32))
        w = real(self, *a, **k)
        seen.append(np.asarray(w, np.float32))
        return w

    StackedOSAFLServer.round_stacked = reading
    try:
        fl_run("osafl", xc, eval_samples=ev, **kw)
    finally:
        StackedOSAFLServer.round_stacked = real
    return seen


def run(ctx) -> SimpleNamespace:
    """Set-up, calls A and B, and the weights' check call where the
    workload asks for it. Returns what the harness reports and compares."""
    from repro.harness import run as fl_run

    wl = ctx.workload
    w, ev = wl["warmup_rounds"], wl["harness"]["eval_samples"]
    kw = run_kwargs(wl["harness"])
    xc = experiment_config(ctx, w)
    hist = fl_run("osafl", xc, eval_samples=ev, **kw)
    # Every program the run keeps is in JAX's persistent cache now. What
    # compiles from here on in under a second is not written there: the
    # round's evaluation compiles anew each round (its weights are
    # constants of the program) and would only fill the cache.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)
    covered = 0
    while True:
        per_round = statistics.median(r["round_s"] for r in hist[-3:])
        extra = max(2, math.ceil(ctx.seconds / per_round))
        if extra <= covered:
            break
        covered = max(2, math.ceil(1.5 * ctx.seconds / per_round))
        hist = fl_run("osafl", dataclasses.replace(xc, rounds=w + covered),
                      eval_samples=ev, **kw)[w:]
    xc_b = dataclasses.replace(xc, rounds=w + extra)
    setup_s = time.perf_counter() - ctx.t_start

    counter = ctx.counter
    counter.armed = True
    c0 = counter.count
    t0 = time.perf_counter()
    fl_run("osafl", xc, eval_samples=ev, **kw)
    wall_a = time.perf_counter() - t0
    c_a = counter.count - c0

    ctx.trace_on()
    c0 = counter.count
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation("harness_run"):
        hist_b = fl_run("osafl", xc_b, eval_samples=ev, **kw)
    wall_b = time.perf_counter() - t0
    ctx.trace_off()
    counter.armed = False
    c_b = counter.count - c0
    weights = (read_weights(fl_run, dataclasses.replace(
        xc, rounds=wl["check_rounds"]), kw, ev)
        if wl.get("compare_weights") else None)
    return SimpleNamespace(
        setup_s=setup_s, window_s=wall_b - wall_a, rounds=extra,
        attempted=extra, failed=0, history=hist_b, weights=weights,
        compiles_in_window=c_b - c_a,
        note=f"wall_a={wall_a:.3f} wall_b={wall_b:.3f} "
             f"compile_events_a={c_a} compile_events_b={c_b}")


def compare_reference(ctx, res, variant=None) -> dict:
    """The numbers compared, between the run's first ``check_rounds`` rounds
    (or, for a control reading, the reference in ``variant``) and the plain
    reference."""
    import jax.numpy as jnp

    wl, n = ctx.workload, ctx.workload["check_rounds"]
    args = (ctx.cfg, wl["harness"], ctx.model, ctx.seed, n)
    ref = run_cohort_reference(*args)
    if variant is None:
        prog = {"loss": [r["test_loss"] for r in res.history[:n]],
                "participants": [r["participants"]
                                 for r in res.history[:n]],
                "w": res.weights}
    elif variant == "bf16":
        prog = run_cohort_reference(*args, dtype=jnp.bfloat16)
    else:
        prog = run_cohort_reference(*args, fault=variant)
    nums = compare.history_numbers(prog, ref)
    if wl.get("compare_weights"):
        nums.update(compare.numbers(prog, ref, ref["sizes"]))
    return nums


def trace_view(ctx, res, tr, tz) -> SimpleNamespace | None:
    """The traced window and what the per-layer metrics need of it."""
    w, cfg, h = ctx.workload["warmup_rounds"], ctx.cfg, ctx.workload["harness"]
    total = w + res.rounds
    starts = tz.round_starts(tr["modules"][0], total)
    if starts is None or res.rounds < 2:
        return None
    lo, hi = starts[w], starts[total - 1]
    rounds = res.history[w:total - 1]
    fl = ctx.model.flops_per_sample()
    eval_n = h["num_users"] * max(h["eval_samples"] // h["num_users"], 4)
    flops = sum(r["participants"] * cfg["kappa_max"] * cfg["batch"]
                * fl["train"] + eval_n * fl["forward"] for r in rounds)
    return SimpleNamespace(
        lo=lo, hi=hi, rounds=len(rounds), flops=flops,
        plane_rows=h["cohort_size"] or h["num_users"],   # all chips' rows
        request_gen_s=[r["request_gen_s"] for r in res.history[w:total]])
