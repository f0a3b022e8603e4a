"""Driver of the one-dispatch round (``repro.core.round_fused.FusedEngine``).

Set-up builds the engine and its first carry as ``repro.harness.run`` does
for the run of the seed (``build_fused_engine``, ``init_carry``), which makes
the run's population, data and weights from the seed. That engine then runs
the first
``check_rounds`` rounds through the same call and feed as the window: one
``run_segment(carry, rounds_per_dispatch)``, the outputs fetched to the host
(``jax.tree.map(np.asarray, outs)``, which syncs), and ``check_outputs``. The
weights before and after them are kept for the comparison. The window then
goes on from that carry for ``--seconds``, timed on the benchmark's own clock.
The host spans ``segment``, ``device_get`` and ``check_outputs`` label the
profiler's trace.
"""
from __future__ import annotations

import gc
import time
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np

from bench.yardstick import compare
from bench.yardstick.program import experiment_config
from bench.yardstick.reference import round_kappas, run_reference
from bench.yardstick.start import start


def numbers(workload: dict) -> set:
    """The names of the numbers this driver compares for ``workload``."""
    return {"loss_gap", "step_gap", "change_gap"}


def build(ctx):
    """The engine and its first carry, as ``repro.harness.run`` builds them
    for a run of the one-dispatch round (``build_fused_engine``,
    ``init_carry``)."""
    from repro.harness.experiments import build_fused_engine

    cfg, U = ctx.cfg, ctx.cfg["num_clients"]
    xc = experiment_config(
        cfg, ctx.seed, ctx.workload["check_rounds"], U,
        round_backend="fused",
        rounds_per_dispatch=ctx.workload["rounds_per_dispatch"])
    engine, s = build_fused_engine("osafl", xc, eval_samples(cfg))
    return engine, engine.init_carry(s.server, s.sbuf, s.rstream, 0)


def eval_samples(cfg: dict) -> int:
    return cfg["num_clients"] * cfg["eval_per_client"]


def run(ctx) -> SimpleNamespace:
    """Set-up, the checked rounds and the window. Returns what the harness
    reports and compares; frees the engine's state before it returns."""
    from repro.core.resource_stacked import ResourceSolveError

    cfg, wl = ctx.cfg, ctx.workload
    k = wl["rounds_per_dispatch"]
    engine, carry = build(ctx)
    failed = 0

    def step(carry):
        nonlocal failed
        with jax.profiler.TraceAnnotation("segment"):
            carry, outs = engine.run_segment(carry, k)
        with jax.profiler.TraceAnnotation("device_get"):
            outs = jax.tree.map(np.asarray, outs)
        with jax.profiler.TraceAnnotation("check_outputs"):
            try:
                engine.check_outputs(outs)
            except ResourceSolveError:
                failed += k
        return carry, outs

    w = [np.asarray(carry.w)]
    loss, participants = [], []
    for _ in range(wl["check_rounds"] // k):
        carry, outs = step(carry)
        loss.extend(float(v) for v in outs["test_loss"])
        participants.extend(int(v) for v in outs["participants"])
        w.append(np.asarray(carry.w))
    prog = {"loss": loss, "w": w, "participants": participants}
    first_round = int(np.asarray(carry.t))
    setup_s = time.perf_counter() - ctx.t_start

    ctx.counter.armed = True
    c0 = ctx.counter.count
    ctx.trace_on()
    t0 = time.perf_counter()
    rounds = 0
    while True:
        carry, outs = step(carry)
        rounds += k
        if time.perf_counter() - t0 >= ctx.seconds:
            break
    jax.block_until_ready(carry)
    window_s = time.perf_counter() - t0
    ctx.trace_off()
    ctx.counter.armed = False
    del carry, outs, engine
    gc.collect()
    return SimpleNamespace(
        setup_s=setup_s, window_s=window_s, rounds=rounds,
        window_rounds=list(range(first_round, first_round + rounds)),
        attempted=rounds, failed=failed, prog=prog,
        compiles_in_window=ctx.counter.count - c0,
        note=f"participants={participants}")


def compare_reference(ctx, res, variant=None) -> dict:
    """The numbers compared, between the program's first ``check_rounds``
    rounds (or, for a control reading, the reference in ``variant``) and the
    plain reference over the same inputs, made again from the seed."""
    args = (ctx.cfg, ctx.model, ctx.seed, ctx.workload["check_rounds"],
            eval_samples(ctx.cfg))
    ref = run_reference(*args)
    if variant is None:
        prog = res.prog
    elif variant == "bf16":
        prog = run_reference(*args, dtype=jnp.bfloat16)
    else:
        prog = run_reference(*args, fault=variant)
    return compare.numbers(prog, ref, ref["sizes"])


def trace_view(ctx, res, tr, tz) -> SimpleNamespace:
    """The traced window, from the first ``segment`` span to the end of the
    last ``check_outputs``, and what the per-layer metrics need of it. The
    kappa_u of each round are the reference's."""
    cfg = ctx.cfg
    lo, hi = tz.window(tr)
    fl = ctx.model.flops_per_sample()
    st = (start(cfg, ctx.seed, cfg["num_clients"], 0, eval_samples(cfg))
          if cfg["use_resource_opt"] else None)
    eval_n = cfg["num_clients"] * cfg["eval_per_client"]
    flops = sum(float(round_kappas(cfg, st, t).sum()) * cfg["batch"]
                * fl["train"] + eval_n * fl["forward"]
                for t in res.window_rounds)
    return SimpleNamespace(lo=lo, hi=hi, rounds=len(res.window_rounds),
                           flops=flops, plane_rows=cfg["num_clients"],
                           request_gen_s=None)
