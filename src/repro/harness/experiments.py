"""The unified FL-experiment harness (moved from ``benchmarks/common.py``).

One facade, ``run(alg, xc)``, fronts every engine the repo has grown:

  * ``loop`` — the paper-faithful per-client oracle (v1 blocking snapshots;
    the write-path anchor for v1→v2 checkpoint read compat);
  * ``stacked`` — the vectorized (U, N) engine, with ``round_backend="fused"``
    folding whole rounds into one device dispatch and
    ``cohort_size``/``participation`` switching on the sparse slot-pool
    engine;
  * ``pod`` — the mesh-sharded online harness (``pod_engine`` flavors);
  * ``centralized`` — the pooled-data genie baseline.

``ExperimentConfig.engine`` picks one (``"auto"`` = pod when a mesh is
passed, stacked otherwise); ``repro.harness.compat`` owns the declarative
compatibility matrix that used to live as scattered ``ValueError``s in the
four ``run_*`` entry points. Those old entry points survive as thin
deprecation shims at the bottom of this module (and re-exported from
``benchmarks.common``) so existing callers keep working.

Hierarchy: ``xc.num_clusters`` routes the stacked/pod server through the
two-tier edge-cluster aggregation in ``core/hierarchy.py`` (K=1 is the
bit-exact flat-parity anchor); on the sparse engine the slot pool becomes K
per-cluster blocks, participation sampling stratifies over the live cluster
map, and the ``cluster_churn`` scenario drives membership moves.

Tracing: the host loops of the dispatch round (stacked and pod engines)
mark each round with ``StepTraceAnnotation("osafl.round", step_num=t)`` and
its phases with ``TraceAnnotation("osafl.<phase>")`` spans (``admit``,
``arrivals``, ``request_draw``, ``fifo_commit``, ``resource_solve``,
``slot_sample``, ``local_sgd``, ``aggregate``, ``eval``, ``sync``); the
device programs name their layers with ``jax.named_scope`` (see
``core/round_fused.py``). Both land in a ``jax.profiler`` trace of a
``run()`` call and record nothing while no profiler is running.
"""
from __future__ import annotations

import dataclasses
import logging
import time
from dataclasses import dataclass
from pathlib import Path
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import StepTraceAnnotation, TraceAnnotation

from repro import checkpoint
from repro.checkpoint import CheckpointError
from repro.configs.base import FLConfig
from repro.core.baselines import make_server
from repro.core.buffer import OnlineBuffer, binomial_arrivals
from repro.core.buffer_stacked import StackedOnlineBuffer
from repro.core.client import local_train, make_vmapped_local_train
from repro.core.cohort import sample_participants
from repro.core.hierarchy import sample_participants_clustered
from repro.core.osafl import ClientUpdate
from repro.core.pod import (make_fedavg_train_step, make_pod_batch_fn,
                            make_recompute_train_step,
                            make_stale_score_train_step, make_tp_train_step)
from repro.core.resource import (NetworkConfig, make_clients, optimize_round)
from repro.core.resource_stacked import optimize_round_batched, stack_clients
from repro.core.round_fused import FusedEngine
from repro.core.shmap import client_rows, make_mesh
from repro.data.online import (binomial_arrivals_batched, dataset_layout,
                               draw_arrival_batch, load_streams_state,
                               pad_arrival_batch, streams_state_dict)
from repro.data.video_caching import make_population
from repro.data.video_caching_stacked import StackedRequestStream
from repro.harness.compat import (ALL_ALGS, POD_ENGINES,
                                  ExperimentConfigError, ResolvedPlan,
                                  resolve)
from repro.models.small import init_small, small_loss
from repro.scenarios import parse_scenario

_LOG = logging.getLogger("repro.harness")

MODEL_PARAMS = {"fcn": 3_900_000, "cnn": 1_100_000, "squeezenet": 740_000,
                "lstm": 430_000, "mlp": 18_000}


# ---------------------------------------------------------------------------
# checkpoint/resume plumbing (RunState snapshots — see DESIGN.md)
# ---------------------------------------------------------------------------

def checkpoint_path(checkpoint_dir, t: int) -> Path:
    """Canonical snapshot location for the state after round t (1-based:
    a snapshot named round_00003 holds the state with rounds 0-2 done)."""
    return Path(checkpoint_dir) / f"round_{t:05d}"


def _validate_ckpt_args(save_every_k, checkpoint_dir,
                        keep_last=None) -> None:
    if bool(save_every_k) != (checkpoint_dir is not None):
        raise ValueError(
            "save_every_k and checkpoint_dir must be passed together "
            f"(got save_every_k={save_every_k!r}, "
            f"checkpoint_dir={checkpoint_dir!r})")
    if keep_last is not None:
        if not save_every_k:
            raise ValueError(
                "keep_last requires save_every_k/checkpoint_dir (there is "
                "nothing to prune without periodic snapshots)")
        if not isinstance(keep_last, int) or keep_last < 1:
            raise ValueError(
                f"keep_last must be a positive int, got {keep_last!r}")


def _make_ckpt_writer(save_every_k, checkpoint_async: bool, keep_last):
    """The harness's checkpoint writer, or None when checkpointing is off.
    Async (default) = the v2 per-shard background writer: ``submit`` on the
    round loop only walks the state tree, ``close()`` at harness exit is
    the drain barrier that makes resume determinism hold. Blocking = the
    synchronous v1 npz path (the write oracle ``bench_serve.py`` measures
    the async writer against, and the harness-level v1→v2 read-compat
    anchor)."""
    if not save_every_k:
        return None
    if checkpoint_async:
        return checkpoint.AsyncCheckpointWriter(keep_last=keep_last)
    return checkpoint.BlockingCheckpointWriter(keep_last=keep_last)


def _run_shape(xc: "ExperimentConfig", eval_samples: int) -> dict:
    """Everything that must match between the saving and the resuming run
    for the trajectory to continue bit-exactly: the whole ExperimentConfig
    (resume re-derives population/capacities/test set/system params from
    it) except ``rounds`` — resuming into a longer run is the point — and
    except the engine-selection fields ``engine``/``pod_engine`` (the
    executing engine is the snapshot's top-level ``engine`` tag, and the pod
    flavor lives in the pod harness's mesh-layout extra — both already
    compared; a deprecation-shim run pins ``engine`` while a ``run()`` call
    may leave it ``"auto"``, and the two must stay mutually resumable) —
    plus the eval set size. JSON-normalized so it compares against a loaded
    snapshot."""
    cfg = dataclasses.asdict(xc)
    cfg.pop("rounds")
    cfg.pop("engine")
    cfg.pop("pod_engine")
    cfg["capacity"] = list(cfg["capacity"])
    cfg["eval_samples"] = int(eval_samples)
    return cfg


def _check_snapshot(snap: dict, engine: str, alg: str,
                    xc: "ExperimentConfig", eval_samples: int,
                    extra: dict = None) -> None:
    """A snapshot is only resumable into the exact run shape it came from.
    Config fields added after a snapshot was written are absent from its
    saved config; such a run behaved like the field's default, so the
    default is what the snapshot is compared as (keeps pre-existing
    checkpoints resumable when ExperimentConfig grows). ``extra`` holds
    harness-specific shape keys outside ExperimentConfig (the pod harness's
    engine flavor + mesh layout), compared with no default-filling."""
    got = dict(snap.get("config") or {}, engine=snap.get("engine"),
               alg=snap.get("alg"))
    want = dict(_run_shape(xc, eval_samples), engine=engine, alg=alg,
                **(extra or {}))
    base = dataclasses.asdict(ExperimentConfig())
    for k in want:                  # _run_shape owns which fields compare
        if k not in got and k in base:
            got[k] = (list(base[k]) if isinstance(base[k], tuple)
                      else base[k])
    bad = sorted(k for k in set(got) | set(want)
                 if got.get(k) != want.get(k))
    if bad:
        raise CheckpointError(
            "cannot resume: snapshot and run disagree on "
            + ", ".join(f"{k} ({got.get(k)!r} vs {want.get(k)!r})"
                        for k in bad))
    if int(snap["next_round"]) > xc.rounds:
        raise CheckpointError(
            f"snapshot already holds {snap['next_round']} rounds, the run "
            f"asks for {xc.rounds}")


def resume_smoke_config(rounds: int, num_clients: int = 8
                        ) -> "ExperimentConfig":
    """Canonical small online run for the resume-determinism checks — one
    definition shared by tests/test_checkpoint_resume.py and the CI smoke
    tools/resume_smoke.py so they always cover the same run shape."""
    return ExperimentConfig(model="mlp", dataset=2, num_clients=num_clients,
                            rounds=rounds, capacity=(12, 24), arrivals=4,
                            batch=8, seed=5)


@dataclass
class ExperimentConfig:
    model: str = "fcn"
    dataset: int = 1                  # 1 | 2
    num_clients: int = 12
    rounds: int = 25
    capacity: tuple = (80, 160)       # D_u range (reduced from paper 320-640)
    arrivals: int = 8                 # E_u (paper: ceil(32 p_u))
    local_lr: float = 0.1
    global_lr: float = 16.0   # paper tunes 20-35; 16 is stable at T=25
    batch: int = 16
    topk: int = 1                     # K (request-model randomness)
    seed: int = 0
    use_resource_opt: bool = True
    engine: str = "auto"              # auto | loop | stacked | pod |
                                      # centralized — which harness run()
                                      # dispatches to. "auto" = pod when a
                                      # mesh is passed, stacked otherwise
                                      # ("centralized" as the alg forces the
                                      # genie). The deprecated run_* shims
                                      # pin it.
    pod_engine: str = "exact_tp"      # pod local-train flavor (POD_ENGINES);
                                      # consulted on the pod engine only
    request_backend: str = "python"   # python (per-user oracle streams) |
                                      # stacked (batched Gumbel-trick sampler,
                                      # stacked/pod engines only)
    round_backend: str = "dispatch"   # dispatch (multi-program round) |
                                      # fused (one-dispatch device-resident
                                      # round, core/round_fused.py; requires
                                      # alg=osafl + request_backend=stacked,
                                      # stacked engine only)
    resource_backend: str = "x64"     # x64 (scoped-f64 parity oracle) |
                                      # f32 (log-domain, accelerator-native)
    rounds_per_dispatch: int = 1      # fused backend: rounds folded into one
                                      # device dispatch between eval/
                                      # checkpoint boundaries
    cohort_size: int = 0              # C: sparse active-slot pool capacity
                                      # (core/cohort.py). 0 = dense (every
                                      # registered user materialized); >0 =
                                      # only C slots are round-live and
                                      # per-user tables carry the rest.
                                      # cohort_size=num_clients is bit-exact
                                      # vs the dense engines (the parity
                                      # anchor, tests/test_cohort.py).
    participation: float = 1.0        # round-active fraction of the pool
                                      # (Dinh et al. partial participation;
                                      # <1 needs cohort_size>0)
    num_clusters: int = 0             # K: hierarchical edge-cluster
                                      # aggregation (core/hierarchy.py).
                                      # 0 = flat PS (historical path); 1 =
                                      # one cluster through the two-tier
                                      # round body (bit-exact vs flat — the
                                      # parity anchor, tests/
                                      # test_hierarchy.py); >1 = K edge
                                      # clusters score-reduce locally and
                                      # the PS combines the K aggregates
                                      # with cluster-level eq. 19-21 scores.
                                      # Stacked/pod engines, dispatch round
                                      # only; K must divide num_clients (and
                                      # cohort_size when the pool is on).
    cell_radius_m: float = 600.0      # milder than Fig.3's 1 km so the
                                      # reduced-round runs see participants
    scenario: str = ""                # wireless-world scenario spec
                                      # (src/repro/scenarios/): "" = none,
                                      # "null" = empty scenario through the
                                      # hook plumbing (bit-exact vs ""),
                                      # else "+"-composed named
                                      # perturbations seeded by xc.seed.
                                      # Stacked/pod engines only; the fused
                                      # round, the loop oracle and the genie
                                      # accept only ""/"null".

    def validate(self, alg: str = "osafl", mesh=None) -> ResolvedPlan:
        """Check this config against the compatibility matrix
        (``repro.harness.compat.RULES``) for algorithm ``alg`` and return
        the resolved plan; raises ``ExperimentConfigError`` (a
        ``ValueError``) naming the first violated rule."""
        return resolve(alg, self, mesh=mesh)


def _draw(stream, n, dataset):
    return (stream.draw_dataset1(n) if dataset == 1
            else stream.draw_dataset2(n))


# ---------------------------------------------------------------------------
# engine bodies (validated: run() resolves the plan before dispatching here)
# ---------------------------------------------------------------------------

def _run_loop(alg: str, xc: "ExperimentConfig", eval_samples: int,
              save_every_k, checkpoint_dir, resume_from, keep_last):
    """The per-client loop-oracle engine (see ``run_experiment``). Always
    writes synchronous v1 snapshots — it is the write-path anchor for v1→v2
    checkpoint read compat."""
    model = xc.model
    cat, streams = make_population(xc.seed, xc.num_clients, topk=xc.topk)
    rng = np.random.default_rng(xc.seed)
    feat_shape, dtype = dataset_layout(xc.dataset)
    bufs = []
    for s in streams:
        cap = int(rng.integers(*xc.capacity))
        buf = OnlineBuffer.create(cap, feat_shape, 100, dtype=dtype)
        x, y = _draw(s, cap, xc.dataset)
        buf.stage(x, y)
        buf.commit()
        bufs.append(buf)
    # online evaluation: the clients' own *future* requests (paper setting —
    # predicting an unseen user's preference-driven stream is not the task)
    per = max(eval_samples // xc.num_clients, 20)
    tests = [_draw(s, per, xc.dataset) for s in streams]
    tx = np.concatenate([t[0] for t in tests])
    ty = np.concatenate([t[1] for t in tests])
    test_batch = {"x": jnp.asarray(tx), "y": jnp.asarray(ty)}

    grad_fn = jax.grad(lambda p, b: small_loss(p, b, model)[0])
    params = init_small(jax.random.PRNGKey(xc.seed), model)
    glr = xc.global_lr if alg in ("osafl", "afa_cd") else 1.0
    fl = FLConfig(num_clients=xc.num_clients, local_lr=xc.local_lr,
                  global_lr=glr, algorithm=alg)
    server = make_server(params, fl, xc.num_clients, seed=xc.seed)

    net = NetworkConfig()
    clients_sys = make_clients(rng, xc.num_clients,
                               cell_radius_m=xc.cell_radius_m)
    n_params = MODEL_PARAMS.get(model, 1_000_000)

    writer = _make_ckpt_writer(save_every_k, False, keep_last)
    history, start_round = [], 0
    if resume_from is not None:
        snap = checkpoint.load_run_state(resume_from)
        _check_snapshot(snap, "loop", alg, xc, eval_samples)
        checkpoint.set_generator_state(rng, snap["rng"])
        server.load_state_dict(snap["server"])
        for b, sd in zip(bufs, snap["buffers"]):
            b.load_state_dict(sd)
        load_streams_state(streams, snap["streams"])
        history = list(snap["history"])
        start_round = int(snap["next_round"])
    for t in range(start_round, xc.rounds):
        t_start = time.perf_counter()
        if xc.use_resource_opt:
            decisions = optimize_round(rng, net, clients_sys, n_params)
        updates = []
        for c, s in enumerate(streams):
            n = binomial_arrivals(rng, xc.arrivals, s.user.p_ac)
            if n:
                x, y = _draw(s, n, xc.dataset)
                bufs[c].stage(x, y)
            bufs[c].commit()
            kappa = decisions[c].kappa if xc.use_resource_opt else 5
            if kappa < 1:
                continue                      # straggler
            d, w = local_train(
                server.params, grad_fn, bufs[c], kappa, fl.local_lr,
                xc.batch, rng,
                prox_mu=fl.fedprox_mu if alg == "fedprox" else 0.0)
            upd = d if alg in ("osafl", "fednova", "afa_cd") else w
            updates.append(ClientUpdate(
                c, upd, kappa, data_size=bufs[c].size,
                label_hist=bufs[c].label_histogram()))
        server.round(updates)
        loss, m = small_loss(server.params, test_batch, model)
        history.append({"round": t, "test_loss": float(loss),
                        "test_acc": float(m["accuracy"]),
                        "participants": len(updates),
                        "round_s": time.perf_counter() - t_start})
        if save_every_k and (t + 1) % save_every_k == 0:
            writer.submit(
                checkpoint_path(checkpoint_dir, t + 1),
                {"engine": "loop", "alg": alg,
                 "config": _run_shape(xc, eval_samples), "next_round": t + 1,
                 "rng": checkpoint.generator_state(rng),
                 "server": server.state_dict(),
                 "buffers": [b.state_dict() for b in bufs],
                 "streams": streams_state_dict(streams),
                 "history": history},
                metadata={"engine": "loop", "alg": alg, "round": t + 1})
    return history


def _stacked_setup(alg: str, xc: "ExperimentConfig", eval_samples: int,
                   mesh=None, stale_scores: bool = False) -> SimpleNamespace:
    """Deterministic run setup shared by the stacked and pod engine bodies:
    population + request streams, capacities, FIFO-buffer initial fill, eval
    set, params/server, system params. One code path so the two harnesses
    consume the host RNG in exactly the same order — the 1-device-mesh
    metric parity between them rests on it. The only knobs that differ are
    ``mesh`` (the pod harness shards the buffer) and ``stale_scores`` (the
    pod stale engine's server-side score lag); neither touches an RNG.
    Config compatibility is the caller's job (``run()`` resolves the plan
    before dispatching; ``build_fused_engine`` resolves its fused shape)."""
    stacked_req = xc.request_backend == "stacked"
    model = xc.model
    U = xc.num_clients
    sparse = xc.cohort_size > 0
    C = xc.cohort_size if sparse else U
    K = int(xc.num_clusters)
    # scenario layer: pure seeded perturbation schedule (hooks fire only when
    # a perturbation applies, so ""/"null" keep the historical code path —
    # the null-parity anchor, tests/test_scenarios.py)
    scn = parse_scenario(xc.scenario, seed=xc.seed)
    if scn is not None:
        scn.bind(U)
    arr_width = scn.arrival_width(xc.arrivals) if scn else xc.arrivals
    cat, streams = make_population(xc.seed, U, topk=xc.topk)
    rstream = (StackedRequestStream.from_streams(cat, streams, seed=xc.seed)
               if stacked_req else None)
    rng = np.random.default_rng(xc.seed)
    feat_shape, dtype = dataset_layout(xc.dataset)
    lo, hi = xc.capacity
    caps = rng.integers(lo, max(hi, lo + 1), size=U)
    if scn is not None:
        caps = scn.setup_capacities(caps)
    server_fl = FLConfig(num_clients=U, local_lr=xc.local_lr,
                         global_lr=(xc.global_lr
                                    if alg in ("osafl", "afa_cd") else 1.0),
                         algorithm=alg, engine="stacked",
                         request_backend=xc.request_backend,
                         round_backend=xc.round_backend,
                         resource_backend=xc.resource_backend,
                         cohort_size=xc.cohort_size,
                         participation=xc.participation,
                         num_clusters=K,
                         scenario=xc.scenario,
                         stale_scores=stale_scores)
    server = make_server(init_small(jax.random.PRNGKey(xc.seed), xc.model),
                         server_fl, U, seed=xc.seed, mesh=mesh)
    if sparse:
        # initial residents: the first C users in slot order — under
        # hierarchy, the first C/K members of each cluster so every block
        # starts full (== arange(C) at K<=1 with the contiguous static map,
        # the dense-parity and flat-parity anchors)
        server.admit(server.initial_residents())
    cohort0 = server.cohort if sparse else np.arange(U)
    sbuf = StackedOnlineBuffer.create(
        caps[cohort0] if sparse else caps, feat_shape, 100,
        stage_capacity=arr_width, dtype=dtype, mesh=mesh,
        # slot storage must fit any later-admitted resident's capacity
        depth=int(caps.max()) if sparse else None)
    # initial fill (residents only): FIFO commits compose, so ingest the
    # cap_u seed samples in arrival-width chunks rather than sizing the
    # staging area (kept for the whole run) for caps.max()
    if stacked_req:
        filled = np.zeros(U, np.int64)
        target = np.zeros(U, np.int64)
        target[cohort0] = caps[cohort0]
        while (filled < target).any():
            chunk = np.minimum(target - filled, xc.arrivals)
            xs, ys, cnt = rstream.draw(chunk, xc.dataset, xc.arrivals)
            sbuf.stage(xs[cohort0], ys[cohort0], cnt[cohort0])
            sbuf.commit()
            filled += chunk
    else:
        init = [_draw(streams[u], int(caps[u]), xc.dataset) for u in cohort0]
        for off in range(0, int(caps[cohort0].max()), xc.arrivals):
            chunk = [(x[off:off + xc.arrivals], y[off:off + xc.arrivals])
                     if off < len(y) else None for x, y in init]
            sbuf.stage(*pad_arrival_batch(chunk, xc.arrivals, xc.dataset))
            sbuf.commit()
    p_ac = np.array([s.user.p_ac for s in streams])

    per = max(eval_samples // U, 4)
    if stacked_req:
        ex, ey, _ = rstream.draw(np.full(U, per), xc.dataset, per)
        test_batch = {"x": ex.reshape((U * per,) + ex.shape[2:]),
                      "y": ey.reshape(U * per)}
    else:
        tests = [_draw(s, per, xc.dataset) for s in streams]
        test_batch = {
            "x": jnp.asarray(np.concatenate([t[0] for t in tests])),
            "y": jnp.asarray(np.concatenate([t[1] for t in tests]))}

    grad_fn = jax.grad(lambda p, b: small_loss(p, b, model)[0])
    fl = server_fl

    net = NetworkConfig()
    sysb = stack_clients(make_clients(rng, U,
                                      cell_radius_m=xc.cell_radius_m))
    if scn is not None:
        sysb = scn.setup_system(sysb)
    n_params = MODEL_PARAMS.get(model, 1_000_000)
    return SimpleNamespace(
        stacked_req=stacked_req, model=model, U=U, streams=streams,
        rstream=rstream, rng=rng, caps=caps, sbuf=sbuf, p_ac=p_ac,
        test_batch=test_batch, grad_fn=grad_fn, fl=fl, server=server,
        scn=scn, arr_width=arr_width,
        codec=server.codec,
        weights_alg=alg in ("fedavg", "fedprox", "feddisco"),
        prox_mu=fl.fedprox_mu if alg == "fedprox" else 0.0,
        net=net, sysb=sysb, n_params=n_params,
        # sparse-cohort bookkeeping (dense: sparse=False, C=U, no resample).
        # m_active is the flat participation target; the clustered sampler
        # draws ceil(m * n_k / U) per cluster, so a K-cluster round seats at
        # most m + K - 1 users (one rounding unit per cluster)
        sparse=sparse, C=C, K=K,
        m_active=max(1, int(round(xc.participation * C))),
        resample=sparse and (C < U or xc.participation < 1.0))


def _resume_stacked(s: SimpleNamespace, snap: dict) -> tuple:
    """Overwrite the deterministic setup's mutable state from a RunState
    snapshot (shared by the stacked and pod engine bodies; the caller has
    already ``_check_snapshot``-ed it)."""
    checkpoint.set_generator_state(s.rng, snap["rng"])
    s.server.load_state_dict(snap["server"])
    s.sbuf.load_state_dict(snap["buffer"])
    if s.stacked_req:
        s.rstream.load_state_dict(snap["streams"])
    else:
        load_streams_state(s.streams, snap["streams"])
    return list(snap["history"]), int(snap["next_round"])


def _gather_sys(sysb, rows):
    """Cohort rows of a ``ClientSystemBatch`` (every field is (U,))."""
    return dataclasses.replace(
        sysb, **{f.name: getattr(sysb, f.name)[rows]
                 for f in dataclasses.fields(sysb)})


def _draw_round_inputs(s: SimpleNamespace, xc: "ExperimentConfig",
                       t: int) -> tuple:
    """One round of host-side draws, in the canonical order: (sparse only)
    scenario cluster moves + the round-active cohort sample + slot-pool
    admissions, then arrival counts + samples (staged and committed FIFO),
    the resource-optimizer kappas, the straggler mask, and the local-SGD
    batch slots. Returns ``(req_s, kappas, active, slots)`` — all arrays
    slot-indexed (width C; the dense path is the C = U identity). At
    cohort_size=num_clients with full participation the sparse branch
    consumes the host RNG in exactly the dense order (identity gathers, no
    cohort sample), which is what makes the parity anchor bit-exact.

    The scenario layer (``s.scn``, src/repro/scenarios/) perturbs this
    round's inputs at five points — the cluster map (hierarchical runs:
    membership churn, scenario-RNG only), the participation sample
    (availability masks + selection weights), the arrival process (E_u /
    p_ac), the resource-config rows, and the final active mask. Scenario
    draws come from the scenario's own pure (seed, round)-keyed streams,
    never ``s.rng``, and each hook leaves its input untouched when it does
    not fire — so a null scenario consumes the host RNG in exactly the
    unscenarioed order (bit-exact, tests/test_scenarios.py)."""
    t0 = time.perf_counter()
    scn = s.scn
    with TraceAnnotation("osafl.admit"):
        if (s.sparse and s.K >= 1 and scn is not None and scn.moves_clusters):
            # membership churn first: this round's participation sample and
            # admissions see the round-t cluster map. Movers re-seat in their
            # new block immediately; like any admission, the reassigned slot's
            # FIFO window resets to the incoming user's capacity.
            mv = scn.round_cluster_moves(t, s.U, s.K)
            if mv is not None:
                moved, res = s.server.apply_cluster_moves(*mv)
                if res is not None and res.newly.any():
                    s.sbuf.reset_rows(res.slots[res.newly],
                                      s.caps[moved[res.newly]])
        avail = scn.round_available(t, s.U) if scn is not None else None
        sel = None
        if s.sparse:
            if s.resample:
                weights = (scn.round_selection_weights(t, s.U)
                           if scn is not None else None)
                if s.K >= 1:
                    # stratified over the live cluster map; delegates verbatim
                    # to sample_participants at K=1 (RNG-stream parity)
                    sel = sample_participants_clustered(
                        s.rng, s.server.assign, s.K, s.m_active, s.C // s.K,
                        weights=weights, available=avail)
                else:
                    sel = sample_participants(s.rng, s.U, s.m_active,
                                              weights=weights, available=avail)
                res = s.server.admit(sel)
                if res.newly.any():
                    # a reassigned slot loses the evicted resident's dataset:
                    # reset its FIFO window to the incoming user's capacity
                    s.sbuf.reset_rows(res.slots[res.newly],
                                      s.caps[sel[res.newly]])
            cohort = s.server.cohort
            p_ac = s.p_ac[cohort]
        else:
            cohort, p_ac = None, s.p_ac
    with TraceAnnotation("osafl.arrivals"):
        e_u = xc.arrivals
        if scn is not None:
            e_u, p_ac = scn.round_arrivals(t, e_u, p_ac)
        if avail is not None:
            # departed users generate no arrivals this round
            p_ac = p_ac * (avail[cohort] if s.sparse else avail)
        counts = binomial_arrivals_batched(s.rng, e_u, p_ac)
    with TraceAnnotation("osafl.request_draw"):
        if s.stacked_req:
            if s.sparse:
                # the stacked stream state stays (U,)-wide; non-residents draw
                # a zero count so their streams do not advance
                full = np.zeros(s.U, counts.dtype)
                full[cohort] = counts
                xs, ys, cnt = s.rstream.draw(full, xc.dataset, s.arr_width)
                arrivals = (xs[cohort], ys[cohort], cnt[cohort])
            else:
                arrivals = s.rstream.draw(counts, xc.dataset, s.arr_width)
            jax.block_until_ready(arrivals[1])   # honest request_gen_s
        else:
            streams = ([s.streams[u] for u in cohort] if s.sparse
                       else s.streams)
            arrivals = draw_arrival_batch(streams, counts, xc.dataset,
                                          width=s.arr_width)
    req_s = time.perf_counter() - t0
    with TraceAnnotation("osafl.fifo_commit"):
        s.sbuf.stage(*arrivals)
        s.sbuf.commit()
    with TraceAnnotation("osafl.resource_solve"):
        if xc.use_resource_opt:
            sysb = s.sysb
            if scn is not None:
                sysb = scn.round_system(t, sysb)
            sysb = _gather_sys(sysb, cohort) if s.sparse else sysb
            kappas = optimize_round_batched(s.rng, s.net, sysb, s.n_params,
                                            backend=xc.resource_backend).kappa
        else:
            kappas = np.full(s.C, s.fl.kappa_max)
    with TraceAnnotation("osafl.slot_sample"):
        active = kappas >= 1                    # kappa = 0 => straggler
        if avail is not None:
            # departed users do not report an update either
            active = active & (avail[cohort] if s.sparse else avail)
        if sel is not None:
            # only the sampled round-active users train; carried residents
            # idle. A freshly admitted slot with zero arrivals has nothing to
            # train on.
            sel_mask = np.zeros(s.C, bool)
            sel_mask[s.server.pool.user_slot[sel]] = True
            active = active & sel_mask & (s.sbuf.sizes > 0)
        slots = s.sbuf.sample_slots(s.rng, (s.fl.kappa_max, xc.batch))
    return req_s, kappas, active, slots


def _server_round(s: SimpleNamespace, alg: str, upd, active, kappas) -> None:
    if alg == "fednova":
        # round_stacked merges sizes/kappas for active clients only, so
        # stragglers keep their last-seen kappa (loop meta semantics)
        s.server.round_stacked(upd, active, sizes=s.sbuf.sizes,
                               kappas=kappas)
    elif alg == "feddisco":
        s.server.round_stacked(upd, active, sizes=s.sbuf.sizes,
                               hists=s.sbuf.label_histograms())
    else:
        s.server.round_stacked(upd, active)


def build_fused_engine(alg: str, xc: "ExperimentConfig",
                       eval_samples: int = 400) -> tuple:
    """Deterministic setup + a ``core/round_fused.FusedEngine`` over it:
    ``(engine, s)`` with ``s`` the ``_stacked_setup`` namespace the engine's
    carries are initialized from / written back to. Shared by the fused
    branch of the stacked engine and the bench/HLO tooling
    (``bench_online.py`` compiles a segment and feeds its optimized HLO to
    ``launch/hlo_analysis.dispatch_report``). Validates the fused shape of
    the compatibility matrix up front, whatever ``xc.round_backend`` says —
    calling this IS choosing the fused round."""
    resolve(alg, dataclasses.replace(xc, engine="stacked",
                                     round_backend="fused"))
    s = _stacked_setup(alg, xc, eval_samples)
    engine = FusedEngine(
        fl=s.fl, codec=s.codec, model=s.model, consts=s.rstream.consts,
        topk=s.rstream.topk, dataset=xc.dataset, arrivals=xc.arrivals,
        batch=xc.batch, p_ac=s.p_ac, sysb=s.sysb, net=s.net,
        n_params=s.n_params, test_batch=s.test_batch, alphas=s.server.alphas,
        sketch_key=s.server._sketch_key, seed=xc.seed,
        use_resource_opt=xc.use_resource_opt,
        resource_backend=xc.resource_backend)
    return engine, s


def _run_fused(alg: str, xc: "ExperimentConfig", eval_samples: int,
               save_every_k, checkpoint_dir, resume_from, checkpoint_async,
               keep_last):
    """The ``round_backend="fused"`` body of the stacked engine: the same
    trajectory state and RunState checkpoints, but rounds execute in
    single-dispatch segments of up to ``xc.rounds_per_dispatch`` (truncated
    at checkpoint boundaries, which are segment boundaries by construction —
    the per-round keying makes the truncation invisible to the trajectory).
    History rows mirror the dispatch engine's; per-round host draws don't
    exist, so ``request_gen_s`` is 0 and ``round_s`` is the fully-synced
    segment wall clock divided by its length."""
    engine, s = build_fused_engine(alg, xc, eval_samples)
    writer = _make_ckpt_writer(save_every_k, checkpoint_async, keep_last)
    history, start_round = [], 0
    if resume_from is not None:
        snap = checkpoint.load_run_state(resume_from)
        _check_snapshot(snap, "stacked", alg, xc, eval_samples)
        history, start_round = _resume_stacked(s, snap)
    carry = engine.init_carry(s.server, s.sbuf, s.rstream, start_round)
    t, outs = start_round, None
    try:
        while t < xc.rounds:
            seg = min(xc.rounds_per_dispatch, xc.rounds - t)
            if save_every_k:
                boundary = (t // save_every_k + 1) * save_every_k
                seg = min(seg, boundary - t)
            t_start = time.perf_counter()
            carry, outs = engine.run_segment(carry, seg)
            outs = jax.tree.map(np.asarray, outs)   # sync: honest round_s
            seg_s = time.perf_counter() - t_start
            engine.check_outputs(outs)
            for i in range(seg):
                history.append({"round": t + i,
                                "test_loss": float(outs["test_loss"][i]),
                                "test_acc": float(outs["test_acc"][i]),
                                "participants": int(outs["participants"][i]),
                                "request_gen_s": 0.0,
                                "round_s": seg_s / seg})
            t += seg
            if save_every_k and t % save_every_k == 0:
                engine.write_back(carry, outs, s.server, s.sbuf, s.rstream)
                writer.submit(
                    checkpoint_path(checkpoint_dir, t),
                    {"engine": "stacked", "alg": alg,
                     "config": _run_shape(xc, eval_samples), "next_round": t,
                     "rng": checkpoint.generator_state(s.rng),
                     "server": s.server.state_dict(),
                     "buffer": s.sbuf.state_dict(),
                     "streams": s.rstream.state_dict(),
                     "history": history},
                    metadata={"engine": "stacked", "alg": alg, "round": t})
        if writer is not None:
            writer.close()          # drain barrier: all snapshots committed
    finally:
        if writer is not None:
            writer.shutdown()
    if outs is not None:
        engine.write_back(carry, outs, s.server, s.sbuf, s.rstream)
    return history


def _run_stacked(alg: str, xc: "ExperimentConfig", eval_samples: int,
                 save_every_k, checkpoint_dir, resume_from, checkpoint_async,
                 keep_last):
    """The dispatch-round stacked engine body (see the deprecated
    ``run_vectorized_experiment`` shim for the full semantics docstring —
    unchanged by the ``run()`` facade)."""
    s = _stacked_setup(alg, xc, eval_samples)
    local_step = make_vmapped_local_train(
        s.grad_fn, s.fl.local_lr, s.fl.kappa_max, prox_mu=s.prox_mu)

    writer = _make_ckpt_writer(save_every_k, checkpoint_async, keep_last)
    history, start_round = [], 0
    if resume_from is not None:
        snap = checkpoint.load_run_state(resume_from)
        _check_snapshot(snap, "stacked", alg, xc, eval_samples)
        history, start_round = _resume_stacked(s, snap)
    try:
        for t in range(start_round, xc.rounds):
            with StepTraceAnnotation("osafl.round", step_num=t):
                t_start = time.perf_counter()
                req_s, kappas, active, slots = _draw_round_inputs(s, xc, t)
                with TraceAnnotation("osafl.local_sgd"):
                    d, w = local_step(s.server.params, s.sbuf.gather(slots),
                                      jnp.asarray(kappas))
                    upd = s.codec.flatten_stacked(w if s.weights_alg else d)
                with TraceAnnotation("osafl.aggregate"):
                    _server_round(s, alg, upd, active, kappas)
                with TraceAnnotation("osafl.eval"):
                    loss, m = small_loss(s.server.params, s.test_batch,
                                         s.model)
                # round_s feeds the bench gates: block on every async output
                # of the round (the server round's weights + the committed
                # buffer), not just the eval loss
                with TraceAnnotation("osafl.sync"):
                    jax.block_until_ready((loss, s.server.w, s.sbuf.state))
                history.append({"round": t, "test_loss": float(loss),
                                "test_acc": float(m["accuracy"]),
                                "participants": int(active.sum()),
                                "request_gen_s": req_s,
                                "round_s": time.perf_counter() - t_start})
                if save_every_k and (t + 1) % save_every_k == 0:
                    writer.submit(
                        checkpoint_path(checkpoint_dir, t + 1),
                        {"engine": "stacked", "alg": alg,
                         "config": _run_shape(xc, eval_samples),
                         "next_round": t + 1,
                         "rng": checkpoint.generator_state(s.rng),
                         "server": s.server.state_dict(),
                         "buffer": s.sbuf.state_dict(),
                         "streams": (s.rstream.state_dict() if s.stacked_req
                                     else streams_state_dict(s.streams)),
                         "history": history},
                        metadata={"engine": "stacked", "alg": alg,
                                  "round": t + 1})
        if writer is not None:
            writer.close()          # drain barrier: all snapshots committed
    finally:
        if writer is not None:
            writer.shutdown()
    return history


def _make_pod_step(pod_engine: str, s: SimpleNamespace, mesh):
    """The online pod local-train step for one engine flavor (all four
    sample their minibatches from the mesh-sharded buffer via
    ``make_pod_batch_fn``; ``core/pod.py`` online mode)."""
    batch_fn = make_pod_batch_fn()
    kw = dict(batch_fn=batch_fn, grad_fn=s.grad_fn, prox_mu=s.prox_mu)
    if pod_engine == "exact_tp":
        step = make_tp_train_step(None, s.fl, mesh, **kw)
    elif pod_engine == "recompute":
        step = make_recompute_train_step(None, s.fl, mesh, s.U, **kw)
    elif pod_engine == "stale":
        step = make_stale_score_train_step(None, s.fl, mesh, s.U, **kw)
    elif pod_engine == "fedavg":
        step = make_fedavg_train_step(None, s.fl, mesh, **kw)
    else:   # unreachable through the harness, which validates up front
        raise ValueError(pod_engine)
    return jax.jit(step)


def _run_pod(alg: str, xc: "ExperimentConfig", pod_engine: str,
             eval_samples: int, mesh, save_every_k, checkpoint_dir,
             resume_from, checkpoint_async, keep_last):
    """The mesh-sharded pod engine body (see the deprecated
    ``run_pod_online_experiment`` shim for the full semantics docstring)."""
    if mesh is None:
        mesh = make_mesh((jax.device_count(), 1), ("data", "model"))
    rows = client_rows(mesh)
    if xc.num_clients % rows:
        raise ValueError(
            f"num_clients {xc.num_clients} is not divisible by the mesh's "
            f"{rows} client rows {dict(zip(mesh.axis_names, mesh.devices.shape))}")
    if xc.cohort_size and xc.cohort_size % rows:
        raise ValueError(
            f"cohort_size {xc.cohort_size} is not divisible by the mesh's "
            f"{rows} client rows (the slot-indexed buffer shards over the "
            "client axes; each shard must own whole slots)")
    if xc.num_clusters > 1 and xc.num_clusters % rows:
        raise ExperimentConfigError(
            "hier-mesh",
            f"num_clusters {xc.num_clusters} is not a multiple of the "
            f"mesh's {rows} client rows: with K>1 each mesh shard must own "
            "whole cluster slot blocks (K=1 spans shards exactly like the "
            "flat buffer and is exempt)")
    s = _stacked_setup(alg, xc, eval_samples, mesh=mesh,
                       stale_scores=pod_engine == "stale")
    pod_step = _make_pod_step(pod_engine, s, mesh)
    mesh_shape = {"pod_engine": pod_engine,
                  "mesh_axes": list(mesh.axis_names),
                  "mesh_shape": [int(n) for n in mesh.devices.shape]}

    writer = _make_ckpt_writer(save_every_k, checkpoint_async, keep_last)
    history, start_round = [], 0
    if resume_from is not None:
        snap = checkpoint.load_run_state(resume_from)
        _check_snapshot(snap, "pod", alg, xc, eval_samples, extra=mesh_shape)
        history, start_round = _resume_stacked(s, snap)
    try:
        for t in range(start_round, xc.rounds):
            with StepTraceAnnotation("osafl.round", step_num=t):
                t_start = time.perf_counter()
                req_s, kappas, active, slots = _draw_round_inputs(s, xc, t)
                with TraceAnnotation("osafl.local_sgd"):
                    d, w = pod_step(s.server.params, s.sbuf.state.x,
                                    s.sbuf.state.y, jnp.asarray(slots),
                                    jnp.asarray(kappas))
                    upd = s.codec.flatten_stacked(w if s.weights_alg else d)
                with TraceAnnotation("osafl.aggregate"):
                    _server_round(s, alg, upd, active, kappas)
                with TraceAnnotation("osafl.eval"):
                    loss, m = small_loss(s.server.params, s.test_batch,
                                         s.model)
                # same fully-synced round_s convention as the vectorized
                # harness
                with TraceAnnotation("osafl.sync"):
                    jax.block_until_ready((loss, s.server.w, s.sbuf.state))
                history.append({"round": t, "test_loss": float(loss),
                                "test_acc": float(m["accuracy"]),
                                "participants": int(active.sum()),
                                "request_gen_s": req_s,
                                "round_s": time.perf_counter() - t_start})
                if save_every_k and (t + 1) % save_every_k == 0:
                    writer.submit(
                        checkpoint_path(checkpoint_dir, t + 1),
                        {"engine": "pod", "alg": alg,
                         "config": dict(_run_shape(xc, eval_samples),
                                        **mesh_shape),
                         "next_round": t + 1,
                         "rng": checkpoint.generator_state(s.rng),
                         "server": s.server.state_dict(),
                         "buffer": s.sbuf.state_dict(),
                         "streams": (s.rstream.state_dict() if s.stacked_req
                                     else streams_state_dict(s.streams)),
                         "history": history},
                        metadata={"engine": "pod", "alg": alg,
                                  "round": t + 1, "pod_engine": pod_engine})
        if writer is not None:
            writer.close()          # drain barrier: all snapshots committed
    finally:
        if writer is not None:
            writer.shutdown()
    return history


def _run_centralized(xc: "ExperimentConfig", eval_samples: int):
    """Genie baseline: all clients' current datasets pooled each round."""
    model = xc.model
    cat, streams = make_population(xc.seed, xc.num_clients, topk=xc.topk)
    rng = np.random.default_rng(xc.seed)
    feat_shape, dtype = dataset_layout(xc.dataset)
    bufs = []
    for s in streams:
        cap = int(rng.integers(*xc.capacity))
        buf = OnlineBuffer.create(cap, feat_shape, 100, dtype=dtype)
        x, y = _draw(s, cap, xc.dataset)
        buf.stage(x, y)
        buf.commit()
        bufs.append(buf)
    per = max(eval_samples // xc.num_clients, 20)
    tests = [_draw(s, per, xc.dataset) for s in streams]
    tx = np.concatenate([t[0] for t in tests])
    ty = np.concatenate([t[1] for t in tests])
    test_batch = {"x": jnp.asarray(tx), "y": jnp.asarray(ty)}
    params = init_small(jax.random.PRNGKey(xc.seed), model)
    grad_fn = jax.jit(jax.grad(lambda p, b: small_loss(p, b, model)[0]))
    history = []
    for t in range(xc.rounds):
        for c, s in enumerate(streams):
            n = binomial_arrivals(rng, xc.arrivals, s.user.p_ac)
            if n:
                x, y = _draw(s, n, xc.dataset)
                bufs[c].stage(x, y)
            bufs[c].commit()
        xs, ys = zip(*[b.dataset() for b in bufs])
        X, Y = np.concatenate(xs), np.concatenate(ys)
        for _ in range(5):                     # kappa=5 epochs-ish steps
            idx = rng.integers(0, len(Y), xc.batch * 4)
            g = grad_fn(params, {"x": jnp.asarray(X[idx]),
                                 "y": jnp.asarray(Y[idx])})
            params = jax.tree.map(lambda w, gg: w - xc.local_lr * gg,
                                  params, g)
        loss, m = small_loss(params, test_batch, model)
        history.append({"round": t, "test_loss": float(loss),
                        "test_acc": float(m["accuracy"])})
    return history


# ---------------------------------------------------------------------------
# the facade
# ---------------------------------------------------------------------------

def run(alg: str, xc: "ExperimentConfig", *, eval_samples: int = 400,
        mesh=None, save_every_k: int = None, checkpoint_dir=None,
        resume_from=None, checkpoint_async: bool = True,
        keep_last: int = None, pod_engine: str = None):
    """Run one FL experiment; returns per-round test metrics.

    The single entry point over every engine: ``xc.engine`` (or ``"auto"``)
    picks the harness, ``repro.harness.compat`` validates the whole knob
    combination up front (one uniform ``ExperimentConfigError``), and the
    resolved plan is logged on the ``repro.harness`` logger so CI lanes name
    the configuration they actually ran.

      * ``engine="loop"`` — the per-client oracle. Checkpoints are always
        synchronous v1 npz snapshots (the v1→v2 read-compat anchor);
        ``checkpoint_async`` is ignored.
      * ``engine="stacked"`` — the vectorized (U, N) engine;
        ``xc.round_backend="fused"`` runs single-dispatch segments,
        ``xc.cohort_size``/``participation`` the sparse slot pool,
        ``xc.num_clusters`` the hierarchical edge-cluster tier.
      * ``engine="pod"`` — the mesh-sharded online harness; ``mesh``
        defaults to all local devices on one ``('data', 'model'=1)`` mesh
        and ``xc.pod_engine`` (or the ``pod_engine`` kwarg) picks the
        local-train flavor.
      * ``engine="centralized"`` (or ``alg="centralized"``) — the pooled-
        data genie baseline; no checkpointing.
      * ``engine="auto"`` — pod when ``mesh`` is passed, else stacked.

    ``save_every_k``/``checkpoint_dir``/``resume_from``/``keep_last``/
    ``checkpoint_async`` are the RunState snapshot controls shared by every
    checkpointing engine (see the deprecation shims' docstrings for the
    engine-specific detail; semantics are unchanged by the facade)."""
    plan = resolve(alg, xc, mesh=mesh, pod_engine=pod_engine)
    _LOG.info("resolved experiment plan: %s", plan.describe())
    if plan.engine == "centralized":
        if (save_every_k or checkpoint_dir is not None
                or resume_from is not None or keep_last is not None):
            raise ValueError(
                "the centralized genie does not checkpoint (it is a "
                "baseline, not a trajectory to resume); drop the "
                "save_every_k/checkpoint_dir/resume_from/keep_last args")
        return _run_centralized(xc, eval_samples)
    _validate_ckpt_args(save_every_k, checkpoint_dir, keep_last)
    if plan.engine == "loop":
        return _run_loop(alg, xc, eval_samples, save_every_k,
                         checkpoint_dir, resume_from, keep_last)
    if plan.engine == "pod":
        return _run_pod(alg, xc, plan.pod_engine, eval_samples, mesh,
                        save_every_k, checkpoint_dir, resume_from,
                        checkpoint_async, keep_last)
    if plan.round_backend == "fused":
        return _run_fused(alg, xc, eval_samples, save_every_k,
                          checkpoint_dir, resume_from, checkpoint_async,
                          keep_last)
    return _run_stacked(alg, xc, eval_samples, save_every_k, checkpoint_dir,
                        resume_from, checkpoint_async, keep_last)


# ---------------------------------------------------------------------------
# deprecated entry points (thin shims over run())
# ---------------------------------------------------------------------------

def run_experiment(alg: str, xc: "ExperimentConfig", eval_samples: int = 400,
                   save_every_k: int = None, checkpoint_dir=None,
                   resume_from=None, keep_last: int = None):
    """Deprecated: use ``repro.harness.run(alg, xc)`` with
    ``xc.engine="loop"``.

    One FL training run on the paper-faithful per-client loop oracle;
    returns per-round test metrics. With ``save_every_k``/``checkpoint_dir``
    set, a full RunState snapshot (params, contribution buffers, FIFO
    buffers incl. staged arrivals, scores, staleness flags, every Generator
    stream) is written after every k-th round; ``resume_from`` restores one
    and continues the trajectory bit-identically
    (tests/test_checkpoint_resume.py). The loop oracle always writes
    synchronous v1 snapshots — it is the write-path anchor for v1→v2 read
    compat; ``keep_last`` prunes all but the newest N."""
    return run(alg, dataclasses.replace(xc, engine="loop"),
               eval_samples=eval_samples, save_every_k=save_every_k,
               checkpoint_dir=checkpoint_dir, resume_from=resume_from,
               keep_last=keep_last)


def run_vectorized_experiment(alg: str, xc: "ExperimentConfig",
                              eval_samples: int = 400,
                              save_every_k: int = None, checkpoint_dir=None,
                              resume_from=None, checkpoint_async: bool = True,
                              keep_last: int = None):
    """Deprecated: use ``repro.harness.run(alg, xc)`` with
    ``xc.engine="stacked"`` (or leave ``engine="auto"``).

    Stacked-engine counterpart of ``run_experiment``: the whole cohort
    trains under one ``jax.vmap``, the server round is one vectorized
    (U, N)-buffer update, and the paper's full *online* setting runs in
    stacked form too — per-client FIFO buffers with Binomial(E_u, p_ac)
    arrivals (``StackedOnlineBuffer``, committed at round boundaries as one
    jitted scatter) and the joint kappa/f/p resource optimizer
    (``resource_stacked``, all clients in one jitted f64 solve). So
    ``xc.num_clients`` can be hundreds to thousands with no loss of paper
    fidelity; only the request streams themselves stay per-client Python.

    ``save_every_k``/``checkpoint_dir``/``resume_from`` mirror
    ``run_experiment``: full RunState snapshots every k rounds, bit-identical
    mid-stream resume (``_stacked_setup`` re-derives everything
    deterministic from ``xc.seed`` — population, capacities, test set,
    system params — and the snapshot then overwrites all mutable state).
    Snapshots default to the streaming v2 writer (``checkpoint/streaming.py``:
    per-shard files written by a background thread, committed atomically;
    ``close()`` at harness exit is the drain barrier that keeps resume
    determinism); ``checkpoint_async=False`` falls back to the synchronous
    v1 npz save. ``keep_last`` prunes all but the newest N committed
    snapshots after each save (live-server claims are never pruned).

    ``xc.request_backend`` picks the request model: ``"python"`` draws from
    the per-user oracle streams (the last O(U) Python loop per round);
    ``"stacked"`` advances all U users at once with the jitted Gumbel-trick
    sampler (``data/video_caching_stacked.py``, distribution-equivalent —
    see DESIGN.md "Request model"). Both backends share the same population
    parameters, capacities, arrival process and system params per seed.

    ``xc.cohort_size``/``xc.participation`` switch on the sparse-cohort
    engine (``core/cohort.py``): only C slots of round state exist, the
    round-active users are sampled and seated via the slot pool each round,
    and per-round cost scales with C while ``num_clients`` counts registered
    users only. ``cohort_size=num_clients`` is bit-exact against the dense
    path (tests/test_cohort.py); DESIGN.md "Sparse cohorts" has the layout.

    ``xc.num_clusters`` adds the hierarchical edge-cluster tier
    (``core/hierarchy.py``): K per-cluster scored reductions + a PS combine
    over the K aggregates; ``num_clusters=1`` is bit-exact vs the flat PS
    (tests/test_hierarchy.py)."""
    return run(alg, dataclasses.replace(xc, engine="stacked"),
               eval_samples=eval_samples, save_every_k=save_every_k,
               checkpoint_dir=checkpoint_dir, resume_from=resume_from,
               checkpoint_async=checkpoint_async, keep_last=keep_last)


def run_pod_online_experiment(alg: str, xc: "ExperimentConfig",
                              eval_samples: int = 400, mesh=None,
                              pod_engine: str = "exact_tp",
                              save_every_k: int = None, checkpoint_dir=None,
                              resume_from=None, checkpoint_async: bool = True,
                              keep_last: int = None):
    """Deprecated: use ``repro.harness.run(alg, xc, mesh=...)`` with
    ``xc.engine="pod"`` and ``xc.pod_engine`` (or pass a mesh under
    ``engine="auto"``).

    The paper's online setting on the pod engines: the same round as the
    stacked engine — FIFO arrivals, batched resource optimizer, straggler
    masking, stacked server — but the cohort's FIFO datasets live **sharded
    over a device mesh** (``StackedOnlineBuffer`` mesh mode: U split over
    the ``('pod','data')`` client axes) and each mesh row samples its
    local-SGD minibatches from its own buffer shard inside the train step
    (``core/pod.py`` online mode). The server's dense ``(U, N)`` round ops
    consume the sharded update rows under auto-SPMD.

    ``pod_engine`` picks the local-train flavor (``POD_ENGINES``):
    ``exact_tp``/``fedavg`` run every shard's clients under one vmap inside
    a shard_map body; ``recompute`` scans clients sequentially (the
    FSDP-era memory-lean shape) under auto-SPMD; ``stale`` is ``exact_tp``
    plus the §Perf A5 one-round score lag (``FLConfig.stale_scores``,
    applied by the stacked OSAFL server). All four execute the identical
    per-client masked local-SGD math, so on a 1-device mesh this harness
    matches the stacked engine metric-for-metric (the parity anchor —
    tests/test_pod_online.py).

    ``mesh`` defaults to all local devices on one ``('data','model'=1)``
    mesh; fake a multi-device CPU mesh with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (EXPERIMENTS.md
    "Pod online harness"). ``xc.num_clients`` must be a multiple of the
    mesh's client rows — and so must ``xc.cohort_size`` when the sparse
    slot-pool engine is on, and ``xc.num_clusters`` when K>1 (each shard
    must own whole cluster slot blocks; see ``core/hierarchy.py``).
    Checkpointing mirrors the stacked engine (engine tag ``"pod"``): by
    default the streaming v2 writer pulls the mesh-sharded buffer and
    cohort tables *per addressable shard* on a background thread — no host
    gather of the full ``(U, D, ...)`` storage ever happens — and resume
    re-shards the reassembled arrays onto the live mesh
    (``load_state_dict``). A snapshot additionally refuses to resume into a
    different ``pod_engine`` or mesh layout."""
    return run(alg, dataclasses.replace(xc, engine="pod"),
               eval_samples=eval_samples, mesh=mesh, pod_engine=pod_engine,
               save_every_k=save_every_k, checkpoint_dir=checkpoint_dir,
               resume_from=resume_from, checkpoint_async=checkpoint_async,
               keep_last=keep_last)


def run_centralized_sgd(xc: "ExperimentConfig", eval_samples: int = 400):
    """Deprecated: use ``repro.harness.run("centralized", xc)``.

    Genie baseline: all clients' current datasets pooled each round."""
    return run("centralized", dataclasses.replace(xc, engine="centralized"),
               eval_samples=eval_samples)
