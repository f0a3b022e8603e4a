"""Sparse-cohort server state (core/cohort.py, DESIGN.md "Sparse cohorts").

Four layers of proof:

  * **Dense parity** — the acceptance anchor: ``cohort_size = num_clients``
    makes the slot pool the identity map and the harness consumes the host
    RNG in exactly the dense order, so the sparse engine is BIT-EXACT
    against the dense stacked engine for every algorithm and both request
    backends.
  * **C < U semantics** — inactive users' carried tables (scores, stale-score
    carry, participation flags) are untouched by rounds they sit out; the
    OSAFL aggregation renormalizes its weights over the sampled cohort only
    (the width-C inner round equals a dense width-C server on the same
    inputs — the Dinh et al. 1910.13067 partial-participation rule);
    admission resets the slot's contribution row and eviction drops it.
  * **SlotPool properties** (tests/_hyp.py shim) — random
    admit/evict/readmit sequences hold the bijection invariants against a
    model-dict mirror (no aliasing, no leaked slots, FIFO eviction order),
    slot reuse wraps around the pool indefinitely, and snapshots taken
    mid-sequence round-trip and continue in lockstep.
  * **Mesh behavior** — on a faked 8-device mesh (subprocess; jax locks the
    device count at first init) the per-user tables carry explicit
    NamedSharding over the client axes, the 2x4 sparse pod run matches the
    1-device mesh, cohort_size must divide the mesh's client rows, and a
    sparse pod snapshot refuses to resume onto a different mesh shape.
"""
import dataclasses
import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.common import (ALL_ALGS, ExperimentConfig, build_fused_engine,
                               run_experiment, run_vectorized_experiment)
from repro.checkpoint import CheckpointError, validate_cohort_shapes
from repro.configs.base import FLConfig
from repro.core.baselines import make_server
from repro.core.cohort import (SlotPool, SparseCohortServer,
                               sample_participants)
from repro.core.osafl import StackedOSAFLServer

from _hyp import given, settings, st

METRICS = ("round", "test_loss", "test_acc", "participants")


def _xc(**kw) -> ExperimentConfig:
    base = dict(model="mlp", dataset=2, num_clients=8, rounds=3,
                capacity=(12, 24), arrivals=4, batch=8, seed=5)
    base.update(kw)
    return ExperimentConfig(**base)


def _params():
    """Tiny two-leaf pytree — the server math is size-agnostic."""
    return {"a": jnp.arange(6, dtype=jnp.float32) / 7.0,
            "b": jnp.ones((2, 3), jnp.float32)}


def _sparse_server(alg="osafl", U=8, C=4, seed=0, mesh=None, **fl_kw):
    fl = FLConfig(num_clients=U, local_lr=0.1, global_lr=1.0,
                  algorithm=alg, engine="stacked", cohort_size=C, **fl_kw)
    srv = make_server(_params(), fl, U, seed=seed, mesh=mesh)
    assert isinstance(srv, SparseCohortServer)
    return srv


# ---------------------------------------------------------------------------
# dense parity: cohort_size = U is bit-exact for every algorithm
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("alg", ALL_ALGS)
def test_cohort_size_U_bit_exact_vs_dense(alg):
    """The acceptance anchor: the sparse engine at C = U reproduces the
    dense stacked trajectory bit-for-bit — same host RNG draws, identity
    slot map, same inner round math."""
    dense = run_vectorized_experiment(alg, _xc(), eval_samples=64)
    sparse = run_vectorized_experiment(alg, _xc(cohort_size=8),
                                       eval_samples=64)
    for a, b in zip(dense, sparse):
        for k in METRICS:
            assert a[k] == b[k], (alg, k, a, b)


def test_cohort_size_U_bit_exact_stacked_requests():
    """Same anchor on the batched Gumbel request backend (the sparse branch
    draws (U,)-wide counts so the device request stream advances
    identically)."""
    dense = run_vectorized_experiment(
        "osafl", _xc(request_backend="stacked"), eval_samples=64)
    sparse = run_vectorized_experiment(
        "osafl", _xc(request_backend="stacked", cohort_size=8),
        eval_samples=64)
    for a, b in zip(dense, sparse):
        for k in METRICS:
            assert a[k] == b[k], (k, a, b)


# ---------------------------------------------------------------------------
# C < U: untouched carries, cohort renormalization, slot-row lifecycle
# ---------------------------------------------------------------------------

def test_inactive_users_tables_untouched():
    """Users outside the cohort keep their initial scores / stale-score
    carry / participation flags through rounds they sit out."""
    srv = _sparse_server("osafl", U=8, C=4)
    srv.admit([0, 2, 4, 6])
    N = int(srv.w.shape[0])
    rng = np.random.default_rng(0)
    for _ in range(3):
        d = jnp.asarray(rng.normal(size=(4, N)).astype(np.float32))
        srv.round_stacked(d, jnp.ones(4, bool))
    scores = np.asarray(srv.tables["scores"])
    lam_prev = np.asarray(srv.tables["lam_prev"])
    part = np.asarray(srv.tables["participated"])
    for u in (1, 3, 5, 7):                       # never admitted
        assert scores[u] == 1.0 and lam_prev[u] == 1.0 and not part[u]
    for u in (0, 2, 4, 6):                       # trained every round
        assert part[u]
    # no dense (U, N) ghost anywhere in the engine
    assert srv.inner.d_buffer.shape == (4, N)
    assert srv.state_dict()["inner"]["d_buffer"].shape == (4, N)


def test_osafl_renormalizes_over_sampled_cohort_only():
    """The sparse round on a C-slot cohort equals a *dense* width-C OSAFL
    server on the same inputs: uniform 1/C aggregation weights over the
    sampled cohort, not 1/U over the registration book."""
    srv = _sparse_server("osafl", U=8, C=4, seed=3)
    srv.admit([5, 1, 7, 3])                      # arbitrary user ids
    ref = StackedOSAFLServer(
        _params(), FLConfig(num_clients=4, local_lr=0.1, global_lr=1.0,
                            engine="stacked"), 4, seed=3)
    np.testing.assert_array_equal(np.asarray(srv.alphas),
                                  np.full(4, 0.25, np.float32))
    N = int(srv.w.shape[0])
    rng = np.random.default_rng(1)
    for r in range(2):
        d = jnp.asarray(rng.normal(size=(4, N)).astype(np.float32))
        active = jnp.asarray([True, True, r == 0, True])
        ws = srv.round_stacked(d, active)
        wr = ref.round_stacked(d, active)
        np.testing.assert_array_equal(np.asarray(ws), np.asarray(wr))
    np.testing.assert_array_equal(np.asarray(srv.last_scores)[[5, 1, 7, 3]],
                                  np.asarray(ref.last_scores))


def test_eviction_drops_slot_row_and_readmission_resets_it():
    srv = _sparse_server("osafl", U=6, C=2)
    srv.admit([0, 1])
    N = int(srv.w.shape[0])
    srv.round_stacked(jnp.ones((2, N), jnp.float32), jnp.ones(2, bool))
    score0 = float(np.asarray(srv.tables["scores"])[0])
    row0 = np.asarray(srv.inner.d_buffer[0]).copy()
    assert not np.array_equal(row0, np.asarray(srv.inner.init_row()))
    # admitting user 2 evicts the oldest-seated resident (user 0) and
    # resets that slot's contribution row to the refresh value
    res = srv.admit([2])
    assert res.evicted.tolist() == [0] and res.newly.all()
    s = int(res.slots[0])
    np.testing.assert_array_equal(np.asarray(srv.inner.d_buffer[s]),
                                  np.asarray(srv.inner.init_row()))
    # the evicted user's carried score survived in the table and rides back
    # in on readmission — only the slot-resident contribution row was lost
    res2 = srv.admit([0])
    assert res2.newly.all()
    s0 = int(res2.slots[0])
    assert float(np.asarray(srv.inner.last_scores)[s0]) == score0
    np.testing.assert_array_equal(np.asarray(srv.inner.d_buffer[s0]),
                                  np.asarray(srv.inner.init_row()))


def test_baseline_meta_carries_across_eviction():
    """FedNova/FedDisco per-user metadata (sizes, kappas, histograms) is
    carried in (U,) host tables and restored on readmission."""
    srv = _sparse_server("fednova", U=6, C=2)
    srv.admit([0, 1])
    N = int(srv.w.shape[0])
    srv.round_stacked(jnp.ones((2, N), jnp.float32), jnp.ones(2, bool),
                      sizes=np.array([10.0, 20.0]),
                      kappas=np.array([3.0, 4.0]))
    srv.admit([2, 3])                            # evicts both residents
    assert srv.pool.resident([0, 1]).tolist() == [False, False]
    res = srv.admit([0])                         # readmit user 0
    s = int(res.slots[0])
    assert srv.inner.sizes[s] == 10.0 and srv.inner.kappas[s] == 3.0


# admissions at U=8, C=4 seating k = 0, 1, C, 1, 3 users (flat and K=2
# alike): free slots, FIFO evictions, an explicit eviction and the
# readmission of user 1, with rounds between them so that the carried
# tables hold values no fresh slot has
ADMISSIONS = ([], [1], "round", [2, 3, 4, 5], "round", ("evict", [4]),
              [1, 5], "round", [0, 6, 7])
ADMITTED_K = [0, 1, 4, 1, 3]


def _slot_state(srv) -> dict:
    inner = srv.inner
    st = {"participated": inner.participated}
    if srv.is_osafl:
        st.update(buffer=inner.d_buffer, lam_prev=inner._lam_prev,
                  last_scores=inner.last_scores)
    else:
        st["buffer"] = inner.buffer
    return {k: np.array(v) for k, v in st.items()}


@pytest.mark.parametrize("alg,K", [("osafl", 0), ("fedavg", 0), ("osafl", 2)],
                         ids=["osafl", "fedavg", "osafl-hier2"])
def test_admission_matches_row_scatter_replay(alg, K):
    """Every admission leaves the slot buffer, ``participated``,
    ``lam_prev`` and ``last_scores`` bit-identical to a numpy replay of the
    row scatter ``x.at[slots].set(values)``: the refresh row into each
    newly seated slot, the seated users' carried tables into its slot
    vectors, every other slot untouched."""
    srv = _sparse_server(alg, U=8, C=4, seed=1, num_clusters=K)
    N = int(srv.w.shape[0])
    rng = np.random.default_rng(0)
    ks = []
    for step in ADMISSIONS:
        if step == "round":
            d = rng.normal(size=(4, N)).astype(np.float32)
            srv.round_stacked(jnp.asarray(d), srv.pool.slot_user >= 0)
            continue
        if isinstance(step, tuple):
            srv.pool.evict(step[1])
            continue
        want = _slot_state(srv)
        tables = {k: np.array(srv.tables[k]) for k in srv.tables.keys()}
        row = np.array(srv.inner.init_row())
        res = srv.admit(step)
        ns = res.slots[res.newly]
        nu = np.asarray(step, np.int64)[res.newly]
        ks.append(int(ns.size))
        want["buffer"][ns] = row
        want["participated"][ns] = tables["participated"][nu]
        if srv.is_osafl:
            want["lam_prev"][ns] = tables["lam_prev"][nu]
            want["last_scores"][ns] = tables["scores"][nu]
        got = _slot_state(srv)
        for k, v in want.items():
            assert got[k].dtype == v.dtype, (step, k)
            np.testing.assert_array_equal(got[k], v, err_msg=f"{step} {k}")
    assert ks == ADMITTED_K


@pytest.mark.parametrize("alg", ["osafl", "fedavg"])
def test_admissions_of_any_k_share_one_program(alg):
    """The admission write compiles once: seating 1, C, 1 and 3 users,
    with rounds in between, reuses one executable of the slot-reset
    program."""
    srv = _sparse_server(alg, U=8, C=4)
    N = int(srv.w.shape[0])
    for users in ([1], [2, 3, 4, 5], [1, 5], [0, 6, 7]):
        srv.admit(users)
        srv.round_stacked(jnp.ones((4, N), jnp.float32), np.ones(4, bool))
    assert srv._slot_reset._cache_size() == 1


@pytest.mark.parametrize("alg,backend", [("osafl", "python"),
                                         ("fednova", "stacked")])
def test_sparse_harness_churn_runs(alg, backend):
    """End-to-end C < U with participation sampling: admissions, evictions
    and buffer resets every round; metrics stay finite and the round-active
    cohort is bounded by participation * C."""
    xc = _xc(num_clients=16, rounds=4, cohort_size=4, participation=0.75,
             request_backend=backend)
    hist = run_vectorized_experiment(alg, xc, eval_samples=64)
    assert [h["round"] for h in hist] == list(range(4))
    assert all(np.isfinite(h["test_loss"]) for h in hist)
    assert all(h["participants"] <= 3 for h in hist)


# ---------------------------------------------------------------------------
# guard rails
# ---------------------------------------------------------------------------

def test_sparse_engine_guard_rails():
    with pytest.raises(ValueError, match="cohort_size"):
        run_vectorized_experiment("osafl", _xc(cohort_size=9),
                                  eval_samples=16)
    with pytest.raises(ValueError, match="participation"):
        run_vectorized_experiment("osafl", _xc(cohort_size=4,
                                               participation=1.5),
                                  eval_samples=16)
    # participation sampling without the slot pool has no defined dense
    # semantics — reject rather than silently ignore
    with pytest.raises(ValueError, match="cohort_size"):
        run_vectorized_experiment("osafl", _xc(participation=0.5),
                                  eval_samples=16)
    # the loop engine and the fused round are dense-only
    with pytest.raises(ValueError, match="slot-pool"):
        run_experiment("osafl", _xc(cohort_size=4), eval_samples=16)
    with pytest.raises(ValueError, match="dense-only"):
        build_fused_engine("osafl", _xc(cohort_size=4,
                                        request_backend="stacked",
                                        round_backend="fused"))
    with pytest.raises(ValueError, match="stacked"):
        make_server(_params(), FLConfig(cohort_size=4), 8)


# ---------------------------------------------------------------------------
# SlotPool properties (hypothesis via the tests/_hyp.py shim)
# ---------------------------------------------------------------------------

def _apply_ops(pool, model, ops, U):
    """Drive pool + a model-dict mirror through an op list; verify every
    AdmitResult against the mirror and the pool invariants after each op.

    ``model`` maps resident user -> seating tick (insertion-ordered FIFO
    mirror of the pool's admit_seq clocks)."""
    tick = [max(model.values(), default=-1) + 1]
    for op in ops:
        u = op % U
        if (op // U) % 3 == 0 and u in model or (op // U) % 3 == 2:
            freed = pool.evict([u])
            if u in model:
                assert freed.size == 1
                del model[u]
            else:
                assert freed.size == 0
        else:
            res = pool.admit([u])
            assert int(pool.user_slot[u]) == int(res.slots[0])
            assert int(pool.slot_user[res.slots[0]]) == u
            if u in model:
                assert not res.newly[0] and res.evicted.size == 0
            else:
                assert res.newly[0]
                if len(model) == pool.C:          # full -> FIFO eviction
                    oldest = min(model, key=model.get)
                    assert res.evicted.tolist() == [oldest]
                    del model[oldest]
                else:
                    assert res.evicted.size == 0
                model[u] = tick[0]
                tick[0] += 1
        pool.check()
        assert sorted(model) == sorted(
            np.flatnonzero(pool.user_slot >= 0).tolist())
        assert pool.occupancy == len(model)


@settings(max_examples=25, deadline=None)
@given(st.integers(1, 5), st.integers(1, 8),
       st.lists(st.integers(0, 999), min_size=1, max_size=40))
def test_slot_pool_admit_evict_readmit_roundtrips(C, extra, ops):
    """Random admit/evict/readmit sequences: the user<->slot maps stay a
    bijection (no aliasing, no leaked slots), evictions are FIFO by seating
    order, and every AdmitResult matches an insertion-ordered model dict."""
    U = C + extra
    pool = SlotPool(U, C)
    pool.check()
    _apply_ops(pool, {}, ops, U)


@settings(max_examples=25, deadline=None)
@given(st.integers(1, 5), st.integers(1, 8),
       st.lists(st.integers(0, 999), min_size=1, max_size=24),
       st.lists(st.integers(0, 999), min_size=1, max_size=24))
def test_slot_pool_snapshot_roundtrip_mid_sequence(C, extra, ops_a, ops_b):
    """A snapshot taken mid-sequence restores into a fresh pool that then
    evolves in exact lockstep with the original."""
    U = C + extra
    pool = SlotPool(U, C)
    model = {}
    _apply_ops(pool, model, ops_a, U)
    sd = pool.state_dict()
    clone = SlotPool(U, C)
    clone.load_state_dict(sd)
    for k, v in clone.state_dict().items():
        np.testing.assert_array_equal(v, sd[k])
    _apply_ops(pool, dict(model), ops_b, U)
    _apply_ops(clone, dict(model), ops_b, U)
    for k, v in clone.state_dict().items():
        np.testing.assert_array_equal(v, pool.state_dict()[k])


def test_slot_pool_fifo_wraparound():
    """> C admissions cycle slot reuse through the whole pool repeatedly:
    every slot is reused, eviction order stays FIFO, invariants hold."""
    U, C = 12, 4
    pool = SlotPool(U, C)
    seated = []
    used = set()
    for u in range(U):                           # 3 full generations
        res = pool.admit([u])
        assert res.newly[0]
        used.add(int(res.slots[0]))
        seated.append(u)
        if len(seated) > C:
            die = seated.pop(0)
            assert res.evicted.tolist() == [die]
        pool.check()
    assert used == set(range(C))                 # every slot reused
    assert sorted(pool.cohort.tolist()) == list(range(U - C, U))
    # explicit evictions free oldest-freed-first for the next admissions
    pool.evict([U - 2, U - 4])
    ra = pool.admit([0])
    rb = pool.admit([1])
    assert int(ra.slots[0]) == int(pool.user_slot[0])
    assert {int(ra.slots[0]), int(rb.slots[0])} == \
        {int(np.flatnonzero(np.isin(pool.slot_user, [0]))[0]),
         int(np.flatnonzero(np.isin(pool.slot_user, [1]))[0])}
    pool.check()


def test_slot_pool_rejects_bad_admissions():
    pool = SlotPool(8, 3)
    with pytest.raises(ValueError, match="1 <= C <= U"):
        SlotPool(4, 5)
    with pytest.raises(ValueError, match="duplicate"):
        pool.admit([1, 1])
    with pytest.raises(ValueError, match=r"\[0, 8\)"):
        pool.admit([8])
    with pytest.raises(ValueError, match="3 slots"):
        pool.admit([0, 1, 2, 3])
    pool.check()                                 # failed calls left no trace
    assert pool.occupancy == 0


def test_validate_cohort_shapes_checks_U_and_C_independently():
    """The restore path reports *which* of the two scales mismatches — a
    wrong user-table length and a wrong slot capacity are different repair
    stories and used to be one fused shape check."""
    sd = SlotPool(8, 4).state_dict()
    validate_cohort_shapes(sd, 8, 4)             # matching: no raise
    with pytest.raises(CheckpointError, match="capacity C=4"):
        validate_cohort_shapes(sd, 8, 3)
    with pytest.raises(CheckpointError, match="U=8 registered"):
        validate_cohort_shapes(sd, 6, 4)
    with pytest.raises(CheckpointError, match="slot-map keys"):
        validate_cohort_shapes({"user_slot": sd["user_slot"]}, 8, 4)
    with pytest.raises(CheckpointError, match="capacity"):
        SlotPool(8, 3).load_state_dict(sd)
    with pytest.raises(CheckpointError, match="registered"):
        SlotPool(6, 4).load_state_dict(sd)


def test_sparse_server_refuses_dense_snapshot():
    srv = _sparse_server("osafl", U=8, C=4)
    with pytest.raises(CheckpointError, match="dense-engine"):
        srv.load_state_dict({"w": np.zeros(4)})


# ---------------------------------------------------------------------------
# multi-device: faked 8-device mesh in a subprocess (pattern from
# tests/test_pod_online.py — jax locks the device count at first init)
# ---------------------------------------------------------------------------

def _run_sub(code: str) -> dict:
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               PYTHONPATH="src")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, cwd=os.path.dirname(
                             os.path.dirname(os.path.abspath(__file__))))
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.splitlines()[-1])


_SUBPROCESS_SPARSE_MESH = textwrap.dedent("""
    import dataclasses, json, tempfile
    import numpy as np, jax
    from benchmarks.common import (ExperimentConfig, checkpoint_path,
                                   run_pod_online_experiment)
    from repro.checkpoint import CheckpointError
    from repro.configs.base import FLConfig
    from repro.core.baselines import make_server
    from repro.models.small import init_small

    mesh24 = jax.make_mesh((2, 4, 1), ("pod", "data", "model"))
    mesh1 = jax.make_mesh((1, 1), ("data", "model"))

    # the per-user carry tables take explicit NamedSharding over the
    # ('pod','data') client axes — all 8 devices own rows
    srv = make_server(init_small(jax.random.PRNGKey(0), "mlp"),
                      FLConfig(num_clients=16, engine="stacked",
                               cohort_size=8),
                      16, seed=0, mesh=mesh24)
    tables_sharded = all(
        len(srv.tables[k].sharding.device_set) == 8
        for k in srv.tables.keys())

    xc = ExperimentConfig(model="mlp", dataset=2, num_clients=16, rounds=3,
                          capacity=(12, 24), arrivals=4, batch=8, seed=5,
                          cohort_size=8, participation=0.75,
                          request_backend="stacked")
    with tempfile.TemporaryDirectory() as td:
        h24 = run_pod_online_experiment("osafl", xc, eval_samples=64,
                                        mesh=mesh24, save_every_k=3,
                                        checkpoint_dir=td)
        h1 = run_pod_online_experiment("osafl", xc, eval_samples=64,
                                       mesh=mesh1)
        dloss = max(abs(a["test_loss"] - b["test_loss"])
                    for a, b in zip(h24, h1))
        parts_ok = all(a["participants"] == b["participants"]
                       for a, b in zip(h24, h1))
        # a sparse pod snapshot refuses to resume onto a different mesh
        try:
            run_pod_online_experiment(
                "osafl", dataclasses.replace(xc, rounds=5), eval_samples=64,
                mesh=mesh1, resume_from=checkpoint_path(td, 3))
            mesh_refused = False
        except CheckpointError:
            mesh_refused = True
    # cohort_size must divide the mesh's client rows (whole slots per shard)
    try:
        run_pod_online_experiment(
            "osafl", dataclasses.replace(xc, cohort_size=4),
            eval_samples=64, mesh=mesh24)
        divisible_ok = False
    except ValueError as e:
        divisible_ok = "cohort_size" in str(e)
    print(json.dumps({"tables_sharded": tables_sharded, "dloss": dloss,
                      "parts_ok": parts_ok, "mesh_refused": mesh_refused,
                      "divisible_ok": divisible_ok,
                      "finite": all(np.isfinite(h["test_loss"])
                                    for h in h24)}))
""")


def test_sparse_pod_run_on_8_device_mesh():
    res = _run_sub(_SUBPROCESS_SPARSE_MESH)
    assert res["tables_sharded"], res
    assert res["finite"], res
    assert res["parts_ok"], res
    assert res["mesh_refused"], res
    assert res["divisible_ok"], res
    assert res["dloss"] <= 1e-5, res


# ---------------------------------------------------------------------------
# scenario-churn sequences: participation sampling, clocks, carried state
# ---------------------------------------------------------------------------

def test_sample_participants_contract():
    """The no-bias path is byte-for-byte the historical draw (the null-
    scenario anchor); availability masks exclude users entirely; an
    all-away round trains nobody; malformed weights are rejected."""
    plain = sample_participants(np.random.default_rng(9), 10, 4)
    hist = np.sort(np.random.default_rng(9).choice(10, size=4,
                                                   replace=False))
    np.testing.assert_array_equal(plain, hist)
    avail = np.zeros(10, bool)
    avail[[2, 5]] = True
    sel = sample_participants(np.random.default_rng(0), 10, 4,
                              available=avail)
    assert set(sel.tolist()) == {2, 5}            # shrinks to the eligible
    assert sample_participants(np.random.default_rng(0), 10, 3,
                               available=np.zeros(10, bool)).size == 0
    w = np.zeros(10)
    w[7] = 3.0
    np.testing.assert_array_equal(
        sample_participants(np.random.default_rng(0), 10, 2, weights=w),
        [7])
    with pytest.raises(ValueError, match="shape"):
        sample_participants(np.random.default_rng(0), 10, 2,
                            weights=np.ones(4))
    with pytest.raises(ValueError, match="non-negative"):
        sample_participants(np.random.default_rng(0), 10, 2,
                            weights=-np.ones(10))


@settings(max_examples=20, deadline=None)
@given(st.integers(2, 5), st.integers(1, 8), st.integers(0, 10 ** 6),
       st.integers(3, 10))
def test_slot_pool_churn_sequences_bijection_and_clock_monotonic(
        C, extra, seed, rounds):
    """Scenario-style churn: each round an availability mask departs a
    random subset and Pareto weights bias the participation sample; the
    sampled users are admitted under slot pressure. Through arbitrary
    depart/rejoin interleavings the user<->slot maps stay a bijection,
    departed users are never seated, and the FIFO clocks advance strictly
    monotonically (every newly seated slot's admit tick exceeds every tick
    issued before it)."""
    U = C + extra
    rng = np.random.default_rng(seed)
    pool = SlotPool(U, C)
    weights = rng.pareto(1.5, U) + 1.0
    last_tick = int(pool.state_dict()["clock"]) - 1
    for t in range(rounds):
        avail = rng.random(U) >= 0.4
        m = int(rng.integers(1, C + 1))
        sel = sample_participants(rng, U, m, weights=weights,
                                  available=avail)
        assert avail[sel].all()                   # departed never sampled
        res = pool.admit(sel)
        pool.check()
        ticks = np.sort(pool.admit_seq[res.slots[res.newly]])
        assert (ticks > last_tick).all(), "admit clock went backwards"
        if ticks.size:
            last_tick = int(ticks[-1])
        resident = np.flatnonzero(pool.user_slot >= 0)
        assert np.isin(sel, resident).all()       # the whole sample seated
        assert resident.size <= C


@settings(max_examples=10, deadline=None)
@given(st.lists(st.integers(0, 5), min_size=1, max_size=10))
def test_scores_and_staleness_carry_across_churn(seq):
    """Arbitrary depart/rejoin (eviction pressure) sequences on the sparse
    OSAFL server: a user's score / stale-score carry / participation flag
    ride back in on readmission exactly as last written, and users sitting
    out keep their table rows untouched — only the slot-resident
    contribution row is lost on eviction (the documented semantics)."""
    srv = _sparse_server("osafl", U=6, C=2, seed=1)
    rng = np.random.default_rng(7)
    N = int(srv.w.shape[0])
    expected = {u: (1.0, 1.0, False) for u in range(6)}
    for u in seq:
        res = srv.admit([u])
        s = int(res.slots[0])
        if res.newly[0]:
            # carried per-user state was gathered into the slot...
            assert float(np.asarray(srv.inner.last_scores)[s]) == \
                expected[u][0]
            assert float(np.asarray(srv.inner._lam_prev)[s]) == \
                expected[u][1]
            assert bool(np.asarray(srv.inner.participated)[s]) == \
                expected[u][2]
            # ...and the contribution row was reset to the refresh value
            np.testing.assert_array_equal(
                np.asarray(srv.inner.d_buffer[s]),
                np.asarray(srv.inner.init_row()))
        cohort = srv.cohort
        live = cohort >= 0
        d = jnp.asarray(rng.normal(size=(2, N)).astype(np.float32))
        srv.round_stacked(d, jnp.asarray(live))
        scores = np.asarray(srv.tables["scores"])
        lam = np.asarray(srv.tables["lam_prev"])
        part = np.asarray(srv.tables["participated"])
        for uu in cohort[live].tolist():
            expected[uu] = (float(scores[uu]), float(lam[uu]),
                            bool(part[uu]))
        # everyone else's table rows are exactly their carried values
        for uu in set(range(6)) - set(cohort[live].tolist()):
            assert (float(scores[uu]), float(lam[uu]),
                    bool(part[uu])) == expected[uu], f"user {uu} drifted"
