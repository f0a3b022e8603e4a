"""The plain reference's parts against the program's own paths on the CPU,
where both compute in the same precision: the float64 resource solve, the
state a run starts from, the request draws and the slot pool."""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.run import BENCH, load_module
from bench.yardstick import request as rq
from bench.yardstick.program import experiment_config
from bench.yardstick.resource import pathloss_linear, solve_kappa
from bench.yardstick.start import Pool, Stream, client_systems, population, \
    start

CFG = json.loads((BENCH / "configs" / "lstm-d2-kappa5.json").read_text())
FCN = json.loads((BENCH / "configs" / "fcn-d1-kappa5.json").read_text())


def test_resource_solve_matches_the_programs_float64_solve():
    from repro.core.resource import NetworkConfig
    from repro.core.resource_stacked import make_solver_core

    rng = np.random.default_rng(3)
    sysc = client_systems(rng, 512, CFG["client_system"])
    gamma = 10 ** (rng.normal(0, 8, 512) / 10)
    ours = solve_kappa(CFG["network"], sysc, gamma, CFG["n_params"])
    with jax.enable_x64(True):
        solve = jax.jit(make_solver_core(NetworkConfig(**CFG["network"]),
                                         "x64"))
        cols = [jnp.asarray(sysc[k], jnp.float64)
                for k in ("c", "s", "f_max", "p_max", "e_bd")]
        theirs = np.asarray(solve(
            *cols, jnp.asarray(pathloss_linear(sysc["distance"])),
            jnp.asarray(gamma), jnp.float64(CFG["n_params"]))[0])
    assert np.array_equal(ours, theirs.astype(np.int32))
    assert 0 < (ours == 0).sum() < 512 and (ours == 5).any()


@pytest.mark.parametrize("cfg", [CFG, FCN], ids=["lstm", "fcn"])
def test_start_matches_the_programs_setup(cfg):
    from repro.harness.experiments import build_fused_engine

    cfg = dict(cfg, num_clients=6, capacity=[12, 24])
    seed = 2 ** 31 + 5
    xc = experiment_config(cfg, seed, 3, 6, round_backend="fused")
    _, s = build_fused_engine("osafl", xc, 24)
    st = start(cfg, seed, 6, 0, 24)
    assert np.array_equal(st.caps, s.caps)
    assert np.array_equal(st.p_ac, s.p_ac)
    buf = s.sbuf.state
    assert np.array_equal(st.fifo.size, np.asarray(buf.size))
    assert np.array_equal(st.fifo.head, np.asarray(buf.head))
    assert np.array_equal(st.fifo.y[:, :12], np.asarray(buf.y)[:, :12])
    x = np.asarray(buf.x)
    for u in range(6):
        live = slice(0, int(st.caps[u]))
        ours = st.fifo.x[u, live]
        if cfg["dataset"] == 1:
            ours = rq.features(st.cat, u, ours)
        assert np.array_equal(ours, x[u, live])
    assert np.array_equal(st.test_x, np.asarray(s.test_batch["x"]))
    assert np.array_equal(st.test_y, np.asarray(s.test_batch["y"]))
    stream = s.rstream.state
    assert np.array_equal(st.stream.file, np.asarray(stream.file))
    assert np.array_equal(st.stream.key, np.asarray(stream.key))
    model = load_module(BENCH / "configs" / f"{cfg['name']}.py")
    for a, b in zip(jax.tree.leaves(model.init(st.key)),
                    jax.tree.leaves(s.server.params)):
        assert np.array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("dataset", [1, 2])
def test_request_draw_matches_the_programs_sampler(dataset):
    """From a cold population (every user still owes its warm-up), with
    some users owing nothing."""
    from repro.data.video_caching_stacked import (StreamConsts, StreamState,
                                                  _draw_block)

    U = 8
    cat, _ = population(7, U, CFG["user"])
    ours = Stream(cat, 7, dataset, 1)
    consts = StreamConsts(*(jnp.asarray(a) for a in (
        cat.feat50, cat.own_sims, cat.popularity, cat.pref, cat.log_pref,
        cat.eps, cat.log_zipf)))
    state = StreamState(ours.key, jnp.full(U, -1, jnp.int32),
                        jnp.full(U, -1, jnp.int32), jnp.zeros(U, bool),
                        jnp.zeros((U, rq.SEQ_LEN), jnp.int32),
                        jnp.zeros(U, jnp.int32))
    for counts in ([0, 1, 2, 3, 8, 8, 5, 4], [8, 0, 3, 3, 1, 0, 2, 8]):
        counts = np.array(counts, np.int32)
        warmup = ours.deficit()
        got = ours.draw(counts, 8)
        state, xs, ys = _draw_block(consts, state, jnp.asarray(counts),
                                    width=8, warmup=warmup, dataset=dataset,
                                    topk=1)
        for u in range(U):
            assert len(got[u]) == counts[u]
            for j, (x, y) in enumerate(got[u]):
                assert y == int(ys[u, j])
                if dataset == 1:
                    x = rq.features(cat, u, np.asarray(x))
                assert np.array_equal(x, np.asarray(xs[u, j]))
        assert np.array_equal(ours.file, np.asarray(state.file))
        assert np.array_equal(ours.hist, np.asarray(state.hist))


def test_slot_pool_matches_the_programs_pool():
    from repro.core.cohort import SlotPool

    rng = np.random.default_rng(11)
    ours, theirs = Pool(40, 10), SlotPool(40, 10)
    ours.admit(np.arange(10))
    theirs.admit(np.arange(10))
    for _ in range(30):
        sel = np.sort(rng.choice(40, size=int(rng.integers(1, 11)),
                                 replace=False))
        new = ours.admit(sel)
        res = theirs.admit(sel)
        assert [s for s, _ in new] == list(res.slots[res.newly])
        assert np.array_equal(ours.slot_user, theirs.slot_user)
