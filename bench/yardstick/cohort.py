"""Plain reference of the host-driven OSAFL round over a slot pool
(``repro.harness.run`` with ``round_backend="dispatch"``): a registered
population of U users, C round-live slots, and a share of the slots sampled
to train each round (partial participation, Dinh et al. arXiv:1910.13067).

The run starts where ``start.py`` makes it start; the rounds take the host
generator up after the clients' systems. Each round t:

1. participants: ``round(participation C)`` users, uniform without
   replacement; each one not resident takes the free slot freed first, or
   evicts the resident seated first that is not among this round's
   participants. A newly seated slot starts with an empty FIFO of the new
   user's D_u and a zero contribution row;
2. arrivals: Binomial(E_u, p_ac_u) for each resident, drawn by the request
   model for the whole population (non-residents draw nothing), and pushed
   into the slot's FIFO ring;
3. kappa_u = kappa_max; a slot trains if its user was sampled and its FIFO
   holds a sample;
4. local SGD: kappa_u steps on batches of B slots drawn uniformly from the
   live window; d_u = (w - w_u) / (eta kappa_u);
5. scored aggregation over the C slots: a training slot's row becomes d_u,
   rows never written since the slot was seated stay 0; lambda_u =
   (chi + cos(d_u, mean)) / (chi + 1); w <- w - eta~ eta sum_u lambda_u
   d_u / C;
6. evaluation: cross-entropy of w on the evaluation set.

Where the traffic runs on a ``mesh``, its client axes (``pod``, ``data``)
split the slots into equal blocks, one a chip; the round is the same.

With ``cohort_size`` 0 every user holds its own slot for the whole run and
trains every round (the dense round). The model runs at "highest" matmul
precision in float32, or in ``dtype`` (the control lowers it to bfloat16).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from bench.yardstick.reference import leaf_sizes, model_fns
from bench.yardstick.start import features, start

FAULTS = ("half_batch", "label", "frozen", "no_exchange")
CLIENT_AXES = ("pod", "data")


def chip_blocks(traffic: dict) -> int:
    """The number of equal blocks the mesh's client axes split the slots
    into (1 without a mesh)."""
    m = traffic.get("mesh")
    if m is None:
        return 1
    return int(np.prod([n for n, a in zip(m["shape"], m["axes"])
                        if a in CLIENT_AXES]))


def run_cohort_reference(cfg: dict, traffic: dict, model, seed: int,
                         rounds: int, dtype=jnp.float32,
                         fault: Optional[str] = None) -> dict:
    """The first ``rounds`` rounds of the run of ``seed``; per round the
    evaluation ``loss`` and the number of ``participants`` (slots that
    trained), the flat weights ``w`` before round 0 and after each round
    (float32 host arrays) and their leaf ``sizes``. ``fault`` plants one of
    ``FAULTS``: local SGD on half of each batch, every new request's label
    off by one, weights that never change, or, on a mesh, the exchange
    between chips left out (the first chip aggregates its own block of
    slots as if it were the whole pool)."""
    if cfg["use_resource_opt"]:
        raise ValueError("the host-driven reference fixes kappa_u at "
                         "kappa_max; the configuration asks for the solve")
    U, C0 = traffic["num_users"], traffic["cohort_size"]
    C = C0 or U
    m = max(1, int(round(traffic["participation"] * C)))
    resample = C0 > 0 and (C < U or traffic["participation"] < 1.0)
    B, kmax, lr = cfg["batch"], cfg["kappa_max"], cfg["local_lr"]
    E, ds = cfg["arrivals"], cfg["dataset"]
    blocks = chip_blocks(traffic)
    if fault == "no_exchange" and blocks == 1:
        raise ValueError("the no_exchange fault needs a mesh of chips")
    st = start(cfg, seed, U, C0, traffic["eval_samples"], fault=fault)
    pool, fifo, rng = st.pool, st.fifo, st.rng
    params0 = model.init(st.key)
    f = model_fns(cfg, model, params0, dtype)
    set_row = jax.jit(lambda m, s, r: m.at[s].set(r), donate_argnums=0)
    w = f.w0
    buf = jnp.zeros((C, w.shape[0]), dtype)
    test_x, test_y = f.batch_x(st.test_x), jnp.asarray(st.test_y)
    out = {"loss": [], "participants": [], "w": [np.asarray(w, np.float32)],
           "sizes": leaf_sizes(params0)}
    for _ in range(rounds):
        picked = np.ones(C, bool)
        if resample:
            sel = np.sort(rng.choice(U, size=m, replace=False))
            for s, u in pool.admit(sel):
                fifo.reset(s, st.caps[u])
                buf = buf.at[s].set(0)
            picked[:] = False
            picked[pool.user_slot[sel]] = True
        cohort = pool.slot_user
        counts = rng.binomial(E, st.p_ac[cohort])
        full = np.zeros(U, np.int64)
        full[cohort] = counts
        samples = st.stream.draw(full, E)
        for s, u in enumerate(cohort):
            for x, y in samples[u]:
                fifo.push(s, x, y)
        active = picked & (fifo.size > 0)
        size = np.maximum(fifo.size, 1)
        j = rng.integers(0, size.reshape(C, 1, 1), size=(C, kmax, B))
        slots = (fifo.head[:, None, None] + j) % fifo.cap[:, None, None]
        params = f.unflatten(w)
        for s in np.flatnonzero(active):
            p = params
            for k in range(kmax):
                xb, yb = fifo.x[s, slots[s, k]], fifo.y[s, slots[s, k]]
                if fault == "half_batch":
                    xb, yb = xb[:B // 2], yb[:B // 2]
                if ds == 1:
                    xb = features(st.cat, cohort[s], xb)
                p = f.sgd(p, f.batch_x(xb), jnp.asarray(yb))
            buf = set_row(buf, s, (w - f.flatten(p))
                          / jnp.asarray(lr * kmax, dtype))
        if fault == "no_exchange":
            w = f.server_step(w, buf[:C // blocks], C // blocks)
        elif fault != "frozen":
            w = f.server_step(w, buf, C)
        loss, _ = f.evaluate(w, test_x, test_y)
        out["loss"].append(float(loss))
        out["participants"].append(int(active.sum()))
        out["w"].append(np.asarray(w, np.float32))
    return out
