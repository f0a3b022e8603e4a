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

With ``cohort_size`` 0 every user holds its own slot for the whole run and
trains every round (the dense round). The model runs at "highest" matmul
precision in float32, or in ``dtype`` (the control lowers it to bfloat16).
"""
from __future__ import annotations

from typing import Optional

import jax.numpy as jnp
import numpy as np

from bench.yardstick.reference import model_fns
from bench.yardstick.start import features, start

FAULTS = ("half_batch", "label", "frozen")


def run_cohort_reference(cfg: dict, traffic: dict, model, seed: int,
                         rounds: int, dtype=jnp.float32,
                         fault: Optional[str] = None) -> dict:
    """The first ``rounds`` rounds of the run of ``seed``; per round the
    evaluation ``loss`` and the number of ``participants`` (slots that
    trained). ``fault`` plants one of ``FAULTS``: local SGD on half of each
    batch, every new request's label off by one, or weights that never
    change."""
    if cfg["use_resource_opt"]:
        raise ValueError("the host-driven reference fixes kappa_u at "
                         "kappa_max; the configuration asks for the solve")
    U, C0 = traffic["num_users"], traffic["cohort_size"]
    C = C0 or U
    m = max(1, int(round(traffic["participation"] * C)))
    resample = C0 > 0 and (C < U or traffic["participation"] < 1.0)
    B, kmax, lr = cfg["batch"], cfg["kappa_max"], cfg["local_lr"]
    E, ds = cfg["arrivals"], cfg["dataset"]
    st = start(cfg, seed, U, C0, traffic["eval_samples"], fault=fault)
    pool, fifo, rng = st.pool, st.fifo, st.rng
    f = model_fns(cfg, model, model.init(st.key), dtype)
    w = f.w0
    buf = jnp.zeros((C, w.shape[0]), dtype)
    test_x, test_y = f.batch_x(st.test_x), jnp.asarray(st.test_y)
    out = {"loss": [], "participants": []}
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
        rows = []
        for s in np.flatnonzero(active):
            p = params
            for k in range(kmax):
                xb, yb = fifo.x[s, slots[s, k]], fifo.y[s, slots[s, k]]
                if fault == "half_batch":
                    xb, yb = xb[:B // 2], yb[:B // 2]
                if ds == 1:
                    xb = features(st.cat, cohort[s], xb)
                p = f.sgd(p, f.batch_x(xb), jnp.asarray(yb))
            rows.append((w - f.flatten(p)) / jnp.asarray(lr * kmax, dtype))
        if rows:
            buf = buf.at[jnp.asarray(np.flatnonzero(active))].set(
                jnp.stack(rows))
        if fault != "frozen":
            w = f.server_step(w, buf, C)
        loss, _ = f.evaluate(w, test_x, test_y)
        out["loss"].append(float(loss))
        out["participants"].append(int(active.sum()))
    return out
