"""Plain reference of the online OSAFL round (arXiv:2408.05886, Algorithms
1-2, eqs. 14-21), one client at a time.

Each round t:

0. the run starts where ``start.py`` makes it start (every client holds its
   own slot);
1. arrivals: client u gets Binomial(E_u, p_ac_u) new requests, drawn as E_u
   Bernoulli trials, and the request model makes them (``request.py``);
2. FIFO commit: each new sample goes into the client's ring of D_u slots,
   evicting the oldest when full;
3. resource decisions: kappa_u from this round's shadowing
   (``resource.py``); kappa_u = 0 makes a straggler;
4. local SGD: kappa_u mini-batch steps on batches drawn uniformly from the
   client's live window, and d_u = (w - w_u) / (eta kappa_u);
5. scored aggregation: participants overwrite their row of the contribution
   buffer, rows never written stay 0; lambda_u = (chi + cos(d_u, mean)) /
   (chi + 1) and w <- w - eta~ eta sum_u alpha_u lambda_u d_u;
6. evaluation: cross-entropy and accuracy of w on the evaluation set.

The random numbers of a round come from the same key schedule as the round
under test: key_t = fold_in(fold_in(PRNGKey(seed), 0x0f5afe), t), split into
arrival, channel and batch-slot keys; the request stream's key splits into
five each round. With them the reference draws the same requests, channels
and batches, and computes everything else in its own plain way: float64 for
the resource solve, float32 at "highest" matmul precision for the model (or
in ``dtype``, which the control lowers to bfloat16).
"""
from __future__ import annotations

from functools import partial
from types import SimpleNamespace
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from bench.yardstick.resource import solve_kappa
from bench.yardstick.start import features, start

ROUND_KEY_TAG = 0x0f5afe
SHADOW_SIGMA_DB = 8.0


def round_keys(seed: int, t: int):
    base = jax.random.fold_in(jax.random.PRNGKey(seed), ROUND_KEY_TAG)
    k_arr, k_chan, k_slots = jax.random.split(jax.random.fold_in(base, t), 3)
    return k_arr, k_chan, k_slots


def round_kappas(cfg: dict, st, t: int) -> np.ndarray:
    """(U,) kappa of round t: the shadowing draw and the float64 solve over
    the clients' systems ``st.sysc``, or kappa_max for every client where
    the configuration turns the solve off."""
    if not cfg["use_resource_opt"]:
        return np.full(cfg["num_clients"], cfg["kappa_max"], np.int32)
    _, k_chan, _ = round_keys(st.seed, t)
    db = np.asarray(jax.random.normal(k_chan, (cfg["num_clients"],),
                                      jnp.float32) * SHADOW_SIGMA_DB)
    gamma = 10.0 ** (db.astype(np.float64) / 10.0)
    return solve_kappa(cfg["network"], st.sysc, gamma, cfg["n_params"])


def leaf_sizes(params) -> list:
    """(name, size) of each leaf in the flattening order."""
    flat, _ = jax.tree_util.tree_flatten_with_path(params)
    return [(jax.tree_util.keystr(p), int(np.prod(v.shape))) for p, v in flat]


def model_fns(cfg: dict, model, params, dtype=jnp.float32) -> SimpleNamespace:
    """The reference's model and server arithmetic, at "highest" matmul
    precision in float32 or at default precision in a lower ``dtype``:
    ``w0`` (the flat weights in ``dtype``), ``flatten``/``unflatten``,
    ``sgd(params, x, y)`` (one mini-batch step), ``evaluate(w, x, y)`` (mean
    cross-entropy and accuracy), ``batch_x`` (feature rows to the device in
    the model's input type) and ``server_step(w, buf, n)`` (eqs. 19-21 over
    the n rows of the contribution buffer, each weighted 1/n)."""
    lr, glr, chi = cfg["local_lr"], cfg["global_lr"], cfg["chi"]
    precision = "highest" if dtype == jnp.float32 else "default"
    treedef = jax.tree.structure(params)
    shapes = [v.shape for v in jax.tree.leaves(params)]
    offs = np.cumsum([0] + [int(np.prod(s)) for s in shapes])

    def unflatten(w):
        return jax.tree.unflatten(treedef, [
            w[a:b].reshape(s) for a, b, s in zip(offs[:-1], offs[1:],
                                                  shapes)])

    def flatten(tree):
        return jnp.concatenate([v.reshape(-1) for v in jax.tree.leaves(tree)])

    def loss_fn(params, x, y):
        logits = model.forward(params, x).astype(jnp.float32)
        logp = jax.nn.log_softmax(logits)
        return -jnp.mean(jnp.take_along_axis(logp, y[:, None], axis=-1)), \
            jnp.mean(jnp.argmax(logits, -1) == y)

    def batch_x(x):
        x = jnp.asarray(x)
        return x.astype(dtype) if cfg["dataset"] == 1 else x

    with jax.default_matmul_precision(precision):
        sgd = jax.jit(lambda p, x, y: jax.tree.map(
            lambda w, g: w - jnp.asarray(lr, dtype) * g, p,
            jax.grad(lambda q: loss_fn(q, x, y)[0])(p)))
        evaluate = jax.jit(lambda w, x, y: loss_fn(unflatten(w), x, y))

        @partial(jax.jit, static_argnums=2)
        def server_step(w, buf, n):
            mean = jnp.mean(buf, axis=0)
            dots = buf @ mean
            norms = jnp.sum(buf * buf, axis=1)
            msq = jnp.sum(mean * mean)
            cos = jnp.clip(dots / jnp.maximum(jnp.sqrt(norms) * jnp.sqrt(msq),
                                              1e-12), -1.0, 1.0)
            lam = ((chi + cos) / (chi + 1.0)).astype(buf.dtype)
            step = (jnp.full((n,), 1.0 / n, buf.dtype) * lam) @ buf
            return w - jnp.asarray(glr * lr, buf.dtype) * step

    return SimpleNamespace(
        w0=flatten(params).astype(dtype), flatten=flatten,
        unflatten=unflatten, sgd=sgd, evaluate=evaluate, batch_x=batch_x,
        server_step=server_step)


def run_reference(cfg: dict, model, seed: int, rounds: int,
                  eval_samples: int, dtype=jnp.float32,
                  fault: Optional[str] = None) -> dict:
    """The first ``rounds`` rounds of the one-dispatch round of ``seed``,
    started where ``start.start`` makes it start. Returns per-round
    ``loss``, ``acc``, ``participants``, the flat weights ``w`` before round
    0 and after each round (float32 host arrays, in the flattening order of
    ``leaf_sizes``), and the weights' leaf ``sizes``.

    ``fault`` plants one of the faults the comparison has to catch:
    ``"half_batch"`` (local SGD on the first half of each batch),
    ``"label"`` (every new request's label off by one) or ``"frozen"``
    (weights that never change)."""
    U, B = cfg["num_clients"], cfg["batch"]
    kmax, lr, ds = cfg["kappa_max"], cfg["local_lr"], cfg["dataset"]
    st = start(cfg, seed, U, 0, eval_samples, fault=fault)
    params = model.init(st.key)
    f = model_fns(cfg, model, params, dtype)
    set_row = jax.jit(lambda m, u, r: m.at[u].set(r), donate_argnums=0)
    take_rows = jax.jit(lambda buf, d, a: jnp.where(a[:, None], d, buf),
                        donate_argnums=(0, 1))
    fifo, p_ac = st.fifo, st.p_ac.astype(np.float32)
    w = f.w0
    N = w.shape[0]
    buf = jnp.zeros((U, N), dtype)
    test_x, test_y = f.batch_x(st.test_x), jnp.asarray(st.test_y)
    out = {"loss": [], "acc": [], "participants": [],
           "w": [np.asarray(w, np.float32)], "sizes": leaf_sizes(params)}
    for t in range(rounds):
        k_arr, _, k_slots = round_keys(seed, t)
        counts = np.sum(np.asarray(jax.random.uniform(
            k_arr, (U, cfg["arrivals"]), jnp.float32))
            < p_ac[:, None], axis=1)
        samples = st.stream.draw(counts, cfg["arrivals"])
        for u in range(U):
            for x, y in samples[u]:
                fifo.push(u, x, y)
        kappa = round_kappas(cfg, st, t)
        active = kappa >= 1
        slots = fifo.batch_slots(np.asarray(jax.random.uniform(
            k_slots, (U, kmax, B), jnp.float32)))
        params = f.unflatten(w)
        d_new = jnp.zeros((U, N), dtype)
        for u in range(U):
            p = params
            for s in range(int(kappa[u])):
                xb = fifo.x[u, slots[u, s]]
                yb = fifo.y[u, slots[u, s]]
                if fault == "half_batch":
                    xb, yb = xb[:B // 2], yb[:B // 2]
                if ds == 1:
                    xb = features(st.cat, u, xb)
                p = f.sgd(p, f.batch_x(xb), jnp.asarray(yb))
            denom = jnp.asarray(lr * max(int(kappa[u]), 1), dtype)
            d_new = set_row(d_new, u, (w - f.flatten(p)) / denom)
        buf = take_rows(buf, d_new, jnp.asarray(active))
        if fault != "frozen":
            w = f.server_step(w, buf, U)
        loss, acc = f.evaluate(w, test_x, test_y)
        out["loss"].append(float(loss))
        out["acc"].append(float(acc))
        out["participants"].append(int(active.sum()))
        out["w"].append(np.asarray(w, np.float32))
    return out
