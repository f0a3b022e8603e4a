"""Client-side local training (paper eqs. 14-16).

A client synchronizes to the global model, performs kappa_u^t mini-batch SGD
steps on its current FIFO dataset, and returns the *normalized accumulated
gradient* d_u^t = (w^{t,0} - w^{t,kappa}) / (eta * kappa). Supports the
FedProx proximal local objective (Algorithm 7).
"""
from __future__ import annotations

from functools import partial
from typing import Callable, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.buffer import OnlineBuffer
from repro.core.scores import tree_scale, tree_sub


@partial(jax.jit, static_argnames=("grad_fn", "prox_mu"))
def _sgd_step(params, batch, lr, grad_fn, prox_mu=0.0, global_params=None):
    g = grad_fn(params, batch)
    if prox_mu:
        g = jax.tree.map(lambda gg, w, w0: gg + prox_mu * (w - w0),
                         g, params, global_params)
    return jax.tree.map(lambda w, gg: w - lr * gg, params, g)


def local_train(global_params, grad_fn: Callable, buffer: OnlineBuffer,
                kappa: int, lr: float, batch_size: int,
                rng: np.random.Generator, prox_mu: float = 0.0
                ) -> Tuple[dict, dict]:
    """Run kappa local SGD steps. Returns (d_u, w_final)."""
    params = global_params
    for _ in range(kappa):
        bx, by = buffer.sample_batch(rng, batch_size)
        batch = {"x": jnp.asarray(bx), "y": jnp.asarray(by)}
        params = _sgd_step(params, batch, lr, grad_fn,
                           prox_mu=prox_mu,
                           global_params=global_params if prox_mu else None)
    d = tree_scale(tree_sub(global_params, params), 1.0 / (lr * kappa))
    return d, params


def make_local_train_body(grad_fn: Callable, lr: float, kappa_max: int,
                          prox_mu: float = 0.0) -> Callable:
    """One client's masked local-SGD body,
    ``one_client(global_params, batch_u, kappa_u) -> (d_u, w_u)`` with
    ``batch_u`` leaves of shape (kappa_max, B, ...): kappa_u real SGD steps
    (steps past kappa_u are masked no-ops; kappa_u == 0 — a straggler —
    yields d_u = 0) and the normalized accumulated gradient. This is the
    single per-client unit of work; ``make_vmapped_local_train`` vmaps it
    for the stacked engine and the pod online steps (``core/pod.py``) run it
    per mesh row inside shard_map / a client scan, so all engines share the
    exact same local-training math.
    """

    @jax.named_scope("local_sgd")
    def one_client(global_params, batch_u, kappa_u):
        def body(params, inp):
            batch_t, t = inp
            stepped = _sgd_step(
                params, batch_t, lr, grad_fn, prox_mu=prox_mu,
                global_params=global_params if prox_mu else None)
            params = jax.tree.map(
                lambda n, o: jnp.where(t < kappa_u, n, o), stepped, params)
            return params, None

        steps = jnp.arange(kappa_max)
        params, _ = jax.lax.scan(body, global_params, (batch_u, steps))
        denom = lr * jnp.maximum(kappa_u, 1).astype(jnp.float32)
        d = jax.tree.map(lambda w0, w: (w0 - w) / denom,
                         global_params, params)
        return d, params

    return one_client


def make_vmapped_local_train(grad_fn: Callable, lr: float, kappa_max: int,
                             prox_mu: float = 0.0) -> Callable:
    """Vectorized local training for the stacked engine: every client runs its
    kappa_u local SGD steps in lockstep under one ``jax.vmap``, so a whole
    cohort trains in a single XLA computation instead of U Python loops.

    Returns a jitted ``fn(global_params, batches, kappas) -> (d, w)`` where
    ``batches`` is a pytree with leaves of shape (U, kappa_max, B, ...),
    ``kappas`` is (U,) int with values in [0, kappa_max], and the outputs are
    stacked pytrees with a leading client axis. Semantics match
    ``local_train`` step-for-step on the same batch sequence (the per-client
    body is ``make_local_train_body``).
    """
    one_client = make_local_train_body(grad_fn, lr, kappa_max,
                                       prox_mu=prox_mu)
    return jax.jit(jax.vmap(one_client, in_axes=(None, 0, 0)))
