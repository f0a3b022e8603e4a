"""OSAFL server (paper Algorithm 2).

The CS keeps a per-client contribution buffer d[u], initialized to w^0/eta
(Algorithm 2 line 1). Participating clients overwrite their slot; clients that
have never participated have their slot refreshed to w^t/eta. Scores
Delta_u^t = lambda_u^t (eq. 35) are computed on the *buffer* (eq. 19 averages
all retained contributions) and the global model takes the scored SGD step
(eq. 17): w^{t+1} = w^t - eta~ * eta * sum_u alpha_u Delta_u d[u].
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.configs.base import FLConfig
from repro.core.flatten import FlatCodec, make_codec, scatter_updates
from repro.core.scores import (lambda_scores, lambda_scores_sketched,
                               sketch_stacked, sketch_tree, tree_add,
                               tree_scale, tree_sub, tree_zeros_like)
from repro.core.shmap import client_axes, shard_map


def make_stacked_round_body(fl: FLConfig, mesh=None):
    """The whole stacked OSAFL round — buffer write-back, never-participated
    refresh, eq. 19-21 scores, scored SGD step — as one pure function

        rnd(w, buf, part_prev, lam_prev, d_new, active, alphas, key)
            -> (w, buf, part, lam_use, lam)

    shared by ``StackedOSAFLServer`` (which jits it stand-alone) and the
    one-dispatch engine (``core/round_fused.py``, which inlines it into the
    fused per-round program). Scoring routes through the Pallas kernel or
    the jnp reference per ``fl.score_backend``. With a ``mesh`` (the pod
    engine, whose update plane is split over the client axes) each device
    reduces its own rows: a Mosaic kernel cannot be partitioned
    automatically, and dots and norms are row-local given the mean.
    """
    from repro.kernels.ref import scored_reduce_reference
    from repro.kernels.scored_reduce import lambda_from_reduce, scored_reduce
    reduce = (scored_reduce if fl.score_backend == "kernel"
              else scored_reduce_reference)
    if mesh is not None:
        axes = client_axes(mesh)
        reduce = shard_map(reduce, mesh=mesh, in_specs=(P(axes, None), P()),
                           out_specs=(P(axes), P(axes), P()))

    @jax.named_scope("aggregate")
    def rnd(w, buf, part_prev, lam_prev, d_new, active, alphas, key):
        part = part_prev | active
        buf = jnp.where(active[:, None], d_new, buf)
        # Algorithm 2 line 17: refresh never-participated slots
        refresh = (w / fl.local_lr if fl.literal_init_buffer
                   else jnp.zeros_like(w))
        buf = jnp.where(part[:, None], buf, refresh[None, :])
        if fl.score_sketch_dim:
            sk = sketch_stacked(buf, key, fl.score_sketch_dim)
            mean = jnp.mean(sk, axis=0)
            dots = sk @ mean
            norms = jnp.sum(sk * sk, axis=1)
            msq = jnp.sum(mean * mean)
        else:
            mean = jnp.mean(buf, axis=0)
            dots, norms, msq = reduce(buf, mean)
        lam = lambda_from_reduce(dots, norms, msq, fl.chi)
        # stale_scores: weight THIS round's buffer with the PREVIOUS
        # round's scores (single-pass pod engine semantics)
        lam_use = lam_prev if fl.stale_scores else lam
        step = (alphas * lam_use) @ buf
        w = w - fl.global_lr * fl.local_lr * step
        return w, buf, part, lam_use, lam

    return rnd


@dataclass
class ClientUpdate:
    uid: int
    d: object                        # normalized accumulated gradient pytree
    kappa: int
    data_size: int = 0
    label_hist: Optional[np.ndarray] = None   # only consumed by M-FedDisco


class OSAFLServer:
    """Paper-faithful cross-device engine (small models, CPU)."""

    def __init__(self, params, fl: FLConfig, num_clients: int,
                 alphas: Optional[np.ndarray] = None, seed: int = 0):
        self.params = params
        self.fl = fl
        self.U = num_clients
        self.alphas = (np.full(num_clients, 1.0 / num_clients)
                       if alphas is None else alphas)
        # Algorithm 2 line 1 (literal): d[u] <- w^0/eta. The literal reading
        # treats a never-participated client as owning the zero model and
        # sign-flips the global weights under heavy straggling; the default
        # here is the no-op reading (zero update). EXPERIMENTS.md documents
        # the deviation; literal_init_buffer=True restores Algorithm 2.
        init_d = (tree_scale(params, 1.0 / fl.local_lr)
                  if fl.literal_init_buffer else tree_zeros_like(params))
        self.d_buffer: List = [init_d for _ in range(num_clients)]
        self.participated = np.zeros(num_clients, bool)
        self.last_scores = np.ones(num_clients)
        self._sketch_key = jax.random.PRNGKey(seed)

    def round(self, updates: Sequence[ClientUpdate]) -> dict:
        fl = self.fl
        for up in updates:
            self.d_buffer[up.uid] = up.d
            self.participated[up.uid] = True
        for u in range(self.U):
            if not self.participated[u]:
                # Algorithm 2 line 17: refresh never-participated slots
                self.d_buffer[u] = (
                    tree_scale(self.params, 1.0 / fl.local_lr)
                    if fl.literal_init_buffer
                    else tree_zeros_like(self.params))
        if fl.score_sketch_dim:
            sk = jnp.stack([sketch_tree(d, self._sketch_key,
                                        fl.score_sketch_dim)
                            for d in self.d_buffer])
            lam = lambda_scores_sketched(sk, fl.chi)
        else:
            lam = lambda_scores(self.d_buffer, fl.chi)
        if fl.stale_scores:
            # single-pass pod engine semantics: weight THIS round's updates
            # with the PREVIOUS round's scores (lam becomes next round's)
            lam, self._lam_next = getattr(self, "_lam_next",
                                          np.ones(self.U)), lam
        self.last_scores = lam
        step = tree_zeros_like(self.params)
        for u in range(self.U):
            w = float(self.alphas[u] * lam[u])
            step = tree_add(step, tree_scale(self.d_buffer[u], w))
        lr = fl.global_lr * fl.local_lr
        self.params = tree_sub(self.params, tree_scale(step, lr))
        return self.params

    # -- checkpointing -------------------------------------------------------
    def state_dict(self) -> dict:
        """Snapshot of everything a round mutates: params, the per-client
        contribution buffer, participation (staleness) flags, eq. 19-21
        scores and the stale-score carry (see repro/checkpoint)."""
        return {"params": self.params,
                "d_buffer": list(self.d_buffer),
                "participated": self.participated,
                "last_scores": np.asarray(self.last_scores),
                "lam_next": getattr(self, "_lam_next", None),
                "sketch_key": np.asarray(self._sketch_key)}

    def load_state_dict(self, sd: dict) -> None:
        as_dev = lambda t: jax.tree.map(jnp.asarray, t)  # noqa: E731
        self.params = as_dev(sd["params"])
        self.d_buffer = [as_dev(d) for d in sd["d_buffer"]]
        self.participated = np.asarray(sd["participated"], bool)
        self.last_scores = np.asarray(sd["last_scores"])
        if sd.get("lam_next") is not None:
            self._lam_next = np.asarray(sd["lam_next"])
        else:
            self.__dict__.pop("_lam_next", None)
        self._sketch_key = jnp.asarray(sd["sketch_key"])


class StackedOSAFLServer:
    """Vectorized Algorithm 2: the same semantics as ``OSAFLServer`` (which is
    kept as the exact-parity reference), but every client's contribution is a
    row of one (U, N) float32 buffer and the whole round — buffer write-back,
    never-participated refresh, scores, scored SGD step — is a single jitted
    function. Scoring routes through the fused Pallas kernel
    ``kernels/scored_reduce.py`` (``fl.score_backend="kernel"``, interpret
    mode on CPU) or the pure-jnp oracle ``kernels/ref.py``
    (``fl.score_backend="reference"``).

    Two entry points:
      * ``round(updates)`` — drop-in for the loop server: a sparse list of
        ``ClientUpdate`` pytrees (or pre-flattened (N,) rows) is scattered
        into the dense buffer.
      * ``round_stacked(d_new, active)`` — the scale path: a dense (U, N)
        update matrix (e.g. from ``client.make_vmapped_local_train``) plus a
        participation mask, with no per-client Python work at all.
    """

    def __init__(self, params, fl: FLConfig, num_clients: int,
                 alphas: Optional[np.ndarray] = None, seed: int = 0,
                 mesh=None):
        self.fl = fl
        self.U = num_clients
        self.codec: FlatCodec = make_codec(params)
        self.alphas = jnp.asarray(
            np.full(num_clients, 1.0 / num_clients) if alphas is None
            else alphas, jnp.float32)
        self.w = self.codec.flatten(params)
        self.d_buffer = jnp.tile(self.init_row()[None, :], (num_clients, 1))
        self.participated = jnp.zeros(num_clients, bool)
        self.last_scores = np.ones(num_clients)
        self._lam_prev = jnp.ones(num_clients, jnp.float32)
        self._sketch_key = jax.random.PRNGKey(seed)
        self._round_fn = jax.jit(make_stacked_round_body(fl, mesh))

    @property
    def params(self):
        return self.codec.unflatten(self.w)

    def init_row(self) -> jnp.ndarray:
        """The (N,) refresh value of a slot holding no live contribution
        (Algorithm 2 line 17 semantics): w/eta under the literal init, zeros
        otherwise. The sparse-cohort engine (``core/cohort.py``) writes this
        into a slot at admission — an evicted client's contribution row is
        slot-resident and lost, so a readmitted client restarts from it."""
        return (self.w / self.fl.local_lr if self.fl.literal_init_buffer
                else jnp.zeros_like(self.w))

    def round_stacked(self, d_new: jnp.ndarray, active) -> jnp.ndarray:
        """d_new: (U, N) f32 update matrix; active: (U,) bool mask. Returns
        the new flat global weights (use ``.params`` for the pytree view)."""
        (self.w, self.d_buffer, self.participated, lam_use,
         self._lam_prev) = self._round_fn(
            self.w, self.d_buffer, self.participated, self._lam_prev,
            d_new, jnp.asarray(active), self.alphas, self._sketch_key)
        self.last_scores = np.asarray(lam_use)
        return self.w

    def round(self, updates: Sequence[ClientUpdate]) -> dict:
        d_new, active = scatter_updates(self.codec, updates, self.U)
        self.round_stacked(jnp.asarray(d_new), active)
        return self.params

    # -- checkpointing -------------------------------------------------------
    def state_dict(self) -> dict:
        """Flat-vector counterpart of ``OSAFLServer.state_dict``: the global
        weights, the (U, N) contribution buffer, participation flags and both
        score vectors (current + stale-score carry)."""
        return {"w": self.w, "d_buffer": self.d_buffer,
                "participated": self.participated,
                "last_scores": np.asarray(self.last_scores),
                "lam_prev": self._lam_prev,
                "sketch_key": np.asarray(self._sketch_key)}

    def load_state_dict(self, sd: dict) -> None:
        self.w = jnp.asarray(sd["w"])
        self.d_buffer = jnp.asarray(sd["d_buffer"])
        self.participated = jnp.asarray(np.asarray(sd["participated"], bool))
        self.last_scores = np.asarray(sd["last_scores"])
        self._lam_prev = jnp.asarray(sd["lam_prev"])
        self._sketch_key = jnp.asarray(sd["sketch_key"])
