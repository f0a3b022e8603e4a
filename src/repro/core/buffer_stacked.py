"""Stacked time-varying FIFO client datasets (paper Section II-A, vectorized).

All U clients' bounded datasets live in one ``(U, D, ...)`` device array
(D = max capacity) with per-client capacity/head/size pointer arrays.
Arrivals are staged during the round and applied FIFO at the round boundary
by one jitted scatter — the closed form of ``core/buffer.py``'s sequential
``_insert`` loop:

  * staged sample j lands in slot ``(head + size + j) mod cap``;
  * of an over-capacity commit only the last ``cap`` staged samples survive
    (earlier ones would be immediately overwritten), so the rest are dropped
    before the scatter and no slot is written twice;
  * ``size`` grows to ``min(size + n, cap)`` and ``head`` advances by the
    overflow ``max(size + n - cap, 0)``.

``core/buffer.py`` remains the semantic oracle: the stacked state (dataset
contents in FIFO order, size, label histogram) must match it exactly over
multi-round runs including wrap-around (tests/test_online_stacked.py).

Mesh-sharded mode (DESIGN.md §3 "Online arrivals"): ``create(..., mesh=...)``
(or ``shard(mesh)``) lays the whole state out over the mesh's
``('pod','data')`` client axes — storage, staging and the cap/head/size
pointer arrays are all ``(U, ...)``-leading, so every leaf gets
``NamedSharding(mesh, P(client_axes, None, ...))`` and each shard owns
U/rows whole clients. Staging and the FIFO commit are purely row-local, so
the sharded ops are the *same* ``_stage``/``_commit`` bodies wrapped in
``shard_map``: per-shard jitted scatters, no cross-shard communication and
no host gather of storage. The pod train steps (``core/pod.py`` online mode)
then sample minibatches from each row's own shard in place.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from functools import partial
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core.shmap import (client_axes, client_rows, client_sharding,
                              shard_map)


class BufState(NamedTuple):
    """Device-array state of all U buffers (a pytree for the jitted ops)."""
    x: jnp.ndarray          # (U, D, *feat) feature storage
    y: jnp.ndarray          # (U, D) labels
    cap: jnp.ndarray        # (U,) int32 per-client capacity D_u (immutable)
    size: jnp.ndarray      # (U,) int32
    head: jnp.ndarray      # (U,) int32 FIFO eviction pointer (oldest sample)
    staged_x: jnp.ndarray   # (U, S, *feat) within-round temp buffer
    staged_y: jnp.ndarray   # (U, S)
    staged_n: jnp.ndarray   # (U,) int32


@jax.named_scope("fifo_commit")
def _stage_impl(state: BufState, x_new, y_new, counts) -> BufState:
    """Append ``counts[u]`` of client u's padded arrival rows to its staged
    buffer. Rows beyond counts[u] are padding and are dropped via an
    out-of-range scatter index. Row-local: safe to run per shard."""
    U, S = state.staged_y.shape
    j = jnp.arange(x_new.shape[1], dtype=jnp.int32)
    pos = state.staged_n[:, None] + j[None, :]
    pos = jnp.where(j[None, :] < counts[:, None], pos, S)
    uu = jnp.arange(U, dtype=jnp.int32)[:, None]
    return state._replace(
        staged_x=state.staged_x.at[uu, pos].set(x_new, mode="drop"),
        staged_y=state.staged_y.at[uu, pos].set(y_new, mode="drop"),
        staged_n=state.staged_n + counts.astype(state.staged_n.dtype))


_stage = jax.jit(_stage_impl)


@jax.named_scope("fifo_commit")
def _commit_impl(state: BufState) -> BufState:
    """Apply all staged arrivals FIFO at the round boundary (one scatter).
    Row-local: safe to run per shard."""
    U, S = state.staged_y.shape
    D = state.y.shape[1]
    n, c, h, s = state.staged_n, state.cap, state.head, state.size
    j = jnp.arange(S, dtype=jnp.int32)
    # keep only the last cap staged samples; they land in distinct slots
    keep = (j[None, :] < n[:, None]) & (j[None, :] >= (n - c)[:, None])
    slot = ((h + s)[:, None] + j[None, :]) % c[:, None]
    slot = jnp.where(keep, slot, D)
    uu = jnp.arange(U, dtype=jnp.int32)[:, None]
    return state._replace(
        x=state.x.at[uu, slot].set(state.staged_x, mode="drop"),
        y=state.y.at[uu, slot].set(state.staged_y, mode="drop"),
        size=jnp.minimum(s + n, c),
        head=(h + jnp.maximum(s + n - c, 0)) % c,
        staged_n=jnp.zeros_like(n))


_commit = jax.jit(_commit_impl)


@partial(jax.jit, static_argnums=1)
def _histograms(state: BufState, num_classes: int) -> jnp.ndarray:
    """(U, C) normalized label histograms over each client's live window."""
    D = state.y.shape[1]
    p = jnp.arange(D, dtype=jnp.int32)[None, :]
    c, h, s = state.cap[:, None], state.head[:, None], state.size[:, None]
    live = (p < c) & (((p - h) % c) < s)
    onehot = jax.nn.one_hot(state.y, num_classes, dtype=jnp.float32)
    hist = jnp.sum(onehot * live[..., None], axis=1)
    return hist / jnp.maximum(jnp.sum(hist, axis=1, keepdims=True), 1.0)


@dataclass
class StackedOnlineBuffer:
    """Vectorized counterpart of ``OnlineBuffer`` for a whole cohort."""
    state: BufState
    num_classes: int
    last_hist: Optional[np.ndarray] = None
    mesh: Optional[object] = None             # set by shard(); None = 1 host
    _stage_fn: Optional[object] = field(default=None, repr=False)
    _commit_fn: Optional[object] = field(default=None, repr=False)
    _shardings: Optional[BufState] = field(default=None, repr=False)

    @classmethod
    def create(cls, capacities, feature_shape: tuple, num_classes: int,
               stage_capacity: Optional[int] = None, dtype=np.float32,
               label_dtype=np.int64, mesh=None,
               depth: Optional[int] = None) -> "StackedOnlineBuffer":
        """``depth`` overrides the allocated storage depth D (default: the
        max initial capacity). The sparse-cohort harness sizes slot storage
        to the *population*-wide capacity max so any later-admitted client's
        D_u fits the row it is reassigned (``reset_rows``)."""
        caps = np.asarray(capacities, np.int32)
        U, D = caps.shape[0], int(depth if depth is not None else caps.max())
        if int(caps.max()) > D:
            raise ValueError(
                f"storage depth {D} is smaller than the largest initial "
                f"capacity {int(caps.max())}")
        S = int(stage_capacity) if stage_capacity else D
        feat = tuple(feature_shape)
        dtype = jax.dtypes.canonicalize_dtype(dtype)
        label_dtype = jax.dtypes.canonicalize_dtype(label_dtype)
        state = BufState(
            x=jnp.zeros((U, D) + feat, dtype),
            y=jnp.zeros((U, D), label_dtype),
            cap=jnp.asarray(caps),
            size=jnp.zeros(U, jnp.int32),
            head=jnp.zeros(U, jnp.int32),
            staged_x=jnp.zeros((U, S) + feat, dtype),
            staged_y=jnp.zeros((U, S), label_dtype),
            staged_n=jnp.zeros(U, jnp.int32))
        buf = cls(state=state, num_classes=num_classes)
        return buf.shard(mesh) if mesh is not None else buf

    # -- mesh-sharded mode ---------------------------------------------------
    def shard(self, mesh) -> "StackedOnlineBuffer":
        """Lay the whole cohort state out over ``mesh``'s client axes: every
        ``(U, ...)``-leading leaf is split over ``('pod','data')`` so each
        shard owns U/rows whole clients, and stage/commit become per-shard
        jitted scatters (the unchanged row-local ``_stage``/``_commit``
        bodies under ``shard_map`` — no cross-shard communication, no host
        gather of storage). Returns ``self`` for chaining."""
        axes = client_axes(mesh)
        if not axes:
            raise ValueError(
                f"mesh {mesh} has no client axis (expected 'pod' or 'data' "
                f"in {mesh.axis_names})")
        rows = client_rows(mesh)
        U = int(self.state.y.shape[0])
        if U % rows:
            raise ValueError(
                f"cohort size {U} is not divisible by the mesh's {rows} "
                "client rows; each shard must own whole clients")

        def spec(leaf):
            return P(axes, *([None] * (leaf.ndim - 1)))

        shardings = jax.tree.map(
            lambda leaf: client_sharding(mesh, leaf.ndim), self.state)
        state_specs = jax.tree.map(spec, self.state)
        self.state = jax.device_put(self.state, shardings)
        self.mesh = mesh
        self._shardings = shardings
        self._stage_fn = jax.jit(shard_map(
            _stage_impl, mesh=mesh,
            in_specs=(state_specs, spec(self.state.staged_x),
                      spec(self.state.staged_y), P(axes)),
            out_specs=state_specs, axis_names=set(axes)))
        self._commit_fn = jax.jit(shard_map(
            _commit_impl, mesh=mesh, in_specs=(state_specs,),
            out_specs=state_specs, axis_names=set(axes)))
        return self

    # -- staging (within-round arrivals go to the temp buffer) ---------------
    def stage(self, x_new, y_new, counts) -> None:
        """x_new (U, A, *feat) / y_new (U, A) padded rows; counts (U,) valid
        prefixes. Total staged per client must fit ``stage_capacity``."""
        counts = np.asarray(counts)
        S = self.state.staged_y.shape[1]
        staged = np.asarray(self.state.staged_n) + counts
        if staged.max(initial=0) > S:
            raise ValueError(f"staged {int(staged.max())} > stage_capacity "
                             f"{S}; raise stage_capacity at create()")
        fn = self._stage_fn if self._stage_fn is not None else _stage
        self.state = fn(self.state, jnp.asarray(x_new), jnp.asarray(y_new),
                        jnp.asarray(counts, jnp.int32))

    def commit(self) -> int:
        """Apply staged arrivals FIFO. Returns total #ingested (cohort)."""
        n = int(np.asarray(self.state.staged_n).sum())
        fn = self._commit_fn if self._commit_fn is not None else _commit
        self.state = fn(self.state)
        return n

    # -- slot reassignment (sparse-cohort admissions) ------------------------
    def reset_rows(self, rows, capacities) -> None:
        """Reassign storage rows to new clients (slot-pool admission,
        ``core/cohort.py``): each row's capacity becomes the incoming
        client's D_u and its FIFO window and staging empty out. The storage
        tensors are reused in place — the evicted client's samples are dead
        (size = 0 masks them from the live window, histograms and slot
        sampling) and are overwritten as the new resident's arrivals land.
        The shift-proxy memory (``last_hist``) keeps the evicted row until
        the next ``distribution_shifts`` call; the sparse harness does not
        consume it."""
        rows = np.asarray(rows, np.int64).ravel()
        if rows.size == 0:
            return
        caps = np.asarray(capacities, np.int32).ravel()
        if caps.shape != rows.shape:
            raise ValueError(
                f"reset_rows needs one capacity per row (got {rows.size} "
                f"rows, {caps.size} capacities)")
        D = int(self.state.y.shape[1])
        if caps.min(initial=1) < 1 or caps.max(initial=0) > D:
            raise ValueError(
                f"reassigned capacities must lie in [1, {D}] (the allocated "
                f"storage depth); got [{caps.min()}, {caps.max()}]")
        idx = jnp.asarray(rows)
        zero = jnp.zeros(rows.size, jnp.int32)
        st = self.state._replace(
            cap=self.state.cap.at[idx].set(jnp.asarray(caps)),
            size=self.state.size.at[idx].set(zero),
            head=self.state.head.at[idx].set(zero),
            staged_n=self.state.staged_n.at[idx].set(zero))
        if self.mesh is not None:
            # pin the pointer arrays back to their explicit layout — the
            # out-of-jit scatters above don't owe us sharding preservation
            st = st._replace(
                cap=jax.device_put(st.cap, self._shardings.cap),
                size=jax.device_put(st.size, self._shardings.size),
                head=jax.device_put(st.head, self._shardings.head),
                staged_n=jax.device_put(st.staged_n,
                                        self._shardings.staged_n))
        self.state = st

    # -- views ----------------------------------------------------------------
    @property
    def sizes(self) -> np.ndarray:
        return np.asarray(self.state.size)

    @property
    def heads(self) -> np.ndarray:
        return np.asarray(self.state.head)

    @property
    def capacities(self) -> np.ndarray:
        return np.asarray(self.state.cap)

    def dataset(self, u: int) -> Tuple[np.ndarray, np.ndarray]:
        """Client u's live samples in FIFO order (oracle ``dataset()``)."""
        h, s, c = int(self.heads[u]), int(self.sizes[u]),\
            int(self.capacities[u])
        idx = (h + np.arange(s)) % c
        return (np.asarray(self.state.x[u])[idx],
                np.asarray(self.state.y[u])[idx])

    def label_histograms(self) -> np.ndarray:
        return np.asarray(_histograms(self.state, self.num_classes))

    def distribution_shifts(self) -> np.ndarray:
        """(U,) empirical Phi_u^t proxies (oracle ``distribution_shift``)."""
        h = self.label_histograms()
        shift = (np.zeros(h.shape[0]) if self.last_hist is None
                 else np.sum((h - self.last_hist) ** 2, axis=1))
        self.last_hist = h
        return shift

    # -- batch sampling ---------------------------------------------------------
    def sample_slots(self, rng: np.random.Generator, sample_shape: tuple
                     ) -> np.ndarray:
        """(U, *sample_shape) storage slots, uniform over each client's live
        window (empty buffers fall back to slot head)."""
        size = np.maximum(self.sizes, 1)
        U = size.shape[0]
        lead = (U,) + (1,) * len(sample_shape)
        j = rng.integers(0, size.reshape(lead),
                         size=(U,) + tuple(sample_shape))
        return (self.heads.reshape(lead) + j) % self.capacities.reshape(lead)

    def gather(self, slots: np.ndarray) -> dict:
        """Device gather of sampled slots -> batch pytree {x, y} with leaves
        (U, *sample_shape, ...) for the vmapped local trainer."""
        U = slots.shape[0]
        uu = np.arange(U).reshape((U,) + (1,) * (slots.ndim - 1))
        slots = jnp.asarray(slots)
        return {"x": self.state.x[uu, slots], "y": self.state.y[uu, slots]}

    # -- checkpointing -------------------------------------------------------
    def state_dict(self) -> dict:
        """Full snapshot of the cohort state: storage tensors, per-client
        capacity/head/size pointers, staged-but-uncommitted arrivals and the
        shift-proxy memory. Everything needed for a mid-stream resume to be
        bit-identical, including wrap-around and over-capacity staging.
        Mesh-sharded tensors are returned as the live device arrays — the
        checkpoint writer pulls them per addressable shard off the round
        loop (``checkpoint/streaming.py``), so a sharded buffer never
        host-gathers; ``load_state_dict`` re-shards on restore."""
        s = self.state
        return {
            **dict(s._asdict()),
            "num_classes": int(self.num_classes),
            "last_hist": self.last_hist,
        }

    def load_state_dict(self, sd: dict) -> None:
        """Restore a ``state_dict`` snapshot (full overwrite; the staged
        arrivals resume exactly where they were, committed or not). The
        snapshot's storage/pointer arrays are shape- and dtype-checked
        against the live buffer's layout (a snapshot only fits the cohort
        shape it came from), then re-laid out over the mesh when the live
        buffer is sharded."""
        from repro.checkpoint.run_state import CheckpointError
        cur = self.state._asdict()
        missing = sorted(set(cur) - set(sd))
        if missing:
            raise CheckpointError(
                "buffer snapshot is missing keys: " + ", ".join(missing))
        loaded = {}
        for k, want in cur.items():
            got = np.asarray(sd[k])
            if tuple(got.shape) != tuple(want.shape):
                raise CheckpointError(
                    f"buffer snapshot {k!r} has shape {tuple(got.shape)}; "
                    f"the live buffer expects {tuple(want.shape)}")
            if got.dtype != np.dtype(want.dtype):
                raise CheckpointError(
                    f"buffer snapshot {k!r} has dtype {got.dtype}; the live "
                    f"buffer expects {np.dtype(want.dtype)}")
            loaded[k] = jnp.asarray(got)
        state = BufState(**loaded)
        if self.mesh is not None:
            state = jax.device_put(state, self._shardings)
        self.state = state
        self.num_classes = int(sd["num_classes"])
        lh = sd["last_hist"]
        self.last_hist = None if lh is None else np.asarray(lh)
