"""Sparse-cohort server state: a fixed-capacity active-slot pool in front of
the stacked servers (DESIGN.md "Sparse cohorts").

The paper's server only ever *computes* on the round-active cohort — scores,
staleness and contributions of inactive clients are carried, not touched
(Algorithm 2 writes back active rows and refreshes never-participated ones;
the partial-participation analysis in Dinh et al., 1910.13067, renormalizes
the aggregation weights over the sampled cohort). The dense engines still
materialize a ``(U, N)`` contribution buffer and ``(U, D, ...)`` datasets for
every *registered* user, which caps U at a few hundred on one host. This
module decouples the two scales:

  * ``SlotPool`` — a host-side bijection between resident user ids and the
    ``C`` pool slots (``user_slot``/``slot_user`` int32 maps, FIFO eviction
    clocks). All round-dense state (contribution rows, FIFO datasets, the
    local-SGD vmap) is slot-indexed and sized ``C``.
  * ``CohortTables`` — persistent per-user ``(U,)`` tables (scores, the
    stale-score carry, staleness/participation flags) with **explicit**
    ``NamedSharding`` over the mesh's ``('pod','data')`` client axes
    (``shmap.client_sharding``), not auto-SPMD propagation: the tables are
    the only O(U) device state left, and their layout must be pinned so
    gather/scatter against them stays a local row op per shard.
  * ``SparseCohortServer`` — the engine: a width-``C`` *inner* stacked server
    (the unchanged ``StackedOSAFLServer``/``Stacked*`` classes) behind the
    pool. Per round the inner server runs the identical jitted round body on
    ``(C, N)`` slot buffers and the results are scattered back into the
    per-user tables; at admission the carried per-user state is gathered
    into the slot and the slot's contribution row is reset to the
    algorithm's refresh value (``init_row``) — slot-resident contributions
    and datasets are *lost* on eviction, by design. The admission write is
    one jitted program over the whole ``(C, N)`` buffer and ``(C,)`` slot
    vectors (``make_slot_reset``), masked by the newly seated slots, so it
    compiles once for any number of admitted users.

Dense parity is the correctness anchor: with ``cohort_size = U`` the pool is
the identity map, the inner server *is* the dense stacked server (same
width, same uniform ``alphas``), and the harness consumes the host RNG in
exactly the dense order — so trajectories are bit-exact against the dense
engines for every algorithm (tests/test_cohort.py). With C < U the inner
width-C aggregation renormalizes weights over the sampled cohort
automatically (uniform ``1/C`` slots; FedNova/FedDisco size/histogram
weights over cohort rows), which is precisely the Dinh et al. partial-
participation rule.
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.configs.base import FLConfig
from repro.core.baselines import STACKED_SERVERS
from repro.core.osafl import StackedOSAFLServer
from repro.core.shmap import client_rows, client_sharding


def sample_participants(rng: np.random.Generator, num_users: int, m: int,
                        weights: Optional[np.ndarray] = None,
                        available: Optional[np.ndarray] = None) -> np.ndarray:
    """Sample the round-active participant set (sorted user ids).

    With neither ``weights`` nor ``available`` this is exactly
    ``np.sort(rng.choice(U, size=m, replace=False))`` — the historical
    host-RNG consumption the dense-parity and null-scenario anchors rest on.
    The scenario layer biases it: ``weights`` (U,) are relative sampling
    weights (Pareto-biased selection), ``available`` (U,) masks departed
    users out entirely (churn); when fewer than ``m`` users remain the
    sample shrinks to the available count (possibly empty — a round where
    everyone is away trains nobody)."""
    if weights is None and available is None:
        return np.sort(rng.choice(num_users, size=m, replace=False))
    w = (np.ones(num_users, np.float64) if weights is None
         else np.asarray(weights, np.float64).copy())
    if w.shape != (num_users,):
        raise ValueError(
            f"selection weights must have shape ({num_users},), "
            f"got {w.shape}")
    if (w < 0).any():
        # a negative weight would silently renormalize into a *valid*
        # probability against a negative sum — reject it loudly
        raise ValueError("selection weights must be non-negative")
    if available is not None:
        w[~np.asarray(available, bool)] = 0.0
    eligible = int(np.count_nonzero(w))
    m = min(int(m), eligible)
    if m == 0:
        return np.empty(0, np.int64)
    return np.sort(rng.choice(num_users, size=m, replace=False,
                              p=w / w.sum()))


class AdmitResult(NamedTuple):
    """Outcome of ``SlotPool.admit``: per requested user its slot, whether
    the user was newly seated this call (slot state must be initialized),
    and which previously-resident users were evicted to make room."""
    slots: np.ndarray       # (k,) int32, aligned with the admitted users
    newly: np.ndarray       # (k,) bool — True where the user was not resident
    evicted: np.ndarray     # (m,) int32 user ids displaced by this call


class SlotPool:
    """Host-side user↔slot bijection with FIFO eviction.

    ``user_slot`` (U,) maps registered user -> slot (-1 = not resident);
    ``slot_user`` (C,) maps slot -> user (-1 = free). Two monotonic int64
    clock tables drive the FIFO policy and make the whole pool a plain dict
    of arrays for RunState snapshots: ``admit_seq[s]`` is the tick slot s's
    resident was seated (-1 = free) and ``free_seq[s]`` the tick it was
    freed (-1 = occupied; fresh slots are pre-freed in index order so
    initial admissions fill 0..C-1 left to right — at C = U that makes the
    pool the identity map, the dense-parity anchor). Eviction takes the
    oldest-seated resident not being admitted in the same call; freed slots
    are reused oldest-freed first. The clocks never wrap (int64), but slot
    *reuse* cycles through the pool indefinitely — the wrap-around the
    property tests exercise."""

    def __init__(self, num_users: int, capacity: int):
        if not 1 <= capacity <= num_users:
            raise ValueError(
                f"slot-pool capacity must satisfy 1 <= C <= U "
                f"(got C={capacity}, U={num_users})")
        self.U = int(num_users)
        self.C = int(capacity)
        self.user_slot = np.full(self.U, -1, np.int32)
        self.slot_user = np.full(self.C, -1, np.int32)
        self.admit_seq = np.full(self.C, -1, np.int64)
        self.free_seq = np.arange(self.C, dtype=np.int64)
        self._clock = self.C

    @property
    def cohort(self) -> np.ndarray:
        """(C,) slot -> user id view (-1 = free slot)."""
        return self.slot_user.copy()

    @property
    def occupancy(self) -> int:
        return int((self.slot_user >= 0).sum())

    def resident(self, users) -> np.ndarray:
        return self.user_slot[np.asarray(users, np.int64)] >= 0

    def admit(self, users) -> AdmitResult:
        users = np.asarray(users, np.int64).ravel()
        if users.size:
            if users.min() < 0 or users.max() >= self.U:
                raise ValueError(
                    f"user ids must be in [0, {self.U}); got range "
                    f"[{users.min()}, {users.max()}]")
            if np.unique(users).size != users.size:
                raise ValueError("duplicate user ids in one admit() call")
        if users.size > self.C:
            raise ValueError(
                f"cannot admit {users.size} users into {self.C} slots")
        protected = set(users.tolist())
        slots = np.empty(users.size, np.int32)
        newly = np.zeros(users.size, bool)
        evicted = []
        for i, u in enumerate(users.tolist()):
            s = int(self.user_slot[u])
            if s < 0:
                free = np.flatnonzero(self.free_seq >= 0)
                if free.size:
                    s = int(free[np.argmin(self.free_seq[free])])
                else:
                    occ = [int(c) for c in np.flatnonzero(self.admit_seq >= 0)
                           if int(self.slot_user[c]) not in protected]
                    s = min(occ, key=lambda c: self.admit_seq[c])
                    ev = int(self.slot_user[s])
                    self.user_slot[ev] = -1
                    evicted.append(ev)
                self.slot_user[s] = u
                self.user_slot[u] = s
                self.admit_seq[s] = self._clock
                self.free_seq[s] = -1
                self._clock += 1
                newly[i] = True
            slots[i] = s
        return AdmitResult(slots=slots, newly=newly,
                           evicted=np.asarray(evicted, np.int32))

    def evict(self, users) -> np.ndarray:
        """Explicitly free the given users' slots (non-residents are
        ignored). Returns the freed slot indices."""
        freed = []
        for u in np.asarray(users, np.int64).ravel().tolist():
            s = int(self.user_slot[u])
            if s < 0:
                continue
            self.user_slot[u] = -1
            self.slot_user[s] = -1
            self.admit_seq[s] = -1
            self.free_seq[s] = self._clock
            self._clock += 1
            freed.append(s)
        return np.asarray(freed, np.int32)

    def check(self) -> None:
        """Raise ``ValueError`` unless the pool invariants hold: the two
        maps are a bijection on residents (no aliasing, no leaked slots) and
        the clock tables mark exactly the occupied/free slots."""
        occ = np.flatnonzero(self.slot_user >= 0)
        res = np.flatnonzero(self.user_slot >= 0)
        if occ.size != res.size:
            raise ValueError(
                f"slot pool leak: {occ.size} occupied slots vs "
                f"{res.size} resident users")
        for s in occ.tolist():
            u = int(self.slot_user[s])
            if int(self.user_slot[u]) != s:
                raise ValueError(
                    f"slot aliasing: slot {s} holds user {u} but "
                    f"user_slot[{u}] = {int(self.user_slot[u])}")
        if ((self.admit_seq >= 0) != (self.slot_user >= 0)).any():
            raise ValueError("admit_seq marks do not match occupied slots")
        if ((self.free_seq >= 0) != (self.slot_user < 0)).any():
            raise ValueError("free_seq marks do not match free slots")
        live = np.concatenate([self.admit_seq[self.admit_seq >= 0],
                               self.free_seq[self.free_seq >= 0]])
        if live.size and live.max(initial=-1) >= self._clock:
            raise ValueError("clock table entry ahead of the pool clock")

    # -- checkpointing -------------------------------------------------------
    def state_dict(self) -> dict:
        return {"user_slot": self.user_slot.copy(),
                "slot_user": self.slot_user.copy(),
                "admit_seq": self.admit_seq.copy(),
                "free_seq": self.free_seq.copy(),
                "clock": np.int64(self._clock)}

    def load_state_dict(self, sd: dict) -> None:
        from repro.checkpoint.run_state import validate_cohort_shapes
        validate_cohort_shapes(sd, self.U, self.C)
        self.user_slot = np.asarray(sd["user_slot"], np.int32).copy()
        self.slot_user = np.asarray(sd["slot_user"], np.int32).copy()
        self.admit_seq = np.asarray(sd["admit_seq"], np.int64).copy()
        self.free_seq = np.asarray(sd["free_seq"], np.int64).copy()
        self._clock = int(sd["clock"])
        self.check()


class CohortTables:
    """Persistent per-user ``(U,)``-leading tables under explicit
    ``NamedSharding`` over the mesh's client axes (``client_sharding``).
    Without a mesh the tables are plain device arrays. Admission reads them
    at the seated slots' users (``make_slot_reset``); scatter writes slot
    results back."""

    def __init__(self, num_users: int, tables: dict, mesh=None):
        self.U = int(num_users)
        self.mesh = mesh
        if mesh is not None and self.U % client_rows(mesh):
            raise ValueError(
                f"user-table length {self.U} is not divisible by the mesh's "
                f"{client_rows(mesh)} client rows")
        self._tables = {k: self._put(jnp.asarray(v))
                        for k, v in tables.items()}

    def _put(self, arr):
        if self.mesh is None:
            return arr
        return jax.device_put(arr, client_sharding(self.mesh, arr.ndim))

    def keys(self):
        return self._tables.keys()

    def __getitem__(self, k):
        return self._tables[k]

    def scatter(self, users, values: dict) -> None:
        idx = jnp.asarray(np.asarray(users, np.int64))
        for k, val in values.items():
            self._tables[k] = self._put(
                self._tables[k].at[idx].set(jnp.asarray(val)))

    # -- checkpointing -------------------------------------------------------
    def state_dict(self) -> dict:
        # live device arrays, NamedSharding intact: the v2 checkpoint writer
        # pulls them per addressable shard (no host gather on the round
        # loop); load_state_dict re-applies client_sharding on restore
        return dict(self._tables)

    def load_state_dict(self, sd: dict) -> None:
        from repro.checkpoint.run_state import CheckpointError
        missing = sorted(set(self._tables) - set(sd))
        if missing:
            raise CheckpointError(
                "cohort-table snapshot is missing keys: "
                + ", ".join(missing))
        for k, cur in self._tables.items():
            got = np.asarray(sd[k])
            if tuple(got.shape) != tuple(cur.shape):
                raise CheckpointError(
                    f"cohort table {k!r} has snapshot shape "
                    f"{tuple(got.shape)}; the live run expects "
                    f"{tuple(cur.shape)}")
            self._tables[k] = self._put(jnp.asarray(got))


def make_slot_reset(mesh=None):
    """The admission write as one jitted program whose shapes do not depend
    on how many users were seated:

        reset_slots(buf, row, reset, slot_user, slot_state, tables)
            -> (buf, slot_state, seated)

    ``buf`` (C, N) is the slot contribution buffer, ``row`` (N,) the
    refresh value, ``reset`` (C,) bool marks the newly seated slots and
    ``slot_user`` (C,) the pool's slot -> user map (-1 = free: clamped, and
    masked out by ``reset``). ``tables`` are the (U,) per-user tables; each
    table is gathered at the slots' users and selected into the (C,)
    ``slot_state`` vector of the same key where ``reset`` holds; the tables
    with no slot state come back gathered (``seated``) for the host to fold
    into its own copies. Every write is a select of the values a row
    scatter would write, so the result is bit-identical to
    ``x.at[slots].set(values)``, with no per-row loop whatever the buffer's
    layout. No argument is donated: a checkpoint writer may still hold the
    live buffer. Under a mesh the slot-indexed outputs take the client
    sharding (``client_sharding``), the layout the round leaves them in."""

    def reset_slots(buf, row, reset, slot_user, slot_state, tables):
        src = jnp.maximum(slot_user, 0)
        buf = jnp.where(reset[:, None], row[None, :], buf)
        seated = {k: jnp.take(t, src, axis=0) for k, t in tables.items()}
        slot_state = {k: jnp.where(reset, seated.pop(k), v)
                      for k, v in slot_state.items()}
        return buf, slot_state, seated

    if mesh is None:
        return jax.jit(reset_slots)
    return jax.jit(reset_slots, out_shardings=(
        client_sharding(mesh, 2), client_sharding(mesh, 1), None))


class SparseCohortServer:
    """The sparse-cohort engine: ``SlotPool`` + ``CohortTables`` wrapped
    around an unchanged width-C stacked server (see module docstring).

    Drop-in for the stacked servers in the harness: ``round_stacked``
    forwards to the inner server (whose round consumes ``(C, N)`` updates
    and a ``(C,)`` active mask, both *slot*-indexed) and then scatters the
    per-slot results back into the per-user tables, so eviction needs no
    extra write — an evicted slot's carry is already in the tables."""

    def __init__(self, params, fl: FLConfig, num_users: int, seed: int = 0,
                 mesh=None, capacity: Optional[int] = None):
        capacity = int(fl.cohort_size if capacity is None else capacity)
        if not 1 <= capacity <= num_users:
            raise ValueError(
                f"cohort_size must satisfy 1 <= C <= num_clients "
                f"(got C={capacity}, num_clients={num_users})")
        self.fl = fl
        self.U = int(num_users)
        self.C = capacity
        self.K = int(fl.num_clusters)
        self.is_osafl = fl.algorithm == "osafl"
        inner_fl = dataclasses.replace(fl, num_clients=capacity,
                                       cohort_size=0, participation=1.0)
        if self.K >= 1:
            # hierarchical: K per-cluster slot blocks in front of the
            # two-tier inner servers (core/hierarchy.py). inner_fl keeps
            # fl.num_clusters, so the width-C inner round body splits its
            # buffer into the same K blocks the pool keeps contiguous.
            from repro.core.hierarchy import (ClusterSlotPool,
                                              contiguous_clusters,
                                              make_hier_server)
            self.assign = contiguous_clusters(self.U, self.K)
            if capacity % self.K:
                raise ValueError(
                    f"num_clusters must divide cohort_size "
                    f"(got K={self.K}, C={capacity})")
            self.inner = make_hier_server(params, inner_fl, capacity,
                                          seed=seed)
            self.pool = ClusterSlotPool(self.U, capacity, self.assign,
                                        self.K)
        elif self.is_osafl:
            self.assign = None
            self.inner = StackedOSAFLServer(params, inner_fl, capacity,
                                            seed=seed, mesh=mesh)
            self.pool = SlotPool(num_users, capacity)
        elif fl.algorithm in STACKED_SERVERS:
            self.assign = None
            self.inner = STACKED_SERVERS[fl.algorithm](params, inner_fl,
                                                       capacity, seed=seed)
            self.pool = SlotPool(num_users, capacity)
        else:
            raise ValueError(f"unknown algorithm {fl.algorithm!r}")
        tables = {"participated": np.zeros(self.U, bool)}
        if self.is_osafl:
            tables["scores"] = np.ones(self.U, np.float32)
            tables["lam_prev"] = np.ones(self.U, np.float32)
        self.tables = CohortTables(self.U, tables, mesh=mesh)
        self._slot_reset = make_slot_reset(mesh)
        if not self.is_osafl:
            # sticky per-user metadata (loop "last seen update" semantics),
            # host-side like the inner servers' own copies
            self.sizes = np.ones(self.U)
            self.kappas = np.ones(self.U)
            self.hists: Optional[np.ndarray] = None
            self.has_hist = np.zeros(self.U, bool)

    # -- delegated views -----------------------------------------------------
    @property
    def params(self):
        return self.inner.params

    @property
    def w(self):
        return self.inner.w

    @property
    def codec(self):
        return self.inner.codec

    @property
    def alphas(self):
        return self.inner.alphas

    @property
    def cohort(self) -> np.ndarray:
        """(C,) slot -> user map of the current residents."""
        return self.pool.cohort

    @property
    def last_scores(self) -> np.ndarray:
        """Per-*user* (U,) score view (OSAFL): the carried score table."""
        if not self.is_osafl:
            raise AttributeError("last_scores is OSAFL-only")
        return np.asarray(self.tables["scores"])

    # -- admission -----------------------------------------------------------
    def initial_residents(self) -> np.ndarray:
        """The users seated before round 0: the first ``C`` ids on the flat
        pool; under hierarchy the first ``C/K`` members of *each* cluster, so
        every cluster block starts full. With the contiguous static map at
        K=1 both are exactly ``arange(C)`` — the parity anchor."""
        if self.K < 1:
            return np.arange(self.C, dtype=np.int64)
        B = self.C // self.K
        return np.concatenate([
            np.flatnonzero(self.assign == k)[:B] for k in range(self.K)])

    def apply_cluster_moves(self, users, dest):
        """Scenario-driven membership churn: move ``users`` to clusters
        ``dest``. Residents among the movers are evicted from their old
        block and immediately re-seated in the destination block (FIFO-
        evicting there as needed) — their carried tables follow them via the
        normal ``admit`` gather, but slot-resident contribution rows and FIFO
        datasets reset (edge migration does not move data between edge
        servers). Returns ``(moved_resident_users, AdmitResult)``; the
        caller must reset the same slots in its slot-indexed dataset buffer,
        exactly as after any admission."""
        if self.K < 1:
            raise ValueError(
                "cluster moves require a hierarchical run (num_clusters>=1)")
        users = np.asarray(users, np.int64).ravel()
        dest = np.asarray(dest, np.int64).ravel()
        if users.size:
            # a user named twice takes the LAST destination (scenario
            # composition order = sequential application)
            _, first_rev = np.unique(users[::-1], return_index=True)
            keep = np.sort(users.size - 1 - first_rev)
            users, dest = users[keep], dest[keep]
        moved = self.pool.reassign(users, dest)
        if moved.size == 0:
            return moved, None
        return moved, self.admit(moved)

    def admit(self, users) -> AdmitResult:
        """Seat ``users`` in the pool (FIFO-evicting as needed) and load each
        newly seated slot: carried per-user state is gathered from the
        tables, the contribution row is reset to the algorithm's refresh
        value (``init_row``) — the evicted resident's row is lost, which is
        the documented eviction semantics. The device-side write is one call
        of the fixed-shape ``make_slot_reset`` program, inside the host span
        ``osafl.slot_reset`` whose ``rows`` stat counts the slots reset. The
        caller owns the slot-indexed *dataset* buffer and must reset the
        same slots (``StackedOnlineBuffer.reset_rows``)."""
        res = self.pool.admit(users)
        ns = res.slots[res.newly]
        with TraceAnnotation("osafl.slot_reset", rows=int(ns.size)):
            if ns.size:
                self._reset_slots(ns, np.asarray(users, np.int64).ravel()[
                    res.newly])
        return res

    def _reset_slots(self, ns: np.ndarray, nu: np.ndarray) -> None:
        """Load the newly seated slots ``ns`` (users ``nu``): one call of the
        slot-reset program, one fetch of the gathered host-side table."""
        reset = np.zeros(self.C, bool)
        reset[ns] = True
        tables = {k: self.tables[k] for k in self.tables.keys()}
        inner = self.inner
        if self.is_osafl:
            inner.d_buffer, st, seated = self._slot_reset(
                inner.d_buffer, inner.init_row(), reset, self.pool.slot_user,
                {"participated": inner.participated,
                 "lam_prev": inner._lam_prev}, tables)
            inner.participated, inner._lam_prev = (st["participated"],
                                                   st["lam_prev"])
            ls = np.array(inner.last_scores)
            ls[ns] = np.asarray(seated["scores"])[ns]
            inner.last_scores = ls
        else:
            inner.buffer, _, seated = self._slot_reset(
                inner.buffer, inner.init_row(), reset, self.pool.slot_user,
                {}, tables)
            inner.participated[ns] = np.asarray(seated["participated"])[ns]
            inner.sizes[ns] = self.sizes[nu]
            inner.kappas[ns] = self.kappas[nu]
            if self.hists is not None:
                if inner.hists is None:
                    inner.hists = np.zeros((self.C, self.hists.shape[1]))
                inner.hists[ns] = self.hists[nu]
            inner.has_hist[ns] = self.has_hist[nu]

    # -- the round -----------------------------------------------------------
    def round_stacked(self, d_new, active, **meta):
        """Slot-indexed round: ``d_new`` (C, N), ``active`` (C,) plus the
        algorithm's metadata kwargs, all in slot order. Runs the inner
        stacked round unchanged, then scatters per-slot results back into
        the per-user carry tables."""
        out = self.inner.round_stacked(d_new, active, **meta)
        self._write_back()
        return out

    def _write_back(self) -> None:
        cohort = self.pool.slot_user
        vs = np.flatnonzero(cohort >= 0)
        if vs.size == 0:
            return
        cu = cohort[vs]
        idx = jnp.asarray(vs)
        if self.is_osafl:
            self.tables.scatter(cu, {
                "participated": jnp.take(self.inner.participated, idx),
                "scores": jnp.take(
                    jnp.asarray(self.inner.last_scores, jnp.float32), idx),
                "lam_prev": jnp.take(self.inner._lam_prev, idx)})
        else:
            self.tables.scatter(cu, {
                "participated": jnp.asarray(self.inner.participated)[idx]})
            self.sizes[cu] = self.inner.sizes[vs]
            self.kappas[cu] = self.inner.kappas[vs]
            if self.inner.hists is not None:
                if self.hists is None:
                    self.hists = np.zeros((self.U,
                                           self.inner.hists.shape[1]))
                self.hists[cu] = self.inner.hists[vs]
            self.has_hist[cu] = self.inner.has_hist[vs]

    # -- checkpointing -------------------------------------------------------
    def state_dict(self) -> dict:
        """Snapshot = the width-C inner server (slot-resident state), the
        slot map, and the per-user carry tables — no dense ``(U, N)`` ghost
        is ever materialized."""
        sd = {"inner": self.inner.state_dict(),
              "pool": self.pool.state_dict(),
              "tables": self.tables.state_dict()}
        if not self.is_osafl:
            sd["user_meta"] = {"sizes": self.sizes.copy(),
                               "kappas": self.kappas.copy(),
                               "hists": self.hists,
                               "has_hist": self.has_hist.copy()}
        return sd

    def load_state_dict(self, sd: dict) -> None:
        from repro.checkpoint.run_state import (CheckpointError,
                                                validate_cohort_shapes)
        missing = sorted(k for k in ("inner", "pool", "tables")
                         if k not in sd)
        if missing:
            raise CheckpointError(
                "not a sparse-cohort snapshot (missing "
                + ", ".join(missing)
                + "); dense-engine snapshots cannot restore into a "
                "cohort_size>0 run")
        if self.K >= 1:
            if "pools" not in sd["pool"]:
                raise CheckpointError(
                    "snapshot slot pool is flat (no per-cluster pools); it "
                    "cannot restore into a num_clusters"
                    f"={self.K} hierarchical run")
            # ClusterSlotPool.load_state_dict validates K/assign/sub-pools
        else:
            if "pools" in sd["pool"]:
                raise CheckpointError(
                    "snapshot slot pool is hierarchical (per-cluster "
                    "pools); it cannot restore into a flat "
                    "(num_clusters=0) run")
            validate_cohort_shapes(sd["pool"], self.U, self.C)
        self.pool.load_state_dict(sd["pool"])
        self.inner.load_state_dict(sd["inner"])
        self.tables.load_state_dict(sd["tables"])
        if not self.is_osafl:
            meta = sd["user_meta"]
            self.sizes = np.asarray(meta["sizes"], float).copy()
            self.kappas = np.asarray(meta["kappas"], float).copy()
            self.hists = (None if meta["hists"] is None
                          else np.asarray(meta["hists"], float).copy())
            self.has_hist = np.asarray(meta["has_hist"], bool).copy()
