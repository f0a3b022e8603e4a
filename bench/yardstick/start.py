"""What a run starts from, made again from its seed the way the run under
test makes it (``repro.harness.run`` and ``build_fused_engine`` on the
stacked request path), so that the reference starts where the program
starts without taking anything the program made.

* population (``numpy.random.default_rng(seed)``): the catalog's content
  features and genre popularity orders, then per user its Dirichlet genre
  preferences, exploitation probability and arrival probability;
* a second generator of the same seed: the FIFO capacities D_u, then the
  clients' systems, five draws a client; the host-driven round goes on
  drawing its participants, arrival counts and batch slots from it;
* the weights from ``PRNGKey(seed)``, by the configuration's ``init``; the
  request stream from ``fold_in(PRNGKey(seed), 0x726571)``, split into five
  keys for each draw of the whole population;
* users 0..C-1 are seated in slots 0..C-1 (C = U without a slot pool); their
  FIFOs fill to D_u with their first requests, ``arrivals`` at a time, and
  then every user draws ``max(eval_samples // U, 4)`` more as the
  evaluation set.

Dataset-1 samples are kept as the id of the request before the labelled one
(``prev``); ``features`` makes the feature rows when a batch needs them.
"""
from __future__ import annotations

from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np

from bench.yardstick import request as rq

STREAM_KEY_TAG = 0x726571


def population(seed: int, num_users: int, user: dict):
    """Catalog constants as the request draws see them, and each user's
    arrival probability (float64)."""
    rng = np.random.default_rng(seed)
    feats = rng.normal(size=(rq.F_FILES, rq.FEAT_DIM)).astype(np.float32)
    pop = np.stack([rng.permutation(rq.FILES_PER_GENRE)
                    for _ in range(rq.G_GENRES)]).astype(np.int32)
    pref = np.empty((num_users, rq.G_GENRES))
    eps = np.empty(num_users)
    p_ac = np.empty(num_users)
    for u in range(num_users):
        pref[u] = rng.dirichlet(user["dirichlet"] * np.ones(rq.G_GENRES))
        eps[u] = rng.uniform(*user["eps"])
        p_ac[u] = rng.uniform(*user["p_ac"])
    unit = feats / np.linalg.norm(feats, axis=1, keepdims=True)
    cos = unit @ unit.T
    f = np.arange(rq.F_FILES)
    own = cos.reshape(rq.F_FILES, rq.G_GENRES, rq.FILES_PER_GENRE)[
        f, f // rq.FILES_PER_GENRE].astype(np.float32)
    ranks = np.arange(1, rq.FILES_PER_GENRE + 1, dtype=np.float64)
    w = 1.0 / (ranks + user["zipf_q"]) ** user["zipf_gamma"]
    pmf = (w / w.sum()).astype(np.float32)
    pref32 = pref.astype(np.float32)
    # the logarithms are taken on the device, as the draws under test take
    # them, so that a near tie of two logits breaks the same way
    cat = SimpleNamespace(
        feat50=feats / np.float32(50.0), own_sims=own, popularity=pop,
        pref=pref32, eps=eps.astype(np.float32),
        log_pref=np.asarray(jnp.log(jnp.asarray(pref32))),
        log_zipf=np.asarray(jnp.log(jnp.asarray(pmf))))
    return cat, p_ac


def client_systems(rng: np.random.Generator, num: int, sysd: dict) -> dict:
    """The paper's client population (Section V-A3), five draws a client in
    turn, as float64 columns."""
    cols = {k: np.empty(num) for k in ("c", "f_max", "p_max", "e_bd",
                                       "distance")}
    for i in range(num):
        cols["c"][i] = rng.uniform(*sysd["cycles_per_bit"])
        cols["f_max"][i] = rng.uniform(*sysd["f_max_ghz"]) * 1e9
        cols["p_max"][i] = 10 ** (rng.uniform(*sysd["p_max_dbm"]) / 10) \
            / 1000
        cols["e_bd"][i] = rng.uniform(*sysd["e_bd_j"])
        cols["distance"][i] = sysd["cell_radius_m"] * np.sqrt(
            rng.uniform(0.01, 1.0))
    cols["s"] = np.full(num, float(sysd["sample_bits"]))
    return cols


def features(cat, users, prev) -> np.ndarray:
    """Dataset-1 feature rows of the requests after ``prev``."""
    return rq.features(cat, users, np.asarray(prev))


class Stream:
    """The whole population's request stream. Each draw advances every user
    that still owes samples, for ``width`` steps plus the warm-up the coldest
    user needs before its first sample (none once every user is warm)."""

    def __init__(self, cat, seed: int, dataset: int, topk: int, fault=None):
        U = cat.pref.shape[0]
        self.cat, self.ds, self.topk, self.fault = cat, dataset, topk, fault
        self.key = jax.random.fold_in(jax.random.PRNGKey(seed),
                                      STREAM_KEY_TAG)
        self.genre = np.full(U, -1, np.int32)
        self.file = np.full(U, -1, np.int32)
        self.has_last = np.zeros(U, bool)
        self.hist = np.zeros((U, rq.SEQ_LEN), np.int32)
        self.hist_len = np.zeros(U, np.int32)
        self.warm = False

    def deficit(self) -> int:
        if self.ds == 1:
            return 0 if self.has_last.all() else 1
        return max(0, rq.SEQ_LEN - int(self.hist_len.min()))

    def draw(self, counts, width: int) -> list:
        """Per user, its ``counts[u]`` new (x, y) samples in arrival order;
        x is the previous request's id (Dataset-1) or the last ``SEQ_LEN``
        ids (Dataset-2). The ``label`` fault puts every label off by one."""
        U = self.genre.shape[0]
        warmup = 0 if self.warm else self.deficit()
        self.warm = warmup == 0
        L = width + warmup
        self.key, k_br, k_genre, k_rank, k_top = jax.random.split(self.key, 5)
        u_br = np.asarray(jax.random.uniform(k_br, (L, U), jnp.float32))
        g_gen = np.asarray(jax.random.gumbel(k_genre, (L, U, rq.G_GENRES),
                                             jnp.float32))
        g_rank = np.asarray(jax.random.gumbel(
            k_rank, (L, U, rq.FILES_PER_GENRE), jnp.float32))
        g_top = np.asarray(jax.random.gumbel(k_top, (L, U, self.topk),
                                             jnp.float32))
        counts = np.asarray(counts)
        emitted = np.zeros(U, np.int64)
        out = [[] for _ in range(U)]
        for step in range(L):
            active = emitted < counts
            f_new = rq.next_request(self.cat, self.genre, self.file,
                                    u_br[step], g_gen[step], g_rank[step],
                                    g_top[step])
            label = ((f_new + 1) % rq.F_FILES if self.fault == "label"
                     else f_new)
            if self.ds == 1:
                emit = active & self.has_last
                for u in np.flatnonzero(emit):
                    out[u].append((self.file[u], label[u]))
                self.has_last = self.has_last | active
            else:
                emit = active & (self.hist_len >= rq.SEQ_LEN)
                for u in np.flatnonzero(emit):
                    out[u].append((self.hist[u].copy(), label[u]))
                pushed = np.concatenate([self.hist[:, 1:], f_new[:, None]],
                                        axis=1)
                self.hist = np.where(active[:, None], pushed, self.hist
                                     ).astype(np.int32)
                self.hist_len = np.where(
                    active, np.minimum(self.hist_len + 1, rq.SEQ_LEN),
                    self.hist_len).astype(np.int32)
            self.genre = np.where(active, f_new // rq.FILES_PER_GENRE,
                                  self.genre).astype(np.int32)
            self.file = np.where(active, f_new, self.file).astype(np.int32)
            emitted += emit
        return out


class Fifo:
    """Every slot's ring of D_u samples, in host memory."""

    def __init__(self, slots: int, depth: int, x_shape: tuple, caps):
        self.x = np.zeros((slots, depth) + x_shape, np.int32)
        self.y = np.zeros((slots, depth), np.int32)
        self.cap = np.asarray(caps, np.int64).copy()
        self.size = np.zeros(slots, np.int64)
        self.head = np.zeros(slots, np.int64)

    def reset(self, s: int, cap: int) -> None:
        self.cap[s], self.size[s], self.head[s] = cap, 0, 0

    def push(self, s: int, x, y) -> None:
        if self.size[s] < self.cap[s]:
            slot = (self.head[s] + self.size[s]) % self.cap[s]
            self.size[s] += 1
        else:
            slot = self.head[s]
            self.head[s] = (self.head[s] + 1) % self.cap[s]
        self.x[s, slot], self.y[s, slot] = x, y

    def batch_slots(self, uniform: np.ndarray) -> np.ndarray:
        """Ring positions of the live samples that uniform draws in [0, 1)
        of shape (slots, ...) pick."""
        sz = np.maximum(self.size, 1)
        lead = (-1,) + (1,) * (uniform.ndim - 1)
        j = np.minimum(np.floor(uniform * sz.astype(np.float32).reshape(lead)
                                ).astype(np.int64), sz.reshape(lead) - 1)
        return (self.head.reshape(lead) + j) % self.cap.reshape(lead)


class Pool:
    """Slot pool: a user not seated takes the slot freed first, or evicts
    the resident seated first that is not among those being seated."""

    def __init__(self, num_users: int, slots: int):
        self.user_slot = np.full(num_users, -1, np.int64)
        self.slot_user = np.full(slots, -1, np.int64)
        self.seated = np.full(slots, -1, np.int64)   # tick seated, -1 free
        self.freed = np.arange(slots, dtype=np.int64)  # tick freed, -1 taken
        self.clock = slots

    def admit(self, users) -> list:
        """Seat ``users``; returns the (slot, user) pairs newly seated."""
        users = np.asarray(users, np.int64)
        wanted = np.zeros(self.user_slot.shape[0], bool)
        wanted[users] = True
        big = np.iinfo(np.int64).max
        new = []
        for u in users:
            if self.user_slot[u] >= 0:
                continue
            if (self.freed >= 0).any():
                s = int(np.argmin(np.where(self.freed >= 0, self.freed, big)))
            else:
                held = self.seated >= 0
                held[held] = ~wanted[self.slot_user[held]]
                s = int(np.argmin(np.where(held, self.seated, big)))
                self.user_slot[self.slot_user[s]] = -1
            self.slot_user[s], self.user_slot[u] = u, s
            self.seated[s], self.freed[s] = self.clock, -1
            self.clock += 1
            new.append((s, int(u)))
        return new


def start(cfg: dict, seed: int, num_users: int, cohort_size: int,
          eval_samples: int, fault=None) -> SimpleNamespace:
    """The run's state before its first round: ``cat``, ``p_ac``, ``caps``,
    ``stream``, ``pool``, ``fifo`` (slot-indexed), the evaluation set
    ``test_prev``/``test_x``, ``test_y``, the host generator ``rng`` where
    the rounds take it up, the clients' systems ``sysc`` and the weights'
    ``key``."""
    U, ds, E = num_users, cfg["dataset"], cfg["arrivals"]
    C = cohort_size or U
    lo, hi = cfg["capacity"]
    cat, p_ac = population(seed, U, cfg["user"])
    stream = Stream(cat, seed, ds, min(cfg["topk"], rq.FILES_PER_GENRE - 1),
                    fault=fault)
    rng = np.random.default_rng(seed)
    caps = rng.integers(lo, max(hi, lo + 1), size=U)
    pool = Pool(U, C)
    pool.admit(np.arange(C))
    fifo = Fifo(C, int(caps.max()), () if ds == 1 else (rq.SEQ_LEN,),
                caps[:C])
    filled = np.zeros(U, np.int64)
    target = np.zeros(U, np.int64)
    target[:C] = caps[:C]
    while (filled < target).any():
        chunk = np.minimum(target - filled, E)
        for s, samples in enumerate(stream.draw(chunk, E)[:C]):
            for x, y in samples:
                fifo.push(s, x, y)
        filled += chunk
    per = max(eval_samples // U, 4)
    test = stream.draw(np.full(U, per), per)
    test_x = np.stack([np.asarray(x) for s in test for x, _ in s])
    test_y = np.array([y for s in test for _, y in s], np.int32)
    if ds == 1:
        users = np.repeat(np.arange(U), per)
        test_x = features(cat, users, test_x)
    sysc = client_systems(rng, U, cfg["client_system"])
    return SimpleNamespace(
        cat=cat, p_ac=p_ac, caps=caps, stream=stream, pool=pool, fifo=fifo,
        test_x=test_x, test_y=test_y, rng=rng, sysc=sysc,
        key=jax.random.PRNGKey(seed), seed=seed)
