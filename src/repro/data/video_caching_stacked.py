"""Batched Gumbel-trick request model: all U users advance per slot as one
jitted JAX program (Algorithm 5 at cohort scale).

``data/video_caching.py`` is the per-user oracle: every decision in
Algorithm 5 is an ``rng.choice(p=pmf)`` over a small categorical — genre by
Dirichlet preference, Zipf-Mandelbrot rank, top-K exploit softmax, explore
re-normalization. Each of those is replaced here by the Gumbel-max trick:
``argmax(log p_i + G_i)`` with ``G_i`` iid Gumbel(0,1) is exactly
``Cat(p)``, and masking an entry's logit to -inf is exactly dropping it and
re-normalizing the rest. That turns the whole per-request branch structure
into a handful of masked ``(U, .)`` argmaxes with no host synchronization:

  * **first request** (``genre < 0``): genre = argmax over ``log pref_u``;
  * **exploit** (``u <= eps_u``): candidate logits are the raw within-genre
    cosine sims with the current file masked out (softmax is a monotone
    reparametrization — ``argmax(sims + G)`` already samples the softmax),
    restricted to the top-K sims via ``lax.top_k``;
  * **explore**: genre = argmax over ``log pref_u`` with the current genre
    masked to -inf (the oracle's re-normalization over the other genres);
  * **Zipf rank** (first/explore): argmax over the cached
    ``log zipf_mandelbrot_pmf`` mapped through the genre's popularity order.

One ``StackedRequestStream.draw_dataset{1,2}(counts, width)`` call runs a
fixed-length ``lax.scan`` of ``width + warmup`` such steps — warmup is the
cohort's worst-case unfilled-window deficit read off the current state (up
to 1 slot for the Dataset-1 sliding window, SEQ_LEN for the Dataset-2
history ring, the same extra requests the oracle's while-loop consumes; 0
once the cohort is warm) — with a per-user ``emitted < counts`` mask so
users that reached their arrival count stop consuming requests, exactly
like the oracle. All randomness is drawn in
four bulk threefry calls before the scan, and the scan itself carries only
the O(U) Markov state: it emits (slot, request-id) pairs, from which the
padded ``(U, width, 3168)`` / ``(U, width, SEQ_LEN)`` blocks are assembled
in one vectorized pass afterwards (the Dataset-1 feature is a deterministic
function of the *previous* request id, so features never enter the scan).
The result is exactly the layout ``data/online.py::pad_arrival_batch``
produces, so it feeds ``core/buffer_stacked.py::StackedOnlineBuffer.stage``
directly.

The streams are **distribution-equivalent**, not bit-equivalent, to the
oracle (the RNG is a JAX counter-based PRNG, not NumPy PCG64):
per-decision-branch pmf parity is enforced by chi-squared tests in
``tests/test_request_stacked.py``. Checkpointing is ``state_dict`` /
``load_state_dict`` over the device-array state (PRNG key, Markov state,
sliding-window carries), round-tripped through the RunState codec.
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import List, NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.data.video_caching import (Catalog, D1_DIM, F_FILES,
                                      FILES_PER_GENRE, G_GENRES,
                                      GENRE_FEAT_DIM, RequestStream, SEQ_LEN,
                                      zipf_mandelbrot_pmf)


class StreamConsts(NamedTuple):
    """Immutable per-population device arrays (catalog + user parameters)."""
    feat50: jnp.ndarray     # (F, 3072) catalog features / 50 (sample layout)
    own_sims: jnp.ndarray   # (F, 20) cosine sims of each file vs its genre
    popularity: jnp.ndarray  # (G, 20) int32 Zipf rank -> in-genre file index
    pref: jnp.ndarray       # (U, G) Dirichlet genre preferences
    log_pref: jnp.ndarray   # (U, G) cached log preferences (genre logits)
    eps: jnp.ndarray        # (U,) exploitation probabilities
    log_zipf: jnp.ndarray   # (20,) cached log Zipf-Mandelbrot pmf


class StreamState(NamedTuple):
    """Mutable cohort state — everything a draw advances (a pytree).

    There is no stored Dataset-1 feature carry: the oracle invariant
    ``_last_feat == dataset1_sample(cat, user, _file)`` (re-established on
    every Dataset-1 request) means the carried feature is always
    reconstructible from ``file``, so only the flag survives here."""
    key: jnp.ndarray        # JAX PRNG key (the whole cohort's stream)
    genre: jnp.ndarray      # (U,) int32 Markov genre, -1 before first request
    file: jnp.ndarray       # (U,) int32 Markov global file id, -1 initially
    has_last: jnp.ndarray   # (U,) bool — a Dataset-1 window carry exists
    hist: jnp.ndarray       # (U, SEQ_LEN) int32 Dataset-2 ring (newest last)
    hist_len: jnp.ndarray   # (U,) int32 valid suffix of hist


def _features_for(consts: StreamConsts, fids: jnp.ndarray) -> jnp.ndarray:
    """Vectorized ``dataset1_sample``: (U, W) request ids -> (U, W, 3168)
    feature rows (content feature/50, genre prefs, within-genre sims, genre
    feature/G, eps)."""
    U, W = fids.shape
    g = (fids // FILES_PER_GENRE).astype(jnp.float32)
    return jnp.concatenate([
        consts.feat50[fids],
        jnp.broadcast_to(consts.pref[:, None, :], (U, W, G_GENRES)),
        consts.own_sims[fids],
        jnp.broadcast_to(g[..., None] / G_GENRES, (U, W, GENRE_FEAT_DIM)),
        jnp.broadcast_to(consts.eps[:, None, None], (U, W, 1)),
    ], axis=-1)


@partial(jax.jit, static_argnames=("width", "warmup", "dataset", "topk"))
@jax.named_scope("request_gen")
def _draw_block(consts: StreamConsts, state: StreamState, counts,
                width: int, warmup: int, dataset: int, topk: int):
    """Advance the cohort until every user u has emitted counts[u] samples
    (counts[u] <= width), returning padded (U, width, ...) blocks."""
    U = counts.shape[0]
    G, P = G_GENRES, FILES_PER_GENRE
    uu = jnp.arange(U, dtype=jnp.int32)
    g_ids = jnp.arange(G, dtype=jnp.int32)[None, :]
    p_ids = jnp.arange(P, dtype=jnp.int32)[None, :]

    # fixed scan length: width emissions + the warmup requests the oracle's
    # while-loop would consume to fill cold windows (0 once the cohort is
    # warm — the caller reads the deficit off the current state)
    L = width + warmup
    # all randomness for the block in 4 bulk draws (per-step threefry calls
    # dominate CPU wall-clock); the cohort key advances once per block
    key, k_br, k_genre, k_rank, k_top = jax.random.split(state.key, 5)
    # dtype pinned: under a scoped-x64 trace (the fused round's "x64"
    # resource backend) the defaults would switch to f64 and draw different
    # random bits than the f32 program
    rnd = (jax.random.uniform(k_br, (L, U), jnp.float32),
           jax.random.gumbel(k_genre, (L, U, G), jnp.float32),
           jax.random.gumbel(k_rank, (L, U, P), jnp.float32),
           jax.random.gumbel(k_top, (L, U, topk), jnp.float32))
    state = state._replace(key=key)

    def step(carry, rnd):
        st, emitted = carry
        u_br, gum_genre, gum_rank, gum_top = rnd
        active = emitted < counts                 # still owes samples
        first = st.genre < 0
        exploit = (~first) & (u_br <= consts.eps)
        explore = (~first) & ~exploit

        # genre: Cat(pref) for first requests; explore masks the current
        # genre (the oracle's re-normalization over the other G-1 genres)
        glog = jnp.where(explore[:, None] & (g_ids == st.genre[:, None]),
                         -jnp.inf, consts.log_pref)
        g_draw = jnp.argmax(glog + gum_genre, axis=1).astype(jnp.int32)

        # Zipf-Mandelbrot rank through the genre's popularity order
        rank = jnp.argmax(consts.log_zipf[None, :] + gum_rank, axis=1)
        f_zipf = g_draw * P + consts.popularity[g_draw, rank]

        # exploit: top-K of the within-genre sims with the current file
        # masked out; argmax(sims + gumbel) over that set IS the oracle's
        # re-normalized top-K softmax draw
        f_safe = jnp.maximum(st.file, 0)
        sims = consts.own_sims[f_safe]            # (U, P)
        sims = jnp.where(p_ids == (f_safe % P)[:, None], -jnp.inf, sims)
        top_v, top_i = jax.lax.top_k(sims, topk)
        kwin = jnp.argmax(top_v + gum_top, axis=1)
        f_exploit = jnp.maximum(st.genre, 0) * P + jnp.take_along_axis(
            top_i, kwin[:, None], axis=1)[:, 0]

        f_new = jnp.where(exploit, f_exploit, f_zipf).astype(jnp.int32)
        genre = jnp.where(active, f_new // P, st.genre)
        file_ = jnp.where(active, f_new, st.file)

        if dataset == 1:
            # sliding window: previous request's feature predicts f_new;
            # emit (slot, label, previous id) — features are built after
            # the scan from the previous ids
            emit = active & st.has_last
            slot = jnp.where(emit, emitted, width)
            out = (slot, f_new, st.file)
            has_last = st.has_last | active
            hist, hist_len = st.hist, st.hist_len
        else:
            # history ring: the SEQ_LEN requests before f_new predict f_new
            emit = active & (st.hist_len >= SEQ_LEN)
            slot = jnp.where(emit, emitted, width)
            out = (slot, f_new, st.hist)
            pushed = jnp.concatenate(
                [st.hist[:, 1:], f_new[:, None].astype(st.hist.dtype)], 1)
            hist = jnp.where(active[:, None], pushed, st.hist)
            hist_len = jnp.where(active,
                                 jnp.minimum(st.hist_len + 1, SEQ_LEN),
                                 st.hist_len)
            has_last = st.has_last
        new_st = StreamState(st.key, genre, file_, has_last, hist, hist_len)
        return (new_st, emitted + emit), out

    init = (state, jnp.zeros(U, jnp.int32))
    (st, emitted), (slots, fids, payload) = jax.lax.scan(step, init, rnd)

    # assemble the padded blocks in one pass: each (u, slot < width) pair is
    # written by exactly one step and only slots < counts[u] are ever
    # emitted, so the zero-initialized padding needs no re-masking
    out_y = jnp.zeros((U, width), jnp.int32
                      ).at[uu[None, :], slots].set(fids, mode="drop")
    if dataset == 1:
        prev = jnp.zeros((U, width), jnp.int32
                         ).at[uu[None, :], slots].set(payload, mode="drop")
        # _features_for builds garbage rows from the prev=0 padding slots —
        # this mask (alone) is load-bearing
        valid = jnp.arange(width, dtype=jnp.int32)[None, :] < counts[:, None]
        out_x = jnp.where(valid[..., None], _features_for(consts, prev), 0.0)
    else:
        out_x = jnp.zeros((U, width, SEQ_LEN), state.hist.dtype
                          ).at[uu[None, :], slots].set(payload, mode="drop")
    return st, out_x, out_y


def warmup_deficit(state: StreamState, dataset: int) -> int:
    """Worst-case warmup requests any user still owes before it can emit a
    sample (0 once the cohort is warm). Host read of the device state; the
    fused round (``core/round_fused.py``) requires this to be 0 at segment
    entry since its in-scan draws run at static warmup=0."""
    if dataset == 1:
        return 0 if bool(np.asarray(state.has_last).all()) else 1
    return max(0, SEQ_LEN - int(np.asarray(state.hist_len).min()))


@dataclass
class StackedRequestStream:
    """Whole-cohort request stream: the vectorized twin of U
    ``RequestStream``s, drawing every user's next slot in one device call."""
    consts: StreamConsts
    state: StreamState
    topk: int
    # per-dataset host cache of "warmup deficit reached 0": the deficit is
    # monotone non-increasing, so once warm the per-draw device read (a
    # blocking transfer) is skipped; reset whenever state is replaced
    _warm: dict = None

    @classmethod
    def from_streams(cls, cat: Catalog, streams: List[RequestStream],
                     seed: int = 0) -> "StackedRequestStream":
        """Import a scalar population mid-stream: user parameters become
        ``(U, ...)`` constants, and each user's Markov state + sliding-window
        carries seed the device state. Only the RNG lineage differs (JAX
        counter PRNG from ``seed`` instead of U PCG64 streams)."""
        users = [s.user for s in streams]
        U = len(users)
        if U == 0:
            raise ValueError("empty population")
        topk = min(int(users[0].topk), FILES_PER_GENRE - 1)
        gamma, q = users[0].gamma, users[0].q
        for u in users:
            if (u.topk, u.gamma, u.q) != (users[0].topk, gamma, q):
                raise ValueError("stacked stream needs homogeneous "
                                 "topk/gamma/q across the cohort")
        own = cat.cos_sim.reshape(F_FILES, G_GENRES, FILES_PER_GENRE)[
            np.arange(F_FILES), np.arange(F_FILES) // FILES_PER_GENRE]
        pref = np.stack([u.genre_pref for u in users]).astype(np.float32)
        consts = StreamConsts(
            feat50=jnp.asarray(cat.features / np.float32(50.0)),
            own_sims=jnp.asarray(own.astype(np.float32)),
            popularity=jnp.asarray(cat.popularity, jnp.int32),
            pref=jnp.asarray(pref),
            log_pref=jnp.log(jnp.asarray(pref)),
            eps=jnp.asarray([u.eps for u in users], jnp.float32),
            log_zipf=jnp.log(jnp.asarray(
                zipf_mandelbrot_pmf(FILES_PER_GENRE, gamma, q),
                jnp.float32)))
        hist = np.zeros((U, SEQ_LEN), np.int32)
        hist_len = np.zeros(U, np.int32)
        for i, s in enumerate(streams):
            h = s._history[-SEQ_LEN:]
            if h:
                hist[i, SEQ_LEN - len(h):] = h
                hist_len[i] = len(h)
        state = StreamState(
            # fold in a tag so the stream's threefry lineage is decorrelated
            # from other PRNGKey(seed) consumers (e.g. model init splits the
            # bare key the same way the first draw block would)
            key=jax.random.fold_in(jax.random.PRNGKey(seed), 0x726571),
            genre=jnp.asarray([u._genre for u in users], jnp.int32),
            file=jnp.asarray([u._file for u in users], jnp.int32),
            has_last=jnp.asarray(
                [s._last_feat is not None for s in streams]),
            hist=jnp.asarray(hist), hist_len=jnp.asarray(hist_len))
        return cls(consts=consts, state=state, topk=topk)

    @property
    def num_users(self) -> int:
        return int(self.state.genre.shape[0])

    # -- drawing -------------------------------------------------------------
    def _draw(self, counts, width: int, dataset: int
              ) -> Tuple[jnp.ndarray, jnp.ndarray, np.ndarray]:
        counts = np.asarray(counts)
        width = int(width)
        if width < 1:
            raise ValueError(f"width must be >= 1, got {width}")
        if counts.shape != (self.num_users,):
            raise ValueError(f"counts shape {counts.shape} != "
                             f"({self.num_users},)")
        if counts.max(initial=0) > width:
            raise ValueError(f"max arrivals {int(counts.max())} > pad "
                             f"width {width}")
        # worst-case warmup requests still owed by any user (0 in steady
        # state, so post-fill rounds scan exactly `width` steps); reading it
        # costs a (U,)-int transfer and at most SEQ_LEN+1 extra traces, and
        # is skipped entirely once the cohort has been seen warm
        if self._warm is None:
            self._warm = {}
        if self._warm.get(dataset):
            warmup = 0
        else:
            warmup = warmup_deficit(self.state, dataset)
        self._warm[dataset] = warmup == 0
        self.state, xs, ys = _draw_block(
            self.consts, self.state, jnp.asarray(counts, jnp.int32),
            width, warmup, dataset, self.topk)
        return xs, ys, counts.astype(np.int32)

    def draw_dataset1(self, counts, width: int):
        """counts[u] fresh Dataset-1 samples per user, padded to
        ``(U, width, 3168)`` / ``(U, width)`` + the (U,) valid counts —
        exactly the ``StackedOnlineBuffer.stage`` argument layout."""
        return self._draw(counts, width, 1)

    def draw_dataset2(self, counts, width: int):
        """Dataset-2 twin: ``(U, width, SEQ_LEN)`` histories -> next ids."""
        return self._draw(counts, width, 2)

    def draw(self, counts, dataset: int, width: int):
        """Dispatch on the dataset id the harness configs carry."""
        return self._draw(counts, width, 1 if dataset == 1 else 2)

    # -- checkpointing -------------------------------------------------------
    def state_dict(self) -> dict:
        """Everything a draw mutates: the cohort PRNG key, per-user Markov
        state and both sliding-window carries. The catalog/user constants are
        rebuilt deterministically from the population seed."""
        st = self.state
        return {"key": st.key, "genre": st.genre, "file": st.file,
                "has_last": st.has_last,
                "hist": st.hist, "hist_len": st.hist_len}

    def load_state_dict(self, sd: dict) -> None:
        self._warm = {}                 # restored state may be colder
        self.state = StreamState(
            key=jnp.asarray(sd["key"]),
            genre=jnp.asarray(sd["genre"], jnp.int32),
            file=jnp.asarray(sd["file"], jnp.int32),
            has_last=jnp.asarray(sd["has_last"], bool),
            hist=jnp.asarray(sd["hist"], jnp.int32),
            hist_len=jnp.asarray(sd["hist_len"], jnp.int32))
