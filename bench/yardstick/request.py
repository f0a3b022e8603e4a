"""The video-caching request model (arXiv:2408.05886, Appendix D, Algorithm 5)
in plain NumPy, one request per user per step.

A user's next request is drawn from its Markov state (genre, file):

* first request: genre ~ Cat(pref_u), then a Zipf-Mandelbrot rank mapped
  through the genre's popularity order;
* exploit (prob eps_u): one of the top-K most similar files of the current
  genre, the current file excluded, drawn by the softmax of the similarities;
* explore: a genre other than the current one ~ Cat(pref_u) renormalised, then
  a Zipf-Mandelbrot rank.

Every categorical draw is written as ``argmax(logits + Gumbel)``, which is
exactly a draw from ``softmax(logits)``. The Gumbel noise is an argument:
the noise the stream's own keys give (``start.Stream``). All sums are
float32, as in a program that draws in float32. The catalog ``cat`` holds
``feat50``, ``own_sims``, ``popularity``, ``pref``, ``log_pref``, ``eps``
and ``log_zipf`` (``start.population``).
"""
from __future__ import annotations

import numpy as np

F_FILES = 100
G_GENRES = 5
FILES_PER_GENRE = F_FILES // G_GENRES
FEAT_DIM = 3 * 32 * 32
GENRE_FEAT_DIM = 70
SEQ_LEN = 10


def next_request(cat, genre, file, u_br, gum_genre, gum_rank,
                 gum_top) -> np.ndarray:
    """(U,) next request ids. ``genre``/``file`` are -1 before a user's first
    request; the noise arrays are (U,), (U, G), (U, 20) and (U, K)."""
    U = genre.shape[0]
    rows = np.arange(U)
    first = genre < 0
    exploit = ~first & (u_br <= cat.eps)
    explore = ~first & ~exploit
    glog = cat.log_pref.copy()
    glog[rows[explore], genre[explore]] = -np.inf
    g_draw = np.argmax(glog + gum_genre, axis=1)
    rank = np.argmax(cat.log_zipf[None, :] + gum_rank, axis=1)
    f_zipf = g_draw * FILES_PER_GENRE + cat.popularity[g_draw, rank]
    f_safe = np.maximum(file, 0)
    sims = cat.own_sims[f_safe].copy()
    sims[rows, f_safe % FILES_PER_GENRE] = -np.inf
    k = gum_top.shape[1]
    top_i = np.argsort(-sims, axis=1, kind="stable")[:, :k]
    top_v = np.take_along_axis(sims, top_i, axis=1)
    kwin = np.argmax(top_v + gum_top, axis=1)
    f_exploit = np.maximum(genre, 0) * FILES_PER_GENRE + top_i[rows, kwin]
    return np.where(exploit, f_exploit, f_zipf).astype(np.int32)


def features(cat: Catalog, users, prev) -> np.ndarray:
    """Dataset-1 feature rows of the requests after ``prev`` (any shape, with
    ``users`` broadcast to it): content feature / 50, the user's genre
    preferences, the file's similarities within its genre, the genre id / G
    over 70 dims, and eps."""
    g = (prev // FILES_PER_GENRE).astype(np.float32)
    shape = prev.shape
    return np.concatenate([
        cat.feat50[prev],
        np.broadcast_to(cat.pref[users], shape + (G_GENRES,)),
        cat.own_sims[prev],
        np.broadcast_to((g / G_GENRES)[..., None], shape + (GENRE_FEAT_DIM,)),
        np.broadcast_to(cat.eps[users][..., None], shape + (1,)),
    ], axis=-1).astype(np.float32)
