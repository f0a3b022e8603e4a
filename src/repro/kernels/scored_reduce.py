"""Pallas TPU kernel for the OSAFL score hot-spot (paper eqs. 19-20).

Given U stacked client updates d (U, N) and the mean update (N,), one fused
pass over HBM computes everything the score needs:

    dots[u]   = <d_u, mean>
    norms[u]  = ||d_u||^2
    mean_sq   = ||mean||^2

Naively this is three separate O(U*N) reductions reading d twice and mean
twice; the fused kernel streams each operand exactly once through VMEM
(block (BLOCK_U, BLOCK_N)) and accumulates along the sequential N grid
dimension; the client dimension is blocked too, so thousand-client cohorts
stay within the ~16 MiB VMEM budget when compiled. By default it compiles
when lowered for a TPU and runs interpreted elsewhere (``kernels/mode.py``);
on CPU the interpreted kernel is validated against kernels/ref.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.mode import by_platform

DEFAULT_BLOCK_N = 2048          # compiled TPU path: (block_u, block_n) f32
DEFAULT_BLOCK_U = 512           # in VMEM: 512 * 2048 * 4B = 4 MiB
INTERPRET_BLOCK_N = 512 * 1024  # interpret mode runs the grid loop at Python
                                # speed, so large blocks (few grid steps) are
                                # ~30x faster on CPU and VMEM doesn't apply


def _scored_kernel(d_ref, mean_ref, dots_ref, norms_ref, msq_ref):
    u = pl.program_id(0)                        # client-block (parallel)
    i = pl.program_id(1)                        # N-block (sequential accum)
    d = d_ref[...].astype(jnp.float32)          # (bu, bn)
    m = mean_ref[...].astype(jnp.float32)       # (1, bn)

    @pl.when(i == 0)
    def _init():
        dots_ref[...] = jnp.zeros_like(dots_ref)
        norms_ref[...] = jnp.zeros_like(norms_ref)

    @pl.when((u == 0) & (i == 0))
    def _init_msq():
        msq_ref[...] = jnp.zeros_like(msq_ref)

    dots_ref[...] += jnp.sum(d * m, axis=1, keepdims=True)
    norms_ref[...] += jnp.sum(d * d, axis=1, keepdims=True)

    @pl.when(u == 0)                            # count ||mean||^2 once
    def _msq():
        msq_ref[...] += jnp.sum(m * m, axis=1, keepdims=True)


@functools.partial(jax.jit,
                   static_argnames=("block_n", "block_u", "interpret"))
def scored_reduce(d, mean, *, block_n=None, block_u=None, interpret=None):
    """d: (U, N); mean: (N,) -> (dots (U,), norms_sq (U,), mean_sq ()).

    ``interpret=None`` picks the mode from the lowering platform
    (``kernels/mode.py``); True/False force it (tests, compile rehearsal)."""
    if interpret is None:
        return by_platform(_scored_reduce, d, mean, block_n=block_n,
                           block_u=block_u)
    return _scored_reduce(d, mean, block_n=block_n, block_u=block_u,
                          interpret=interpret)


def _scored_reduce(d, mean, *, block_n, block_u, interpret):
    U, N = d.shape
    if block_n is None:
        block_n = INTERPRET_BLOCK_N if interpret else DEFAULT_BLOCK_N
    if block_u is None:
        block_u = U if interpret else DEFAULT_BLOCK_U
    block_n = min(block_n, N)
    block_u = min(block_u, U)
    pad_n = (-N) % block_n
    pad_u = (-U) % block_u
    if pad_n or pad_u:
        d = jnp.pad(d, ((0, pad_u), (0, pad_n)))   # zero rows: dots/norms 0
        mean = jnp.pad(mean, (0, pad_n))
    Up, Np = U + pad_u, N + pad_n
    grid = (Up // block_u, Np // block_n)
    dots, norms, msq = pl.pallas_call(
        _scored_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_u, block_n), lambda u, i: (u, i)),
            pl.BlockSpec((1, block_n), lambda u, i: (0, i)),
        ],
        out_specs=[
            pl.BlockSpec((block_u, 1), lambda u, i: (u, 0)),
            pl.BlockSpec((block_u, 1), lambda u, i: (u, 0)),
            pl.BlockSpec((1, 1), lambda u, i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Up, 1), jnp.float32),
            jax.ShapeDtypeStruct((Up, 1), jnp.float32),
            jax.ShapeDtypeStruct((1, 1), jnp.float32),
        ],
        interpret=interpret,
        name="scored_reduce",
    )(d, mean.reshape(1, Np))
    return dots[:U, 0], norms[:U, 0], msq[0, 0]


def osafl_scores_fused(d, chi: float = 1.0, *, interpret=None):
    """End-to-end scored weights from stacked updates d (U, N):
    lambda_u = (chi + cos(d_u, mean)) / (chi + 1), in [0, 1] for chi >= 0."""
    mean = jnp.mean(d, axis=0)
    dots, norms, msq = scored_reduce(d, mean, interpret=interpret)
    return lambda_from_reduce(dots, norms, msq, chi)


def lambda_from_reduce(dots, norms, msq, chi):
    """eq. 19-21 scores from the fused reduction's outputs. ``cos`` is
    clamped to [-1, 1]: rounding in the norms can push it past 1 (and
    lambda past 1) for a row parallel to the mean."""
    cos = jnp.clip(dots / jnp.maximum(jnp.sqrt(norms) * jnp.sqrt(msq), 1e-12),
                   -1.0, 1.0)
    return (chi + cos) / (chi + 1.0)
