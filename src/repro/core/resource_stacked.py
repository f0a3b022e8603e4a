"""Batched joint resource optimization (paper Section II-C, Appendix B).

Vectorized port of ``core/resource.py``: the same Lemma 1 / Lemma 2 closed
forms and the interval-endpoint SCA power step, evaluated for all U clients
at once as elementwise jnp over (U,) arrays. The math is purely elementwise,
so the port is a broadcast rewrite of the scalar module; the per-client
NumPy module remains the oracle and this module must agree with it exactly
on kappa/feasibility and to <= 1e-6 relative on (f, p)
(tests/test_online_stacked.py).

Two numeric backends (``resource_backend`` in the harness configs):

  * ``"x64"`` (default, the parity oracle): the solve runs in float64 under
    a scoped ``jax.enable_x64`` context (the repo keeps the
    global x64 flag off). The SCA's minimum-SNR term
    2^(Nb / (omega * t_left)) overflows float32 under tight deadlines, and
    the scalar-oracle parity bar sits far below f32 resolution.
  * ``"f32"``: the accelerator-native path. The minimum-SNR/minimum-power
    step is reformulated in the log domain — ``log p_lo =
    log(expm1(Nb ln2 / (omega t_left))) - log g`` compared against
    ``log p_max`` — so the solve never materializes 2^x and compiles and
    runs without x64 on TPU/GPU. Everything else is the identical formula
    set in f32. Tolerance vs the x64 oracle is documented in DESIGN.md
    ("Fused round"): kappa/feasibility match exactly away from the
    ``_J_SLACK``/``_P_SLACK`` knife edges, (f, p) to ~1e-3 relative.

``make_solver_core`` exposes the un-jitted solve body so the fused round
(``core/round_fused.py``) can inline it into a larger single-dispatch
program; ``optimize_clients_batched`` remains the host entry point and owns
the x64 scope boundary: results are materialized to host NumPy *inside* the
scope (device f64 arrays must never escape ``enable_x64()`` — later jnp ops
outside the scope would silently downcast them) and checked finite, raising
``ResourceSolveError`` naming the offending clients otherwise.

Per-client early exits in the scalar algorithm (straggler breaks, frequency
fallback, SCA convergence) become lane masks; iteration counts are the
static ``NetworkConfig.outer_iters`` / ``sca_iters``, so the whole
alternating solve — all five initial power points of Algorithm 1's sweep —
jits to one XLA program per (network configuration, backend).

Channel sampling is vectorized too, and ``np.random.Generator`` draws are
stream-equivalent between one size-U array draw and U sequential scalar
draws, so ``sample_channels`` reproduces the loop path's channels exactly
for the same generator state.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from functools import lru_cache
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.resource import (_J_SLACK, _P_SLACK, FPP, ClientSystem,
                                 NetworkConfig, pathloss_linear)

_LN2 = float(np.log(2.0))

RESOURCE_BACKENDS = ("x64", "f32")


class ResourceSolveError(RuntimeError):
    """The batched solve produced non-finite kappa/f/p on feasible lanes
    (f32 knife-edge regime — see the f32 notes in the module docstring)."""


@dataclass
class ClientSystemBatch:
    """Column-stacked ``ClientSystem``: every field an (U,) float64 array."""
    c: np.ndarray
    s: np.ndarray
    f_max: np.ndarray
    p_max: np.ndarray
    e_bd: np.ndarray
    distance: np.ndarray

    def __len__(self) -> int:
        return self.c.shape[0]


def stack_clients(clients: Sequence[ClientSystem]) -> ClientSystemBatch:
    """Stack a ``make_clients`` population into (U,) field arrays."""
    cols = {f.name: np.array([getattr(cl, f.name) for cl in clients],
                             np.float64)
            for f in dataclasses.fields(ClientSystem)}
    return ClientSystemBatch(**cols)


@dataclass
class ChannelBatch:
    """Per-round wireless channels for the whole cohort: (U,) arrays."""
    xi: np.ndarray
    gamma: np.ndarray


def sample_channels(rng: np.random.Generator, sysb: ClientSystemBatch,
                    shadow_sigma_db: float = 8.0) -> ChannelBatch:
    """Whole-cohort ``resource.sample_channel``: one array draw, same stream
    as U sequential scalar draws from the same generator state."""
    gamma = 10 ** (rng.normal(0.0, shadow_sigma_db, size=len(sysb)) / 10)
    return ChannelBatch(xi=pathloss_linear(sysb.distance), gamma=gamma)


@dataclass
class ResourceDecisionBatch:
    """Column-stacked ``ResourceDecision``; ``kappa`` is 0 for stragglers."""
    kappa: np.ndarray       # (U,) int64
    f: np.ndarray           # (U,) float64
    p: np.ndarray           # (U,) float64
    feasible: np.ndarray    # (U,) bool
    t_total: np.ndarray     # (U,) float64
    e_total: np.ndarray     # (U,) float64


def make_solver_core(net: NetworkConfig, backend: str = "x64"):
    """The all-clients solve as a pure (un-jitted) function.

    Maps (c, s, f_max, p_max, e_bd, xi, gamma, n_params) — all (U,) arrays
    of the backend's dtype except the scalar payload — to the six decision
    columns. Every formula mirrors the scalar module line-for-line; only the
    control flow changes (breaks -> lane masks, init-point loop -> vmap),
    and on the f32 backend the minimum-power step runs in the log domain
    (the lone f32-overflowing term — see the module docstring). The x64
    variant must be traced under ``enable_x64``; ``core/round_fused.py``
    inlines either variant into the one-dispatch round program.
    """
    if backend not in RESOURCE_BACKENDS:
        raise ValueError(f"unknown resource backend {backend!r} "
                         f"(expected one of {RESOURCE_BACKENDS})")
    log_domain = backend == "f32"
    noise = net.noise_power
    fracs = np.array([1.0, 0.1, 0.01, 1e-3, 1e-4])
    ks = np.arange(1.0, net.kappa_max + 1)          # (K,) candidate kappas

    @jax.named_scope("resource_solve")
    def solve(c, s, f_max, p_max, e_bd, xi, gamma, n_params):
        xg = xi * gamma
        cc = net.n * net.nbar * c * s               # cycles per local round
        nb = n_params * (FPP + 1)                   # upload payload (bits)
        g = xg / noise                              # SNR slope: snr = g*p

        def rate(p):
            return net.omega * jnp.log2(1.0 + xg * p / noise)

        def t_up(p):
            return nb / jnp.maximum(rate(p), 1e-12)

        def e_up(p):
            return t_up(p) * p

        def opt_kappa(f, p):
            """Lemma 1 (eq. 42)."""
            j1 = (e_bd - e_up(p)) / (0.5 * net.v * cc * f ** 2)
            j2 = f * (net.t_th - t_up(p)) / cc
            k = jnp.minimum(float(net.kappa_max),
                            jnp.floor(jnp.minimum(j1, j2) + _J_SLACK))
            return jnp.maximum(k, 0.0)

        def opt_freq(kappa, p):
            """Lemma 2 (eq. 48); inf where upload alone exceeds deadline."""
            r = rate(p)
            denom = net.t_th * r - nb
            val = cc * kappa * r / jnp.where(denom > 0, denom, 1.0)
            return jnp.where(denom > 0, val, jnp.inf)

        def min_power(t_left, valid):
            """(52c)/(11c): smallest p meeting the deadline at (kappa, f).

            The direct form 2^(Nb/(omega*t_left)) - 1 overflows f32 for
            tight deadlines; the log-domain form compares log p_lo against
            log p_max and only exponentiates the clipped value, so the f32
            backend never materializes the overflow."""
            t_safe = jnp.where(valid, t_left, 1.0)
            if not log_domain:
                snr_min = 2.0 ** (nb / (net.omega * t_safe)) - 1.0
                p_lo = snr_min / g
                valid &= p_lo <= p_max * (1 + _P_SLACK)
                return jnp.where(valid, jnp.minimum(p_lo, p_max), 1e-6), valid
            a = nb * _LN2 / (net.omega * t_safe)    # log(1 + snr_min)
            # log(expm1(a)): exact small-a form, overflow-free large-a form
            log_snr = jnp.where(a > 10.0,
                                a + jnp.log1p(-jnp.exp(-jnp.maximum(a, 10.0))),
                                jnp.log(jnp.expm1(jnp.minimum(a, 10.0))))
            log_p_lo = log_snr - jnp.log(g)
            log_cap = jnp.log(p_max)
            valid &= log_p_lo <= log_cap + jnp.log1p(_P_SLACK)
            p_lo = jnp.exp(jnp.minimum(log_p_lo, log_cap))
            return jnp.where(valid, p_lo, 1e-6), valid

        def sca_power(kappa, f, p0):
            """SCA (eqs. 50-52) with convergence/abort masks per lane."""
            e_cp = 0.5 * net.v * cc * kappa * f ** 2
            t_cp = cc * kappa / f
            t_left = net.t_th - t_cp
            valid = t_left > 0
            p_lo, valid = min_power(t_left, valid)
            p = jnp.maximum(jnp.maximum(jnp.minimum(p0, p_max), p_lo), 1e-6)
            done = jnp.zeros(valid.shape, bool)
            for _ in range(net.sca_iters):
                act = valid & ~done
                ln = jnp.log1p(g * p)
                obj_slope = (net.omega / _LN2) * (g / (p * (1 + g * p))
                                                  - ln / p ** 2)
                e_at = nb * _LN2 / net.omega * (p / ln)
                e_slope = nb * _LN2 / net.omega * (1 / ln - g * p /
                                                   (ln ** 2 * (1 + g * p)))
                pos = e_slope > 0
                p_hi = jnp.where(
                    pos,
                    jnp.minimum(p_max, p + (e_bd - e_cp - e_at)
                                / jnp.where(pos, e_slope, 1.0)),
                    p_max)
                bad = p_hi < p_lo - 1e-12
                valid &= ~(act & bad)
                act &= ~bad
                p_new = jnp.clip(jnp.where(obj_slope >= 0, p_hi, p_lo),
                                 p_lo, p_max)
                conv = jnp.abs(p_new - p) < net.tol
                p = jnp.where(act, jnp.where(conv, p_new,
                                             0.5 * (p + p_new)), p)
                done |= act & conv
            ok = valid & (e_up(p) + e_cp <= e_bd * (1 + 1e-6)) \
                & (t_cp + t_up(p) <= net.t_th * (1 + 1e-6))
            return p, ok

        def from_point(p0):
            """Masked ``resource._optimize_from`` over all lanes at once."""
            f, p = f_max, p0
            alive = jnp.ones(p0.shape, bool)
            rk = jnp.zeros_like(p0)
            rf, rp = f, p
            rfeas = jnp.zeros(p0.shape, bool)
            rt = jnp.zeros_like(p0)
            re_ = jnp.zeros_like(p0)
            for _ in range(net.outer_iters):
                kappa = opt_kappa(f, p)
                alive &= kappa >= 1
                f_new = opt_freq(kappa, p)
                good = jnp.isfinite(f_new) & (f_new <= f_max)
                # deadline infeasible at kappa: largest k2 < kappa that fits
                f_all = opt_freq(ks[:, None], p[None, :])        # (K, U)
                ok_all = jnp.isfinite(f_all) & (f_all <= f_max[None, :])
                cand = ok_all & (ks[:, None] <= (kappa - 1)[None, :])
                k2 = jnp.max(jnp.where(cand, ks[:, None], 0.0), axis=0)
                f_k2 = jnp.sum(jnp.where(ks[:, None] == k2[None, :],
                                         f_all, 0.0), axis=0)
                kappa = jnp.where(good, kappa, k2)
                f_new = jnp.where(good, f_new, f_k2)
                alive &= good | (k2 >= 1)
                f = jnp.where(alive, jnp.clip(f_new, 1e6, f_max), f)
                p_sca, sca_ok = sca_power(kappa, f, p)
                alive &= sca_ok
                p = jnp.where(alive, p_sca, p)
                t_tot = cc * kappa / f + t_up(p)
                e_tot = 0.5 * net.v * cc * kappa * f ** 2 + e_up(p)
                okc = alive & (t_tot <= net.t_th * (1 + 1e-6)) \
                    & (e_tot <= e_bd * (1 + 1e-6))
                rk = jnp.where(okc, kappa, rk)
                rf = jnp.where(okc, f, rf)
                rp = jnp.where(okc, p, rp)
                rt = jnp.where(okc, t_tot, rt)
                re_ = jnp.where(okc, e_tot, re_)
                rfeas |= okc
            return rk, rf, rp, rfeas, rt, re_

        # Algorithm 1's sweep over initial power points: all five at once
        sk, sf, sp, sfeas, st_, se = jax.vmap(from_point)(
            p_max[None, :] * fracs[:, None])
        bk = jnp.zeros_like(c)
        bf, bp = f_max, p_max
        bfeas = jnp.zeros(c.shape, bool)
        bt = jnp.zeros_like(c)
        be = jnp.zeros_like(c)
        for i in range(len(fracs)):                 # keep the scalar order
            better = sfeas[i] & (~bfeas | (sk[i] > bk))
            bk = jnp.where(better, sk[i], bk)
            bf = jnp.where(better, sf[i], bf)
            bp = jnp.where(better, sp[i], bp)
            bt = jnp.where(better, st_[i], bt)
            be = jnp.where(better, se[i], be)
            bfeas |= sfeas[i]
        return bk, bf, bp, bfeas, bt, be

    return solve


@lru_cache(maxsize=8)
def _make_solver(net_fields: tuple, backend: str):
    """Jitted-and-cached ``make_solver_core`` per (NetworkConfig, backend)."""
    return jax.jit(make_solver_core(NetworkConfig(*net_fields), backend))


def _check_finite(kappa, f, p, feas, backend: str) -> None:
    """Feasible lanes must carry finite decisions; the f32 backend can lose
    them at the ``_J_SLACK``/``_P_SLACK`` knife edges (documented contract:
    raise, never hand non-finite kappa/f/p to the round loop)."""
    bad = feas & ~(np.isfinite(kappa) & np.isfinite(f) & np.isfinite(p))
    if bad.any():
        lanes = np.flatnonzero(bad)[:8]
        raise ResourceSolveError(
            f"resource solve ({backend} backend) produced non-finite "
            f"kappa/f/p on {int(bad.sum())} feasible client(s) "
            f"(first lanes {lanes.tolist()}: "
            f"kappa={kappa[lanes].tolist()}, f={f[lanes].tolist()}, "
            f"p={p[lanes].tolist()}); for tight-deadline/knife-edge "
            "configurations run resource_backend='x64'")


def optimize_clients_batched(net: NetworkConfig, sysb: ClientSystemBatch,
                             ch: ChannelBatch, n_params: int,
                             backend: str = "x64") -> ResourceDecisionBatch:
    """All-clients ``resource.optimize_client``: one jitted solve.

    ``backend="x64"`` (default) is the scalar-parity oracle under scoped
    ``enable_x64``; ``backend="f32"`` is the accelerator-native log-domain
    solve. Either way the returned columns are **host NumPy float64/int64**:
    the x64 scope boundary materializes every output inside the scope so no
    f64 device array escapes it (escaped arrays silently downcast on the
    next op once the scope closes)."""
    if backend not in RESOURCE_BACKENDS:
        raise ValueError(f"unknown resource backend {backend!r} "
                         f"(expected one of {RESOURCE_BACKENDS})")
    solver = _make_solver(dataclasses.astuple(net), backend)
    cols = (sysb.c, sysb.s, sysb.f_max, sysb.p_max, sysb.e_bd,
            ch.xi, ch.gamma)
    if backend == "x64":
        with jax.enable_x64(True):
            out = solver(*[jnp.asarray(a, jnp.float64) for a in cols],
                         jnp.float64(n_params))
            # scope boundary: host-materialize before the scope closes
            out = [np.asarray(o) for o in out]
            assert all(isinstance(o, np.ndarray) for o in out)
            assert all(o.dtype == np.float64 for o in out[:3]), \
                "x64 solve returned non-f64 decision columns"
    else:
        out = solver(*[jnp.asarray(a, jnp.float32) for a in cols],
                     jnp.float32(n_params))
        out = [np.asarray(o) for o in out]
    kappa, f, p, feas, t, e = out
    feas = feas.astype(bool)
    _check_finite(kappa, f, p, feas, backend)
    return ResourceDecisionBatch(kappa=kappa.astype(np.int64),
                                 f=f.astype(np.float64),
                                 p=p.astype(np.float64),
                                 feasible=feas,
                                 t_total=t.astype(np.float64),
                                 e_total=e.astype(np.float64))


def optimize_round_batched(rng: np.random.Generator, net: NetworkConfig,
                           sysb: ClientSystemBatch, n_params: int,
                           backend: str = "x64") -> ResourceDecisionBatch:
    """One FL round: vectorized channel sampling + the batched solve (5)."""
    return optimize_clients_batched(net, sysb, sample_channels(rng, sysb),
                                    n_params, backend=backend)
