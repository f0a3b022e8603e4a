"""The per-round resource decisions (arXiv:2408.05886, Section II-C and
Appendix B) in plain NumPy float64, every client a lane.

Each client chooses its local SGD steps kappa, CPU frequency f and transmit
power p under a deadline and an energy budget, by the paper's alternating
solve: Lemma 1 for kappa (eq. 42), Lemma 2 for f (eq. 48), and successive
convex approximation for p (eqs. 50-52), whose linearised problem is solved
at an end of its feasible interval. Five initial powers are tried, and the
largest feasible kappa wins. A client with no feasible point is a straggler
(kappa = 0).
"""
from __future__ import annotations

import numpy as np

FPP = 32            # bits per uploaded parameter
J_SLACK = 1e-7      # keeps floor(J2) on the exact-arithmetic side
P_SLACK = 1e-9      # the same for the minimum-power check
LN2 = np.log(2.0)


def noise_power(net: dict) -> float:
    return 10 ** ((net["noise_psd_dbm"] + net["noise_figure_db"] - 30) / 10) \
        * net["omega"]


def pathloss_linear(distance_m) -> np.ndarray:
    """PL(dB) = 128.1 + 37.6 log10(d / 1 km), as a linear gain."""
    d = np.maximum(np.asarray(distance_m, np.float64), 1.0) / 1000.0
    return 10 ** (-(128.1 + 37.6 * np.log10(d)) / 10)


def solve_kappa(net: dict, sysc: dict, gamma, n_params: int) -> np.ndarray:
    """(U,) int kappa for one round's shadowing ``gamma`` (linear, (U,))."""
    c, s = sysc["c"], sysc["s"]
    f_max, p_max, e_bd = sysc["f_max"], sysc["p_max"], sysc["e_bd"]
    xi = pathloss_linear(sysc["distance"])
    g = xi * np.asarray(gamma, np.float64) / noise_power(net)
    cc = net["n"] * net["nbar"] * c * s
    nb = n_params * (FPP + 1)
    omega, t_th, v = net["omega"], net["t_th"], net["v"]
    kmax = net["kappa_max"]

    def rate(p):
        return omega * np.log2(1.0 + g * p)

    def t_up(p):
        return nb / np.maximum(rate(p), 1e-12)

    def e_up(p):
        return t_up(p) * p

    def opt_kappa(f, p):
        j1 = (e_bd - e_up(p)) / (0.5 * v * cc * f ** 2)
        j2 = f * (t_th - t_up(p)) / cc
        return np.maximum(np.minimum(float(kmax),
                                     np.floor(np.minimum(j1, j2) + J_SLACK)),
                          0.0)

    def opt_freq(kappa, p):
        r = rate(p)
        denom = t_th * r - nb
        with np.errstate(divide="ignore", invalid="ignore"):
            return np.where(denom > 0, cc * kappa * r / denom, np.inf)

    def sca_power(kappa, f, p0, alive):
        e_cp = 0.5 * v * cc * kappa * f ** 2
        t_cp = cc * kappa / f
        t_left = t_th - t_cp
        ok = alive & (t_left > 0)
        with np.errstate(over="ignore", divide="ignore", invalid="ignore"):
            snr_min = 2.0 ** (nb / (omega * np.where(ok, t_left, 1.0))) - 1.0
        p_lo = snr_min / g
        ok &= p_lo <= p_max * (1 + P_SLACK)
        p_lo = np.where(ok, np.minimum(p_lo, p_max), 1e-6)
        p = np.maximum(np.maximum(np.minimum(p0, p_max), p_lo), 1e-6)
        run = ok.copy()
        for _ in range(net["sca_iters"]):
            ln = np.log1p(g * p)
            obj_slope = (omega / LN2) * (g / (p * (1 + g * p)) - ln / p ** 2)
            e_at = nb * LN2 / omega * (p / ln)
            e_slope = nb * LN2 / omega * (1 / ln - g * p
                                          / (ln ** 2 * (1 + g * p)))
            with np.errstate(divide="ignore", invalid="ignore"):
                p_hi = np.where(e_slope > 0, np.minimum(
                    p_max, p + (e_bd - e_cp - e_at) / e_slope), p_max)
            bad = run & (p_hi < p_lo - 1e-12)
            ok &= ~bad
            run &= ~bad
            p_new = np.clip(np.where(obj_slope >= 0, p_hi, p_lo), p_lo, p_max)
            conv = np.abs(p_new - p) < net["tol"]
            p = np.where(run, np.where(conv, p_new, 0.5 * (p + p_new)), p)
            run &= ~conv
        ok &= (e_up(p) + e_cp <= e_bd * (1 + 1e-6)) \
            & (t_cp + t_up(p) <= t_th * (1 + 1e-6))
        return p, ok

    ks = np.arange(1.0, kmax + 1)[:, None]

    def from_point(p0):
        f, p = f_max.copy(), p0.copy()
        alive = np.ones(p.shape, bool)
        best_k = np.zeros(p.shape)
        feas = np.zeros(p.shape, bool)
        for _ in range(net["outer_iters"]):
            kappa = opt_kappa(f, p)
            alive &= kappa >= 1
            f_new = opt_freq(kappa, p)
            good = np.isfinite(f_new) & (f_new <= f_max)
            # deadline missed at kappa: the largest smaller kappa that fits
            f_all = opt_freq(ks, p[None, :])
            fits = np.isfinite(f_all) & (f_all <= f_max[None, :]) \
                & (ks <= kappa[None, :] - 1)
            k2 = np.max(np.where(fits, ks, 0.0), axis=0)
            f_k2 = np.sum(np.where(ks == k2[None, :], f_all, 0.0), axis=0)
            kappa = np.where(good, kappa, k2)
            f_new = np.where(good, f_new, f_k2)
            alive &= good | (k2 >= 1)
            f = np.where(alive, np.clip(f_new, 1e6, f_max), f)
            p_sca, sca_ok = sca_power(kappa, f, p, alive)
            alive &= sca_ok
            p = np.where(alive, p_sca, p)
            t_tot = cc * kappa / f + t_up(p)
            e_tot = 0.5 * v * cc * kappa * f ** 2 + e_up(p)
            okc = alive & (t_tot <= t_th * (1 + 1e-6)) \
                & (e_tot <= e_bd * (1 + 1e-6))
            best_k = np.where(okc, kappa, best_k)
            feas |= okc
        return best_k, feas

    kappa = np.zeros(c.shape)
    feas = np.zeros(c.shape, bool)
    for frac in (1.0, 0.1, 0.01, 1e-3, 1e-4):
        k, ok = from_point(p_max * frac)
        better = ok & (~feas | (k > kappa))
        kappa = np.where(better, k, kappa)
        feas |= ok
    return kappa.astype(np.int32)
