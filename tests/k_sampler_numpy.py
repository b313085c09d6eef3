"""Float64 numpy transcriptions of the k-samplers: the reference the eager
loop is held to (tests/test_k_samplers.py).

Each function is k-diffusion's sampler written out step by step, scalars and
latents alike in float64, independent of ``sampling/lane_specs.py`` (whose
plans drive the program's loop). ``denoise(x, sigma) -> x0``; ``noise(i, col)``
is step ``i``'s draw under the program's key discipline (``fold_in(rng, i)``;
``col`` picks dpmpp_sde's ``split`` halves), computed by the caller with jax
so both sides add the same numbers.
"""

from __future__ import annotations

import numpy as np


def _ancestral(s, s_next, eta=1.0):
    up = min(s_next, eta * np.sqrt(max(s_next**2 * (s**2 - s_next**2) / s**2, 0.0)))
    return np.sqrt(max(s_next**2 - up**2, 0.0)), up


def euler(denoise, x, sig, noise=None):
    for i in range(len(sig) - 1):
        x0 = denoise(x, sig[i])
        x = x + (x - x0) / sig[i] * (sig[i + 1] - sig[i])
    return x


def euler_ancestral(denoise, x, sig, noise, eta=1.0):
    for i in range(len(sig) - 1):
        s, sn = sig[i], sig[i + 1]
        x0 = denoise(x, s)
        sd, su = _ancestral(s, sn, eta)
        x = x + (x - x0) / s * (sd - s)
        if sn > 0:
            x = x + su * noise(i, 0)
    return x


def euler_ancestral_rf(denoise, x, sig, noise, eta=1.0):
    for i in range(len(sig) - 1):
        s, sn = sig[i], sig[i + 1]
        x0 = denoise(x, s)
        if sn == 0.0:
            x = x0
            continue
        sd = sn * (1.0 + (sn / s - 1.0) * eta)
        a1, ad = 1.0 - sn, 1.0 - sd
        renoise = np.sqrt(max(sn**2 - sd**2 * a1**2 / ad**2, 0.0))
        x = (sd / s) * x + (1.0 - sd / s) * x0
        x = (a1 / ad) * x + renoise * noise(i, 0)
    return x


def heun(denoise, x, sig, noise=None):
    for i in range(len(sig) - 1):
        s, sn = sig[i], sig[i + 1]
        x0 = denoise(x, s)
        d = (x - x0) / s
        x_pred = x + d * (sn - s)
        if sn == 0.0:
            x = x_pred
        else:
            d2 = (x_pred - denoise(x_pred, sn)) / sn
            x = x + 0.5 * (d + d2) * (sn - s)
    return x


def dpm_2(denoise, x, sig, noise=None):
    for i in range(len(sig) - 1):
        s, sn = sig[i], sig[i + 1]
        d = (x - denoise(x, s)) / s
        if sn == 0.0:
            x = x + d * (sn - s)
        else:
            mid = np.exp(0.5 * (np.log(s) + np.log(sn)))
            x_2 = x + d * (mid - s)
            x = x + (x_2 - denoise(x_2, mid)) / mid * (sn - s)
    return x


def dpm_2_ancestral(denoise, x, sig, noise, eta=1.0):
    for i in range(len(sig) - 1):
        s, sn = sig[i], sig[i + 1]
        d = (x - denoise(x, s)) / s
        sd, su = _ancestral(s, sn, eta)
        if sd == 0.0:
            x = x + d * (sd - s)
        else:
            mid = np.exp(0.5 * (np.log(s) + np.log(sd)))
            x_2 = x + d * (mid - s)
            x = x + (x_2 - denoise(x_2, mid)) / mid * (sd - s)
        if sn > 0:
            x = x + su * noise(i, 0)
    return x


def dpmpp_2s_ancestral(denoise, x, sig, noise, eta=1.0):
    for i in range(len(sig) - 1):
        s, sn = sig[i], sig[i + 1]
        x0 = denoise(x, s)
        sd, su = _ancestral(s, sn, eta)
        if sd == 0.0:
            x = x + (x - x0) / s * (sd - s)
        else:
            t, tn = -np.log(s), -np.log(sd)
            h = tn - t
            mid = np.exp(-(t + 0.5 * h))
            x_2 = (mid / s) * x - np.expm1(-0.5 * h) * x0
            x = (sd / s) * x - np.expm1(-h) * denoise(x_2, mid)
        if sn > 0:
            x = x + su * noise(i, 0)
    return x


def dpmpp_2s_ancestral_rf(denoise, x, sig, noise, eta=1.0):
    for i in range(len(sig) - 1):
        s, sn = sig[i], sig[i + 1]
        x0 = denoise(x, s)
        sd = sn * (1.0 + (sn / s - 1.0) * eta)
        if sn == 0.0:
            x = x + (x - x0) / s * (sd - s)
            continue
        a1, ad = 1.0 - sn, 1.0 - sd
        renoise = np.sqrt(max(sn**2 - sd**2 * a1**2 / ad**2, 0.0))
        if s >= 1.0:
            mid = 0.9999
        else:
            t_i, t_dn = np.log((1.0 - s) / s), np.log((1.0 - sd) / sd)
            mid = 1.0 / (np.exp(t_i + 0.5 * (t_dn - t_i)) + 1.0)
        u = (mid / s) * x + (1.0 - mid / s) * x0
        x = (sd / s) * x + (1.0 - sd / s) * denoise(u, mid)
        x = (a1 / ad) * x + renoise * noise(i, 0)
    return x


def dpmpp_sde(denoise, x, sig, noise, eta=1.0, r=0.5):
    for i in range(len(sig) - 1):
        s, sn = sig[i], sig[i + 1]
        x0 = denoise(x, s)
        if sn == 0.0:
            x = x + (x - x0) / s * (sn - s)
            continue
        t, tn = -np.log(s), -np.log(sn)
        h = tn - t
        mid = np.exp(-(t + r * h))
        fac = 1.0 / (2.0 * r)
        sd1, su1 = _ancestral(s, mid, eta)
        x_2 = (sd1 / s) * x - np.expm1(t + np.log(max(sd1, 1e-10))) * x0
        x_2 = x_2 + su1 * noise(i, 0)
        x0_2 = denoise(x_2, mid)
        sd2, su2 = _ancestral(s, sn, eta)
        blend = (1.0 - fac) * x0 + fac * x0_2
        x = (sd2 / s) * x - np.expm1(t + np.log(max(sd2, 1e-10))) * blend
        x = x + su2 * noise(i, 1)
    return x


def dpmpp_2m(denoise, x, sig, noise=None):
    old = None
    for i in range(len(sig) - 1):
        s, sn = sig[i], sig[i + 1]
        x0 = denoise(x, s)
        t, tn = -np.log(s), -np.log(max(sn, 1e-10))
        h = tn - t
        if old is None or sn == 0.0:
            x = (sn / s) * x - np.expm1(-h) * x0
        else:
            r = (t + np.log(sig[i - 1])) / h
            x = (sn / s) * x - np.expm1(-h) * (
                (1 + 1 / (2 * r)) * x0 - (1 / (2 * r)) * old)
        old = x0
    return x


def dpmpp_2m_sde(denoise, x, sig, noise, eta=1.0):
    old = h_last = None
    for i in range(len(sig) - 1):
        s, sn = sig[i], sig[i + 1]
        x0 = denoise(x, s)
        if sn == 0.0:
            x = x0
        else:
            h = np.log(s) - np.log(sn)
            eta_h = eta * h
            x = (sn / s) * np.exp(-eta_h) * x - np.expm1(-h - eta_h) * x0
            if old is not None:
                x = x - 0.5 * np.expm1(-h - eta_h) * (h / h_last) * (x0 - old)
            if eta > 0:
                x = x + sn * np.sqrt(max(-np.expm1(-2 * eta_h), 0.0)) * noise(i, 0)
            h_last = h
        old = x0
    return x


def dpmpp_3m_sde(denoise, x, sig, noise, eta=1.0):
    x0_1 = x0_2 = h_1 = h_2 = None
    for i in range(len(sig) - 1):
        s, sn = sig[i], sig[i + 1]
        x0 = denoise(x, s)
        if sn == 0.0:
            x = x0  # no history update on a zero step
            continue
        h = np.log(s) - np.log(sn)
        h_eta = h * (eta + 1.0)
        x = np.exp(-h_eta) * x - np.expm1(-h_eta) * x0
        if h_2 is not None:
            r0, r1 = h_1 / h, h_2 / h
            d1_0, d1_1 = (x0 - x0_1) / r0, (x0_1 - x0_2) / r1
            d1 = d1_0 + (d1_0 - d1_1) * r0 / (r0 + r1)
            d2 = (d1_0 - d1_1) / (r0 + r1)
            phi_2 = np.expm1(-h_eta) / h_eta + 1.0
            x = x + phi_2 * d1 - (phi_2 / h_eta - 0.5) * d2
        elif h_1 is not None:
            x = x + (np.expm1(-h_eta) / h_eta + 1.0) * (x0 - x0_1) / (h_1 / h)
        if eta > 0:
            x = x + sn * np.sqrt(max(-np.expm1(-2.0 * eta * h), 0.0)) * noise(i, 0)
        x0_1, x0_2 = x0, x0_1
        h_1, h_2 = h, h_1
    return x


def lcm(denoise, x, sig, noise):
    for i in range(len(sig) - 1):
        x = denoise(x, sig[i])
        if sig[i + 1] > 0:
            x = x + sig[i + 1] * noise(i, 0)
    return x


def lcm_rf(denoise, x, sig, noise):
    for i in range(len(sig) - 1):
        x = denoise(x, sig[i])
        if sig[i + 1] > 0:
            x = sig[i + 1] * noise(i, 0) + (1.0 - sig[i + 1]) * x
    return x


def ddpm(denoise, x, sig, noise):
    for i in range(len(sig) - 1):
        s, sn = sig[i], sig[i + 1]
        eps = (x - denoise(x, s)) / s
        acp, acp_prev = 1.0 / (s**2 + 1.0), 1.0 / (sn**2 + 1.0)
        alpha = acp / acp_prev
        mu = np.sqrt(1.0 / alpha) * (
            x / np.sqrt(1.0 + s**2) - (1.0 - alpha) * eps / np.sqrt(1.0 - acp))
        if sn > 0:
            var = (1.0 - alpha) * (1.0 - acp_prev) / (1.0 - acp)
            x = (mu + np.sqrt(var) * noise(i, 0)) * np.sqrt(1.0 + sn**2)
        else:
            x = mu
    return x


def lms(denoise, x, sig, noise=None, order=4):
    from numpy.polynomial.legendre import leggauss

    nodes, weights = leggauss(16)

    def coeff(cur, i, j):
        a, b = sig[i], sig[i + 1]
        tau = 0.5 * (b - a) * nodes + 0.5 * (b + a)
        prod = np.ones_like(tau)
        for k in range(cur):
            if k != j:
                prod *= (tau - sig[i - k]) / (sig[i - j] - sig[i - k])
        return 0.5 * (b - a) * np.sum(weights * prod)

    ds = []
    for i in range(len(sig) - 1):
        ds.append((x - denoise(x, sig[i])) / sig[i])
        ds = ds[-order:]
        cur = min(i + 1, order)
        x = x + sum(coeff(cur, i, j) * d for j, d in zip(range(cur), reversed(ds)))
    return x


def _uni_pc(denoise, x, sig, variant, order=3):
    """UniPC in sigma space (λ = −log σ): the official multistep
    predictor-corrector with the order-2 predictor weight fixed at 0.5 and
    the order ramped down at both ends."""
    lam = -np.log(np.maximum(sig, 1e-10))
    n = len(sig) - 1
    hist = [denoise(x, sig[0])]
    for i in range(n):
        m0 = hist[-1]
        if sig[i + 1] == 0.0:
            x = m0
            continue
        p = max(1, min(order, i + 1, n - i))
        hh = lam[i] - lam[i + 1]
        h_phi_1 = np.expm1(hh)
        B_h = hh if variant == "bh1" else np.expm1(hh)
        rks = [(lam[i - j] - lam[i]) / (lam[i + 1] - lam[i]) for j in range(1, p)]
        D1s = [(hist[-1 - j] - m0) / rk for j, rk in zip(range(1, p), rks)]
        rks.append(1.0)
        R = np.array([[rk**k for rk in rks] for k in range(p)])
        b, fact, h_phi_k = np.zeros(p), 1.0, h_phi_1 / hh - 1.0
        for k in range(1, p + 1):
            b[k - 1] = h_phi_k * fact / B_h
            fact *= k + 1
            h_phi_k = h_phi_k / hh - 1.0 / fact
        if p == 1:
            rhos_p = []
        elif p == 2:
            rhos_p = [0.5]
        else:
            rhos_p = np.linalg.solve(R[:-1, :-1], b[:-1])
        rhos_c = np.linalg.solve(R, b) if p > 1 else np.array([0.5])
        base = (sig[i + 1] / sig[i]) * x - h_phi_1 * m0
        x_pred = base - B_h * sum(r * d for r, d in zip(rhos_p, D1s))
        m_t = denoise(x_pred, sig[i + 1])
        x = base - B_h * (sum(r * d for r, d in zip(rhos_c[:-1], D1s))
                          + rhos_c[-1] * (m_t - m0))
        hist = (hist + [m_t])[-order:]
    return x


def uni_pc(denoise, x, sig, noise=None):
    return _uni_pc(denoise, x, sig, "bh1")


def uni_pc_bh2(denoise, x, sig, noise=None):
    return _uni_pc(denoise, x, sig, "bh2")


REFERENCE = {
    "euler": euler, "euler_ancestral": euler_ancestral, "heun": heun,
    "dpm_2": dpm_2, "dpm_2_ancestral": dpm_2_ancestral, "lms": lms,
    "dpmpp_2s_ancestral": dpmpp_2s_ancestral, "dpmpp_sde": dpmpp_sde,
    "dpmpp_2m": dpmpp_2m, "dpmpp_2m_sde": dpmpp_2m_sde,
    "dpmpp_3m_sde": dpmpp_3m_sde, "lcm": lcm, "ddpm": ddpm,
    "uni_pc": uni_pc, "uni_pc_bh2": uni_pc_bh2,
}
# The rectified-flow forms the program swaps in under prediction="flow".
REFERENCE_FLOW = {
    "euler_ancestral": euler_ancestral_rf,
    "dpmpp_2s_ancestral": dpmpp_2s_ancestral_rf,
    "lcm": lcm_rf,
}
