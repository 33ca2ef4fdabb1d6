"""Plain per-point reference of the PDF computation, in float64 NumPy.

It restates the semantics of arXiv:1805.03141 §5-6 as the program defines
them, independently of the program's code (nothing of ``repro`` is
imported here): per point the moments (mean, unbiased variance, g1 skew,
excess kurtosis, min, max), the method-of-moments parameters of every
candidate type, the Eq.-5 error of each type over ``num_bins`` equal
intervals of [min, max], and, for the fit-all method, the type of least
error. Non-finite errors count as 1e30, as in the program.

``control_values`` rounds observations to bfloat16: the control that a
change storing or streaming the cube in half the bytes would be.
"""

from __future__ import annotations

import numpy as np
from scipy import special

EPS = 1e-12
BIG = 1e30
GAMMA_WH_K = 1e4  # above this shape, the Wilson-Hilferty normal approximation


def control_values(x: np.ndarray) -> np.ndarray:
    """float32 -> nearest bfloat16 (round half to even), back in float32."""
    u = np.ascontiguousarray(x, np.float32).view(np.uint32).astype(np.uint64)
    u = (u + 0x7FFF + ((u >> 16) & 1)) & 0xFFFF0000
    return u.astype(np.uint32).view(np.float32)


def moments(x: np.ndarray) -> dict[str, np.ndarray]:
    """(k, n) observations -> per-point moments, float64."""
    x = np.asarray(x, np.float64)
    n = x.shape[1]
    mean = x.mean(axis=1)
    d = x - mean[:, None]
    m2 = (d * d).mean(axis=1)
    m3 = (d ** 3).mean(axis=1)
    m4 = (d ** 4).mean(axis=1)
    var = m2 * n / max(n - 1, 1)
    sig = np.sqrt(np.maximum(m2, EPS))
    return {
        "mean": mean, "var": var, "std": np.sqrt(np.maximum(var, 0.0)),
        "skew": m3 / sig ** 3, "kurt": m4 / np.maximum(m2, EPS) ** 2 - 3.0,
        "vmin": x.min(axis=1), "vmax": x.max(axis=1),
    }


def _weibull_cv2(k):
    return np.exp(special.gammaln(1.0 + 2.0 / k) - 2.0 * special.gammaln(1.0 + 1.0 / k)) - 1.0


def fit(kind: str, m: dict) -> np.ndarray:
    """Method-of-moments parameters of one type: (k, 3), unused slots 0."""
    mean, var, std = m["mean"], m["var"], m["std"]
    zero = np.zeros_like(mean)
    if kind == "normal":
        cols = (mean, np.maximum(std, EPS))
    elif kind == "uniform":
        cols = (m["vmin"], np.maximum(m["vmax"], m["vmin"] + EPS))
    elif kind == "exponential":
        cols = (1.0 / np.maximum(mean, EPS),)
    elif kind == "lognormal":
        mp = np.maximum(mean, EPS)
        s2 = np.log1p(np.maximum(var, 0.0) / mp ** 2)
        cols = (np.log(mp) - 0.5 * s2, np.sqrt(np.maximum(s2, EPS)))
    elif kind == "cauchy":
        cols = (mean, np.maximum(0.5 * std, EPS))
    elif kind == "gamma":
        mp, vp = np.maximum(mean, EPS), np.maximum(var, EPS)
        cols = (np.maximum(mp ** 2 / vp, EPS), np.maximum(vp / mp, EPS))
    elif kind == "geometric":
        cols = (np.clip(1.0 / (1.0 + np.maximum(mean, 0.0)), EPS, 1.0),)
    elif kind == "logistic":
        cols = (mean, np.maximum(std * np.sqrt(3.0) / np.pi, EPS))
    elif kind == "student_t":
        nu = np.clip(4.0 + 6.0 / np.maximum(m["kurt"], EPS), 4.5, 50.0)
        scale = np.sqrt(np.maximum(var, EPS) * (nu - 2.0) / nu)
        cols = (mean, np.maximum(scale, EPS), nu)
    elif kind == "weibull":
        mp = np.maximum(mean, EPS)
        target = np.clip(np.maximum(var, EPS) / mp ** 2, 1e-6, 1e4)
        lo, hi = np.full_like(mp, 0.2), np.full_like(mp, 50.0)
        for _ in range(20):  # the same 20 halvings of (0.2, 50)
            mid = 0.5 * (lo + hi)
            smaller = _weibull_cv2(mid) < target
            hi = np.where(smaller, mid, hi)
            lo = np.where(smaller, lo, mid)
        k = 0.5 * (lo + hi)
        cols = (k, mp / np.exp(special.gammaln(1.0 + 1.0 / k)))
    else:
        raise ValueError(f"unknown distribution type {kind!r}")
    cols = cols + (zero,) * (3 - len(cols))
    return np.stack(cols, axis=-1)


def _phi(z):
    return 0.5 * (1.0 + special.erf(z / np.sqrt(2.0)))


def cdf(kind: str, p: np.ndarray, x: np.ndarray) -> np.ndarray:
    """params (k, 3), points (k, e) -> CDF (k, e)."""
    a, b, c = p[:, 0:1], p[:, 1:2], p[:, 2:3]
    with np.errstate(all="ignore"):
        if kind == "normal":
            return _phi((x - a) / b)
        if kind == "uniform":
            return np.clip((x - a) / (b - a), 0.0, 1.0)
        if kind == "exponential":
            return np.where(x <= 0, 0.0, 1.0 - np.exp(-a * np.maximum(x, 0.0)))
        if kind == "lognormal":
            return np.where(x <= 0, 0.0, _phi((np.log(np.maximum(x, EPS)) - a) / b))
        if kind == "cauchy":
            return 0.5 + np.arctan((x - a) / b) / np.pi
        if kind == "gamma":
            xs = np.maximum(x, 0.0) / b
            exact = special.gammainc(np.minimum(a, GAMMA_WH_K),
                                     np.minimum(xs, 2.0 * GAMMA_WH_K))
            kk = np.maximum(a, EPS)
            z = (np.cbrt(xs / kk) - (1.0 - 1.0 / (9.0 * kk))) * np.sqrt(9.0 * kk)
            return np.where(x <= 0, 0.0, np.where(a > GAMMA_WH_K, _phi(z), exact))
        if kind == "geometric":
            k = np.floor(np.maximum(x, 0.0))
            return np.where(x < 0, 0.0, 1.0 - np.exp(
                (k + 1.0) * np.log1p(-np.minimum(a, 1 - EPS))))
        if kind == "logistic":
            return special.expit((x - a) / b)
        if kind == "student_t":
            t = (x - a) / b
            ib = special.betainc(0.5 * c, 0.5, c / (c + t ** 2))
            return np.where(t >= 0, 1.0 - 0.5 * ib, 0.5 * ib)
        if kind == "weibull":
            z = np.maximum(x, 0.0) / b
            return np.where(x <= 0, 0.0, -np.expm1(-(z ** a)))
    raise ValueError(f"unknown distribution type {kind!r}")


def compute(x: np.ndarray, types, num_bins: int) -> dict[str, np.ndarray]:
    """(k, n) observations -> moments, (k, T, 3) params and (k, T) Eq.-5
    errors of every type, and the fit-all answer ``best``."""
    x = np.asarray(x, np.float64)
    m = moments(x)
    vmin, vmax = m["vmin"], m["vmax"]
    span = np.maximum(vmax - vmin, EPS)
    edges = vmin[:, None] + span[:, None] * np.arange(num_bins + 1) / num_bins
    idx = np.clip(np.floor((x - vmin[:, None]) / span[:, None] * num_bins),
                  0, num_bins - 1).astype(np.int64)
    rows = np.repeat(np.arange(x.shape[0]), x.shape[1])
    freq = np.zeros((x.shape[0], num_bins))
    np.add.at(freq, (rows, idx.ravel()), 1.0)
    rel = freq / max(x.shape[1], 1)
    params = np.stack([fit(t, m) for t in types], axis=1)  # (k, T, 3)
    errors = []
    for i, t in enumerate(types):
        f = cdf(t, params[:, i], edges)
        errors.append(np.abs(rel - (f[:, 1:] - f[:, :-1])).sum(axis=1))
    errors = np.stack(errors, axis=1)
    errors = np.where(np.isfinite(errors), errors, BIG)
    out = dict(m, params=params, errors=errors)
    out["best"] = np.argmin(errors, axis=1)
    return out
