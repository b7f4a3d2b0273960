"""Plain float64 NumPy reference of what the timed calls produce.

Independent of the program: it imports nothing from ``src/`` and takes none
of the program's scales, Grams, directions or weights. It starts from the
float32 rows the program was handed and from the keys the harness drew.
Key-derived plans (hull directions, CountSketch rows and signs, the
sampler's uniforms) are drawn here again with ``jax.random`` from the same
keys. They are the benchmark's inputs, not the program's outputs.

Row work runs in blocks on a thread pool, so it stays inside host memory
and takes a few seconds at the benchmark's sizes.
"""
from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor
from math import comb

import numpy as np

BLOCK = 1 << 18
RCOND = 1e-6


def _threads() -> int:
    return max(1, min(12, (os.cpu_count() or 2) - 1))


def blocks(n: int, block: int = BLOCK):
    return [(lo, min(lo + block, n)) for lo in range(0, n, block)]


def pmap_blocks(fn, n: int, block: int = BLOCK):
    """``fn(lo, hi)`` over row blocks on a thread pool, results in order."""
    with ThreadPoolExecutor(_threads()) as ex:
        return list(ex.map(lambda b: fn(*b), blocks(n, block)))


# ---------------------------------------------------------------------------
# basis (the paper's Bernstein design and its derivative)
# ---------------------------------------------------------------------------


def _binom(m: int) -> np.ndarray:
    return np.array([comb(m, k) for k in range(m + 1)], np.float64)


def bernstein64(t: np.ndarray, m: int) -> np.ndarray:
    """The degree-m Bernstein basis C(m,k)·t^k·(1−t)^(m−k), k = 0..m, in
    float64; powers by repeated products, which is exact enough and several
    times quicker than ``**``."""
    t = np.clip(t, 0.0, 1.0)
    up = np.empty(t.shape + (m + 1,))
    down = np.empty_like(up)
    up[..., 0] = down[..., m] = 1.0
    for k in range(1, m + 1):
        up[..., k] = up[..., k - 1] * t
        down[..., m - k] = down[..., m - k + 1] * (1.0 - t)
    return _binom(m) * up * down


def _unit(Y, low, high) -> np.ndarray:
    low = np.asarray(low, np.float64)
    high = np.asarray(high, np.float64)
    return (np.asarray(Y, np.float64) - low) / (high - low)


def host_features(Y, low, high, degree: int) -> tuple[np.ndarray, np.ndarray]:
    """(A, A′) of shape (n, J, d) in float64 from the float32 rows; A′ is
    the derivative in y, so it carries the 1/(high − low) of each column."""
    T = _unit(Y, low, high)
    A = bernstein64(T, degree)
    lower = bernstein64(T, degree - 1)
    pad = np.zeros(lower.shape[:-1] + (1,))
    dA = degree * (np.concatenate([pad, lower], -1) - np.concatenate([lower, pad], -1))
    span = np.asarray(high, np.float64) - np.asarray(low, np.float64)
    return A, dA / span[:, None]


def design(Y, low, high, degree: int) -> np.ndarray:
    """The flattened basis rows X = [a(y_1), …, a(y_J)] (n, J·d)."""
    A = bernstein64(_unit(Y, low, high), degree)
    return A.reshape(A.shape[0], -1)


# ---------------------------------------------------------------------------
# leverage: exact, and sketched from a given CountSketch plan
# ---------------------------------------------------------------------------


def factor(G: np.ndarray, rcond: float = RCOND) -> tuple[np.ndarray, np.ndarray]:
    """eigh pseudo-inverse of a Gram with the relative eigenvalue cutoff."""
    w, V = np.linalg.eigh(np.asarray(G, np.float64))
    inv = np.where(w > rcond * np.abs(w).max(), 1.0 / np.maximum(w, 1e-300), 0.0)
    return V, inv


class Design:
    """The basis rows X of the data in float64, made once in row blocks and
    kept (n·J·d·8 bytes: 1.9 GB at 2^24 rows of J=2, 2.3 GB at 2^22 rows
    of J=10), so the Gram, a sketch and the leverage read it without
    evaluating the basis again."""

    def __init__(self, Y, low, high, degree: int):
        self.n = Y.shape[0]
        self.parts = pmap_blocks(lambda lo, hi: design(Y[lo:hi], low, high, degree), self.n)
        self.starts = [lo for lo, _ in blocks(self.n)]

    def _map(self, fn):
        with ThreadPoolExecutor(_threads()) as ex:
            return list(ex.map(fn, self.starts, self.parts))

    def gram(self) -> np.ndarray:
        return np.sum(self._map(lambda lo, X: X.T @ X), axis=0)

    def sketch(self, rows, signs, sketch_size: int) -> np.ndarray:
        """SX = S·X for the CountSketch S with one ±1 per row at ``rows``."""
        rows = np.asarray(rows)
        signs = np.asarray(signs, np.float64)

        def part(lo, X):
            r, sg = rows[lo:lo + X.shape[0]], signs[lo:lo + X.shape[0], None]
            return np.stack([np.bincount(r, (X * sg)[:, c], minlength=sketch_size)
                             for c in range(X.shape[1])], axis=1)

        return np.sum(self._map(part), axis=0)

    def leverage(self, V, inv) -> np.ndarray:
        """u_i = Σ_m (X_i V)²_m · inv_m."""
        return np.concatenate(self._map(lambda lo, X: np.sum((X @ V) ** 2 * inv, axis=1)))


# ---------------------------------------------------------------------------
# hull: directional extremes of the derivative rows, exactly
# ---------------------------------------------------------------------------


def _deriv_polys(degree: int) -> list[np.polynomial.Polynomial]:
    """dA_k(t) for k = 0..degree in the power basis of t."""
    P = np.polynomial.Polynomial
    t = P([0.0, 1.0])
    lower = [comb(degree - 1, k) * t**k * (1 - t) ** (degree - 1 - k)
             for k in range(degree)]
    zero = P([0.0])
    return [degree * ((lower[k - 1] if k > 0 else zero) - (lower[k] if k < degree else zero))
            for k in range(degree + 1)]


class HullReference:
    """Exact max and min of ⟨p, v⟩ over every derivative row p of the data.

    Each row of column j is a′(t)/(high_j − low_j), a polynomial of degree
    d − 2 in t. Its projection on v is then a polynomial f_j(t), and on
    the sorted t of column j the largest value sits at the ends or next to
    a root of f_j′. So after one sort per column, each direction costs a
    root solve and a few evaluations, not a pass over the data.
    """

    def __init__(self, Y, low, high, degree: int):
        low = np.asarray(low, np.float64)
        high = np.asarray(high, np.float64)
        self.low, self.high, self.degree = low, high, degree
        T = (np.asarray(Y, np.float64) - low) / (high - low)
        with ThreadPoolExecutor(_threads()) as ex:
            self.sorted_t = list(ex.map(np.sort, T.T))
        self.polys = _deriv_polys(degree)

    def _f(self, v, j):
        coef = sum(v[k] * self.polys[k] for k in range(self.degree + 1))
        return coef / (self.high[j] - self.low[j])

    def extremes(self, v) -> tuple[float, float]:
        """(max, min) of ⟨p, v⟩ over all derivative rows p."""
        best_max, best_min = -np.inf, np.inf
        for j, ts in enumerate(self.sorted_t):
            f = self._f(v, j)
            crit = [r.real for r in f.deriv().roots() if abs(r.imag) < 1e-9]
            pos = [0, ts.size - 1]
            for c in crit:
                i = int(np.searchsorted(ts, c))
                pos += list(range(max(i - 3, 0), min(i + 3, ts.size)))
            vals = f(ts[np.unique(pos)])
            best_max = max(best_max, float(vals.max()))
            best_min = min(best_min, float(vals.min()))
        return best_max, best_min

    def values(self, Y_points, v) -> np.ndarray:
        """⟨p, v⟩ for every derivative row of the given points."""
        _, dA = host_features(Y_points, self.low, self.high, self.degree)
        return dA @ np.asarray(v, np.float64)


def hull_gap(ref: HullReference, dirs: np.ndarray, Y_hull: np.ndarray) -> float:
    """Largest share of a direction's spread by which the best row of the
    returned hull points falls short of the data's own extreme, over
    ``dirs`` (directions whose extreme the hull must hold)."""
    worst = 0.0
    for v in np.asarray(dirs, np.float64):
        hi, lo = ref.extremes(v)
        got = float(ref.values(Y_hull, v).max())
        worst = max(worst, (hi - got) / max(hi - lo, 1e-300))
    return worst


# ---------------------------------------------------------------------------
# the MCTM objective: weighted negative log-likelihood, value and gradient
# ---------------------------------------------------------------------------

LOG_2PI = float(np.log(2.0 * np.pi))


def monotone_theta(theta_raw: np.ndarray, min_slope: float) -> np.ndarray:
    """Strictly increasing coefficients from unconstrained ones: θ_0 = raw_0,
    θ_k = θ_(k−1) + softplus(raw_k) + min_slope."""
    raw = np.asarray(theta_raw, np.float64)
    steps = np.logaddexp(0.0, raw[:, 1:]) + min_slope
    return np.concatenate([raw[:, :1], raw[:, :1] + np.cumsum(steps, axis=1)], axis=1)


class MCTMObjective:
    """Σ_i w_i·nll_i(ϑ, λ) of an MCTM on weighted rows, in float64.

    nll_i = Σ_j ½·z_ij² − log max(h̃′_ij, η) + ½·log 2π, with h̃_ij = a(y_ij)ᵀϑ_j,
    h̃′_ij = a′(y_ij)ᵀϑ_j, ϑ = monotone(raw) and z_i = Λ·h̃_i for the unit
    lower-triangular Λ whose strict lower part holds λ in row-major order.
    The gradient is written out, so nothing here is differentiated by a tool.
    """

    def __init__(self, Y, w, low, high, degree: int, eta: float, min_slope: float):
        self.A, self.dA = host_features(Y, low, high, degree)
        self.w = np.asarray(w, np.float64)
        self.J, self.d = self.A.shape[1], self.A.shape[2]
        self.eta, self.min_slope = float(eta), float(min_slope)
        self.rows, self.cols = np.tril_indices(self.J, k=-1)

    def unpack(self, x: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
        n_theta = self.J * self.d
        return x[:n_theta].reshape(self.J, self.d), x[n_theta:]

    def value_and_grad(self, theta_raw, lam) -> tuple[float, np.ndarray, np.ndarray]:
        """(total, ∂/∂raw, ∂/∂λ) of the weighted sum."""
        raw = np.asarray(theta_raw, np.float64)
        theta = monotone_theta(raw, self.min_slope)
        h = np.einsum("njd,jd->nj", self.A, theta)
        hp = np.einsum("njd,jd->nj", self.dA, theta)
        L = np.eye(self.J)
        L[self.rows, self.cols] = lam
        z = h @ L.T
        live = hp > self.eta
        terms = np.sum(0.5 * z * z - np.log(np.maximum(hp, self.eta)) + 0.5 * LOG_2PI, axis=1)
        gz = self.w[:, None] * z
        g_hp = np.where(live, -self.w[:, None] / np.where(live, hp, 1.0), 0.0)
        g_theta = (np.einsum("nj,njd->jd", gz @ L, self.A)
                   + np.einsum("nj,njd->jd", g_hp, self.dA))
        # θ_k depends on raw_0 and on raw_m for 1 ≤ m ≤ k, the latter through
        # softplus, whose derivative is the logistic function
        g_raw = np.cumsum(g_theta[:, ::-1], axis=1)[:, ::-1]
        g_raw[:, 1:] *= np.exp(-np.logaddexp(0.0, -raw[:, 1:]))
        g_lam = (gz.T @ h)[self.rows, self.cols]
        return float(self.w @ terms), g_raw, g_lam

    def value(self, theta_raw, lam) -> float:
        return self.value_and_grad(theta_raw, lam)[0]


def polish(obj: MCTMObjective, theta_raw, lam, maxiter: int = 1000):
    """Minimise the objective in float64 with scipy's L-BFGS-B from the given
    parameters. The objective is normalised as the program's L-BFGS fit
    normalises it, by the total weight Σw, so the tolerances read on the
    same scale. Returns (raw, λ, total at the end)."""
    from scipy.optimize import minimize

    norm = float(obj.w.sum())

    def fun(x):
        v, g_raw, g_lam = obj.value_and_grad(*obj.unpack(x))
        return v / norm, np.concatenate([g_raw.ravel(), g_lam]) / norm

    x0 = np.concatenate([np.ravel(np.asarray(theta_raw, np.float64)),
                         np.asarray(lam, np.float64)])
    res = minimize(fun, x0, jac=True, method="L-BFGS-B",
                   options={"maxiter": maxiter, "maxcor": 20, "ftol": 0.0, "gtol": 1e-12})
    raw, lam = obj.unpack(res.x)
    return raw, lam, obj.value(raw, lam)
