"""The benchmark's own copies of the data generators.

Copied so that a change to the program cannot move the yardstick:
``normal_mixture`` is the paper's bivariate mixture (Section E.1.1), and
``covertype`` is the synthetic stand-in for the 10 continuous terrain
columns of UCI Covertype (three elevation regimes, skewed distances,
bounded hillshade). A configuration names its generator under ``"dgp"``.
"""
from __future__ import annotations

import numpy as np


def _mvn(rng, n, mean, cov):
    return rng.multivariate_normal(np.asarray(mean, float), np.asarray(cov, float), size=n)


def normal_mixture(rng: np.random.Generator, n: int) -> np.ndarray:
    z = rng.random(n) < 0.5
    a = _mvn(rng, n, [0, 0], [[1, 0.8], [0.8, 1]])
    b = _mvn(rng, n, [3, -2], [[1.5, -0.5], [-0.5, 1.5]])
    return np.where(z[:, None], a, b)


def covertype(rng: np.random.Generator, n: int) -> np.ndarray:
    regime = rng.choice(3, n, p=[0.45, 0.35, 0.2])
    elev_mu = np.array([2400.0, 2900.0, 3300.0])[regime]
    elevation = rng.normal(elev_mu, 180.0)
    aspect = rng.uniform(0, 360, n)
    slope = np.clip(rng.gamma(2.5, 5.0, n), 0, 60)
    hd_hydro = rng.gamma(1.5, 180.0, n) * (1 + 0.0004 * (elevation - 2400))
    vd_hydro = rng.normal(0.12 * hd_hydro, 30.0)
    hd_road = rng.gamma(2.0, 900.0, n)
    az = np.deg2rad(aspect)
    sl = np.deg2rad(slope)

    def shade(sun_az_deg, sun_alt_deg):
        sa, sh = np.deg2rad(sun_az_deg), np.deg2rad(sun_alt_deg)
        v = np.cos(sh) * np.cos(sl) + np.sin(sh) * np.sin(sl) * np.cos(sa - az)
        return np.clip(254 * np.clip(v, 0, 1) + rng.normal(0, 6, n), 0, 254)

    hs9, hs12, hs15 = shade(90, 45), shade(180, 60), shade(270, 45)
    hd_fire = rng.gamma(1.8, 700.0, n) * (1 + 0.3 * (regime == 2))
    return np.stack(
        [elevation, aspect, slope, hd_hydro, vd_hydro, hd_road, hs9, hs12, hs15, hd_fire],
        axis=1,
    )


GENERATORS = {"normal_mixture": normal_mixture, "covertype": covertype}


def generate(name: str, n: int, seed: int, data_seed: int = 0) -> np.ndarray:
    """(n, J) float32 rows, as a user holds them: one data set drawn from
    ``data_seed``, in the row order ``seed`` draws.

    Every seed gets the same rows in another order, so every seed gives the
    program the same work: the same scaler, hence the same compiled
    programs (the program bakes the scaler into them), the same Gram and
    the same hull, up to the order of the sums."""
    rows = GENERATORS[name](np.random.default_rng(data_seed), n)
    return rows[np.random.default_rng(seed).permutation(n)].astype(np.float32)


def scaler_bounds(Y: np.ndarray, margin: float = 0.05) -> tuple[np.ndarray, np.ndarray]:
    """The basis interval per dimension: the data range widened by
    ``margin`` of its span on each side (the paper's fit-once scaler)."""
    lo, hi = Y.min(axis=0), Y.max(axis=0)
    span = np.maximum(hi - lo, 1e-9)
    return lo - margin * span, hi + margin * span
