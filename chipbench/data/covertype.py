"""A synthetic stand-in for the 10 continuous terrain columns of UCI
Covertype, J = 10 response columns: three elevation regimes, skewed
distances, bounded hillshade."""
from __future__ import annotations

import numpy as np


def generate(rng: np.random.Generator, n: int) -> np.ndarray:
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
