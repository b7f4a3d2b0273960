"""The paper's bivariate simulation (Section E.1.1): an equal mixture of two
correlated normals, J = 2 response columns."""
from __future__ import annotations

import numpy as np


def _mvn(rng, n, mean, cov):
    return rng.multivariate_normal(np.asarray(mean, float), np.asarray(cov, float), size=n)


def generate(rng: np.random.Generator, n: int) -> np.ndarray:
    z = rng.random(n) < 0.5
    a = _mvn(rng, n, [0, 0], [[1, 0.8], [0.8, 1]])
    b = _mvn(rng, n, [3, -2], [[1.5, -0.5], [-0.5, 1.5]])
    return np.where(z[:, None], a, b)
