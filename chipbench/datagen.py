"""The benchmark's own copies of the data generators, one file each.

Copied so that a change to the program cannot move the yardstick. A
configuration names its generator under ``"dgp"``; the generator is
``chipbench/data/<dgp>.py``, whose ``generate(rng, n)`` returns the
configuration's columns, responses first, then any covariates the
configuration declares.
"""
from __future__ import annotations

import os

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


def generate(name: str, n: int, seed: int, data_seed: int = 0, *,
             bench_dir: str = HERE) -> np.ndarray:
    """(n, columns) float32 rows, as a user holds them: one data set drawn
    from ``data_seed`` by ``<bench_dir>/data/<name>.py``, in the row order
    ``seed`` draws.

    Every seed gets the same rows in another order, so every seed gives the
    program the same work: the same scaler, hence the same compiled
    programs (the program bakes the scaler into them), the same Gram and
    the same hull, up to the order of the sums."""
    from chipbench.run import load_module

    gen = load_module(os.path.join(bench_dir, "data", name + ".py"), "chipbench_data_" + name)
    rows = gen.generate(np.random.default_rng(data_seed), n)
    return rows[np.random.default_rng(seed).permutation(n)].astype(np.float32)


def scaler_bounds(Y: np.ndarray, margin: float = 0.05) -> tuple[np.ndarray, np.ndarray]:
    """The basis interval per dimension: the data range widened by
    ``margin`` of its span on each side (the paper's fit-once scaler)."""
    lo, hi = Y.min(axis=0), Y.max(axis=0)
    span = np.maximum(hi - lo, 1e-9)
    return lo - margin * span, hi + margin * span
