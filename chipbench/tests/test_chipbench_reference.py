"""The float64 reference against brute force at small sizes."""
from __future__ import annotations

import hashlib
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import chipbench_testlib  # noqa: E402,F401

from chipbench import datagen  # noqa: E402
from chipbench import reference as R  # noqa: E402


@pytest.fixture(scope="module")
def data():
    Y = datagen.generate("covertype", 3001, seed=4)[:, :3]
    low, high = datagen.scaler_bounds(Y.astype(np.float64))
    return Y, low, high


def test_generators_are_seeded_and_shaped():
    a = datagen.generate("normal_mixture", 1001, seed=2**31 + 3)
    assert a.shape == (1001, 2) and a.dtype == np.float32
    assert np.array_equal(a, datagen.generate("normal_mixture", 1001, seed=2**31 + 3))
    assert datagen.generate("covertype", 11, seed=1).shape == (11, 10)
    # another seed: the same rows in another order
    b = datagen.generate("normal_mixture", 1001, seed=7)
    assert not np.array_equal(a, b)
    assert np.array_equal(np.sort(a, axis=0), np.sort(b, axis=0))
    assert not np.array_equal(a, datagen.generate("normal_mixture", 1001, seed=7, data_seed=1))


@pytest.mark.parametrize("name, digest", [
    ("normal_mixture", "5a27add4db49c15eeb7903d4666fc25ff08ce46439b8e0d1e8ce6695d32d5906"),
    ("covertype", "dce788016b2c4f354d8f08c5310867ef99724216d66e6c445df03c4cf1b29dc6"),
])
def test_generator_rows_are_pinned(name, digest):
    """The rows every cell's data comes from, to the byte: the digests were
    taken when the generators lived in ``datagen.py``, before they moved to
    ``chipbench/data/``."""
    rows = datagen.generate(name, 4097, 3)
    assert hashlib.sha256(rows.tobytes()).hexdigest() == digest


def test_blocked_leverage_matches_dense(data):
    Y, low, high = data
    X = R.design(Y, low, high, 6)
    V, inv = R.factor(X.T @ X)
    dense = np.sum((X @ V) ** 2 * inv, axis=1)
    blocked = R.Design(Y, low, high, 6)
    np.testing.assert_allclose(blocked.gram(), X.T @ X, rtol=1e-12)
    np.testing.assert_allclose(blocked.leverage(V, inv), dense, rtol=1e-10)
    # leverage sums to the rank
    assert dense.sum() == pytest.approx(np.sum(inv > 0), rel=1e-6)


def test_sketch_is_the_countsketch_product(data):
    Y, low, high = data
    rng = np.random.default_rng(0)
    rows = rng.integers(0, 50, Y.shape[0])
    signs = rng.choice([-1.0, 1.0], Y.shape[0])
    S = np.zeros((50, Y.shape[0]))
    S[rows, np.arange(Y.shape[0])] = signs
    X = R.design(Y, low, high, 6)
    np.testing.assert_allclose(R.Design(Y, low, high, 6).sketch(rows, signs, 50), S @ X,
                               atol=1e-12)


def test_hull_extremes_match_brute_force(data):
    Y, low, high = data
    ref = R.HullReference(Y, low, high, 6)
    _, dA = R.host_features(Y, low, high, 6)
    P = dA.reshape(-1, 7)
    for v in np.random.default_rng(1).normal(size=(20, 7)):
        hi, lo = ref.extremes(v)
        assert hi == pytest.approx((P @ v).max(), rel=1e-9, abs=1e-12)
        assert lo == pytest.approx((P @ v).min(), rel=1e-9, abs=1e-12)


def test_hull_gap_is_zero_for_the_true_extremes(data):
    Y, low, high = data
    ref = R.HullReference(Y, low, high, 6)
    _, dA = R.host_features(Y, low, high, 6)
    dirs = np.random.default_rng(2).normal(size=(8, 7))
    best = np.unique([np.argmax((dA @ v).max(axis=1)) for v in dirs])
    assert R.hull_gap(ref, dirs, Y[best]) == pytest.approx(0.0, abs=1e-12)
    assert R.hull_gap(ref, dirs, Y[:5]) > 1e-3


def test_fit_objective_matches_the_program_in_float64(data):
    """The written-out objective and gradient against the program's
    ``mctm.nll`` and autodiff, normalised as ``method_batch_plan`` normalises
    the L-BFGS fit, all in float64."""
    import jax
    import jax.numpy as jnp

    from repro.core import mctm as M
    from repro.core.bernstein import DataScaler
    from repro.core.mctm_fit import method_batch_plan

    Y, low, high = data
    Y = Y[:500]
    rng = np.random.default_rng(3)
    w = rng.uniform(0.5, 3.0, Y.shape[0]).astype(np.float32)
    raw, lam = rng.normal(size=(3, 6)), 0.3 * rng.normal(size=3)
    ref = R.MCTMObjective(Y, w, low, high, 5, 1e-3, 1e-4)
    v, g_raw, g_lam = ref.value_and_grad(raw, lam)
    with jax.enable_x64(True):
        cfg = M.MCTMConfig(J=3, degree=5, eta=1e-3, min_slope=1e-4)
        A, Ap = M.basis_features(cfg, DataScaler(low=low, high=high),
                                 jnp.asarray(Y, jnp.float64))
        norm = method_batch_plan("lbfgs", Y.shape[0], w, 16384, None)[-1]

        def objective(p):
            return M.nll(cfg, p, A, Ap, jnp.asarray(w, jnp.float64)) / norm

        pv, pg = jax.value_and_grad(objective)(M.MCTMParams(jnp.asarray(raw), jnp.asarray(lam)))
    assert norm == pytest.approx(float(w.astype(np.float64).sum()), rel=1e-6)
    assert float(pv) * norm == pytest.approx(v, rel=1e-12)
    np.testing.assert_allclose(np.asarray(pg.theta_raw) * norm, g_raw, rtol=1e-10, atol=1e-10)
    np.testing.assert_allclose(np.asarray(pg.lam) * norm, g_lam, rtol=1e-10, atol=1e-10)


def test_polish_goes_down_to_a_stationary_point(data):
    Y, low, high = data
    Y = Y[:500]
    w = np.random.default_rng(4).uniform(0.5, 3.0, Y.shape[0])
    ref = R.MCTMObjective(Y, w, low, high, 4, 1e-3, 1e-4)
    raw = np.tile(np.linspace(-2.0, 2.0, 5), (3, 1))
    lam = np.zeros(3)
    start = ref.value(raw, lam)
    raw2, lam2, end = R.polish(ref, raw, lam, maxiter=3000)
    assert end == ref.value(raw2, lam2) and end < start
    # a second polish from there finds next to nothing more
    assert (end - R.polish(ref, raw2, lam2)[2]) / abs(end) < 1e-7
