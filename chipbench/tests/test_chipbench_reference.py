"""The float64 reference against brute force at small sizes."""
from __future__ import annotations

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
