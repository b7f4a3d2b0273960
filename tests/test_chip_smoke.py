"""chip_smoke.py off the chip: it refuses to run, and its float64 host
reference computes the same quantities as the code it checks."""
import os
import shutil
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import mctm as M
from repro.core.bernstein import DataScaler
from repro.core.scoring import ScoringEngine

REPO = os.path.normpath(os.path.join(os.path.dirname(__file__), ".."))
sys.path.insert(0, REPO)
import chip_smoke  # noqa: E402


def _run(script, cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, script], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def test_refuses_without_a_tpu(tmp_path):
    proc = _run(os.path.join(REPO, "chip_smoke.py"), str(tmp_path))
    assert proc.returncode == 2, proc.stderr
    assert "no TPU found" in proc.stderr
    assert '"ok"' not in proc.stdout


def test_refuses_outside_the_repository(tmp_path):
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    proc = _run(str(tmp_path / "chip_smoke.py"), str(tmp_path))
    assert proc.returncode == 2, proc.stderr
    assert "cannot import the repro package" in proc.stderr
    assert '"ok"' not in proc.stdout


def _data(n=503, seed=0):
    # uniform rows keep the degree-6 Gram well-conditioned, so the f32 code
    # and the f64 reference agree to the CPU tests' tight tolerances
    Y = np.random.default_rng(seed).random((n, chip_smoke.J)).astype(np.float32)
    return M.MCTMConfig(J=chip_smoke.J, degree=chip_smoke.DEGREE), DataScaler.fit(Y), Y


def test_host_reference_features_and_leverage_match_the_code():
    cfg, scaler, Y = _data()
    A, dA = chip_smoke.host_features(scaler, Y)
    A32, dA32 = M.basis_features(cfg, scaler, jnp.asarray(Y))
    np.testing.assert_allclose(A, np.asarray(A32), atol=1e-6)
    np.testing.assert_allclose(dA, np.asarray(dA32), rtol=1e-5, atol=1e-5)
    # the engine's own float64-Gram path: same eigh cutoff rule as the reference
    u = ScoringEngine(cfg, scaler, chunk_size=0, gram_dtype="float64").score(
        jnp.asarray(Y), method="l2-only").leverage
    np.testing.assert_allclose(chip_smoke.host_leverage(A), u, atol=1e-6)


def test_host_reference_nll_matches_the_code():
    cfg, scaler, Y = _data(seed=1)
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    params = M.MCTMParams(params.theta_raw, jnp.asarray([0.3], jnp.float32))
    A, dA = chip_smoke.host_features(scaler, Y)
    want = chip_smoke.host_nll_terms(cfg, params, A, dA)
    got = M.nll_terms(cfg, params, *M.basis_features(cfg, scaler, jnp.asarray(Y)))
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("argv,four", [([], False), (["--four-chips"], True)])
def test_parse_args(argv, four):
    args = chip_smoke.parse_args(argv)
    assert args.four_chips is four
    assert args.out.startswith(os.path.join(REPO, "chiprun_out"))
