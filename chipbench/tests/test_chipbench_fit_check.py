"""The fit cell's check on the CPU at a tiny size: a sound run is correct,
and a run with the timed path broken underneath, or the control in the
program's place, is not.

Each test skips the look for a chip and drives the rest of a run
(``run.run_cell(on_chip=False)``) on the tiny fit cell added as new files."""
from __future__ import annotations

import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import chipbench_testlib as lib  # noqa: E402

from chipbench import control, run  # noqa: E402

WL = "tiny.fit.lbfgs"


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return lib.tiny_bench(tmp_path_factory.mktemp("bench"))


@pytest.fixture
def fit_module():
    from repro.core import mctm_fit

    return mctm_fit


def _wrap_fit(monkeypatch, mctm_fit, make):
    monkeypatch.setattr(mctm_fit, "fit_mctm_streaming", make(mctm_fit.fit_mctm_streaming))


def _fails(rec, number):
    c = rec["checks"][number]
    return not rec["correct"] and c["value"] > c["limit"]


def test_sound_fit_is_correct(root):
    rec = lib.run_tiny(root, WL)
    assert rec["correct"], rec["checks"]
    assert rec["attempted"] >= 1 and rec["failed"] == 0
    assert rec["metrics"]["fit_s"]["value"] > 0
    assert set(rec["checks"]) == {"obj_dev", "fit_gap", "shape_err"}
    assert list(rec)[-1] == "checks"


def test_fit_cut_short_is_caught(root, monkeypatch, fit_module):
    """Five L-BFGS iterations where the mix asks for sixty."""
    _wrap_fit(monkeypatch, fit_module,
              lambda real: lambda *a, **kw: real(*a, **{**kw, "steps": 5}))
    assert _fails(lib.run_tiny(root, WL), "fit_gap")


def test_a_step_that_returns_its_state_unchanged_is_caught(root, monkeypatch, fit_module):
    real_loop = fit_module.train_loop

    def frozen(step_fn, state, *a, **kw):
        def step(s, batch):
            return s._replace(step=s.step + 1), step_fn(s, batch)[1]

        return real_loop(step, state, *a, **kw)

    monkeypatch.setattr(fit_module, "train_loop", frozen)
    assert _fails(lib.run_tiny(root, WL), "fit_gap")


def test_dropped_weights_are_caught(root, monkeypatch, fit_module):
    _wrap_fit(monkeypatch, fit_module,
              lambda real: lambda cfg, scaler, Y, weights=None, **kw: real(cfg, scaler, Y, None, **kw))
    rec = lib.run_tiny(root, WL)
    assert _fails(rec, "obj_dev") and _fails(rec, "fit_gap")


def test_half_the_coreset_left_out_is_caught(root, monkeypatch, fit_module):
    """Every other row fitted, the objective normalised by the rest's weight."""
    _wrap_fit(monkeypatch, fit_module,
              lambda real: lambda cfg, scaler, Y, weights=None, **kw:
              real(cfg, scaler, Y[::2], weights[::2], **kw))
    rec = lib.run_tiny(root, WL)
    assert _fails(rec, "obj_dev") and _fails(rec, "fit_gap")


def test_parameters_altered_after_the_fit_are_caught(root, monkeypatch, fit_module):
    """The largest λ zeroed after ``final_nll`` was taken."""

    def make(real):
        def altered(*a, **kw):
            fit = real(*a, **kw)
            lam = fit.params.lam
            fit.params = fit.params._replace(lam=lam.at[int(np.argmax(np.abs(lam)))].set(0.0))
            return fit

        return altered

    _wrap_fit(monkeypatch, fit_module, make)
    assert _fails(lib.run_tiny(root, WL), "obj_dev")


def test_control_is_not_correct(root):
    """The reference objective from bf16 operands, minimised in the
    program's place by the same L-BFGS settings."""
    cell = run.find_cell(WL, root=root)
    run.import_program(lib.ROOT)
    ctx = run.RunContext(cell, 3, 0.0, False)
    ctx.state = cell.entry.setup(ctx)
    ctx.results = [cell.entry.control_call(ctx, ctx.state, i) for i in range(2)]
    ok, checks = run.judge(ctx, cell.entry.check(ctx, ctx.state, ctx.results,
                                                 np.random.default_rng(3)))
    assert not ok
    assert checks["obj_dev"]["value"] > 3 * checks["obj_dev"]["limit"], checks


def test_control_starts_where_the_program_starts():
    import jax

    from repro.core import mctm as M

    cfg = M.MCTMConfig(J=4, degree=5)
    p = M.init_params(jax.random.PRNGKey(0), cfg)
    raw, lam = control.default_init(cfg.J, cfg.d, cfg.min_slope)
    np.testing.assert_allclose(raw, np.asarray(p.theta_raw), rtol=1e-6, atol=1e-7)
    np.testing.assert_array_equal(lam, np.asarray(p.lam))


@pytest.mark.parametrize("products", ["bf16", "f32"])
def test_control_gradient_is_the_reference_gradient(monkeypatch, products):
    """The control's written-out value and gradient against the float64
    reference's: to f32 rounding with plain f32 products, and to a bf16
    rounding of each operand (2⁻⁸ of a product at most) as the control
    forms them."""
    import jax.numpy as jnp

    from chipbench import datagen
    from chipbench import reference as R

    if products == "f32":
        monkeypatch.setattr(control, "mul1", lambda a, b: a * b)
    tol = 4e-3 if products == "bf16" else 1e-5
    Y = datagen.generate("covertype", 301, seed=5)[:, :3]
    low, high = datagen.scaler_bounds(Y)
    w = np.random.default_rng(0).uniform(0.5, 3.0, Y.shape[0]).astype(np.float32)
    raw, _ = control.default_init(3, 5, 1e-4)
    lam = np.array([0.3, -0.2, 0.1])
    ref = R.MCTMObjective(Y, w, low, high, 4, 1e-3, 1e-4)
    v, g_raw, g_lam = ref.value_and_grad(raw, lam)
    X, P = control.features(jnp.asarray(Y), jnp.asarray(low), jnp.asarray(high), 4)
    cv, c_raw, c_lam = control._fit_fn(3, 1e-3, 1e-4)(
        jnp.asarray(raw, jnp.float32), jnp.asarray(lam, jnp.float32),
        X.reshape(-1, 3, 5), P.reshape(-1, 3, 5), jnp.asarray(w))
    assert float(cv) == pytest.approx(v, rel=tol)
    scale = max(np.abs(g_raw).max(), np.abs(g_lam).max())
    np.testing.assert_allclose(np.asarray(c_raw), g_raw, atol=tol * scale)
    np.testing.assert_allclose(np.asarray(c_lam), g_lam, atol=tol * scale)


def test_call_weights_are_the_coreset_scaled_by_a_power_of_two_new_to_the_cache(root, tmp_path):
    """Call i fits the coreset's rows with its weights times 2**e: exactly
    the same fit, at a Σw that no call of this run and no earlier run with
    the same cache has taken; the first run's order comes from the seed."""
    entry = run.find_cell(WL, root=root).entry
    rng = np.random.default_rng(0)
    Y, w = rng.normal(size=(50, 3)).astype(np.float32), rng.uniform(1, 9, 50).astype(np.float32)
    record = str(tmp_path / "cache" / entry.RECORD)

    def state(seed):
        return {"coresets": [(Y, w)], "seed": seed, "exponents": {}, "record": record}

    first = state(2**31 + 7)
    exps = []
    for i in range(4):
        Yc, wc = entry.coreset(first, i)
        e = entry.exponent(first, i)
        assert Yc is Y and wc.dtype == np.float32 and 1 <= e <= entry.EXPONENTS
        np.testing.assert_array_equal(wc, w * np.float32(2.0 ** e))
        np.testing.assert_array_equal(wc, entry.coreset(first, i)[1])
        exps.append(e)
    assert len(set(exps)) == 4
    # the same seed with no record starts where the first run started
    assert entry.exponent({**state(2**31 + 7), "record": None}, 0) == exps[0]
    # a second run with the cache takes none of them, until all are taken
    second = state(2**31 + 7)
    later = [entry.exponent(second, i) for i in range(entry.EXPONENTS - 4)]
    assert not set(later) & set(exps) and len(set(later)) == entry.EXPONENTS - 4
    assert entry.exponent(state(5), 0) in range(1, entry.EXPONENTS + 1)


def test_each_window_fit_lowers_and_compiles_only_its_two_oracles(root):
    """Set-up's warm-up compiles every other program a fit runs, so the
    window's fits lower and compile their own value-and-gradient and
    Hessian-vector oracles, and nothing else."""
    import jax

    cell = run.find_cell(WL, root=root)
    run.import_program(lib.ROOT)
    counter = run.EventCounter()
    jax.monitoring.register_event_duration_secs_listener(counter)
    jax.monitoring.register_event_listener(counter)
    ctx = run.RunContext(cell, 11, 0.0, False)
    ctx.state = cell.entry.setup(ctx)
    run.run_window(ctx, counter)
    assert len(ctx.results) == 1
    assert ctx.counters.get(run.LOWERING_EVENT, 0) == 2, ctx.counters
    assert run.window_compiles(ctx) == 2, ctx.counters
