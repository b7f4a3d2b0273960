"""Supervised recovery: RunSupervisor retry/backoff/abort contract, LR
backoff on non-finite signals, injected-failure fit recovery, straggler
backup draws, and re-sharded checkpoint restore onto a shrunk mesh."""
import os
import subprocess
import sys
import tempfile
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.errors import JaxRuntimeError

from repro.checkpoint import CheckpointManager
from repro.core import mctm as M
from repro.core.bernstein import DataScaler
from repro.core.mctm_fit import MCTMDensityModel, fit_density_model
from repro.data.pipeline import with_backup_draws
from repro.ft import ElasticPlanner, FailureSimulator, RunSupervisor, StragglerPolicy
from repro.ft.config import ft_overrides, get_ft_config
from repro.ft.failure import InjectedFailure, NonFiniteError
from repro.ft.supervisor import MeshPlan
from repro.optim import adamw, scale_updates
from repro.train.loop import train_loop

REPO_SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def run_in_subprocess(code: str):
    """Fresh interpreter with 8 fake CPU devices (device count is fixed at
    first jax init, so mesh-shrink scenarios can't run in-process)."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = REPO_SRC
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True, text=True, env=env, timeout=600,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


# ---------------------------------------------------------------- simulator


def test_simulator_once_fires_single_time_across_retries():
    sim = FailureSimulator().inject("scoring", 3)
    with pytest.raises(InjectedFailure):
        sim.maybe_fail(3, phase="scoring")
    sim.maybe_fail(3, phase="scoring")  # replay after retry: no re-fire
    sim.maybe_fail(3, phase="fit")      # other phases never match
    assert sim.log == [{"phase": "scoring", "step": 3, "mode": "once", "count": 1}]


def test_simulator_every_refires_and_log_persists():
    sim = FailureSimulator().inject("fit", 2, mode="every")
    for expect_count in (1, 2, 3):
        with pytest.raises(InjectedFailure):
            sim.maybe_fail(2, phase="fit")
        assert sim.log[-1]["count"] == expect_count
    assert len(sim.log) == 3  # never cleared — the abort diagnostic needs it


# --------------------------------------------------------------- supervisor


def test_supervisor_retries_then_succeeds_with_backoff():
    slept = []
    sup = RunSupervisor(label="t", sleep=slept.append)
    calls = []

    def attempt(ctx):
        calls.append((ctx.attempt, ctx.resume))
        if ctx.attempt < 2:
            raise RuntimeError("transient")
        return "done"

    with ft_overrides(max_retries=3, backoff_base_s=0.05, backoff_factor=2.0):
        assert sup.run(attempt) == "done"
    assert calls == [(0, False), (1, True), (2, True)]
    assert slept == [0.05, 0.1]  # exponential
    assert [e["kind"] for e in sup.events] == ["failure", "failure"]


def test_supervisor_budget_exhausted_diagnostic_includes_injection_log():
    ft = get_ft_config()
    sim = FailureSimulator().inject("fit", 0, mode="every")
    with ft_overrides(max_retries=1, backoff_base_s=0.0):
        ft.simulator = sim
        try:
            sup = RunSupervisor(label="crash")
            with pytest.raises(RuntimeError) as ei:
                sup.run(lambda ctx: sim.maybe_fail(0, phase="fit"))
        finally:
            ft.simulator = None
    msg = str(ei.value)
    assert "retry budget exhausted after 2 attempts" in msg
    assert "injection log" in msg and "'fit'" in msg
    assert isinstance(ei.value.__cause__, InjectedFailure)


@pytest.mark.parametrize("exc", [
    ValueError("bad"), TypeError("bad"), NotImplementedError("bad"),
    # compile refusals and out-of-memory repeat identically on every attempt
    JaxRuntimeError("RESOURCE_EXHAUSTED: Ran out of memory in memory space hbm"),
    JaxRuntimeError("INTERNAL: Mosaic failed to compile TPU kernel: bad tiling"),
    JaxRuntimeError("INVALID_ARGUMENT: unsupported operand layout"),
])
def test_supervisor_non_retryable_propagates_immediately(exc):
    sup = RunSupervisor()
    calls = []

    def attempt(ctx):
        calls.append(ctx.attempt)
        raise exc

    with pytest.raises(type(exc)):
        sup.run(attempt)
    assert calls == [0]  # no retry burned on a programming error


def test_supervisor_retries_transient_xla_errors():
    """Device loss / collective timeouts stay retryable: only compile
    refusals and out-of-memory are permanent."""
    sup = RunSupervisor(sleep=lambda s: None)
    calls = []

    def attempt(ctx):
        calls.append(ctx.attempt)
        if ctx.attempt == 0:
            raise JaxRuntimeError("UNAVAILABLE: peer task is gone")
        if ctx.attempt == 1:
            raise JaxRuntimeError("DEADLINE_EXCEEDED: barrier timed out")
        return "ok"

    with ft_overrides(max_retries=3, backoff_base_s=0.0):
        assert sup.run(attempt) == "ok"
    assert calls == [0, 1, 2]


def test_supervisor_nonfinite_backs_off_lr_without_replanning():
    planner = ElasticPlanner(model_parallel=1, base_data_parallel=8)
    sup = RunSupervisor(planner=planner, devices_fn=lambda: 8,
                        remesh=lambda plan: "mesh", sleep=lambda s: None)
    seen = []

    def attempt(ctx):
        seen.append((ctx.lr_scale, ctx.plan))
        if ctx.attempt < 2:
            raise NonFiniteError(ctx.attempt, loss=float("nan"))
        return "ok"

    with ft_overrides(max_retries=3, lr_backoff_factor=0.5, backoff_base_s=0.0):
        sup.run(attempt)
    assert [s[0] for s in seen] == [1.0, 0.5, 0.25]
    assert all(p is None for _, p in seen)  # divergence ≠ dead hardware


def test_supervisor_replans_on_failure_with_shrunk_pool():
    planner = ElasticPlanner(model_parallel=2, base_data_parallel=4,
                             base_global_batch=64)
    alive = [8, 6]  # two devices die before the first retry
    sup = RunSupervisor(planner=planner, devices_fn=lambda: alive[-1],
                        remesh=lambda plan: ("mesh", plan.shape),
                        sleep=lambda s: None)
    seen = []

    def attempt(ctx):
        seen.append(ctx)
        if ctx.attempt == 0:
            raise RuntimeError("node lost")
        return ctx

    with ft_overrides(max_retries=2, backoff_base_s=0.0, rescale_lr=True):
        ctx = sup.run(attempt)
    assert isinstance(ctx.plan, MeshPlan)
    assert ctx.plan.shape == (3, 2) and ctx.mesh == ("mesh", (3, 2))
    assert ctx.plan.global_batch == 48 and ctx.batch_scale == 48 / 64
    assert ctx.lr_scale == pytest.approx(ctx.plan.lr_scale)
    assert sup.events[0]["plan"]["shape"] == (3, 2)


# ----------------------------------------------------- lr backoff machinery


def test_scale_updates_halves_updates_same_state_structure():
    opt = adamw(1e-2)
    assert scale_updates(opt, 1.0) is opt  # identity: no wrapper in the way
    params = {"w": jnp.ones((3,))}
    grads = {"w": jnp.full((3,), 2.0)}
    s0 = opt.init(params)
    u_full, s1 = opt.update(grads, s0, params, jnp.asarray(0))
    u_half, s1h = scale_updates(opt, 0.5).update(grads, s0, params, jnp.asarray(0))
    np.testing.assert_allclose(np.asarray(u_half["w"]), 0.5 * np.asarray(u_full["w"]))
    # state structure + values untouched → pre-backoff checkpoints restore
    assert jax.tree.structure(s1) == jax.tree.structure(s1h)
    for a, b in zip(jax.tree.leaves(s1), jax.tree.leaves(s1h)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_train_loop_raises_nonfinite_before_checkpointing(tmp_path):
    mgr = CheckpointManager(str(tmp_path))
    state = {"step": jnp.asarray(0, jnp.int32), "x": jnp.zeros(())}

    def step_fn(state, batch):
        i = int(state["step"])
        loss = np.nan if i == 2 else 1.0
        new = {"step": state["step"] + 1, "x": state["x"]}
        return new, {"loss": jnp.asarray(loss), "grad_norm": jnp.asarray(0.0)}

    with ft_overrides(nonfinite_rollback=True, nonfinite_check_every=1):
        with pytest.raises(NonFiniteError) as ei:
            train_loop(step_fn, state, lambda i: {}, 8, mgr=mgr, ckpt_every=1)
    assert ei.value.step == 2
    assert mgr.latest_step() == 2  # poisoned step-3 state never saved


# ----------------------------------------------------- straggler mitigation


def test_with_backup_draws_fake_clock():
    clock = {"t": 0.0, "cost": 0.0}

    def tick():
        clock["t"] += clock["cost"]
        return clock["t"]

    primary = lambda step: {"src": "primary", "step": step}
    backup = lambda step: {"src": "backup", "step": step}
    fn = with_backup_draws(primary, backup, StragglerPolicy(deadline_ms=100),
                           clock=tick)
    clock["cost"] = 0.01  # 10ms per tick → primary well under deadline
    assert fn(3) == {"src": "primary", "step": 3}
    clock["cost"] = 0.2   # 200ms → deadline missed, deterministic backup
    assert fn(4) == {"src": "backup", "step": 4}


# ------------------------------------------------------- fit-layer recovery


def _density_fixture(n=512, seed=0):
    rng = np.random.default_rng(seed)
    Y = rng.normal(size=(n, 2)).astype(np.float32)
    cfg = M.MCTMConfig(J=2, degree=4)
    model = MCTMDensityModel(cfg, DataScaler.fit(Y))
    p0 = M.init_params(jax.random.PRNGKey(0), cfg)
    batch = {"Y": Y, "weights": np.ones(n, np.float32)}
    return model, p0, batch


def test_adam_injected_failure_recovers_bit_identical():
    """Crash at step 12 of 24 → supervisor resumes from the step-6/12 ckpt
    and the deterministic full-batch replay lands on identical params."""
    model, p0, batch = _density_fixture()
    ft = get_ft_config()

    def run(inject):
        with tempfile.TemporaryDirectory() as d:
            mgr = CheckpointManager(d, keep=2)
            if inject:
                ft.simulator = FailureSimulator().inject("fit", 12)
            try:
                params, losses, _ = fit_density_model(
                    model, p0, batch, optimizer=adamw(5e-2), steps=24,
                    checkpoint=mgr, ckpt_every=6)
            finally:
                ft.simulator = None
            return params, losses

    p_clean, l_clean = run(False)
    p_rec, l_rec = run(True)
    for a, b in zip(jax.tree.leaves(p_clean), jax.tree.leaves(p_rec)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert l_rec[-1] == l_clean[-1]


def test_lbfgs_deterministic_nonfinite_crash_loops_to_clean_abort():
    """NaN data → non-finite objective on every attempt → the retry budget
    drains and the supervisor aborts with the full diagnostic (this is the
    intended behavior for a deterministically-poisoned objective)."""
    _, p0, _ = _density_fixture(n=64)
    cfg = M.MCTMConfig(J=2, degree=4)
    bad_Y = np.full((64, 2), np.nan, np.float32)
    good = np.random.default_rng(1).normal(size=(64, 2)).astype(np.float32)
    model = MCTMDensityModel(cfg, DataScaler.fit(good))
    bad = {"Y": bad_Y, "weights": np.ones(64, np.float32)}
    with ft_overrides(max_retries=2, backoff_base_s=0.0):
        with pytest.raises(RuntimeError) as ei:
            fit_density_model(model, p0, bad, steps=4, method="lbfgs")
    msg = str(ei.value)
    assert "retry budget exhausted after 3 attempts" in msg
    assert "non-finite" in msg


def test_minibatch_straggler_policy_swaps_in_backup_draws():
    """With a straggler deadline of ~0ms every primary draw misses, so the
    fit must run entirely on backup draws — and still converge/replay."""
    model, p0, batch = _density_fixture(n=256)
    common = dict(optimizer=adamw(5e-2), steps=8, method="minibatch",
                  batch_size=64)
    _, l_plain, _ = fit_density_model(model, p0, batch, **common)
    with ft_overrides(straggler_deadline_ms=1e-9):
        _, l_backup, _ = fit_density_model(model, p0, batch, **common)
    l_plain = [float(x) for x in l_plain]
    l_backup = [float(x) for x in l_backup]
    assert len(l_backup) == 8 and np.all(np.isfinite(l_backup))
    # backup draws use an offset seed → a genuinely different batch sequence
    assert l_backup != l_plain


# --------------------------------------------- re-shard restore, shrunk mesh


def test_restore_train_state_reshards_onto_shrunk_ragged_mesh():
    """Checkpoint written on the full 8-device pool restores onto a 6-device
    (3×2) survivor mesh via ``restore_train_state(shardings=)`` — values
    bit-identical, leaves committed to the degraded mesh's shardings."""
    run_in_subprocess(
        """
        import tempfile
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        from repro.checkpoint import CheckpointManager
        from repro.optim import adamw
        from repro.train import init_train_state
        from repro.train.loop import restore_train_state

        opt = adamw(1e-3)
        params = {"w": jnp.arange(24.0).reshape(6, 4), "b": jnp.ones((5,))}
        state = init_train_state(params, opt).replace(step=jnp.asarray(7, jnp.int32))

        with tempfile.TemporaryDirectory() as d:
            mgr = CheckpointManager(d)
            mgr.save(7, state)

            # 6 of 8 devices survive: a (3, 2) degraded mesh
            mesh = Mesh(np.asarray(jax.devices()[:6]).reshape(3, 2), ("data", "model"))

            def spec(x):
                if x.ndim >= 1 and x.shape[0] % 3 == 0:
                    return NamedSharding(mesh, P("data", *([None] * (x.ndim - 1))))
                return NamedSharding(mesh, P())

            template = jax.tree.map(jnp.zeros_like, state)
            shardings = jax.tree.map(spec, template)
            restored, start = restore_train_state(mgr, template, shardings=shardings)

        assert start == 7, start
        for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(restored)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        w = restored.params["w"]
        assert w.sharding.mesh.devices.size == 6
        assert w.sharding.spec == P("data", None), w.sharding.spec
        assert restored.params["b"].sharding.spec == P(), restored.params["b"].sharding
        print("OK")
        """
    )
