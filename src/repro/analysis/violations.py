"""Deliberately broken programs that the analysis gate MUST fail on.

These prove the auditor has teeth: each violation seeds exactly one bug of a
class the checks exist to catch, against an honest budget a reviewer would
have written for the *correct* program. They are kept out of the main
registry (``all_programs()`` stays clean) and reached via
``scripts/analysis_gate.py --seed-violation <name>`` and the tests.
"""
from __future__ import annotations

import numpy as np

from repro.analysis.registry import (
    CollectiveBudget,
    MaterializationBudget,
    ProgramSpec,
)

_N, _J, _D = 1024, 2, 8  # rows, dims, basis width for the toy programs


def _build_extra_psum():
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from repro.utils.compat import make_mesh, shard_map

    mesh = make_mesh((jax.device_count(),), ("data",))

    def body(y):
        # the bug: a second psum call site where one fused psum suffices
        s = jax.lax.psum(jnp.sum(y), "data")
        ss = jax.lax.psum(jnp.sum(jnp.square(y)), "data")
        return s + ss

    fn = jax.jit(shard_map(
        body, mesh=mesh, in_specs=P("data", None), out_specs=P(),
    ))
    y = np.ones((_N, _J), np.float32)
    return fn, (y,)


def _build_stacked_basis():
    import jax

    from repro.core.mctm import MCTMConfig, basis_features
    from repro.core.bernstein import DataScaler

    Y = np.random.default_rng(0).normal(size=(_N, _J)).astype(np.float32)
    cfg = MCTMConfig(J=_J, degree=3)
    scaler = DataScaler.fit(Y)
    # the bug: featurizing ALL n rows at once → an (n, J, d) basis block
    fn = jax.jit(lambda y: basis_features(cfg, scaler, y))
    return fn, (Y,)


def _build_f64_promotion():
    import jax
    import jax.numpy as jnp

    # the bug: an np.float64 scalar constant — harmless at x64=off, but it
    # promotes the whole f32 array under JAX_ENABLE_X64=1
    scale = np.float64(1.5)
    fn = jax.jit(lambda x: jnp.sum(x * scale))
    x = np.ones((64,), np.float32)
    return fn, (x,)


def _build_missing_donation():
    import jax
    import jax.numpy as jnp

    # the bug: state declared donated, but the update reshapes it, so XLA
    # cannot alias the buffer — the "in-place" update silently copies
    fn = jax.jit(lambda s: jnp.ravel(s + 1.0), donate_argnums=(0,))
    s = np.zeros((8, 8), np.float32)
    return fn, (s,)


def _build_host_callback():
    import jax
    import jax.numpy as jnp

    def log_loss(v):
        pass  # stand-in for print/logging/metrics push

    def fn(x):
        loss = jnp.sum(x)
        # the bug: a host callback inside the hot path — every step now
        # round-trips to python
        jax.debug.callback(log_loss, loss)
        return loss

    x = np.ones((64,), np.float32)
    return jax.jit(fn), (x,)


VIOLATIONS: dict[str, ProgramSpec] = {
    "extra_psum": ProgramSpec(
        name="violation_extra_psum",
        description="second psum call site against a one-all-reduce budget",
        build=_build_extra_psum,
        collectives=CollectiveBudget(all_reduce=1),
        needs_devices=2,
    ),
    "stacked_basis": ProgramSpec(
        name="violation_stacked_basis",
        description="full (n, J, d) basis materialized against a chunk budget",
        build=_build_stacked_basis,
        materialization=MaterializationBudget(row_elems=_J, fixed_elems=2048),
    ),
    "f64_promotion": ProgramSpec(
        name="violation_f64_promotion",
        description="np.float64 constant promotes an f32 array under x64",
        build=_build_f64_promotion,
    ),
    "missing_donation": ProgramSpec(
        name="violation_missing_donation",
        description="donated state silently copied (reshape breaks aliasing)",
        build=_build_missing_donation,
        donated_outputs=1,
    ),
    "host_callback": ProgramSpec(
        name="violation_host_callback",
        description="debug callback inside a jitted hot path",
        build=_build_host_callback,
    ),
}
