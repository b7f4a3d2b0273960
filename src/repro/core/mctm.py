"""Multivariate Conditional Transformation Models (Klein et al. 2022) in JAX.

Model: Z = Λ h̃(Y) ~ N(0, I) with Λ unit lower triangular and
h̃_j(y) = a_j(y)ᵀ ϑ_j a monotone Bernstein expansion. Negative log-likelihood
of point y_i (paper Eq. 1, plus the Gaussian constant so likelihood *ratios*
are meaningful):

    Σ_j ½ (Σ_{l<j} λ_{jl} h̃_l(y_il) + h̃_j(y_ij))² − log h̃'_j(y_ij)
        + J/2 log(2π)

This module is the pure-model layer: parameter pytrees, NLL, sampling, and a
(weighted) maximum-likelihood fit — everything the coreset layer needs to
reproduce the paper's experiments.
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.bernstein import (
    DataScaler,
    bernstein_deriv_design,
    bernstein_design,
    monotone_theta,
)

LOG_2PI = float(np.log(2.0 * np.pi))


@dataclasses.dataclass(frozen=True)
class MCTMConfig:
    """Static model configuration."""

    J: int                   # output dimension
    degree: int = 6          # Bernstein degree M; d = degree + 1 coefficients
    eta: float = 1e-3        # D(η) floor for the log-Jacobian term (paper: η = 2ε)
    min_slope: float = 1e-4  # strict-monotonicity margin of ϑ

    @property
    def d(self) -> int:
        return self.degree + 1

    @property
    def n_params(self) -> int:
        return self.J * self.d + self.J * (self.J - 1) // 2


class MCTMParams(NamedTuple):
    """Unconstrained parameters: ϑ via cumulative-softplus, λ strict-lower."""

    theta_raw: jax.Array  # (J, d)
    lam: jax.Array        # (J*(J-1)//2,) strict lower-triangular entries


def init_params(key: jax.Array, cfg: MCTMConfig, dtype=jnp.float32) -> MCTMParams:
    k1, _ = jax.random.split(key)
    # Start near the identity transform: h̃(y) ≈ 4·t − 2 (covers N(0,1) mass).
    base = jnp.linspace(-2.0, 2.0, cfg.d, dtype=dtype)
    from repro.core.bernstein import monotone_theta_inverse

    theta_raw = jnp.tile(monotone_theta_inverse(base, cfg.min_slope), (cfg.J, 1))
    theta_raw = theta_raw + 0.01 * jax.random.normal(k1, theta_raw.shape, dtype)
    lam = jnp.zeros((cfg.J * (cfg.J - 1) // 2,), dtype)
    return MCTMParams(theta_raw=theta_raw, lam=lam)


def lambda_matrix(cfg: MCTMConfig, lam_flat: jax.Array) -> jax.Array:
    """Unit lower-triangular Λ from the flat strict-lower entries."""
    J = cfg.J
    eye = jnp.eye(J, dtype=lam_flat.dtype)
    if J == 1:
        return eye
    # static indices: np, not jnp — jnp.tril_indices traces a tril(ones(J,J))
    # mask at the default float dtype (f64 under JAX_ENABLE_X64)
    rows, cols = np.tril_indices(J, k=-1)
    return eye.at[rows, cols].set(lam_flat)


def basis_features(
    cfg: MCTMConfig, scaler: DataScaler, Y: jax.Array
) -> tuple[jax.Array, jax.Array]:
    """Evaluate (A, A′): a_j(y_ij) and d/dy a_j(y_ij), shapes (n, J, d)."""
    T = scaler.transform(Y)  # (n, J) in [0,1]
    A = bernstein_design(T, cfg.degree)
    Ap = bernstein_deriv_design(T, cfg.degree) * jnp.asarray(
        scaler.inv_span, dtype=T.dtype
    )[..., None]
    return A, Ap


def transform_parts(
    cfg: MCTMConfig, params: MCTMParams, A: jax.Array, Ap: jax.Array
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Return (z, h̃, h̃′): copula inputs and marginal transform/derivative."""
    # full f32 products: a TPU's default single bf16 pass moves per-point
    # log-densities by ~1e-3 relative
    hi = jax.lax.Precision.HIGHEST
    theta = monotone_theta(params.theta_raw, cfg.min_slope)  # (J, d)
    htilde = jnp.einsum("njd,jd->nj", A, theta, precision=hi)
    hprime = jnp.einsum("njd,jd->nj", Ap, theta, precision=hi)
    Lam = lambda_matrix(cfg, params.lam)
    z = jnp.dot(htilde, Lam.T, precision=hi)  # z_ij = Σ_{k≤j} λ_{jk} h̃_k(y_ik)
    return z, htilde, hprime


def nll_terms(
    cfg: MCTMConfig, params: MCTMParams, A: jax.Array, Ap: jax.Array
) -> jax.Array:
    """Per-point negative log-likelihood contributions, shape (n,)."""
    z, _, hprime = transform_parts(cfg, params, A, Ap)
    # D(η): floor the Jacobian term away from the log's asymptote. With the
    # monotone reparameterization hprime > 0 always; the floor additionally
    # realizes the paper's η-shifted domain for *unconstrained* parameters.
    log_jac = jnp.log(jnp.maximum(hprime, cfg.eta))
    per_dim = 0.5 * jnp.square(z) - log_jac + 0.5 * LOG_2PI
    return jnp.sum(per_dim, axis=-1)


def nll(
    cfg: MCTMConfig,
    params: MCTMParams,
    A: jax.Array,
    Ap: jax.Array,
    weights: jax.Array | None = None,
) -> jax.Array:
    """(Weighted) total negative log-likelihood — the paper's f(A, ϑ, λ)."""
    terms = nll_terms(cfg, params, A, Ap)
    if weights is None:
        return jnp.sum(terms)
    return jnp.sum(weights * terms)


def loss_parts(
    cfg: MCTMConfig,
    params: MCTMParams,
    A: jax.Array,
    Ap: jax.Array,
    weights: jax.Array | None = None,
) -> dict[str, jax.Array]:
    """The paper's split f = f1 (squared) + f2 (log⁺) − ... per Section 2.

    f1 = ½ Σ w_ij z_ij²;  f2 = Σ w_ij max(log h̃′, 0);  f3 = Σ w_ij max(−log h̃′, 0).
    """
    z, _, hprime = transform_parts(cfg, params, A, Ap)
    log_jac = jnp.log(jnp.maximum(hprime, cfg.eta))
    w = jnp.ones(z.shape[0], z.dtype) if weights is None else weights
    w = w[:, None]
    return {
        "f1": 0.5 * jnp.sum(w * jnp.square(z)),
        "f2": jnp.sum(w * jnp.maximum(log_jac, 0.0)),
        "f3": jnp.sum(w * jnp.maximum(-log_jac, 0.0)),
    }


# ---------------------------------------------------------------------------
# Fitting
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class FitResult:
    params: MCTMParams
    losses: np.ndarray
    final_nll: float


def fit_mctm(
    cfg: MCTMConfig,
    scaler: DataScaler,
    Y: jax.Array,
    weights: jax.Array | None = None,
    *,
    key: jax.Array | None = None,
    init: MCTMParams | None = None,
    steps: int = 1500,
    lr: float = 5e-2,
    method: str = "adam",
    mesh=None,
    chunk_size: int | None = None,
    microbatches: int | None = None,
    batch_size: int | None = None,
    optimizer=None,
    checkpoint=None,
    ckpt_every: int = 0,
    resume: bool = False,
) -> FitResult:
    """Weighted maximum-likelihood fit of an MCTM.

    ``weights`` are the coreset weights (None → unweighted full-data fit).
    The mean-normalized objective keeps the lr scale-free across coreset
    sizes.

    ``method`` selects a fit-subsystem mode (``repro.core.mctm_fit`` — see
    its module-doc method table): ``"adam"`` full-batch first-order,
    ``"lbfgs"`` streaming-HVP quasi-Newton (``steps`` are iterations), or
    ``"minibatch"`` (``batch_size`` sampled weighted rows per step). All
    three stream the basis microbatch-by-microbatch (inputs beyond
    ``chunk_size`` rows — default ``scoring.DEFAULT_CHUNK`` — never
    materialize an (n, J, d) tensor), run SPMD-sharded with ``mesh=``, and
    support ``checkpoint=`` (a ``CheckpointManager``) periodic saves +
    ``resume=True`` restart. ``method="scipy-lbfgs"`` is the dense small-n
    oracle kept for tests (scipy L-BFGS-B on a materialized basis).
    """
    if init is None:
        if key is None:
            key = jax.random.PRNGKey(0)
        init = init_params(key, cfg)
    if method in ("adam", "lbfgs", "minibatch"):
        from repro.core import mctm_fit
        from repro.core.scoring import DEFAULT_CHUNK

        return mctm_fit.fit_mctm_streaming(
            cfg,
            scaler,
            Y,
            weights,
            init=init,
            steps=steps,
            lr=lr,
            optimizer=optimizer,
            method=method,
            mesh=mesh,
            chunk_size=DEFAULT_CHUNK if chunk_size is None else chunk_size,
            microbatches=microbatches,
            batch_size=batch_size,
            checkpoint=checkpoint,
            ckpt_every=ckpt_every,
            resume=resume,
        )
    if method != "scipy-lbfgs":
        raise ValueError(f"unknown fit method: {method}")

    Yj = jnp.asarray(Y)
    wj = None if weights is None else jnp.asarray(weights)
    total_w = float(Y.shape[0]) if weights is None else float(jnp.sum(wj))

    def loss_fn(params: MCTMParams) -> jax.Array:
        # featurize INSIDE the (jitted) objective: the (n, J, d) basis exists
        # only for the duration of each evaluation instead of sitting in this
        # closure for the whole optimize
        A, Ap = basis_features(cfg, scaler, Yj)
        return nll(cfg, params, A, Ap, wj) / total_w

    params, losses = _scipy_lbfgs_fit(loss_fn, init)
    final = float(jax.jit(loss_fn)(params)) * total_w
    return FitResult(params=params, losses=np.asarray(losses), final_nll=final)


def _scipy_lbfgs_fit(loss_fn, params0: MCTMParams):
    """L-BFGS-B via scipy on the flattened parameter vector — the dense
    small-n oracle the streaming L-BFGS (``mctm_fit``, ``method="lbfgs"``)
    is tested against. ``loss_fn`` should featurize inside its (jitted) body
    rather than close over a materialized basis, so nothing O(n·J·d) lives
    across the optimize."""
    import jax.flatten_util  # not auto-imported on all supported jax versions
    from scipy.optimize import minimize

    flat0, unravel = jax.flatten_util.ravel_pytree(params0)
    vg = jax.jit(jax.value_and_grad(lambda f: loss_fn(unravel(f))))
    losses = []

    def fun(x):
        v, g = vg(jnp.asarray(x, dtype=jnp.float32))
        losses.append(float(v))
        return float(v), np.asarray(g, dtype=np.float64)

    res = minimize(fun, np.asarray(flat0, np.float64), jac=True, method="L-BFGS-B",
                   options={"maxiter": 500})
    return unravel(jnp.asarray(res.x, jnp.float32)), np.asarray(losses)


# ---------------------------------------------------------------------------
# Density / sampling utilities (used by examples and DGP visualization)
# ---------------------------------------------------------------------------


def log_density(
    cfg: MCTMConfig, params: MCTMParams, scaler: DataScaler, Y: jax.Array
) -> jax.Array:
    A, Ap = basis_features(cfg, scaler, Y)
    return -nll_terms(cfg, params, A, Ap)


def sample(
    cfg: MCTMConfig,
    params: MCTMParams,
    scaler: DataScaler,
    key: jax.Array,
    n: int,
    n_grid: int = 512,
) -> jax.Array:
    """Draw samples by inverting h̃ on a grid (h is triangular: solve per dim)."""
    z = jax.random.normal(key, (n, cfg.J))
    Lam = lambda_matrix(cfg, params.lam)
    # h̃(Y) = Λ^{-1} z  → invert each monotone marginal on a grid.
    htilde_target = jax.scipy.linalg.solve_triangular(Lam, z.T, lower=True).T
    theta = monotone_theta(params.theta_raw, cfg.min_slope)
    t_grid = jnp.linspace(0.0, 1.0, n_grid)
    basis = bernstein_design(t_grid, cfg.degree)  # (G, d)
    vals = basis @ theta.T  # (G, J) monotone in G per column
    low = jnp.asarray(scaler.low, jnp.float32)
    high = jnp.asarray(scaler.high, jnp.float32)

    def invert_dim(j, tgt):
        idx = jnp.searchsorted(vals[:, j], tgt)
        idx = jnp.clip(idx, 1, n_grid - 1)
        v0, v1 = vals[idx - 1, j], vals[idx, j]
        t0, t1 = t_grid[idx - 1], t_grid[idx]
        frac = jnp.clip((tgt - v0) / jnp.maximum(v1 - v0, 1e-12), 0.0, 1.0)
        t = t0 + frac * (t1 - t0)
        return low[j] + t * (high[j] - low[j])

    cols = [invert_dim(j, htilde_target[:, j]) for j in range(cfg.J)]
    return jnp.stack(cols, axis=1)


# Convenience jitted evaluators --------------------------------------------------

full_nll = jax.jit(nll, static_argnums=0)
full_nll_terms = jax.jit(nll_terms, static_argnums=0)
