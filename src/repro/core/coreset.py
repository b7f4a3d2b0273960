"""Hybrid coreset construction for MCTMs — the paper's Algorithm 1.

Pipeline (ℓ2-hull):
  1. basis-transform the raw data:  A, A' ∈ (n, J, d)
  2. leverage scores u_i of Ã = flatten(A) (≡ leverage of the paper's block B)
  3. sensitivity proxy s_i = u_i + 1/n → probabilities p_i
  4. sample k1 = ⌊α·k⌋ points, weights 1/(k1·p_i)
  5. hull augmentation: k2 = k − k1 extremal points of {a'_ij} (ε/J-kernel,
     Blum et al. 2019), weight 1
  6. fit the MCTM on the weighted union.

Baselines from the paper's experiments: `uniform`, `l2-only`, `ridge-lss`,
`root-l2` — all share this entry point via ``method=``.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Literal

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.core import mctm as M
from repro.core.bernstein import DataScaler
from repro.core.hull import stable_first_unique
from repro.core.scoring import DEFAULT_CHUNK, ScoringEngine

Method = Literal["uniform", "l2-only", "l2-hull", "ridge-lss", "root-l2"]

__all__ = [
    "CoresetResult",
    "build_coreset",
    "coreset_scores",
    "coreset_from_scoring",
    "exact_hull_points",
    "CORESET_METHODS",
]

CORESET_METHODS: tuple[str, ...] = ("uniform", "l2-only", "l2-hull", "ridge-lss", "root-l2")


@dataclasses.dataclass
class CoresetResult:
    indices: np.ndarray        # (k,) point indices into the full dataset
    weights: np.ndarray        # (k,) positive weights
    scores: np.ndarray | None  # (n,) sampling scores used (None for uniform)
    method: str
    seconds: float

    @property
    def size(self) -> int:
        return int(self.indices.shape[0])


def coreset_scores(
    cfg: M.MCTMConfig,
    scaler: DataScaler,
    Y: jax.Array,
    method: str = "l2-hull",
    *,
    sketch_size: int = 0,
    key: jax.Array | None = None,
    ridge_reg: float = 1.0,
    chunk_size: int | None = DEFAULT_CHUNK,
) -> np.ndarray:
    """Per-point sampling scores s_i (sensitivity proxies) for each method.

    Backed by the chunked ``ScoringEngine``: inputs larger than ``chunk_size``
    are streamed with O(chunk·J·d) peak memory instead of materializing the
    (n, J, d) basis tensor.
    """
    n = np.asarray(Y).shape[0]
    if method == "uniform":
        return np.full(n, 1.0 / n)
    if method not in CORESET_METHODS:
        raise ValueError(f"unknown coreset method: {method}")
    if sketch_size > 0:
        assert key is not None
    engine = ScoringEngine(cfg, scaler, chunk_size=chunk_size)
    res = engine.score(
        jnp.asarray(Y),
        method=method,
        key=key,
        sketch_size=sketch_size,
        ridge_reg=ridge_reg,
    )
    return res.scores


def exact_hull_points(res, scores: np.ndarray, k_hull: int) -> np.ndarray:
    """Exactly ``k_hull`` distinct point ids from a ``ScoringResult``'s hull
    candidates, in first-occurrence (direction-priority) order.

    The ε-kernel candidate rows can dedup to fewer than ``k_hull`` distinct
    points (low-diversity hulls: many directions extremized by the same
    point); the shortfall is topped up deterministically from the next-ranked
    points by sampling score so callers always get the size they asked for.
    Requires ``k_hull ≤ n``.
    """
    r = res.rows_per_point
    pts = (
        stable_first_unique(np.asarray(res.hull_rows) // r, k_hull)
        if res.hull_rows is not None
        else np.zeros(0, np.int64)
    )
    short = k_hull - pts.shape[0]
    if short > 0:
        chosen = set(pts.tolist())
        ranked = np.argsort(-scores, kind="stable")
        extra = np.fromiter(
            (i for i in ranked if i not in chosen), dtype=np.int64, count=short
        )
        pts = np.concatenate([pts, extra])
    return pts


def coreset_from_scoring(
    res,
    n: int,
    k: int,
    method: str,
    alpha: float,
    key_draw: jax.Array,
    t0: float,
) -> CoresetResult:
    """Sampling + hull-union step of Algorithm 1 from a ``ScoringResult``.

    Shared by ``build_coreset`` and the sharded
    ``distributed_coreset.distributed_build_coreset`` — both engines emit the
    same ``ScoringResult`` contract, so the post-scoring assembly is one code
    path. Always returns exactly ``k`` points (hull shortfall topped up — see
    ``exact_hull_points``).
    """
    k_sample = int(np.floor(alpha * k)) if method == "l2-hull" else k
    k_hull = k - k_sample if method == "l2-hull" else 0
    scores = res.scores
    with TraceAnnotation("repro.coreset.sample"):
        probs = scores / scores.sum()
        idx = np.asarray(
            jax.random.choice(
                key_draw, n, shape=(k_sample,), replace=True, p=jnp.asarray(probs)
            )
        )
        w = 1.0 / (k_sample * probs[idx])

    if method == "l2-hull" and k_hull > 0:
        with TraceAnnotation("repro.coreset.hull_points"):
            hull_pts = exact_hull_points(res, scores, k_hull)
            idx = np.concatenate([idx, hull_pts])
            w = np.concatenate([w, np.ones(k_hull)])

    return CoresetResult(idx, w, scores, method, time.perf_counter() - t0)


def build_coreset(
    cfg: M.MCTMConfig,
    scaler: DataScaler,
    Y: np.ndarray,
    k: int,
    method: str = "l2-hull",
    *,
    key: jax.Array,
    alpha: float = 0.8,
    sketch_size: int = 0,
    chunk_size: int | None = DEFAULT_CHUNK,
) -> CoresetResult:
    """Paper Algorithm 1 (and its baselines). Returns indices + weights.

    The whole pre-sampling phase (leverage + hull extremes) runs as ONE
    two-pass sweep of the ``ScoringEngine``: the basis is evaluated at most
    once per chunk per pass — the dense path evaluates it exactly once — and
    nothing of size (n, J, d) is materialized when ``n > chunk_size``.
    """
    t0 = time.perf_counter()
    Y = np.asarray(Y)
    n = Y.shape[0]
    k = min(k, n)
    k_hull = k - int(np.floor(alpha * k)) if method == "l2-hull" else 0

    if method == "uniform":
        idx = np.asarray(jax.random.choice(key, n, shape=(k,), replace=False))
        w = np.full(k, n / k)
        return CoresetResult(idx, w, None, method, time.perf_counter() - t0)

    # independent streams from the parent key: scoring (sketch), hull
    # directions, and the sample draw (k_draw must NOT be re-derived from
    # k_score — the sketch already consumed it)
    k_score, k_hull_key, k_draw = jax.random.split(key, 3)
    engine = ScoringEngine(cfg, scaler, chunk_size=chunk_size)
    res = engine.score(
        jnp.asarray(Y),
        method=method,
        key=k_score,
        sketch_size=sketch_size,
        hull_k=k_hull,
        hull_key=k_hull_key,
    )
    return coreset_from_scoring(res, n, k, method, alpha, k_draw, t0)


# ---------------------------------------------------------------------------
# End-to-end evaluation harness (paper's metrics: §E.1.3 Main Workflow)
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class CoresetEvaluation:
    method: str
    k: int
    param_l2: float        # ||ϑ_cs − ϑ_full||₂ (paper "Param. ℓ2 dist.")
    lambda_err: float      # ||λ_cs − λ_full||₂ (paper "λ error")
    likelihood_ratio: float  # NLL_full(θ_cs)/NLL_full(θ_full), ≥ ~1, →1 better
    fit_seconds: float
    sample_seconds: float


def evaluate_coreset(
    cfg: M.MCTMConfig,
    scaler: DataScaler,
    Y: np.ndarray,
    full_fit: M.FitResult,
    k: int,
    method: str,
    key: jax.Array,
    *,
    steps: int = 1200,
    lr: float = 5e-2,
    alpha: float = 0.8,
) -> CoresetEvaluation:
    """Build a coreset, refit, and score against the full-data fit."""
    k_build, k_fit = jax.random.split(key)
    cs = build_coreset(cfg, scaler, Y, k, method, key=k_build, alpha=alpha)
    t0 = time.perf_counter()
    fit = M.fit_mctm(
        cfg,
        scaler,
        jnp.asarray(Y[cs.indices]),
        weights=jnp.asarray(cs.weights, jnp.float32),
        key=k_fit,
        steps=steps,
        lr=lr,
    )
    fit_s = time.perf_counter() - t0

    # Evaluate with a strict η (no floor): the fit uses the paper's η = Θ(ε)
    # corrected domain, but the reported likelihood must expose any log-term
    # blow-up a coreset failed to guard against (the hull's whole purpose).
    # Streamed (mctm_fit.streamed_nll): the full-data evaluation never
    # materializes the (n, J, d) basis.
    from repro.core.mctm_fit import likelihood_ratio, streamed_nll

    nll_full_at_cs = streamed_nll(cfg, scaler, fit.params, Y, eta=1e-9)
    nll_full_at_full = streamed_nll(cfg, scaler, full_fit.params, Y, eta=1e-9)

    from repro.core.bernstein import monotone_theta

    th_cs = monotone_theta(fit.params.theta_raw, cfg.min_slope)
    th_full = monotone_theta(full_fit.params.theta_raw, cfg.min_slope)
    param_l2 = float(jnp.linalg.norm(th_cs - th_full))
    lam_err = float(jnp.linalg.norm(fit.params.lam - full_fit.params.lam))
    # Likelihood ratio: NLL_full(θ_cs)/NLL_full(θ_full) as in the paper's
    # experiments, with the shared shift normalization for non-positive NLLs
    # (mctm_fit.likelihood_ratio).
    lr_metric = likelihood_ratio(nll_full_at_cs, nll_full_at_full)
    return CoresetEvaluation(
        method=method,
        k=cs.size,
        param_l2=param_l2,
        lambda_err=lam_err,
        likelihood_ratio=float(lr_metric),
        fit_seconds=fit_s,
        sample_seconds=cs.seconds,
    )
