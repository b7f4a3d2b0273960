"""Pass-strategy scoring core for Algorithm 1's pre-sampling phase.

The paper's construction must score *all n* points before it ever samples:
leverage scores u_i of the flattened basis matrix X̃ ∈ R^{n×Jd}, plus the
directional hull extremes of the derivative rows {a'_ij} ⊂ R^d that feed the
ε-kernel augmentation. ``ScoringEngine`` streams row-chunks of Y through a
fused featurize and keeps peak memory at O(chunk·J·d) — but *how many times*
each row is streamed, and what small sufficient statistic is carried across
chunks, is owned by a pluggable **pass strategy**.

Pass-strategy contract (every strategy implements)
--------------------------------------------------
  state    — the cross-chunk carry, a jax pytree of O((Jd)²)-ish arrays
             (``init_state``). On the sharded engine the whole state tuple
             joins the ONE fused psum at the end of the shard-local scan, so
             anything a strategy carries must be sum-reducible across shards:
             ``TwoPassExact`` carries (G = X̃ᵀX̃, Σp, Σppᵀ),
             ``TwoPassSketched`` carries (SX = CountSketch(X̃), Σp, Σppᵀ),
             ``OnePassSketched`` carries just SX (its direction net is fixed
             upfront, so the moments would be dead weight).
  update   — per-chunk accumulation (``update(state, X, P, sw, plan_slice)``),
             pure and traceable (it runs inside jit / lax.scan / shard_map
             bodies). May additionally *emit* a per-row block: the one-pass
             strategy returns z = (√w·X)Ω, the sketch-projected rows leverage
             is later read off from.
  finalize — ``gram``/``result_gram``/``moments`` read the accumulated state:
             ``gram`` feeds the (tiny, host-side f64) eigh that produces the
             leverage projection (V, w⁺); ``moments`` feed the hull direction
             net. The chunk loop, hull running-extreme reduction, and the
             ``ScoringResult`` assembly live in the engine driver and are
             written exactly once for all strategies and both engines.

Strategies
----------
  ``TwoPassExact``   — pass 1 accumulates the exact Gram (plus hull moments),
      pass 2 re-streams the chunks to emit leverage and the fused directional
      hull extremes. ``gram_dtype="float64"`` accumulates the Gram host-side
      in f64 so degree-6 Bernstein bases no longer sit at the f32 rcond
      cutoff (the sharded engine instead casts inside the scan body, which
      requires x64 mode).
  ``TwoPassSketched`` — pass 1 accumulates the CountSketch Gram (SX)ᵀ(SX)
      (Woodruff 2014 Thm 2.13); pass 2 re-streams as above. Constant-factor
      leverage at O(nnz) pass-1 cost, but still two data sweeps.
  ``OnePassSketched`` — TRUE one-pass: the single sweep accumulates the row
      CountSketch SX, tracks the directional hull extremes against an
      upfront direction net, and emits the sketch-projected row blocks
      z_c = (√w·X_c)Ω. Leverage is finalized from z against the sketched
      Gram — u_i = z_i ((SXΩ)ᵀSXΩ)⁺ z_iᵀ — without ever touching a row
      twice, which is the shape insertion-only streams (Merge & Reduce
      blocks) and one-shot sharded I/O need. The saved sweep is bought with
      retention: the z blocks are O(n·q) device memory (q = Jd with
      ``proj_size=None``, where Ω = identity and the estimate reproduces the
      classic sketched leverage ‖X̃_i R⁻¹‖² exactly; ``proj_size=q < Jd``
      compresses retention at a rank-truncation cost). Callers who need
      O(chunk) peak memory more than they need the single sweep should ask
      for ``strategy="two-pass-sketched"`` instead. Because the direction
      net cannot see the data covariance before the sweep, its ±principal
      axes are replaced by the coordinate axes (an identity covariance prior
      through the same ``hull_directions``); the random directions are drawn
      identically to the two-pass net.

Strategy comparison (what each sweep costs; see docs/KERNELS.md for the
kernel dispatch contract behind ``fused_update``):

  strategy           sweeps  carry                 retention   chunk body
  TwoPassExact       2       (G, Σp, Σppᵀ)         O(chunk)    matmul + fused hull sweep 2
  TwoPassSketched    2       (SX, Σp, Σppᵀ)        O(chunk)    fused sweep (sketch+moments)
  OnePassSketched    1       SX                    O(n·q)      fused sweep (sketch+z+hull)

``fused_update`` is the strategy hook behind the single-residency sweep:
one call per chunk covering the sketch/Gram update, the optional emitted z
block, AND the block-local hull extremes (``repro.kernels.sweep`` — Pallas
kernel on TPU, fused-jnp oracle elsewhere). Strategies that don't fuse fall
back to ``update`` + a standalone hull reduction; the sketched strategies
override it, which is what makes the true one-pass sweep one dispatch per
chunk and strictly faster than two-pass (BENCH_scoring.json
``one_pass_vs_two_pass``, floor-gated ≥ 1.0 by scripts/bench_gate.py). The
fused op returns chunk-LOCAL extremes which the drivers fold at their own
row offsets, so engine state layouts — and sweep checkpoints — stay
byte-identical to the unfused formulation.

The per-chunk math (``pass1_update``, ``leverage_chunk``,
``hull_chunk_extremes``) and the between-pass host algebra
(``projection_from_gram``, ``directions_from_moments``, ``finalize_scoring``)
are module-level functions so the sharded realization
(``repro.core.distributed_coreset.DistributedScoringEngine`` — the same
strategies driven inside a shard_map body, state psum'd once) reuses them
verbatim.

When the input fits in a single chunk the engine featurizes exactly once and
shares the block between sweeps. Weighted inputs (Merge & Reduce streaming
buckets) scale X̃ rows by √w — leverage of the weighted matrix — while the
hull operates on the raw derivative rows, matching the batch construction.
"""
from __future__ import annotations

import dataclasses
import os
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.hull import hull_directions, stable_first_unique
from repro.ft.config import get_ft_config, maybe_inject
from repro.kernels.extremes.ops import directional_extremes
from repro.kernels.gram.ops import gram_matrix
from repro.kernels.sweep.ops import fused_sweep_update

__all__ = [
    "ScoringEngine",
    "ScoringResult",
    "score_chunks",
    "gram_projection",
    "PassStrategy",
    "TwoPassExact",
    "TwoPassSketched",
    "OnePassSketched",
    "resolve_strategy",
    "sketch_plan",
    "upfront_directions",
    "RunningExtremes",
    "pass1_update",
    "leverage_chunk",
    "hull_chunk_extremes",
    "projection_from_gram",
    "directions_from_moments",
    "finalize_scoring",
    "DEFAULT_CHUNK",
]

DEFAULT_CHUNK = 65_536

# f32 matmuls on a TPU default to one bf16 pass. Leverage reads small-
# eigenvalue modes of an ill-conditioned Gram and the hull net is built from
# the P moments, so those products ask for full f32.
HIGHEST = jax.lax.Precision.HIGHEST

SCORE_METHODS = ("l2-only", "l2-hull", "ridge-lss", "root-l2")
GRAM_DTYPES = ("float32", "float64")


def _spectrum_inverse(w, *, ridge_reg: float, rcond: float, xp):
    """Inverted eigenvalue weights shared by every projection variant.

    ``xp`` is the array module (np or jnp) so the jitted distributed path and
    the engine's f64 host path stay threshold-identical by construction.
    """
    if ridge_reg > 0.0:
        return 1.0 / (xp.maximum(w, 0.0) + ridge_reg)
    wmax = xp.max(xp.abs(w))
    return xp.where(w > rcond * wmax, 1.0 / xp.maximum(w, 1e-30), 0.0)


def gram_projection(
    G: jax.Array, *, ridge_reg: float = 0.0, rcond: float = 1e-6
) -> tuple[jax.Array, jax.Array]:
    """Factor G into (V, inv) with u_i = Σ_m (X_i V)²_m · inv_m.

    ``ridge_reg == 0`` reproduces ``leverage.leverage_from_gram``'s eigh
    pseudo-inverse (rank-deficient Bernstein Grams); ``ridge_reg > 0`` gives
    ridge leverage scores u_i(λ) = X_i (G + λI)⁻¹ X_iᵀ through the same
    eigenbasis (G and G + λI commute). ``rcond`` sits above the f32 noise
    floor so exactly-null modes are excluded regardless of how G was
    accumulated (see ``leverage_from_gram``).
    """
    w, V = jnp.linalg.eigh(G)
    return V, _spectrum_inverse(w, ridge_reg=ridge_reg, rcond=rcond, xp=jnp)


@dataclasses.dataclass
class ScoringResult:
    """Everything the sampling step of Algorithm 1 needs, for n points."""

    scores: np.ndarray             # (n,) sampling scores s_i (method-dependent)
    leverage: np.ndarray           # (n,) raw leverage-type scores u_i
    gram: np.ndarray               # (D, D) accumulated (possibly sketched) Gram
    hull_rows: np.ndarray | None   # ordered extremal row ids into the (n·r) P rows
    hull_points: np.ndarray | None  # unique point ids hit by hull_rows (sorted)
    n: int
    n_chunks: int
    rows_per_point: int = 1        # r: P rows per input point (row → point ÷ r)
    # accumulated P moments (s1, s2, n_rows) when the strategy tracked them —
    # the seed for the NEXT block's direction net in two-round streaming
    # (streaming.StreamingCoresetMaintainer); None otherwise
    moments: tuple | None = None

    @property
    def hull_candidates(self) -> np.ndarray | None:
        """Alias for ``hull_rows`` (the ε-kernel candidate set)."""
        return self.hull_rows


# jitted featurize closures keyed on (cfg, scaler bounds): build_coreset /
# coreset_scores construct a fresh engine per call, and without this cache
# each engine would carry its own empty jit trace cache and recompile the
# fused basis evaluation every call
_MCTM_FEATURIZE_CACHE: dict = {}


def _mctm_featurize(cfg, scaler) -> Callable[[jax.Array], tuple[jax.Array, jax.Array]]:
    """Fused basis+derivative evaluation for one chunk of Y.

    Returns (X̃ chunk (c, J·d), P chunk (c·J, d)). Single jitted trace per
    distinct chunk length; the math is exactly ``mctm.basis_features``.
    """
    from repro.core import mctm as M

    cache_key = (
        cfg,
        np.asarray(scaler.low).tobytes(),
        np.asarray(scaler.high).tobytes(),
    )
    cached = _MCTM_FEATURIZE_CACHE.get(cache_key)
    if cached is not None:
        return cached

    @jax.jit
    def featurize(Yc: jax.Array) -> tuple[jax.Array, jax.Array]:
        A, Ap = M.basis_features(cfg, scaler, Yc)
        c = A.shape[0]
        return A.reshape(c, cfg.J * cfg.d), Ap.reshape(c * cfg.J, cfg.d)

    if len(_MCTM_FEATURIZE_CACHE) > 64:  # bound growth across many configs
        _MCTM_FEATURIZE_CACHE.clear()
    _MCTM_FEATURIZE_CACHE[cache_key] = featurize
    return featurize


# --------------------------------------------------------------------------
# per-chunk steps. The pure bodies (pass1_update, leverage_chunk,
# hull_chunk_extremes) are shared with the sharded engine, where they run
# inside shard_map scan bodies; the jitted _acc_* wrappers exist so all
# single-host engines share trace caches.
# --------------------------------------------------------------------------


def pass1_update(G, s1, s2, X, P, sw, gram_dtype: str | None = None):
    """Pass-1 accumulation: Gram of √w-scaled rows + P first/second moments.

    Pure (traceable anywhere — jit, scan bodies, shard_map). ``P is None``
    skips the hull moments. ``gram_dtype="float64"`` casts the Gram update
    to f64 (requires an f64 carry and x64 mode; straight XᵀX — the Pallas
    gram kernel is f32-only); this is the sharded engine's f64 carry, the
    single-host ``TwoPassExact`` accumulates host-side instead.
    """
    Xw = X * sw[:, None]
    if gram_dtype == "float64":
        Xw64 = Xw.astype(jnp.float64)
        G = G + Xw64.T @ Xw64
    else:
        G = G + gram_matrix(Xw)
    if P is not None:
        s1 = s1 + jnp.sum(P, axis=0)
        s2 = s2 + jnp.dot(P.T, P, precision=HIGHEST)
    return G, s1, s2


def leverage_chunk(X, sw, V, inv):
    """u_i = Σ_m ((√w·X)_i V)²_m · inv_m for one chunk of rows. Pure."""
    Xw = X * sw[:, None]
    return jnp.sum(jnp.square(jnp.dot(Xw, V, precision=HIGHEST)) * inv, axis=1)


def hull_chunk_extremes(P, dirs, mask=None):
    """Per-chunk directional extremes: (max, argmax, min, argmin) per direction.

    Backend-dispatched like ``gram_matrix``: the fused Pallas kernel on TPU
    (each (m, block_rows) score tile is folded lane-wise into (m, 128)
    running-extreme accumulators in VMEM, reduced across lanes once per
    call), the jnp oracle elsewhere (``kernels.extremes``). ``mask`` (c·r,) excludes padding
    rows (sharded inputs padded to a shard multiple) by sending their scores
    to ∓inf. Pure — both the two-pass and one-pass scan bodies (single-host
    and sharded) fold this into their running extremes.
    """
    return directional_extremes(P, dirs, mask)


def _moments_update(s1, s2, P):
    """Hull-moment half of ``pass1_update`` (the f64-Gram host path still
    accumulates moments on device in f32). Pure."""
    return s1 + jnp.sum(P, axis=0), s2 + jnp.dot(P.T, P, precision=HIGHEST)


def _sketch_update(SX, s1, s2, X, P, sw, rows, signs):
    """CountSketch accumulation: SX += S_chunk · (√w·X) chunk. Pure."""
    Xw = X * sw[:, None]
    SX = SX.at[rows].add(signs[:, None] * Xw)
    if P is not None:
        s1, s2 = _moments_update(s1, s2, P)
    return SX, s1, s2


def _weighted_project(X, sw, omega):
    """z = (√w·X)Ω — the one-pass strategy's per-row emission (Ω=None → √w·X).
    Pure."""
    Xw = X * sw[:, None]
    return Xw if omega is None else Xw @ omega


def _z_leverage(z, V, inv):
    """Leverage read-off from stored (already √w-scaled) row blocks. Pure."""
    return jnp.sum(jnp.square(jnp.dot(z, V, precision=HIGHEST)) * inv, axis=1)


_acc_stats = jax.jit(pass1_update, static_argnames=("gram_dtype",))
# the fused one-pass sweep step (kernels.sweep): CountSketch + moments +
# extremes + z in ONE dispatch — the single-host realization shares this
# trace cache, the sharded scan bodies trace the op inline
_fused_sweep = jax.jit(
    fused_sweep_update,
    static_argnames=("want_z", "block_rows", "backend", "interpret"),
)
_acc_moments = jax.jit(_moments_update)
_acc_sketch = jax.jit(_sketch_update)
_leverage_chunk = jax.jit(leverage_chunk)
_hull_chunk = jax.jit(hull_chunk_extremes)
_project_rows = jax.jit(_weighted_project)
_z_leverage_jit = jax.jit(_z_leverage)
_weighted_rows = jax.jit(lambda X, sw: X * sw[:, None])


def sketch_plan(key, n: int, sketch_size: int):
    """CountSketch rows/signs for all n rows — identical draws to
    ``leverage.sketched_leverage`` so the strategies and the standalone
    baseline are comparable row for row."""
    k1, k2 = jax.random.split(key)
    rows = jax.random.randint(k1, (n,), 0, sketch_size)
    signs = jax.random.rademacher(k2, (n,), dtype=jnp.float32)
    return rows, signs


# --------------------------------------------------------------------------
# between-pass host algebra — shared by the single-host and sharded engines
# --------------------------------------------------------------------------


def projection_from_gram(G, method: str, ridge_reg: float, rcond: float = 1e-6):
    """(V, inv) via float64 host eigh — same thresholds as ``gram_projection``
    but solver noise far below the f32 Gram's own accumulation error, so
    leverage is stable across chunk sizes (and across shard layouts).

    G is (Jd)², so the f64 eigh costs microseconds regardless of n.
    """
    G = np.asarray(G, np.float64)
    w, V = np.linalg.eigh(G)
    reg = ridge_reg if method == "ridge-lss" else 0.0
    inv = _spectrum_inverse(w, ridge_reg=reg, rcond=rcond, xp=np)
    return jnp.asarray(V, jnp.float32), jnp.asarray(inv, jnp.float32)


def directions_from_moments(
    hull_key, s1, s2, n_rows: int, hull_k: int, oversample: int = 4
) -> np.ndarray:
    """Direction net from accumulated P moments (cov = E[ppᵀ] − μμᵀ).

    ``n_rows`` is the number of REAL P rows the moments were accumulated over
    (padding rows must be masked to zero before accumulation).
    """
    s1 = np.asarray(s1, np.float64)
    s2 = np.asarray(s2, np.float64)
    mu = s1 / max(n_rows, 1)
    cov = s2 / max(n_rows, 1) - np.outer(mu, mu)
    m = max(oversample * hull_k, 8)
    return hull_directions(hull_key, cov, m).astype(np.float32)


def upfront_directions(
    hull_key, p: int, hull_k: int, oversample: int = 4
) -> np.ndarray:
    """Direction net for one-pass strategies — buildable BEFORE any data is
    seen. Same ``hull_directions`` construction and identical random draws as
    the two-pass net, but with an identity covariance prior, so the
    ±principal axes degenerate to the coordinate axes of the P rows.
    """
    m = max(oversample * hull_k, 8)
    return hull_directions(hull_key, np.eye(p), m).astype(np.float32)


class RunningExtremes:
    """Host-side running (max, argmax, min, argmin) per direction across
    chunks. Strict comparisons keep the first-occurrence (lowest-row)
    tie-break of a dense ``np.argmax`` over the full score matrix — the same
    reduction the sharded engine performs across shards via all_gather.
    """

    def __init__(self, m: int):
        self.best_max = np.full(m, -np.inf, np.float32)
        self.best_min = np.full(m, np.inf, np.float32)
        self.best_imax = np.zeros(m, np.int64)
        self.best_imin = np.zeros(m, np.int64)

    def update(self, vmax, imax, vmin, imin, offset: int) -> None:
        # widen the device int32 argmax ids BEFORE adding the chunk offset:
        # n·rows_per_point may exceed int32 on the single-host path
        vmax, imax = np.asarray(vmax), np.asarray(imax, np.int64) + offset
        vmin, imin = np.asarray(vmin), np.asarray(imin, np.int64) + offset
        upd = vmax > self.best_max
        self.best_max[upd], self.best_imax[upd] = vmax[upd], imax[upd]
        upd = vmin < self.best_min
        self.best_min[upd], self.best_imin[upd] = vmin[upd], imin[upd]

    def candidates(self) -> np.ndarray:
        """ALL distinct extremal row ids, first-occurrence order (≤ 2m):
        truncating to hull_k rows here would discard genuine extremal points
        after the row → point dedup when rows_per_point > 1."""
        cand = np.concatenate([self.best_imax, self.best_imin])
        return stable_first_unique(cand)

    def state(self) -> dict[str, np.ndarray]:
        """Checkpointable snapshot (f32/int64 arrays — exact roundtrip)."""
        return {
            "max": self.best_max.copy(),
            "imax": self.best_imax.copy(),
            "min": self.best_min.copy(),
            "imin": self.best_imin.copy(),
        }

    def load(self, s) -> None:
        self.best_max = np.asarray(s["max"], np.float32).copy()
        self.best_imax = np.asarray(s["imax"], np.int64).copy()
        self.best_min = np.asarray(s["min"], np.float32).copy()
        self.best_imin = np.asarray(s["imin"], np.int64).copy()


def finalize_scoring(
    n: int, n_chunks: int, method: str, G, u, hull_rows, rows_per_point: int,
    moments: tuple | None = None,
) -> ScoringResult:
    """Assemble a ``ScoringResult`` from raw leverage + hull candidates."""
    u = np.asarray(u)
    if method == "root-l2":
        lev = np.sqrt(np.clip(u, 0.0, None))
    else:
        lev = u
    scores = lev + 1.0 / n
    hull_points = None
    if hull_rows is not None:
        hull_points = np.unique(hull_rows // rows_per_point)
    return ScoringResult(
        scores=scores,
        leverage=lev,
        gram=np.asarray(G),
        hull_rows=hull_rows,
        hull_points=hull_points,
        n=n,
        n_chunks=n_chunks,
        rows_per_point=rows_per_point,
        moments=moments,
    )


# --------------------------------------------------------------------------
# pass strategies
# --------------------------------------------------------------------------


class PassStrategy:
    """Base contract — see the module doc. Subclasses set ``one_pass`` /
    ``needs_key`` and implement ``init_state`` / ``update`` / ``gram``;
    ``result_gram`` defaults to ``gram`` and ``moments`` to the (s1, s2)
    slots of the state tuple."""

    one_pass = False
    needs_key = False
    n_data_passes = 2

    def begin(self, n: int, D: int, key):
        """Per-call plan (sketch rows/signs, Ω). ``None`` when stateless."""
        return None

    def slice_plan(self, plan, lo: int, hi: int) -> tuple:
        """The per-chunk slice of the plan fed to ``update``."""
        return ()

    def moments(self, state):
        return state[1], state[2]

    def result_gram(self, state, plan=None):
        return self.gram(state, plan)

    def fused_update(self, state, X, P, sw, plan_slice=(), dirs=None):
        """Per-chunk accumulation fused with the directional-extremes block.

        Returns ``(state', z, ext)`` where ``ext`` is the chunk-LOCAL
        (vmax, imax, vmin, imin) against ``dirs`` (``None`` when ``dirs``
        is). The engine drivers call THIS — strategies whose sweep can fold
        the hull reduction into their accumulation (``OnePassSketched`` via
        ``kernels.sweep``) override it; the default composes ``update`` with
        the standalone extremes kernel, which is exactly the unfused
        behavior.
        """
        state, z = self.update(state, X, P, sw, plan_slice)
        ext = _hull_chunk(P, dirs) if dirs is not None else None
        return state, z, ext

    # init_state / update / gram: subclass responsibility


@dataclasses.dataclass(frozen=True)
class TwoPassExact(PassStrategy):
    """Exact Gram accumulation; re-streams for the leverage/extremes pass.

    ``gram_dtype="float64"`` accumulates G host-side in f64 (order-independent
    to ~1e-15, so chunk/shard layouts agree even when genuine degree-6
    eigenvalues sit at the f32 rcond cutoff). The moments stay f32 on device —
    the direction net only needs the covariance's coarse shape.
    """

    gram_dtype: str = "float32"

    def __post_init__(self):
        if self.gram_dtype not in GRAM_DTYPES:
            raise ValueError(f"gram_dtype must be one of {GRAM_DTYPES}")

    def init_state(self, D: int, p: int | None):
        if self.gram_dtype == "float64":
            G = np.zeros((D, D), np.float64)
        else:
            G = jnp.zeros((D, D), jnp.float32)
        if p is None:
            return (G, None, None)
        return (G, jnp.zeros((p,), jnp.float32), jnp.zeros((p, p), jnp.float32))

    def update(self, state, X, P, sw, plan_slice=()):
        G, s1, s2 = state
        if self.gram_dtype == "float64":
            Xw = np.asarray(_weighted_rows(X, sw), np.float64)
            G = G + Xw.T @ Xw
            if P is not None:
                s1, s2 = _acc_moments(s1, s2, P)
            return (G, s1, s2), None
        return _acc_stats(G, s1, s2, X, P, sw), None

    def gram(self, state, plan=None):
        return state[0]


@dataclasses.dataclass(frozen=True)
class _SketchedBase(PassStrategy):
    """Shared CountSketch plan/state for the sketched strategies.

    ``gram_dtype="float64"`` carries the CountSketch accumulator SX in f64 —
    the sketched analogue of the two-pass f64 Gram carry (same x64
    requirement: the accumulation runs on device, so a silent f32 downcast
    must be refused loudly). The streamed rows, moments and emitted z blocks
    stay f32; only the accumulator (and the Grams read off it) widen.
    """

    sketch_size: int = 0
    gram_dtype: str = "float32"

    needs_key = True

    def __post_init__(self):
        if self.sketch_size <= 0:
            raise ValueError("sketched strategies require sketch_size > 0")
        if self.gram_dtype not in GRAM_DTYPES:
            raise ValueError(f"gram_dtype must be one of {GRAM_DTYPES}")

    def _acc_dtype(self):
        if self.gram_dtype == "float64":
            if not jax.config.jax_enable_x64:
                raise ValueError(
                    "gram_dtype='float64' on a sketched strategy carries the "
                    "CountSketch accumulator in f64 on device and requires "
                    "x64 mode (JAX_ENABLE_X64=1 / jax.config.update"
                    "('jax_enable_x64', True))"
                )
            return jnp.float64
        return jnp.float32

    def begin(self, n: int, D: int, key):
        return sketch_plan(key, n, self.sketch_size)

    def slice_plan(self, plan, lo: int, hi: int) -> tuple:
        return (plan[0][lo:hi], plan[1][lo:hi])

    def init_state(self, D: int, p: int | None):
        SX = jnp.zeros((self.sketch_size, D), self._acc_dtype())
        if p is None:
            return (SX, None, None)
        return (SX, jnp.zeros((p,), jnp.float32), jnp.zeros((p, p), jnp.float32))

    def gram(self, state, plan=None):
        return state[0].T @ state[0]


@dataclasses.dataclass(frozen=True)
class TwoPassSketched(_SketchedBase):
    """CountSketch Gram in pass 1; still re-streams for pass 2 (the engine's
    pre-refactor ``sketch_size`` behavior, kept as an explicit strategy).
    Pass 1 runs through the fused sweep op (sketch + hull moments in one
    dispatch, ``want_z=False`` — nothing is retained)."""

    def update(self, state, X, P, sw, plan_slice=()):
        rows, signs = plan_slice
        moments = (state[1], state[2]) if P is not None else None
        SX, _, _, mom = _fused_sweep(
            state[0], X, P, sw, rows, signs, moments=moments, want_z=False
        )
        s1, s2 = mom if mom is not None else (state[1], state[2])
        return (SX, s1, s2), None


@dataclasses.dataclass(frozen=True)
class OnePassSketched(_SketchedBase):
    """True one-pass sketched scoring — see the module doc.

    ``proj_size=None`` stores the √w-scaled rows themselves (Ω = identity):
    leverage is then exactly the classic sketched estimate ‖X̃_i R⁻¹‖², at
    O(n·Jd) retained memory. ``proj_size=q < Jd`` right-projects the retained
    rows through a fixed Gaussian Ω (drawn from the same key), shrinking
    retention to O(n·q); leverage of XΩ equals leverage of X whenever q ≥
    rank(X) (rank-preserving right-multiplication), and degrades gracefully
    below.

    ``track_moments=True`` additionally accumulates the P hull moments
    (Σp, Σppᵀ) in the same fused dispatch (``kernels.sweep`` carries them for
    free next to the sketch). The moments cannot improve THIS sweep's net —
    it is fixed before the data is seen — but they surface on the
    ``ScoringResult`` so a streaming caller can seed the NEXT block's net via
    ``directions_from_moments`` + ``score(hull_dirs=...)``: the two-round
    streaming direction net that fixes the coordinate-axes weakness without
    re-streaming.
    """

    proj_size: int | None = None
    track_moments: bool = False

    one_pass = True
    n_data_passes = 1

    def begin(self, n: int, D: int, key):
        rows, signs = sketch_plan(key, n, self.sketch_size)
        omega = None
        if self.proj_size is not None and self.proj_size < D:
            ok = jax.random.fold_in(key, 0x0E60)
            omega = jax.random.normal(
                ok, (D, self.proj_size), jnp.float32
            ) / np.sqrt(self.proj_size)
        return (rows, signs, omega)

    def slice_plan(self, plan, lo: int, hi: int) -> tuple:
        return (plan[0][lo:hi], plan[1][lo:hi], plan[2])

    def init_state(self, D: int, p: int | None = None):
        # without track_moments there is no (p, p) moment gram: the one-pass
        # net is fixed upfront, so the moments would be dead weight on the
        # hot streaming path
        SX = jnp.zeros((self.sketch_size, D), self._acc_dtype())
        if self.track_moments and p is not None:
            return (SX, jnp.zeros((p,), jnp.float32), jnp.zeros((p, p), jnp.float32))
        return (SX, None, None)

    def update(self, state, X, P, sw, plan_slice=()):
        state, z, _ = self.fused_update(state, X, P, sw, plan_slice)
        return state, z

    def fused_update(self, state, X, P, sw, plan_slice=(), dirs=None):
        """The fused realization (kernels.sweep): CountSketch + z emission +
        hull extremes (+ optional moments) in ONE dispatch — single VMEM
        residency on TPU, one fused XLA call on CPU. ``ext`` carries
        chunk-local indices; the driver folds them with its row offset, so
        the carried state (and any sweep checkpoint written from it) is laid
        out exactly as the unfused path's."""
        rows, signs, omega = plan_slice
        moments = (
            (state[1], state[2])
            if state[1] is not None and P is not None
            else None
        )
        keep_P = dirs is not None or moments is not None
        SX, z, ext, mom = _fused_sweep(
            state[0], X, P if keep_P else None, sw, rows, signs,
            dirs=dirs, omega=omega, moments=moments,
        )
        s1, s2 = mom if mom is not None else (state[1], state[2])
        return (SX, s1, s2), z, ext

    def gram(self, state, plan=None):
        """Projection Gram — (SXΩ)ᵀ(SXΩ), the Gram of the retained z rows."""
        SX = state[0]
        if plan is not None and plan[2] is not None:
            SX = SX @ plan[2]
        return SX.T @ SX

    def result_gram(self, state, plan=None):
        """Reported Gram stays the full (D, D) sketched Gram."""
        return state[0].T @ state[0]


_STRATEGY_NAMES = ("two-pass", "two-pass-sketched", "one-pass")


def resolve_strategy(
    strategy, *, sketch_size: int = 0, gram_dtype: str = "float32"
) -> PassStrategy:
    """Resolve the ``strategy=`` argument of ``score``.

    ``None`` decides from ``sketch_size``: exact two-pass without a sketch,
    ONE-pass sketched with one — a deliberate default change from the
    pre-strategy engine (which re-streamed a second sweep even when
    sketching): a sketch caller has already accepted constant-factor scores,
    so the second data sweep buys nothing the retained z rows don't. Note
    the trade: one-pass retains O(n·proj_size) sketch-projected rows and
    draws its hull net from the upfront (identity-prior) directions — pass
    ``strategy="two-pass-sketched"`` to keep the old O(chunk)-memory,
    moment-net sketched behavior. Strings name the built-ins; instances
    pass through untouched.
    """
    if isinstance(strategy, PassStrategy):
        return strategy
    if strategy is None:
        if sketch_size > 0:
            return OnePassSketched(sketch_size, gram_dtype)
        return TwoPassExact(gram_dtype)
    if strategy == "two-pass":
        return TwoPassExact(gram_dtype)
    if strategy == "two-pass-sketched":
        return TwoPassSketched(sketch_size, gram_dtype)
    if strategy == "one-pass":
        return OnePassSketched(sketch_size, gram_dtype)
    raise ValueError(
        f"unknown pass strategy {strategy!r} (expected one of {_STRATEGY_NAMES} "
        "or a PassStrategy instance)"
    )


# --------------------------------------------------------------------------
# the engine — one driver for every strategy
# --------------------------------------------------------------------------


class _SweepCheckpoints:
    """Per-sweep ``CheckpointManager`` pair for resumable chunk scans.

    ``root`` is a directory (or anything with a ``directory`` attribute);
    sweep 1 and sweep 2 get separate subdirectories so their cursors cannot
    shadow each other. Cadence comes from the ``ft`` config.
    """

    def __init__(self, root):
        from repro.checkpoint import CheckpointManager

        if not isinstance(root, (str, os.PathLike)):
            root = getattr(root, "directory")
        self.every = max(int(get_ft_config().sweep_ckpt_every_chunks), 1)
        self.mgr1 = CheckpointManager(os.path.join(str(root), "sweep1"), keep=2)
        self.mgr2 = CheckpointManager(os.path.join(str(root), "sweep2"), keep=2)


def _restore_like(template, restored):
    """Rehydrate a restored host pytree to its template's array flavors
    (np leaves stay np — the f64 host Gram — jax leaves go back on device)."""
    return jax.tree.map(
        lambda t, v: np.asarray(v) if isinstance(t, np.ndarray) else jnp.asarray(v),
        template,
        restored,
    )


class ScoringEngine:
    """Drives the pre-sampling phase of Algorithm 1 with O(chunk) memory
    (two-pass strategies; the one-pass strategy additionally retains the
    O(n·proj_size) sketch-projected rows it reads leverage from — see the
    module doc).

    Parameters
    ----------
    cfg, scaler: the MCTM model config and data scaler. The default featurizer
        is the fused Bernstein basis+derivative evaluation.
    featurize: optional override ``Y_chunk -> (X_chunk (c, D), P_chunk or
        None)`` for non-MCTM workloads (e.g. embedding features in the LM data
        pipeline; pass ``P_chunk = X_chunk`` to run hull selection on the
        feature rows themselves).
    chunk_size: rows of Y per chunk. Inputs with ``n <= chunk_size`` take the
        dense fast path (single basis evaluation). ``None``/0 → never chunk.
    rows_per_point: how many P rows each input point contributes (J for the
        MCTM derivative rows, 1 for generic features).
    gram_dtype: default Gram accumulation dtype for auto-resolved
        ``TwoPassExact`` strategies ("float64" → host-side f64, see above).
    """

    def __init__(
        self,
        cfg=None,
        scaler=None,
        *,
        featurize: Callable | None = None,
        chunk_size: int | None = DEFAULT_CHUNK,
        rows_per_point: int | None = None,
        hull_oversample: int = 4,
        gram_dtype: str = "float32",
    ):
        if featurize is None:
            if cfg is None or scaler is None:
                raise ValueError("either (cfg, scaler) or featurize is required")
            featurize = _mctm_featurize(cfg, scaler)
            rows_per_point = cfg.J
        if gram_dtype not in GRAM_DTYPES:
            raise ValueError(f"gram_dtype must be one of {GRAM_DTYPES}")
        self.cfg = cfg
        self.scaler = scaler
        self.featurize = featurize
        self.chunk_size = int(chunk_size) if chunk_size else 0
        self.rows_per_point = int(rows_per_point or 1)
        self.hull_oversample = hull_oversample
        self.gram_dtype = gram_dtype

    # ---------------------------------------------------------------- public

    def score(
        self,
        Y,
        *,
        method: str = "l2-hull",
        weights=None,
        key: jax.Array | None = None,
        sketch_size: int = 0,
        ridge_reg: float = 1.0,
        hull_k: int = 0,
        hull_key: jax.Array | None = None,
        hull_dirs=None,
        strategy=None,
        gram_dtype: str | None = None,
        sweep_ckpt=None,
        resume: bool = False,
    ) -> ScoringResult:
        """Score all n points (and optionally select hull candidates).

        ``method`` follows ``coreset.CORESET_METHODS`` minus "uniform" (which
        needs no scoring pass). ``weights`` (n,) triggers the √w-scaled
        leverage of Merge & Reduce reductions. ``hull_k > 0`` sizes the
        direction net and returns ALL distinct ε-kernel candidate rows in
        first-occurrence order (requires ``hull_key``); truncation to k
        points happens at coreset assembly (``coreset.exact_hull_points``).
        ``hull_dirs`` (m, p) overrides the direction net entirely — the
        two-round streaming hook: a caller with moments from a PREVIOUS
        block (``ScoringResult.moments`` + ``directions_from_moments``)
        seeds this sweep's net instead of the one-pass identity prior (or
        this sweep's own moment net on two-pass strategies).
        ``strategy`` selects the pass strategy (name or instance — see
        ``resolve_strategy``); the default is decided by ``sketch_size``.

        ``sweep_ckpt`` (a directory path) makes the chunk-scan state a
        checkpointable pytree saved every ``ft`` config
        ``sweep_ckpt_every_chunks`` chunks: strategy carry, running extremes,
        retained z rows / emitted leverage, and the chunk cursor. With
        ``resume=True`` a crashed sweep restarts from its cursor instead of
        row 0, and the result is bit-identical to the uninterrupted sweep
        (the carry is f32/f64/int64 arrays — exact save/restore roundtrip —
        and chunk accumulation order is preserved).
        """
        if method not in SCORE_METHODS:
            raise ValueError(f"unknown scoring method: {method}")
        Y = jnp.asarray(Y)
        n = int(Y.shape[0])
        if n == 0:
            raise ValueError("cannot score an empty dataset")
        if hull_k > 0 and hull_key is None:
            raise ValueError("hull_k > 0 requires hull_key")
        strat = resolve_strategy(
            strategy,
            sketch_size=sketch_size,
            gram_dtype=gram_dtype or self.gram_dtype,
        )
        if strat.needs_key and key is None:
            raise ValueError("sketch_size > 0 requires key")
        sqrt_w = (
            jnp.sqrt(jnp.asarray(weights, jnp.float32)) if weights is not None else None
        )
        if hull_dirs is not None and hull_k <= 0:
            raise ValueError("hull_dirs requires hull_k > 0")
        chunk = self.chunk_size if self.chunk_size > 0 else n
        return self._drive(
            strat, key, Y, sqrt_w, n, chunk, method, ridge_reg, hull_k, hull_key,
            hull_dirs=hull_dirs, sweep_ckpt=sweep_ckpt, resume=resume,
        )

    # --------------------------------------------------------------- helpers

    def _projection(self, G, method, ridge_reg, rcond=1e-6):
        """See ``projection_from_gram``."""
        return projection_from_gram(G, method, ridge_reg, rcond)

    def _directions(self, hull_key, s1, s2, n_rows: int, hull_k: int) -> np.ndarray:
        """Direction net from the accumulated P moments (cov = E[ppᵀ] − μμᵀ)."""
        return directions_from_moments(
            hull_key, s1, s2, n_rows, hull_k, self.hull_oversample
        )

    # ---------------------------------------------------------------- driver

    def _drive(
        self, strat, key, Y, sqrt_w, n, chunk, method, ridge_reg, hull_k, hull_key,
        hull_dirs=None, sweep_ckpt=None, resume=False,
    ) -> ScoringResult:
        """The shared chunk loop — ONE implementation for every strategy.

        Sweep 1 streams every chunk through ``strat.update`` (plus, for
        one-pass strategies, the fused hull running-extreme tracking against
        the upfront direction net). Two-pass strategies then re-stream the
        same chunks for leverage emission + extremes against the moment-
        derived net; one-pass strategies read leverage off the retained z
        blocks instead. Dense inputs (one chunk) featurize exactly once and
        share the block between sweeps.

        ``sweep_ckpt`` turns each sweep's carry into a checkpointable pytree
        (fixed-shape — restore validates shapes) saved every N chunks with a
        chunk cursor; ``resume`` skips the chunks the cursor covers. The
        between-sweep algebra (V, inv, direction net) is recomputed
        deterministically from the restored carry, so a resumed run is
        bit-identical to an uninterrupted one. Only this checkpointed path
        pays an extra shape-discovery featurize of chunk 0; the plain path
        is byte-for-byte the pre-existing loop (featurize call counts
        unchanged).
        """
        featurize = self.featurize
        r = self.rows_per_point
        want_hull = hull_k > 0
        # track_moments keeps P flowing even without a hull stage (the
        # moments seed a FUTURE sweep's net, not this one's)
        want_P = want_hull or getattr(strat, "track_moments", False)
        n_chunks = -(-n // chunk)
        ranges = [(lo, min(lo + chunk, n)) for lo in range(0, n, chunk)]

        def _prep(lo, hi):
            Xc, Pc = featurize(Y[lo:hi])
            if want_hull and Pc is None:
                raise ValueError("hull_k > 0 requires a featurize that returns P rows")
            if not want_P:
                Pc = None  # no hull stage → don't pay for the P moment gram
            swc = (
                sqrt_w[lo:hi]
                if sqrt_w is not None
                else jnp.ones((hi - lo,), jnp.float32)
            )
            return lo, hi, Xc, Pc, swc

        if n_chunks == 1:
            # dense fast path: featurize once, share the block between sweeps
            cached: list = []

            def get_chunk(lo, hi):
                if not cached:
                    cached.append(_prep(lo, hi))
                return cached[0]

        else:
            get_chunk = _prep

        # ---- sweep 1: strategy accumulation (the only data sweep for
        # one-pass strategies), O((Jd)²)-ish carried state
        state = plan = None
        z_blocks: list = []
        z_buf = None
        ext = dirs1 = None
        ck = _SweepCheckpoints(sweep_ckpt) if sweep_ckpt is not None else None
        done1 = 0
        if ck is not None:
            # fixed-shape checkpoint payloads need (D, p) before the loop:
            # probe-featurize chunk 0 for shapes (cached on the dense path)
            _, _, Xc0, Pc0, _ = get_chunk(*ranges[0])
            D = int(Xc0.shape[1])
            p = int(Pc0.shape[1]) if Pc0 is not None else None
            plan = strat.begin(n, D, key)
            state = strat.init_state(D, p)
            if strat.one_pass:
                width = D
                if plan is not None and plan[2] is not None:
                    width = int(plan[2].shape[1])
                z_buf = np.zeros((n, width), np.float32)
                if want_hull:
                    dirs1 = jnp.asarray(
                        hull_dirs
                        if hull_dirs is not None
                        else upfront_directions(
                            hull_key, p, hull_k, self.hull_oversample
                        )
                    )
                    ext = RunningExtremes(int(dirs1.shape[0]))

            def payload1():
                out = {"chunks": np.asarray(done1, np.int64), "state": state}
                if z_buf is not None:
                    out["z"] = z_buf
                if ext is not None:
                    out["ext"] = ext.state()
                return out

            if resume and ck.mgr1.latest_step() is not None:
                got = ck.mgr1.restore(jax.tree.map(np.asarray, payload1()))
                done1 = int(got["chunks"])
                state = _restore_like(state, got["state"])
                if z_buf is not None:
                    z_buf = np.asarray(got["z"], np.float32)
                if ext is not None:
                    ext.load(got["ext"])

        for ci, (lo, hi) in enumerate(ranges):
            if ci < done1:
                continue
            lo, hi, Xc, Pc, swc = get_chunk(lo, hi)
            if state is None:
                D = int(Xc.shape[1])
                p = int(Pc.shape[1]) if Pc is not None else None
                plan = strat.begin(n, D, key)
                state = strat.init_state(D, p)
                if strat.one_pass and want_hull:
                    dirs1 = jnp.asarray(
                        hull_dirs
                        if hull_dirs is not None
                        else upfront_directions(
                            hull_key, p, hull_k, self.hull_oversample
                        )
                    )
                    ext = RunningExtremes(int(dirs1.shape[0]))
            state, z, extb = strat.fused_update(
                state, Xc, Pc, swc, strat.slice_plan(plan, lo, hi), dirs=dirs1
            )
            if z is not None:
                if z_buf is not None:
                    z_buf[lo:hi] = np.asarray(z)
                else:
                    z_blocks.append(z)
            if ext is not None:
                ext.update(*extb, offset=lo * r)
            if ck is not None and ((ci + 1) % ck.every == 0 or ci + 1 == n_chunks):
                done1 = ci + 1
                ck.mgr1.save(ci + 1, payload1())
            maybe_inject("scoring", ci + 1)

        # ---- between sweeps: (Jd)²-scale host algebra only
        V, inv = self._projection(strat.gram(state, plan), method, ridge_reg)

        hull_rows = None
        if strat.one_pass:
            if z_buf is not None:
                u = np.empty(n, np.float32)
                for lo, hi in ranges:  # chunk-sized device transfers
                    u[lo:hi] = np.asarray(
                        _z_leverage_jit(jnp.asarray(z_buf[lo:hi]), V, inv)
                    )
            else:
                u = np.concatenate(
                    [np.asarray(_z_leverage_jit(z, V, inv)) for z in z_blocks]
                )
            if ext is not None:
                hull_rows = ext.candidates()
        else:
            # ---- sweep 2: leverage emission + fused directional hull extremes
            if want_hull:
                if hull_dirs is not None:
                    dirs = jnp.asarray(hull_dirs)
                else:
                    s1, s2 = strat.moments(state)
                    dirs = jnp.asarray(
                        self._directions(hull_key, s1, s2, n * r, hull_k)
                    )
                ext = RunningExtremes(int(dirs.shape[0]))
            u = np.zeros(n, np.float32)
            done2 = 0
            if ck is not None:

                def payload2():
                    out = {"chunks": np.asarray(done2, np.int64), "u": u}
                    if ext is not None:
                        out["ext"] = ext.state()
                    return out

                if resume and ck.mgr2.latest_step() is not None:
                    got = ck.mgr2.restore(jax.tree.map(np.asarray, payload2()))
                    done2 = int(got["chunks"])
                    u = np.asarray(got["u"], np.float32)
                    if ext is not None:
                        ext.load(got["ext"])
            for ci, (lo, hi) in enumerate(ranges):
                if ci < done2:
                    continue
                lo, hi, Xc, Pc, swc = get_chunk(lo, hi)
                u[lo:hi] = np.asarray(_leverage_chunk(Xc, swc, V, inv))
                if ext is not None:
                    ext.update(*_hull_chunk(Pc, dirs), offset=lo * r)
                if ck is not None and ((ci + 1) % ck.every == 0 or ci + 1 == n_chunks):
                    done2 = ci + 1
                    ck.mgr2.save(ci + 1, payload2())
                maybe_inject("scoring", n_chunks + ci + 1)
            if ext is not None:
                hull_rows = ext.candidates()

        moments = None
        if getattr(strat, "track_moments", False) and state[1] is not None:
            moments = (np.asarray(state[1]), np.asarray(state[2]), n * r)
        return finalize_scoring(
            n, n_chunks, method, strat.result_gram(state, plan), u, hull_rows, r,
            moments=moments,
        )


def score_chunks(cfg, scaler, Y, **kwargs) -> ScoringResult:
    """Functional one-shot entry: ``ScoringEngine(cfg, scaler).score(Y, ...)``.

    ``chunk_size`` may be passed alongside the ``score`` kwargs.
    """
    chunk_size = kwargs.pop("chunk_size", DEFAULT_CHUNK)
    engine = ScoringEngine(cfg, scaler, chunk_size=chunk_size)
    return engine.score(Y, **kwargs)
