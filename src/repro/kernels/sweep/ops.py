"""Backend-dispatching wrapper: the fused one-pass sweep step.

``fused_sweep_update`` mirrors ``gram_matrix``/``directional_extremes``'s
dispatch contract: the single-VMEM-residency Pallas kernel compiled on TPU,
the fused-jnp oracle (one XLA dispatch with the two-level extremes
reduction) elsewhere. Interpret-mode Pallas is a *debug* path and only runs
when explicitly requested. ``block_rows`` is the same tuning knob as
``kernels.extremes`` (the two kernels tile the same streamed rows).

The Pallas path realizes row masking as a valid-POINT count (prefix-ones
masks only — real rows, then shard padding; the P-row validity is the count
scaled by rows-per-point). The jnp oracle honors arbitrary masks. The f64
CountSketch accumulator (``gram_dtype="float64"``) is oracle-only, exactly
like the f64 Gram carry bypasses the Pallas gram kernel.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.extremes.kernel import DEFAULT_BLOCK_ROWS, LANE
from repro.kernels.sweep.kernel import sweep_kernel
from repro.kernels.sweep.ref import fused_sweep_ref

__all__ = ["DEFAULT_BLOCK_ROWS", "default_sweep_backend", "fused_sweep_update"]


def default_sweep_backend() -> str:
    """'pallas' (compiled kernel) on TPU, 'jnp' (fused XLA oracle) elsewhere."""
    return "pallas" if jax.default_backend() == "tpu" else "jnp"


def _pad_to(x, rows: int, cols: int):
    out = jnp.zeros((rows, cols), jnp.float32)
    return out.at[: x.shape[0], : x.shape[1]].set(x.astype(jnp.float32))


# jitted so the kernel's custom call is named after this wrapper
# (``%_sweep_pallas.N``), as the gram and extremes kernels are
@partial(jax.jit, static_argnames=("want_z", "block_rows", "interpret"))
def _sweep_pallas(
    SX, X, P, sw, rows, signs, n_valid, dirs, omega, moments,
    *, want_z: bool, block_rows: int, interpret: bool,
):
    """Pads rows/lanes, runs the kernel, folds the deltas into the carried
    state. Pad X rows get sw = signs = 0 (sketch/z/moment-inert); pad P rows
    are zero and masked off the extremes by the validity count."""
    n, D = X.shape
    sk = SX.shape[0]
    block_rows = min(block_rows, -(-n // 8) * 8)
    n_pad = -(-n // block_rows) * block_rows
    D_pad = -(-D // LANE) * LANE
    sk_pad = -(-sk // 8) * 8
    xp = _pad_to(X, n_pad, D_pad)
    swp = jnp.zeros((n_pad, 1), jnp.float32).at[:n, 0].set(sw)
    rowsp = jnp.zeros((1, n_pad), jnp.int32).at[0, :n].set(rows.astype(jnp.int32))
    signsp = jnp.zeros((1, n_pad), jnp.float32).at[0, :n].set(signs)
    nv = jnp.reshape(jnp.asarray(n_valid, jnp.int32), (1, 1))

    r = 1
    pp = dirsp = omegap = None
    if P is not None:
        r = P.shape[0] // n
        d = P.shape[1]
        d_pad = -(-d // LANE) * LANE
        pp = _pad_to(P, n_pad * r, d_pad)
    if dirs is not None:
        m = dirs.shape[0]
        m_pad = -(-m // LANE) * LANE
        dirsp = _pad_to(dirs, m_pad, d_pad)
    if omega is not None:
        q = omega.shape[1]
        omegap = _pad_to(omega, D_pad, -(-q // LANE) * LANE)

    outs = list(
        sweep_kernel(
            xp, pp, swp, rowsp, signsp, nv, dirsp, omegap,
            sketch_rows=sk_pad,
            r=r,
            want_moments=moments is not None,
            want_z=want_z,
            block_rows=block_rows,
            interpret=interpret,
        )
    )
    SX = SX + outs.pop(0)[:sk, :D]
    z = None
    if want_z:
        width = q if omega is not None else D
        z = outs.pop(0)[:n, :width]
    ext = None
    if dirs is not None:
        vmax, imax, vmin, imin = (outs.pop(0) for _ in range(4))
        ext = (vmax[0, :m], imax[0, :m], vmin[0, :m], imin[0, :m])
    out_moments = None
    if moments is not None:
        s1, s2 = moments
        out_moments = (s1 + outs.pop(0)[0, :d], s2 + outs.pop(0)[:d, :d])
    return SX, z, ext, out_moments


def fused_sweep_update(
    SX,
    X,
    P,
    sw,
    rows,
    signs,
    *,
    dirs=None,
    omega=None,
    mask=None,
    moments=None,
    want_z: bool = True,
    block_rows: int = DEFAULT_BLOCK_ROWS,
    backend: str | None = None,
    interpret: bool | None = None,
):
    """One fused sweep step over a (chunk, D) basis block.

    SX: (sketch, D) CountSketch carry (f32, or f64 under x64 — oracle only);
    X: (c, D) basis rows; P: (c·r, d) derivative rows or None; sw: (c,)
    √weights; rows/signs: the chunk's CountSketch plan slice; dirs: (m, d)
    direction net or None; omega: (D, q) projection or None; mask: optional
    row validity — per point (c,) or per P row (c·r,); the Pallas backend
    requires the engines' prefix-ones pattern. moments: optional (Σp, Σppᵀ)
    carry to accumulate. Returns ``(SX', z, ext, moments')`` — z the emitted
    (√w·X)Ω block (None when ``want_z`` is False), ext the block-LOCAL
    (vmax, imax, vmin, imin) against dirs (None when dirs is — the caller
    folds them into its running extremes with its own row offset, keeping
    engine state layouts byte-identical to the unfused path), moments' the
    accumulated moment carry. Pure — traceable inside jit / lax.scan /
    shard_map bodies; the backend branch resolves at trace time exactly like
    ``gram_matrix``.
    """
    if interpret and backend is None:
        backend = "pallas"
    if backend is None:
        backend = default_sweep_backend()
    if backend == "jnp":
        return fused_sweep_ref(
            SX, X, P, sw, rows, signs,
            dirs=dirs, omega=omega, mask=mask, moments=moments,
            want_z=want_z, tile=block_rows,
        )
    if backend != "pallas":
        raise ValueError(f"unknown sweep backend: {backend}")
    if SX.dtype != jnp.float32:
        raise ValueError(
            "the fused sweep Pallas kernel is f32-only — "
            "gram_dtype='float64' sketch accumulation runs on the jnp oracle"
        )
    if mask is None:
        n_valid = X.shape[0]
    else:
        n_valid = jnp.sum((mask > 0).astype(jnp.int32))
        if P is not None and mask.shape[0] == P.shape[0] != X.shape[0]:
            # per-P-row mask → valid-point count
            n_valid = n_valid // (P.shape[0] // X.shape[0])
    return _sweep_pallas(
        SX, X, P, sw, rows, signs, n_valid, dirs, omega, moments,
        want_z=want_z, block_rows=block_rows, interpret=bool(interpret),
    )
