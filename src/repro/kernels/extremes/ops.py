"""Backend-dispatching wrapper: fused directional extremes per row block.

``directional_extremes`` mirrors ``gram_matrix``'s dispatch: the tiled Pallas
running-(max, argmax) kernel compiled on TPU, the XLA oracle elsewhere.
Interpret-mode Pallas is a *debug* path (orders of magnitude slower than XLA
on CPU) and only runs when explicitly requested.

The Pallas path realizes row masking as a valid-row COUNT (rows ≥ n_valid
score ∓inf inside the kernel): every engine call site masks a prefix-ones /
tail-zeros pattern (real rows followed by shard padding), so the count is
exactly ``mask.sum()``. The jnp oracle honors arbitrary masks.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.extremes.kernel import EXTREMES_BLOCK_ROWS, LANE, extremes_kernel
from repro.kernels.extremes.ref import directional_extremes_ref


def default_extremes_backend() -> str:
    """'pallas' (compiled kernel) on TPU, 'jnp' (XLA oracle) elsewhere."""
    return "pallas" if jax.default_backend() == "tpu" else "jnp"


def _pad_to(x: jax.Array, rows: int, cols: int) -> jax.Array:
    out = jnp.zeros((rows, cols), jnp.float32)
    return out.at[: x.shape[0], : x.shape[1]].set(x.astype(jnp.float32))


@partial(jax.jit, static_argnames=("block_rows", "interpret"))
def _extremes_pallas(P, dirs, n_valid, *, block_rows: int, interpret: bool):
    """Pads rows/lanes (pad rows are masked by the n_valid count, pad lanes
    contribute zero to the scores, pad directions are sliced off). The row
    tile is a multiple of LANE, clamped to the lane-padded row count."""
    n, d = P.shape
    m = dirs.shape[0]
    block_rows = min(-(-block_rows // LANE), -(-n // LANE)) * LANE
    n_pad = -(-n // block_rows) * block_rows
    d_pad = -(-d // LANE) * LANE
    m_pad = -(-m // LANE) * LANE
    nv = jnp.reshape(jnp.asarray(n_valid, jnp.int32), (1, 1))
    vmax, imax, vmin, imin = extremes_kernel(
        _pad_to(P, n_pad, d_pad),
        _pad_to(dirs, m_pad, d_pad),
        nv,
        block_rows=block_rows,
        interpret=interpret,
    )
    return vmax[0, :m], imax[0, :m], vmin[0, :m], imin[0, :m]


def directional_extremes(
    P: jax.Array,
    dirs: jax.Array,
    mask: jax.Array | None = None,
    *,
    block_rows: int = EXTREMES_BLOCK_ROWS,
    backend: str | None = None,
    interpret: bool | None = None,
):
    """Fused (max, argmax, min, argmin) of ``dirs @ Pᵀ`` per direction.

    P: (rows, d) points, dirs: (m, d) unit directions, mask: optional (rows,)
    row validity (the Pallas backend requires the engines' prefix-ones
    pattern; the jnp oracle accepts any mask). Returns per-direction
    (vmax, imax, vmin, imin) with indices into P's rows. Pure — traceable
    inside jit / lax.scan / shard_map bodies; the backend branch resolves at
    trace time exactly like ``gram_matrix``.
    """
    if interpret and backend is None:
        backend = "pallas"
    if backend is None:
        backend = default_extremes_backend()
    if backend == "jnp":
        return directional_extremes_ref(P, dirs, mask)
    if backend != "pallas":
        raise ValueError(f"unknown extremes backend: {backend}")
    n_valid = P.shape[0] if mask is None else jnp.sum(mask.astype(jnp.int32))
    return _extremes_pallas(
        P, dirs, n_valid, block_rows=block_rows, interpret=bool(interpret)
    )
