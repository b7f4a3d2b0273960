"""Fused directional-extremes Pallas kernel: running (max, argmax) accumulator.

The hull stage of Algorithm 1 scores every derivative row against a direction
net — ``dirs @ Pᵀ`` followed by per-direction argmax/argmin. Done naively the
(m, rows) score block round-trips HBM; done here the grid walks row blocks of
P, the MXU emits one (m, block_rows) score tile per step, and the four
running extremes (max, argmax, min, argmin) are folded into revisited
(1, m) output blocks that never leave VMEM — the same accumulation idiom as
the Gram kernel, with an argmax carried next to the max.

Row validity is a *count*: rows with global index ≥ n_valid score ∓inf. Every
engine mask is a prefix-ones pattern (real rows, then shard padding), so the
count is the whole mask — see ``ops.directional_extremes``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEFAULT_BLOCK_ROWS = 512
LANE = 128
# Mosaic's default for an f32 dot is a single bf16 pass (inputs rounded to an
# 8-bit mantissa); the scoring statistics need f32, so every dot asks for it
HIGHEST = jax.lax.Precision.HIGHEST


def _kernel(p_ref, d_ref, nv_ref, vmax_ref, imax_ref, vmin_ref, imin_ref,
            *, block_rows: int):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        vmax_ref[...] = jnp.full(vmax_ref.shape, -jnp.inf, jnp.float32)
        imax_ref[...] = jnp.zeros(imax_ref.shape, jnp.int32)
        vmin_ref[...] = jnp.full(vmin_ref.shape, jnp.inf, jnp.float32)
        imin_ref[...] = jnp.zeros(imin_ref.shape, jnp.int32)

    # (m, block_rows) score tile: contraction over the (lane-padded) feature
    # dim; zero-padded lanes contribute nothing
    S = jax.lax.dot_general(
        d_ref[...], p_ref[...], (((1,), (1,)), ((), ())),
        precision=HIGHEST, preferred_element_type=jnp.float32,
    )
    base = i * block_rows
    ridx = base + jax.lax.broadcasted_iota(jnp.int32, S.shape, 1)
    valid = ridx < nv_ref[0, 0]
    smax = jnp.where(valid, S, -jnp.inf)
    smin = jnp.where(valid, S, jnp.inf)

    # within-block argmax picks the lowest row; strict comparisons against the
    # running best keep the first-occurrence (lowest-global-row) tie-break of
    # a dense argmax — identical to scoring.RunningExtremes
    lv = jnp.max(smax, axis=1)[None, :]
    gi = (base + jnp.argmax(smax, axis=1).astype(jnp.int32))[None, :]
    upd = lv > vmax_ref[...]
    imax_ref[...] = jnp.where(upd, gi, imax_ref[...])
    vmax_ref[...] = jnp.where(upd, lv, vmax_ref[...])

    lv = jnp.min(smin, axis=1)[None, :]
    gi = (base + jnp.argmin(smin, axis=1).astype(jnp.int32))[None, :]
    upd = lv < vmin_ref[...]
    imin_ref[...] = jnp.where(upd, gi, imin_ref[...])
    vmin_ref[...] = jnp.where(upd, lv, vmin_ref[...])


def extremes_kernel(
    p: jax.Array,
    dirs: jax.Array,
    n_valid: jax.Array,
    *,
    block_rows: int = DEFAULT_BLOCK_ROWS,
    interpret: bool = False,
):
    """p: (n_pad, d_pad) rows, dirs: (m_pad, d_pad), n_valid: (1, 1) int32.

    n_pad % block_rows == 0, d_pad lane-padded, m_pad lane-padded (it is the
    lane dimension of the outputs). Returns (vmax, imax, vmin, imin), each
    (1, m_pad) with indices global row ids into p.
    """
    n, _ = p.shape
    m_pad = dirs.shape[0]
    grid = (n // block_rows,)
    out = jax.ShapeDtypeStruct((1, m_pad), jnp.float32)
    iout = jax.ShapeDtypeStruct((1, m_pad), jnp.int32)
    return pl.pallas_call(
        functools.partial(_kernel, block_rows=block_rows),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_rows, p.shape[1]), lambda i: (i, 0)),
            pl.BlockSpec((m_pad, dirs.shape[1]), lambda i: (0, 0)),
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, m_pad), lambda i: (0, 0)),
            pl.BlockSpec((1, m_pad), lambda i: (0, 0)),
            pl.BlockSpec((1, m_pad), lambda i: (0, 0)),
            pl.BlockSpec((1, m_pad), lambda i: (0, 0)),
        ],
        out_shape=[out, iout, out, iout],
        interpret=interpret,
    )(p, dirs, n_valid)
