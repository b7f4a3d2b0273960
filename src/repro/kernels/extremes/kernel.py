"""Fused directional-extremes Pallas kernel: a lane-wise running fold.

The hull stage of Algorithm 1 scores every derivative row against a direction
net — ``dirs @ Pᵀ`` followed by per-direction argmax/argmin. Done naively the
(m, rows) score block round-trips HBM; done here the grid walks row blocks of
P and the MXU emits one (m, block_rows) score tile per step, which is folded
into four lane-partial accumulators of shape (m, 128) held in VMEM scratch
for the whole grid: per lane position, the best max and min so far and the
global row of each. A step folds its tile one 128-lane slice at a time with
elementwise compares and selects only; the cross-lane reduction to the four
(1, m) outputs runs once, on the last step.

Tie-break: the dense argmax's lowest global row. Within a lane position the
rows arrive in increasing order and strict ``>`` / ``<`` keep the first, so
each lane holds the lowest row of its own best value; the last step takes,
among the lanes that hold the overall best, the lowest row. The lowest row
that attains the best lives in some lane, whose accumulator holds that value
and that row, so it is the one picked. A call with no valid row returns
∓inf with index 0, as the dense argmax of an all-∓inf row does.

Row validity is a *count*: rows with global index ≥ n_valid never update the
accumulators. Every engine mask is a prefix-ones pattern (real rows, then
shard padding), so the count is the whole mask — see
``ops.directional_extremes``. Only a block that reaches past the count is
masked.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# the streamed-row tile of the sweep kernel (kernels/sweep imports it)
DEFAULT_BLOCK_ROWS = 512
# the extremes kernel's own row tile, a multiple of LANE
EXTREMES_BLOCK_ROWS = 512
LANE = 128
# Mosaic's default for an f32 dot is a single bf16 pass (inputs rounded to an
# 8-bit mantissa); the scoring statistics need f32, so every dot asks for it
HIGHEST = jax.lax.Precision.HIGHEST


def _fold(p_ref, d_ref, base, n_valid, amax_ref, rmax_ref, amin_ref,
          rmin_ref, *, masked: bool):
    """Score one row block and fold it into the lane accumulators."""
    # (m, block_rows) score tile: contraction over the (lane-padded) feature
    # dim; zero-padded lanes contribute nothing. It is formed here, inside
    # the branch that folds it: formed before the branch, a call took 29–47%
    # longer on a TPU v5e at the J=2 and J=10 chunk shapes
    S = jax.lax.dot_general(
        d_ref[...], p_ref[...], (((1,), (1,)), ((), ())),
        precision=HIGHEST, preferred_element_type=jnp.float32,
    )
    lane = jax.lax.broadcasted_iota(jnp.int32, amax_ref.shape, 1)
    for s in range(S.shape[1] // LANE):
        t = S[:, s * LANE:(s + 1) * LANE]
        row = base + s * LANE + lane
        up = t > amax_ref[...]
        dn = t < amin_ref[...]
        if masked:
            ok = row < n_valid
            up = up & ok
            dn = dn & ok
        amax_ref[...] = jnp.where(up, t, amax_ref[...])
        rmax_ref[...] = jnp.where(up, row, rmax_ref[...])
        amin_ref[...] = jnp.where(dn, t, amin_ref[...])
        rmin_ref[...] = jnp.where(dn, row, rmin_ref[...])


def _lowest_row_of_best(acc, rows, best):
    """(1, m) lowest row among the lanes whose accumulator equals ``best``."""
    big = jnp.iinfo(jnp.int32).max
    return jnp.min(jnp.where(acc == best, rows, big), axis=1)[None, :]


def _kernel(p_ref, d_ref, nv_ref, vmax_ref, imax_ref, vmin_ref, imin_ref,
            amax_ref, rmax_ref, amin_ref, rmin_ref, *, block_rows: int):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        amax_ref[...] = jnp.full(amax_ref.shape, -jnp.inf, jnp.float32)
        rmax_ref[...] = jnp.zeros(rmax_ref.shape, jnp.int32)
        amin_ref[...] = jnp.full(amin_ref.shape, jnp.inf, jnp.float32)
        rmin_ref[...] = jnp.zeros(rmin_ref.shape, jnp.int32)

    base = i * block_rows
    n_valid = nv_ref[0, 0]
    args = (p_ref, d_ref, base, n_valid, amax_ref, rmax_ref, amin_ref, rmin_ref)
    ragged = base + block_rows > n_valid

    @pl.when(jnp.logical_not(ragged))
    def _full():
        _fold(*args, masked=False)

    @pl.when(ragged)
    def _tail():
        _fold(*args, masked=True)

    @pl.when(i == pl.num_programs(0) - 1)
    def _reduce_lanes():
        amax, amin = amax_ref[...], amin_ref[...]
        vmax = jnp.max(amax, axis=1, keepdims=True)
        vmin = jnp.min(amin, axis=1, keepdims=True)
        vmax_ref[...] = vmax[:, 0][None, :]
        imax_ref[...] = _lowest_row_of_best(amax, rmax_ref[...], vmax)
        vmin_ref[...] = vmin[:, 0][None, :]
        imin_ref[...] = _lowest_row_of_best(amin, rmin_ref[...], vmin)


def extremes_kernel(
    p: jax.Array,
    dirs: jax.Array,
    n_valid: jax.Array,
    *,
    block_rows: int = EXTREMES_BLOCK_ROWS,
    interpret: bool = False,
):
    """p: (n_pad, d_pad) rows, dirs: (m_pad, d_pad), n_valid: (1, 1) int32.

    block_rows % LANE == 0, n_pad % block_rows == 0, d_pad lane-padded, m_pad
    lane-padded (it is the lane dimension of the outputs). Returns (vmax,
    imax, vmin, imin), each (1, m_pad) with indices global row ids into p.
    """
    n, _ = p.shape
    m_pad = dirs.shape[0]
    # the fold walks whole 128-lane slices of the score tile
    assert block_rows % LANE == 0 and n % block_rows == 0, (n, block_rows)
    grid = (n // block_rows,)
    out = jax.ShapeDtypeStruct((1, m_pad), jnp.float32)
    iout = jax.ShapeDtypeStruct((1, m_pad), jnp.int32)
    acc = pltpu.VMEM((m_pad, LANE), jnp.float32)
    rows = pltpu.VMEM((m_pad, LANE), jnp.int32)
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
        scratch_shapes=[acc, rows, acc, rows],
        interpret=interpret,
    )(p, dirs, n_valid)
