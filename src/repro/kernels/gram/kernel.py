"""Tiled Gram-matrix accumulation  G = XᵀX  (leverage-score front-end).

Grid iterates over row blocks of X; the (D, D) output block is revisited by
every grid step (index_map → (0, 0)) and accumulated in VMEM — the standard
Pallas reduction idiom. Row blocks are (256, D) with D padded to a lane
multiple; the MXU sees (D, 256) @ (256, D) per step.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEFAULT_BLOCK_ROWS = 256
# Mosaic's default for an f32 dot is a single bf16 pass (inputs rounded to an
# 8-bit mantissa); the scoring statistics need f32, so every dot asks for it
HIGHEST = jax.lax.Precision.HIGHEST


def _kernel(x_ref, g_ref):
    @pl.when(pl.program_id(0) == 0)
    def _init():
        g_ref[...] = jnp.zeros_like(g_ref)

    x = x_ref[...].astype(jnp.float32)
    g_ref[...] += jax.lax.dot_general(
        x, x, (((0,), (0,)), ((), ())),
        precision=HIGHEST, preferred_element_type=jnp.float32,
    )


def gram_kernel(
    x: jax.Array, *, block_rows: int = DEFAULT_BLOCK_ROWS, interpret: bool = False
) -> jax.Array:
    """x: (n, D) with n % block_rows == 0, D lane-padded → (D, D) f32."""
    n, D = x.shape
    grid = (n // block_rows,)
    return pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((block_rows, D), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((D, D), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((D, D), jnp.float32),
        interpret=interpret,
    )(x)
