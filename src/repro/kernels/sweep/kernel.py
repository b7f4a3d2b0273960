"""Fused one-pass sweep Pallas kernel: one VMEM residency per basis block.

The pre-fused one-pass engine issued three ops per chunk — the CountSketch
scatter, the sketch-projected z emission, and the directional-extremes
reduction — each round-tripping the (chunk, Jd) basis block through HBM.
Here the grid walks row blocks ONCE and everything the sweep accumulates
stays resident:

* ``dSX += E_b @ (√w·X_b)`` — the CountSketch update realized as a one-hot
  matmul (``E_b[s, i] = sign_i·[row_i = s]``), which puts the scatter on the
  MXU instead of a serialized gather/scatter unit;
* ``z_b = (√w·X_b)Ω`` (or the scaled rows themselves when Ω is identity) —
  written straight from the registers that produced the sketch update;
* the running (max, argmax, min, argmin) of ``dirs @ P_bᵀ`` — the same
  revisited-accumulator idiom as ``kernels.extremes``, folded next to the
  sketch so the derivative rows are read once;
* optionally ``(Σp, Σppᵀ)`` hull-moment accumulation for the sketched
  two-pass strategy's pass 1.

Outputs follow the accumulate-OUTSIDE convention: the kernel emits the
block-scan's *delta* (dSX, moment deltas, block-local extremes) and the ops
wrapper folds them into the caller's carried state — the (sketch, D)-sized
add is noise next to the streamed rows, and it keeps the engine state layout
(and sweep checkpoints) byte-identical to the unfused path.

Row validity is a count (prefix-ones masks only, like ``kernels.extremes``):
padded X rows carry sw = 0 so they cannot touch the sketch, z or moments;
padded P rows score ∓inf via ``n_valid``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.extremes.kernel import DEFAULT_BLOCK_ROWS, HIGHEST, LANE  # noqa: F401


def _kernel(*refs, block_rows: int, r: int, has_p: bool, hull: bool,
            has_omega: bool, want_moments: bool, want_z: bool):
    it = iter(refs)
    x_ref = next(it)
    p_ref = next(it) if has_p else None
    sw_ref = next(it)
    rows_ref = next(it)
    signs_ref = next(it)
    nv_ref = next(it)
    dirs_ref = next(it) if hull else None
    omega_ref = next(it) if has_omega else None
    dsx_ref = next(it)
    z_ref = next(it) if want_z else None
    if hull:
        vmax_ref, imax_ref, vmin_ref, imin_ref = (
            next(it), next(it), next(it), next(it)
        )
    if want_moments:
        s1_ref, s2_ref = next(it), next(it)

    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        dsx_ref[...] = jnp.zeros(dsx_ref.shape, jnp.float32)
        if hull:
            vmax_ref[...] = jnp.full(vmax_ref.shape, -jnp.inf, jnp.float32)
            imax_ref[...] = jnp.zeros(imax_ref.shape, jnp.int32)
            vmin_ref[...] = jnp.full(vmin_ref.shape, jnp.inf, jnp.float32)
            imin_ref[...] = jnp.zeros(imin_ref.shape, jnp.int32)
        if want_moments:
            s1_ref[...] = jnp.zeros(s1_ref.shape, jnp.float32)
            s2_ref[...] = jnp.zeros(s2_ref.shape, jnp.float32)

    # (block_rows, D) weighted rows; padded rows have sw = 0
    Xw = x_ref[...] * sw_ref[...]

    # CountSketch as a one-hot matmul: E (sketch, block_rows) has sign_i at
    # (row_i, i), zero elsewhere (pad rows: sign 0 → no contribution)
    E = jnp.where(
        jax.lax.broadcasted_iota(
            jnp.int32, (dsx_ref.shape[0], block_rows), 0
        ) == rows_ref[...],
        signs_ref[...],
        0.0,
    )
    dsx_ref[...] += jax.lax.dot_general(
        E, Xw, (((1,), (0,)), ((), ())),
        precision=HIGHEST, preferred_element_type=jnp.float32,
    )

    if want_z:
        z_ref[...] = (
            jax.lax.dot_general(
                Xw, omega_ref[...], (((1,), (0,)), ((), ())),
                precision=HIGHEST, preferred_element_type=jnp.float32,
            )
            if has_omega
            else Xw
        )

    if want_moments:
        # padded P rows are zero — they vanish from both moment sums
        Pb = p_ref[...]
        s1_ref[...] += jnp.sum(Pb, axis=0)[None, :]
        s2_ref[...] += jax.lax.dot_general(
            Pb, Pb, (((0,), (0,)), ((), ())),
            precision=HIGHEST, preferred_element_type=jnp.float32,
        )

    if hull:
        # (m, block_rows·r) score tile; same running fold as kernels.extremes,
        # with the validity count in points scaled to P rows
        S = jax.lax.dot_general(
            dirs_ref[...], p_ref[...], (((1,), (1,)), ((), ())),
            precision=HIGHEST, preferred_element_type=jnp.float32,
        )
        base = i * block_rows * r
        ridx = base + jax.lax.broadcasted_iota(jnp.int32, S.shape, 1)
        valid = ridx < nv_ref[0, 0] * r
        smax = jnp.where(valid, S, -jnp.inf)
        smin = jnp.where(valid, S, jnp.inf)

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


def sweep_kernel(
    x: jax.Array,
    p: jax.Array | None,
    sw: jax.Array,
    rows: jax.Array,
    signs: jax.Array,
    n_valid: jax.Array,
    dirs: jax.Array | None,
    omega: jax.Array | None,
    *,
    sketch_rows: int,
    r: int,
    want_moments: bool = False,
    want_z: bool = True,
    block_rows: int = DEFAULT_BLOCK_ROWS,
    interpret: bool = False,
):
    """x: (n_pad, D_pad), p: (n_pad·r, d_pad) or None, sw: (n_pad, 1),
    rows/signs: (1, n_pad) int32/f32, n_valid: (1, 1) int32 point count,
    dirs: (m_pad, d_pad) or None, omega: (D_pad, q_pad) or None.

    n_pad % block_rows == 0; every trailing dim lane-padded; ``sketch_rows``
    sublane-padded (multiple of 8). Returns the tuple
    ``(dSX, [z], [vmax, imax, vmin, imin], [ds1, ds2])`` with the optional
    groups present per (want_z, dirs, want_moments): dSX (sketch_rows, D_pad)
    is this call's sketch DELTA, z (n_pad, q_pad or D_pad), extremes
    (1, m_pad) block-local with global row ids into p, moment deltas
    (1, d_pad) / (d_pad, d_pad).
    """
    n_pad, D_pad = x.shape
    hull = dirs is not None
    has_p = p is not None
    has_omega = omega is not None
    grid = (n_pad // block_rows,)

    operands = [x]
    in_specs = [pl.BlockSpec((block_rows, D_pad), lambda i: (i, 0))]
    if has_p:
        d_pad = p.shape[1]
        operands.append(p)
        in_specs.append(pl.BlockSpec((block_rows * r, d_pad), lambda i: (i, 0)))
    operands += [sw, rows, signs, n_valid]
    in_specs += [
        pl.BlockSpec((block_rows, 1), lambda i: (i, 0)),
        pl.BlockSpec((1, block_rows), lambda i: (0, i)),
        pl.BlockSpec((1, block_rows), lambda i: (0, i)),
        pl.BlockSpec((1, 1), lambda i: (0, 0)),
    ]
    if hull:
        m_pad = dirs.shape[0]
        operands.append(dirs)
        in_specs.append(pl.BlockSpec(dirs.shape, lambda i: (0, 0)))
    if has_omega:
        operands.append(omega)
        in_specs.append(pl.BlockSpec(omega.shape, lambda i: (0, 0)))

    out_shape = [jax.ShapeDtypeStruct((sketch_rows, D_pad), jnp.float32)]
    out_specs = [pl.BlockSpec((sketch_rows, D_pad), lambda i: (0, 0))]
    if want_z:
        q_pad = omega.shape[1] if has_omega else D_pad
        out_shape.append(jax.ShapeDtypeStruct((n_pad, q_pad), jnp.float32))
        out_specs.append(pl.BlockSpec((block_rows, q_pad), lambda i: (i, 0)))
    if hull:
        for dt in (jnp.float32, jnp.int32, jnp.float32, jnp.int32):
            out_shape.append(jax.ShapeDtypeStruct((1, m_pad), dt))
            out_specs.append(pl.BlockSpec((1, m_pad), lambda i: (0, 0)))
    if want_moments:
        out_shape.append(jax.ShapeDtypeStruct((1, d_pad), jnp.float32))
        out_specs.append(pl.BlockSpec((1, d_pad), lambda i: (0, 0)))
        out_shape.append(jax.ShapeDtypeStruct((d_pad, d_pad), jnp.float32))
        out_specs.append(pl.BlockSpec((d_pad, d_pad), lambda i: (0, 0)))

    return pl.pallas_call(
        functools.partial(
            _kernel,
            block_rows=block_rows,
            r=r,
            has_p=has_p,
            hull=hull,
            has_omega=has_omega,
            want_moments=want_moments,
            want_z=want_z,
        ),
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
    )(*operands)
