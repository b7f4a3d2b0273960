"""Backend-dispatching wrapper: flat input of any length → (basis, deriv) (n, d).

``bernstein_basis_deriv`` follows ``gram_matrix``'s dispatch contract: the
fused Pallas kernel compiled on TPU, the jnp oracle (``ref.py``) elsewhere.
Interpret-mode Pallas is a *debug* path and only runs when explicitly
requested.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.bernstein.kernel import DEFAULT_ROWS, LANE, bernstein_kernel
from repro.kernels.bernstein.ref import bernstein_basis_deriv_ref


def default_bernstein_backend() -> str:
    """'pallas' (compiled kernel) on TPU, 'jnp' (XLA oracle) elsewhere."""
    return "pallas" if jax.default_backend() == "tpu" else "jnp"


@partial(jax.jit, static_argnames=("degree", "interpret"))
def _bernstein_pallas(t: jax.Array, degree: int, *, interpret: bool):
    """Pads to (8·k, 128) tiles, runs the fused kernel, and untiles."""
    n = t.shape[0]
    tile = DEFAULT_ROWS * LANE
    n_pad = (n + tile - 1) // tile * tile
    tp = jnp.zeros((n_pad,), jnp.float32).at[:n].set(t.astype(jnp.float32))
    tiles = tp.reshape(n_pad // LANE, LANE)
    basis, deriv = bernstein_kernel(tiles, degree, interpret=interpret)
    d = degree + 1
    basis = basis.transpose(1, 2, 0).reshape(n_pad, d)[:n]
    deriv = deriv.transpose(1, 2, 0).reshape(n_pad, d)[:n]
    return basis, deriv


def bernstein_basis_deriv(
    t: jax.Array,
    degree: int,
    *,
    backend: str | None = None,
    interpret: bool | None = None,
):
    """t: (n,) in [0,1] → (basis (n, d), deriv (n, d)), d = degree+1.

    backend: None → ``default_bernstein_backend()``; "pallas" → the compiled
    kernel; "jnp" → the oracle. ``interpret=True`` forces the Pallas
    interpreter (kernel validation on CPU) and implies ``backend="pallas"``.
    """
    if interpret and backend is None:
        backend = "pallas"
    if backend is None:
        backend = default_bernstein_backend()
    if backend == "jnp":
        return bernstein_basis_deriv_ref(t.astype(jnp.float32), degree)
    if backend != "pallas":
        raise ValueError(f"unknown bernstein backend: {backend}")
    return _bernstein_pallas(t, degree, interpret=bool(interpret))
