"""Per-kernel allclose sweeps (interpret=True) against the jnp oracles."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.bernstein.ops import bernstein_basis_deriv
from repro.kernels.bernstein.ref import bernstein_basis_deriv_ref
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.gram.ops import gram_matrix
from repro.kernels.gram.ref import gram_ref
from repro.kernels.ssd.ops import ssd_chunked
from repro.kernels.ssd.ref import ssd_ref


# ---------------------------------------------------------------- bernstein


@pytest.mark.parametrize("n", [1, 100, 1024, 2049])
@pytest.mark.parametrize("degree", [1, 4, 7])
def test_bernstein_kernel_sweep(n, degree):
    rng = np.random.default_rng(n * 10 + degree)
    t = jnp.asarray(rng.random(n), jnp.float32)
    # interpret=True: the Pallas kernel itself (off-TPU the default backend is
    # the jnp oracle, which would compare ref to ref)
    basis, deriv = bernstein_basis_deriv(t, degree, interpret=True)
    bref, dref = bernstein_basis_deriv_ref(t, degree)
    np.testing.assert_allclose(np.asarray(basis), np.asarray(bref), atol=1e-5)
    np.testing.assert_allclose(np.asarray(deriv), np.asarray(dref), atol=1e-4)


def test_bernstein_default_backend_is_oracle_off_tpu():
    """No silent interpret mode: off-TPU the default is the jnp oracle, and
    an unknown backend is refused."""
    t = jnp.linspace(0.0, 1.0, 37, dtype=jnp.float32)
    for got, ref in zip(bernstein_basis_deriv(t, 5), bernstein_basis_deriv_ref(t, 5)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))
    with pytest.raises(ValueError, match="unknown bernstein backend"):
        bernstein_basis_deriv(t, 5, backend="cuda")


# --------------------------------------------------------------------- gram


@pytest.mark.parametrize("shape", [(64, 4), (777, 14), (1024, 128), (300, 200)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_gram_kernel_sweep(shape, dtype):
    rng = np.random.default_rng(shape[0])
    x = jnp.asarray(rng.standard_normal(shape), dtype)
    # interpret=True: exercise the Pallas kernel itself on CPU (the default
    # backend off-TPU is the jnp oracle, which would compare ref to ref)
    got = np.asarray(gram_matrix(x, interpret=True))
    ref = np.asarray(gram_ref(x))
    tol = 1e-3 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(got, ref, rtol=tol, atol=tol * np.abs(ref).max())


# ----------------------------------------------------------- extremes


@pytest.mark.parametrize(
    "n,m,d,block_rows,n_valid,ties",
    [
        pytest.param(64, 8, 5, None, None, False, id="64-8-5"),
        pytest.param(777, 24, 7, None, None, False, id="777-24-7"),
        pytest.param(1024, 130, 14, None, None, False, id="1024-130-14"),
        # eight grid steps over a row count that is no multiple of 128
        pytest.param(1000, 24, 7, 128, None, False, id="steps"),
        # tied copies in other grid steps and lane positions (below)
        pytest.param(1152, 40, 7, 128, None, True, id="ties"),
        pytest.param(1152, 40, 7, 128, 1030, True, id="ties-ragged"),
        # a ragged tail whose masked rows would win if they counted
        pytest.param(1000, 24, 7, 256, 777, False, id="ragged"),
        pytest.param(300, 16, 6, 128, 0, False, id="all-masked"),
    ],
)
def test_extremes_kernel_sweep(n, m, d, block_rows, n_valid, ties):
    """The kernel against the dense-argmax oracle: indices bit for bit
    (lowest row among equal values; ∓inf and index 0 with no valid row),
    values to 1e-4."""
    from repro.kernels.extremes.ops import directional_extremes
    from repro.kernels.extremes.ref import directional_extremes_ref

    rng = np.random.default_rng(n + m)
    P_np = rng.standard_normal((n, d)).astype(np.float32)
    if ties:
        # 50 dominant rows, copied at rows 140, 396 (the same lanes, two steps
        # later) and 1000 (lanes 104.., and 0.. past 1024): every extreme is
        # a three-way tie that the first copy must win, though the last
        # copy's rows from 1024 on sit in lower lanes
        top = 3.0 * P_np[:50]
        for lo in (140, 396, 1000):
            P_np[lo:lo + 50] = top
    mask = None
    if n_valid is not None:
        P_np[n_valid:] *= 10.0
        mask = jnp.arange(n) < n_valid
    P = jnp.asarray(P_np)
    dirs = jnp.asarray(rng.standard_normal((m, d)), jnp.float32)
    kw = {} if block_rows is None else {"block_rows": block_rows}
    vmax, imax, vmin, imin = directional_extremes(P, dirs, mask, interpret=True, **kw)
    rvmax, rimax, rvmin, rimin = directional_extremes_ref(P, dirs, mask)
    np.testing.assert_array_equal(np.asarray(imax), np.asarray(rimax))
    np.testing.assert_array_equal(np.asarray(imin), np.asarray(rimin))
    np.testing.assert_allclose(np.asarray(vmax), np.asarray(rvmax), atol=1e-4)
    np.testing.assert_allclose(np.asarray(vmin), np.asarray(rvmin), atol=1e-4)
    if ties:
        for idx in (imax, imin):
            assert np.all((np.asarray(idx) >= 140) & (np.asarray(idx) < 190))
    if n_valid == 0:
        assert np.all(np.isneginf(np.asarray(vmax))) and not np.any(np.asarray(imax))
        assert np.all(np.isposinf(np.asarray(vmin))) and not np.any(np.asarray(imin))


def test_extremes_kernel_mask_and_ties():
    """Tail masks (the engines' shard-padding pattern) and exact duplicates:
    masked rows can never win, ties break to the lowest row id — matching the
    dense-argmax oracle bit for bit on the indices."""
    from repro.kernels.extremes.ops import directional_extremes
    from repro.kernels.extremes.ref import directional_extremes_ref

    rng = np.random.default_rng(0)
    P_np = rng.standard_normal((300, 6)).astype(np.float32)
    P_np[100:200] = P_np[:100]  # duplicate block → cross-block ties
    P = jnp.asarray(P_np)
    dirs = jnp.asarray(rng.standard_normal((16, 6)), jnp.float32)
    n_valid = 257  # ragged tail mask
    mask = jnp.arange(300) < n_valid
    vmax, imax, vmin, imin = directional_extremes(P, dirs, mask, interpret=True)
    rvmax, rimax, rvmin, rimin = directional_extremes_ref(P, dirs, mask)
    np.testing.assert_array_equal(np.asarray(imax), np.asarray(rimax))
    np.testing.assert_array_equal(np.asarray(imin), np.asarray(rimin))
    np.testing.assert_allclose(np.asarray(vmax), np.asarray(rvmax), atol=1e-4)
    np.testing.assert_allclose(np.asarray(vmin), np.asarray(rvmin), atol=1e-4)
    assert int(np.max(imax)) < n_valid and int(np.max(imin)) < n_valid
    # any direction whose max lives in the duplicated block must have resolved
    # the cross-block tie toward the first copy (rows < 100)
    assert not np.any((np.asarray(imax) >= 100) & (np.asarray(imax) < 200))


def test_extremes_backend_dispatch():
    from repro.kernels.extremes.ops import directional_extremes

    P = jnp.ones((4, 2), jnp.float32)
    dirs = jnp.ones((3, 2), jnp.float32)
    with pytest.raises(ValueError):
        directional_extremes(P, dirs, backend="nope")


# ----------------------------------------------------------- flash attention


@pytest.mark.parametrize(
    "B,S,H,KV,d", [(1, 128, 2, 2, 32), (2, 256, 4, 2, 64), (1, 512, 8, 1, 64)]
)
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_sweep(B, S, H, KV, d, causal):
    rng = np.random.default_rng(S + H)
    q = jnp.asarray(rng.standard_normal((B, S, H, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, S, KV, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, S, KV, d)), jnp.float32)
    out = flash_attention(q, k, v, causal=causal, block_q=64, block_k=64)
    g = H // KV
    kq, vq = jnp.repeat(k, g, 2), jnp.repeat(v, g, 2)

    def flat(a):
        return a.transpose(0, 2, 1, 3).reshape(B * H, S, d)

    ref = attention_ref(flat(q), flat(kq), flat(vq), causal=causal)
    ref = ref.reshape(B, H, S, d).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_flash_attention_bf16():
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((1, 128, 2, 64)), jnp.bfloat16)
    k = jnp.asarray(rng.standard_normal((1, 128, 2, 64)), jnp.bfloat16)
    v = jnp.asarray(rng.standard_normal((1, 128, 2, 64)), jnp.bfloat16)
    out = flash_attention(q, k, v, block_q=64, block_k=64)

    def flat(a):
        return a.transpose(0, 2, 1, 3).reshape(2, 128, 64)

    ref = attention_ref(flat(q), flat(k), flat(v))
    ref = ref.reshape(1, 2, 128, 64).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), atol=3e-2
    )


# ---------------------------------------------------------------------- ssd


@pytest.mark.parametrize("T,chunk", [(64, 16), (100, 32), (256, 128), (31, 32)])
@pytest.mark.parametrize("P,N", [(16, 8), (64, 32)])
def test_ssd_kernel_sweep(T, chunk, P, N):
    rng = np.random.default_rng(T + P)
    BH = 3
    x = jnp.asarray(rng.standard_normal((BH, T, P)), jnp.float32)
    dt = jnp.asarray(rng.random((BH, T)) * 0.5 + 0.01, jnp.float32)
    A = jnp.asarray(-rng.random((BH,)) * 2 - 0.1, jnp.float32)
    Bm = jnp.asarray(rng.standard_normal((BH, T, N)), jnp.float32)
    Cm = jnp.asarray(rng.standard_normal((BH, T, N)), jnp.float32)
    y = ssd_chunked(x, dt, A, Bm, Cm, chunk=chunk)
    yr = ssd_ref(x, dt[..., None], A[:, None], Bm, Cm)
    scale = float(jnp.abs(yr).max())
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), atol=2e-4 * max(scale, 1))


def test_ssd_matches_model_chunked_path():
    """kernel vs the model's _ssd_chunked lax implementation (same math)."""
    from repro.models.ssm import _ssd_chunked

    rng = np.random.default_rng(7)
    B, T, H, P, N = 2, 64, 4, 16, 8
    x = jnp.asarray(rng.standard_normal((B, T, H, P)), jnp.float32)
    dt = jnp.asarray(rng.random((B, T, H)) * 0.5 + 0.01, jnp.float32)
    A = jnp.asarray(-rng.random((H,)) - 0.1, jnp.float32)
    Bm = jnp.asarray(rng.standard_normal((B, T, 1, N)), jnp.float32)
    Cm = jnp.asarray(rng.standard_normal((B, T, 1, N)), jnp.float32)
    state0 = jnp.zeros((B, H, P, N), jnp.float32)
    y_model, _ = _ssd_chunked(x, dt, A, Bm, Cm, state0, chunk=16)

    # kernel layout: fold (B,H) → BH, broadcast Bm/Cm per head
    xk = x.transpose(0, 2, 1, 3).reshape(B * H, T, P)
    dtk = dt.transpose(0, 2, 1).reshape(B * H, T)
    Ak = jnp.tile(A, (B,))
    Bk = jnp.repeat(Bm[:, :, 0, :][:, None], H, 1).reshape(B * H, T, N)
    Ck = jnp.repeat(Cm[:, :, 0, :][:, None], H, 1).reshape(B * H, T, N)
    y_kernel = ssd_chunked(xk, dtk, Ak, Bk, Ck, chunk=16)
    y_kernel = y_kernel.reshape(B, H, T, P).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(y_kernel), np.asarray(y_model), atol=1e-4)
