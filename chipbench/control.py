"""The control: the reference, put in the program's place, one precision down.

The configurations state float32 at ``Precision.HIGHEST`` for every product
on the build and fit paths. For the build the nearest precision below is
``HIGH``: three bf16 passes (hi·hi + hi·lo + lo·hi, f32 accumulation),
written out here as explicit bf16 splits (on the chip XLA folds the
``astype`` round trip of ``split_bf16`` away, so ``dot3`` runs one bf16
pass there). The draw of the coreset's rows, float32 arithmetic that is no
product, goes down to bf16. For the fit every product is formed from bf16
operands, one pass: the chip runs the fit's contractions as plain f32
arithmetic whatever their precision flag, and ``HIGH``'s error, a common
factor on every product, moves the fitted objective to second order only,
so no number of a fit tells it from ``HIGHEST`` (PERF.md §2). The check has
to call the control incorrect.

``build`` and ``fit`` take the same inputs as the program's entries and return
the same kind of answer, so ``entries/build.py``'s and ``entries/fit.py``'s
checks judge both alike.

    python chipbench/control.py --workload <name> --seeds 1 2 … 12 --control-seeds 1 2 3

runs, for each seed, the program's calls (and, on the control seeds, the
control's) in one process at the cell's own size, and prints every number
the check computes beside the cell's limits. It needs the chip, like
``run.py``.
"""
from __future__ import annotations

import os
import sys
from math import comb

import numpy as np

if __package__ in (None, ""):
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _jax():
    import jax
    import jax.numpy as jnp

    return jax, jnp


def split_bf16(x):
    _, jnp = _jax()
    hi = x.astype(jnp.bfloat16)
    lo = (x - hi.astype(jnp.float32)).astype(jnp.bfloat16)
    return hi, lo


def dot3(a, b):
    """a @ b for f32 operands in three bf16 passes (``Precision.HIGH``)."""
    jax, jnp = _jax()
    ah, al = split_bf16(a)
    bh, bl = split_bf16(b)

    def d(x, y):
        return jnp.matmul(x, y, preferred_element_type=jnp.float32)

    return d(ah, bh) + (d(ah, bl) + d(al, bh))


def _bernstein(t, m: int):
    _, jnp = _jax()
    t = jnp.clip(t, 0.0, 1.0)[..., None]
    k = jnp.arange(m + 1, dtype=jnp.float32)
    c = jnp.asarray([comb(m, i) for i in range(m + 1)], jnp.float32)
    return c * t**k * (1.0 - t) ** (m - k)


def features(Yc, low, high, degree: int):
    """(X (c, J·d), P (c·J, d)) in f32, the program's featurize contract."""
    _, jnp = _jax()
    span = high - low
    T = (Yc - low) / span
    A = _bernstein(T, degree)
    lower = _bernstein(T, degree - 1)
    pad = jnp.zeros(lower.shape[:-1] + (1,), jnp.float32)
    dA = degree * (jnp.concatenate([pad, lower], -1) - jnp.concatenate([lower, pad], -1))
    dA = dA / span[:, None]
    c, J, d = A.shape
    return A.reshape(c, J * d), dA.reshape(c * J, d)


# ---------------------------------------------------------------------------
# build
# ---------------------------------------------------------------------------


def _chunked(Y, chunk: int):
    n = Y.shape[0]
    n_pad = -(-n // chunk) * chunk
    Yp = np.concatenate([Y, np.broadcast_to(Y[:1], (n_pad - n, Y.shape[1]))])
    mask = (np.arange(n_pad) < n).astype(np.float32)
    return Yp.reshape(-1, chunk, Y.shape[1]), mask.reshape(-1, chunk)


def _build_fns(J: int, degree: int, chunk: int, sketch_size: int):
    jax, jnp = _jax()
    D = J * (degree + 1)

    def feats(yc, low, high):
        return features(yc, low, high, degree)

    @jax.jit
    def pass1(Yc, M, low, high, rows, signs):
        def step(carry, xs):
            G, s1, s2 = carry
            yc, mc, rc, sc = xs
            X, P = feats(yc, low, high)
            X = X * mc[:, None]
            Pm = P * jnp.repeat(mc, J)[:, None]
            if sketch_size:
                G = G.at[rc].add(sc[:, None] * X)
            else:
                G = G + dot3(X.T, X)
            return (G, s1 + Pm.sum(0), s2 + dot3(Pm.T, Pm)), None

        G0 = jnp.zeros((sketch_size or D, D), jnp.float32)
        d = degree + 1
        init = (G0, jnp.zeros((d,), jnp.float32), jnp.zeros((d, d), jnp.float32))
        return jax.lax.scan(step, init, (Yc, M, rows, signs))[0]

    @jax.jit
    def pass2(Yc, M, low, high, V, inv, dirs):
        m = dirs.shape[0]

        def step(carry, xs):
            bmax, imax, bmin, imin = carry
            ci, yc, mc = xs
            X, P = feats(yc, low, high)
            u = jnp.sum(jnp.square(dot3(X, V)) * inv, axis=1)
            s = dot3(P, dirs.T)
            live = jnp.repeat(mc, J)[:, None] > 0
            smax = jnp.where(live, s, -jnp.inf)
            smin = jnp.where(live, s, jnp.inf)
            off = ci * chunk * J
            vmax, amax = smax.max(0), jnp.argmax(smax, 0) + off
            vmin, amin = smin.min(0), jnp.argmin(smin, 0) + off
            up, dn = vmax > bmax, vmin < bmin
            return (jnp.where(up, vmax, bmax), jnp.where(up, amax, imax),
                    jnp.where(dn, vmin, bmin), jnp.where(dn, amin, imin)), u

        init = (jnp.full((m,), -jnp.inf), jnp.zeros((m,), jnp.int32),
                jnp.full((m,), jnp.inf), jnp.zeros((m,), jnp.int32))
        ci = jnp.arange(Yc.shape[0], dtype=jnp.int32)
        return jax.lax.scan(step, init, (ci, Yc, M))

    return pass1, pass2


def _first_unique(cand, k: int) -> np.ndarray:
    uniq, first = np.unique(cand, return_index=True)
    return uniq[np.argsort(first, kind="stable")][:k]


def build(inputs: dict, key) -> dict:
    """Algorithm 1 (ℓ2-hull) at ``HIGH``: scores, hull points and the drawn
    coreset, from the same keys and the same inputs as the program."""
    jax, jnp = _jax()
    from chipbench import reference as R
    from chipbench.costs.shapes import HULL_OVERSAMPLE

    Y, low, high = inputs["Y"], inputs["low"], inputs["high"]
    degree, k, alpha = inputs["degree"], inputs["k"], inputs["alpha"]
    chunk, sketch = inputs["chunk"], inputs["sketch_size"]
    n, J = Y.shape
    d = degree + 1
    k_score, k_hull, k_draw = jax.random.split(key, 3)
    k_sample = int(np.floor(alpha * k))
    m_rand = max(HULL_OVERSAMPLE * (k - k_sample), 8)
    pass1, pass2 = _build_fns(J, degree, chunk, sketch)
    Yc, M = _chunked(Y, chunk)
    lo32, hi32 = jnp.asarray(low, jnp.float32), jnp.asarray(high, jnp.float32)
    if sketch:
        k1, k2 = jax.random.split(k_score)
        rows = jax.random.randint(k1, (n,), 0, sketch)
        signs = jax.random.rademacher(k2, (n,), dtype=jnp.float32)
        pad = Yc.shape[0] * chunk - n
        rows = jnp.concatenate([rows, jnp.zeros((pad,), rows.dtype)]).reshape(M.shape)
        signs = jnp.concatenate([signs, jnp.zeros((pad,), jnp.float32)]).reshape(M.shape)
    else:
        rows = jnp.zeros(M.shape, jnp.int32)
        signs = jnp.zeros(M.shape, jnp.float32)
    G, s1, s2 = pass1(jnp.asarray(Yc), jnp.asarray(M), lo32, hi32, rows, signs)
    if sketch:  # the sketch's Gram, a product like any other
        G = dot3(G.T, G)
    V, inv = R.factor(np.asarray(G, np.float64))
    g = np.array(jax.random.normal(k_hull, (m_rand, d), dtype=jnp.float32))
    g /= np.maximum(np.linalg.norm(g, axis=1, keepdims=True), 1e-12)
    if sketch:
        axes = np.eye(d)
    else:
        nr = n * J
        mu = np.asarray(s1, np.float64) / nr
        _, axes = np.linalg.eigh(np.asarray(s2, np.float64) / nr - np.outer(mu, mu))
    dirs = np.concatenate([g, axes.T, -axes.T]).astype(np.float32)
    (_, imax, _, imin), u = pass2(jnp.asarray(Yc), jnp.asarray(M), lo32, hi32,
                                  jnp.asarray(V, jnp.float32),
                                  jnp.asarray(inv, jnp.float32), jnp.asarray(dirs))
    u = np.asarray(u, np.float64).reshape(-1)[:n]
    scores = u + 1.0 / n
    probs = scores / scores.sum()
    # the draw one precision down: the probabilities in bf16, so their
    # cumulative sum and the uniforms are bf16 as well
    idx = np.asarray(jax.random.choice(k_draw, n, shape=(k_sample,), replace=True,
                                       p=jnp.asarray(probs, jnp.bfloat16)))
    w = 1.0 / (k_sample * probs[idx])
    k_h = k - k_sample
    cand = np.concatenate([np.asarray(imax), np.asarray(imin)]).astype(np.int64) // J
    pts = _first_unique(cand, k_h)
    if pts.size < k_h:
        ranked = np.argsort(-scores, kind="stable")
        ranked = ranked[~np.isin(ranked, pts)]
        pts = np.concatenate([pts, ranked[: k_h - pts.size]])
    return {"indices": np.concatenate([idx, pts]),
            "weights": np.concatenate([w, np.ones(k_h)]),
            "scores": scores}


# ---------------------------------------------------------------------------
# fit
# ---------------------------------------------------------------------------


def bf16_bits(x):
    """The bfloat16 nearest to x (ties to even), held in an f32 array. By
    integer arithmetic on the bits: XLA on the chip folds an f32 → bf16 →
    f32 round trip away, so a rounding through ``astype`` is none there."""
    jax, jnp = _jax()
    u = jax.lax.bitcast_convert_type(x, jnp.uint32)
    u = u + jnp.uint32(0x7FFF) + ((u >> 16) & jnp.uint32(1))
    return jax.lax.bitcast_convert_type(u & jnp.uint32(0xFFFF0000), jnp.float32)


def mul1(a, b):
    """a·b elementwise from bf16 operands, the product and the sums it
    enters in f32: one bf16 pass, as the chip's matrix unit forms a
    product at ``DEFAULT``."""
    return bf16_bits(a) * bf16_bits(b)


def default_init(J: int, d: int, min_slope: float):
    """The program's default starting point (``init_params`` with
    ``PRNGKey(0)``): the monotone coefficients of 4·t − 2 in every column
    plus 0.01 of a normal draw, and Λ = I."""
    jax, jnp = _jax()
    k1, _ = jax.random.split(jax.random.PRNGKey(0))
    base = jnp.linspace(-2.0, 2.0, d, dtype=jnp.float32)
    steps = jnp.clip(jnp.diff(base) - jnp.float32(min_slope), 1e-6, None)
    raw = jnp.concatenate([base[:1], jnp.log(jnp.expm1(steps))])
    raw = jnp.tile(raw, (J, 1)) + 0.01 * jax.random.normal(k1, (J, d), jnp.float32)
    return np.asarray(raw, np.float64), np.zeros(J * (J - 1) // 2)


def _fit_fn(J: int, eta: float, min_slope: float):
    """The weighted NLL and its gradient, written out as in
    ``reference.MCTMObjective``, with every product from bf16 operands."""
    jax, jnp = _jax()
    from chipbench.reference import LOG_2PI

    rows, cols = np.tril_indices(J, k=-1)

    @jax.jit
    def value_and_grad(raw, lam, A, dA, w):
        steps = jax.nn.softplus(raw[:, 1:]) + jnp.float32(min_slope)
        theta = jnp.concatenate([raw[:, :1], raw[:, :1] + jnp.cumsum(steps, axis=1)], axis=1)
        h = mul1(A, theta[None]).sum(-1)
        hp = mul1(dA, theta[None]).sum(-1)
        L = jnp.eye(J, dtype=jnp.float32).at[rows, cols].set(lam)
        z = mul1(h[:, None, :], L[None]).sum(-1)
        live = hp > eta
        terms = jnp.sum(0.5 * z * z - jnp.log(jnp.maximum(hp, jnp.float32(eta)))
                        + jnp.float32(0.5 * LOG_2PI), axis=1)
        gz = w[:, None] * z
        gh = mul1(gz[:, :, None], L[None]).sum(1)
        g_hp = jnp.where(live, -w[:, None] / jnp.where(live, hp, 1.0), 0.0)
        g_theta = mul1(gh[:, :, None], A).sum(0) + mul1(g_hp[:, :, None], dA).sum(0)
        tail = jnp.cumsum(g_theta[:, ::-1], axis=1)[:, ::-1]
        g_raw = tail.at[:, 1:].multiply(jax.nn.sigmoid(raw[:, 1:]))
        g_lam = mul1(gz[:, :, None], h[:, None, :]).sum(0)[rows, cols]
        return jnp.sum(w * terms), g_raw, g_lam

    return value_and_grad


def _two_loop(g, S, Yv):
    q = g.copy()
    alpha = []
    for s, y in zip(reversed(S), reversed(Yv)):
        a = (s @ q) / (s @ y)
        q -= a * y
        alpha.append(a)
    r = q * ((S[-1] @ Yv[-1]) / max(Yv[-1] @ Yv[-1], 1e-30)) if S else q
    for (s, y), a in zip(zip(S, Yv), reversed(alpha)):
        r += s * (a - (y @ r) / (s @ y))
    return r


def lbfgs(vg, x0: np.ndarray, *, steps: int, gtol: float, history: int,
          max_linesearch: int = 20):
    """The program's L-BFGS settings (iterations, gradient-norm stop,
    history, Armijo backtracking from a unit step, the first step scaled by
    1/|g|₁), with the textbook curvature pairs y = ∇f(x₊) − ∇f(x): the
    control's gradient is written out, and gives no Hessian-vector product.
    Returns (x, iterations, sweeps)."""
    x = np.asarray(x0, np.float64)
    f, g = vg(x)
    sweeps, iters = 1, 0
    S, Yv = [], []
    for _ in range(steps):
        gnorm = float(np.linalg.norm(g))
        if not np.isfinite(f) or gnorm <= gtol:
            break
        iters += 1
        d = -_two_loop(g, S, Yv)
        gd = float(g @ d)
        if not np.isfinite(gd) or gd >= 0.0:
            d, gd = -g, -gnorm * gnorm
        t = 1.0 if S else min(1.0, 1.0 / max(float(np.abs(g).sum()), 1e-12))
        for _ in range(max_linesearch):
            f_t, g_t = vg(x + t * d)
            sweeps += 1
            if np.isfinite(f_t) and f_t <= f + 1e-4 * t * gd:
                break
            t *= 0.5
        else:
            break
        s, y = t * d, g_t - g
        if s @ y > 1e-10 * np.linalg.norm(s) * np.linalg.norm(y):
            S, Yv = (S + [s])[-history:], (Yv + [y])[-history:]
        x, f, g = x + s, f_t, g_t
    return x, iters, sweeps


def fit(inputs: dict) -> dict:
    """The weighted MCTM fit with every product from bf16 operands: the
    reference objective, normalised by Σw as the program's L-BFGS fit is,
    minimised from the program's default starting point. Returns what the
    fit entry's call returns."""
    _, jnp = _jax()

    Y, w = inputs["Y"], np.asarray(inputs["w"], np.float32)
    degree = inputs["degree"]
    J, d = Y.shape[1], degree + 1
    X, P = features(jnp.asarray(Y, jnp.float32), jnp.asarray(inputs["low"], jnp.float32),
                    jnp.asarray(inputs["high"], jnp.float32), degree)
    A, dA = X.reshape(-1, J, d), P.reshape(-1, J, d)
    wj = jnp.asarray(w)
    vg_j = _fit_fn(J, inputs["eta"], inputs["min_slope"])
    norm = float(w.sum())
    n_theta = J * d

    def total(x):
        v, g_raw, g_lam = vg_j(jnp.asarray(x[:n_theta].reshape(J, d), jnp.float32),
                               jnp.asarray(x[n_theta:], jnp.float32), A, dA, wj)
        return float(v), np.concatenate([np.ravel(np.asarray(g_raw, np.float64)),
                                         np.asarray(g_lam, np.float64)])

    def vg(x):
        v, g = total(x)
        return v / norm, g / norm

    raw0, lam0 = default_init(J, d, inputs["min_slope"])
    x, iters, sweeps = lbfgs(vg, np.concatenate([raw0.ravel(), lam0]),
                             steps=inputs["steps"], gtol=inputs["gtol"],
                             history=inputs["history"])
    x = x.astype(np.float32).astype(np.float64)
    return {"theta_raw": x[:n_theta].reshape(J, d), "lam": x[n_theta:],
            "final_nll": total(x)[0], "steps": iters,
            "sweeps": {"vg": sweeps, "hvp": 0, "iters": iters}}


if __name__ == "__main__":
    from chipbench import run

    sys.exit(run.control_main(sys.argv[1:]))
