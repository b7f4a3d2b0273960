"""Closed loop of coreset builds: the model's build call back to back.

Each call is one whole user call: float32 host rows in, ``CoresetResult``
out, on the cell's data mesh, with its own key from the seed. The
configuration's model (``chipbench/models/<model>.py``) makes the program
objects, the call and the float64 reference; this loop, its keys and the
check are every build cell's. The traffic file sets the strategy:
``sketch_factor`` 0 is the exact two-pass build, f > 0 the one-pass
sketched build with sketch f·D².

The check compares, for builds drawn from the seed, everything a build
returns against the float64 reference: the leverage part of the scores
(exact, or sketched from the same CountSketch plan), the hull points
against the exact directional extremes of the data, and the weights of
the drawn rows against 1/(k·p) from the reference scores, and the drawn
rows themselves against the reference's draw from the same key.
"""
from __future__ import annotations

import collections

import numpy as np

from chipbench import datagen
from chipbench import reference as R
from chipbench.costs.shapes import HULL_OVERSAMPLE, build_shapes

NAME = "build"
# directions of the hull net whose extreme the returned hull must hold: the
# first min(128, k_hull) random ones. Their argmax rows come first in the
# candidate list, so they are among the first k_hull distinct points, which
# the build always keeps.
CHECK_DIRS = 128


def setup(ctx) -> dict:
    import jax

    cfg, model = ctx.config, ctx.cell.model
    Y = datagen.generate(cfg["dgp"], cfg["n"], ctx.seed, cfg["data_seed"],
                         bench_dir=ctx.cell.bench_dir)
    ctx.phase("data")
    state = {
        "Y": Y,
        "program": model.program(cfg, Y),
        "mesh": ctx.cell.data_mesh(),
        "base_key": jax.random.PRNGKey(ctx.seed),
        "sketch": build_shapes(cfg, ctx.traffic, model)["sketch"],
    }
    # one whole call warms every shape the window uses; its key is never
    # drawn in the window
    call(ctx, state, -1)
    ctx.phase("warm-up call")
    return state


def key_for(state, i: int):
    import jax

    return jax.random.fold_in(state["base_key"], i + 1)


def call(ctx, state, i: int) -> dict:
    cs = ctx.cell.model.build(ctx.config, state["program"], state["Y"], mesh=state["mesh"],
                              key=key_for(state, i), sketch_size=state["sketch"])
    return {"i": i, "indices": np.asarray(cs.indices), "weights": np.asarray(cs.weights),
            "scores": np.asarray(cs.scores)}


def work(ctx, result) -> dict:
    return {"rows": ctx.config["n"]}


def control_call(ctx, state, i: int) -> dict:
    out = ctx.cell.model.control(ctx.config, state["Y"], key_for(state, i), state["sketch"])
    out["i"] = i
    return out


def release(state) -> None:
    for k in ("program", "mesh"):
        state.pop(k, None)


def score_stats(scores, u_ref, idx, w, k_sample: int) -> dict:
    """The returned scores and sampling weights against the reference's.

    - ``lev_tv``: total variation between the program's sampling
      distribution p = s/Σs (s = leverage + 1/n) and the reference's;
    - ``w_dev``: the drawn rows' weights against 1/(k·p_ref), summed
      absolute gap over the sum of the reference weights.
    """
    n = scores.size
    s = np.asarray(scores, np.float64)
    s_ref = u_ref + 1.0 / n
    p, p_ref = s / s.sum(), s_ref / s_ref.sum()
    w_ref = 1.0 / (k_sample * p_ref[idx[:k_sample]])
    gap = np.abs(np.asarray(w[:k_sample], np.float64) - w_ref)
    return {"lev_tv": float(0.5 * np.abs(p - p_ref).sum()),
            "w_dev": float(gap.sum() / w_ref.sum())}


def draw_gap(idx, q, u_ref) -> float:
    """The drawn rows against the reference's draw from the same uniforms.

    The program draws row i for the uniform q where its cumulative
    distribution first reaches q. Each drawn row's gap is how far q lies
    outside that row's interval of the reference's cumulative distribution
    (0 where the reference draws the same row); the largest over the draws.
    """
    s_ref = u_ref + 1.0 / u_ref.size
    cdf = np.cumsum(s_ref)
    cdf /= cdf[-1]
    hi = cdf[idx]
    lo = np.where(idx > 0, cdf[np.maximum(idx - 1, 0)], 0.0)
    return float(np.max(np.maximum(0.0, np.maximum(lo - q, q - hi))))


def draw_uniforms(k_draw, k_sample: int) -> np.ndarray:
    """The q of each draw, as ``jax.random.choice`` takes it from the key."""
    import jax
    import jax.numpy as jnp

    u = jax.random.uniform(k_draw, (k_sample,), dtype=jnp.float32)
    return 1.0 - np.asarray(u, np.float64)


def check(ctx, state, results, rng) -> list[tuple[str, float]]:
    """Numbers compared, each the worst over the sampled builds."""
    import jax
    import jax.numpy as jnp

    cfg = ctx.config
    Y = state["Y"]
    n = Y.shape[0]
    d = ctx.cell.model.widths(cfg)["d"]
    k, sketch = cfg["k"], state["sketch"]
    k_sample = int(np.floor(cfg["alpha"] * k))
    k_hull = k - k_sample
    n_check = min(len(results), int(ctx.traffic.get("check_calls", 2)))
    picks = sorted(rng.choice(len(results), size=n_check, replace=False).tolist())
    X, hull = ctx.cell.model.reference(cfg, Y)
    exact = None
    worst = collections.defaultdict(float)
    for p in picks:
        res = results[p]
        k_score, k_hull_key, k_draw = jax.random.split(key_for(state, res["i"]), 3)
        if sketch:
            k1, k2 = jax.random.split(k_score)
            rows = np.asarray(jax.random.randint(k1, (n,), 0, sketch))
            signs = np.asarray(jax.random.rademacher(k2, (n,), dtype=jnp.float32))
            SX = X.sketch(rows, signs, sketch)
            u_ref = X.leverage(*R.factor(SX.T @ SX))
        else:
            if exact is None:
                exact = X.leverage(*R.factor(X.gram()))
            u_ref = exact
        idx, w, scores = res["indices"], res["weights"], res["scores"]
        ok_shape = (idx.shape == (k,) and w.shape == (k,) and scores.shape == (n,)
                    and idx.min() >= 0 and idx.max() < n
                    and np.unique(idx[k_sample:]).size == k_hull
                    and np.all(w[k_sample:] == 1.0))
        worst["shape_err"] = max(worst["shape_err"], 0.0 if ok_shape else 1.0)
        if not ok_shape:
            continue
        for name, v in score_stats(scores, u_ref, idx, w, k_sample).items():
            worst[name] = max(worst[name], v)
        worst["draw_gap"] = max(worst["draw_gap"], draw_gap(
            idx[:k_sample], draw_uniforms(k_draw, k_sample), u_ref))
        g = np.array(jax.random.normal(k_hull_key,
                                       (max(HULL_OVERSAMPLE * k_hull, 8), d),
                                       dtype=jnp.float32))
        g /= np.maximum(np.linalg.norm(g, axis=1, keepdims=True), 1e-12)
        worst["hull_gap"] = max(worst["hull_gap"],
                                R.hull_gap(hull, g[:min(CHECK_DIRS, k_hull)],
                                           Y[idx[k_sample:]]))
    return list(worst.items())
