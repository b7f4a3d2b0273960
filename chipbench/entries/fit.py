"""Closed loop of coreset fits: ``fit_mctm_streaming`` back to back.

Set-up builds the traffic's coresets with the configuration model's own
two-pass build (``chipbench/models/<model>.py``: ``distributed_build_coreset``
for the MCTM) on the cell's data mesh and keeps each as a user
holds it: float32 host rows ``Y[idx]`` and their weights. They come from
the configuration's ``data_seed`` data set in its fixed row order, with
keys from ``data_seed``, so every run fits the same rows. Each call is one
whole user call: the weighted maximum-likelihood fit of one coreset by the
traffic's method from the program's default initialisation, ending in its
``final_nll``. Call i fits coreset i mod ``coresets``, its weights times
2**e for an e of the call's own (``exponent``).

The factor leaves every number of the fit as it is: the L-BFGS fit
minimises Σ w·nll / Σw, and a power of two scales float32 exactly, so
every call does the same work to the last bit. But the program bakes Σw
into the two oracles it builds for each fit, and no run with this
persistent cache has fitted at that Σw: to the program each call is a
coreset it has never compiled for, as every new coreset of a user's is.
Each fit lowers and compiles its oracles in the window, with the cache set
as the program's entry points set it.

The check compares, for fits drawn from the seed, the fitted parameters
against the float64 reference objective (``reference.MCTMObjective``) on the
call's rows and scaled weights:

- ``obj_dev``: |final_nll − f64(θ)| / |f64(θ)|, where f64(θ) is the
  weighted sum Σ w·nll that ``streamed_nll`` reports as ``final_nll``;
- ``fit_gap``: (f64(θ) − f64(θ*)) / |f64(θ*)|, θ* the float64 L-BFGS-B
  polish started from θ: how far the reference can still go down;
- ``shape_err``: 0 when θ and λ have the configuration's shapes and every
  number returned is finite.
"""
from __future__ import annotations

import collections
import os

import numpy as np

from chipbench import datagen
from chipbench import reference as R

NAME = "fit"
# a call's weights are scaled by 2**e, 1 ≤ e ≤ EXPONENTS (float32 holds them
# exactly: the coreset's weights and totals stay far from its limits)
EXPONENTS = 80
RECORD = "chipbench_fit_exponents.txt"


def setup(ctx) -> dict:
    import jax

    cfg, traffic, model = ctx.config, ctx.traffic, ctx.cell.model
    # every run fits the same coresets: the data set in its fixed row order,
    # the builds' keys from the configuration's data_seed
    Y = datagen.generate(cfg["dgp"], cfg["n"], cfg["data_seed"], cfg["data_seed"],
                         bench_dir=ctx.cell.bench_dir)
    ctx.phase("data")
    prog = model.program(cfg, Y)
    mesh = ctx.cell.data_mesh()
    base_key = jax.random.PRNGKey(cfg["data_seed"])
    coresets = []
    for j in range(int(traffic["coresets"])):
        cs = model.build(cfg, prog, Y, mesh=mesh, key=jax.random.fold_in(base_key, j),
                         sketch_size=0)
        coresets.append((Y[np.asarray(cs.indices)], np.asarray(cs.weights, np.float32)))
    ctx.phase("coreset builds")
    low, high = datagen.scaler_bounds(Y.astype(np.float64))
    cache_dir = jax.config.jax_compilation_cache_dir
    state = {"program": prog, "mesh": mesh, "coresets": coresets,
             "low": low, "high": high, "seed": ctx.seed, "exponents": {},
             "record": os.path.join(cache_dir, RECORD) if cache_dir else None}
    # one step of each coreset at its own weights (e = 0) compiles every
    # program a fit runs; the window's fits, at e ≥ 1, compile their oracles
    for Yc, wc in coresets:
        fit_rows(state, Yc, wc, {**traffic, "steps": 1})
    ctx.phase("warm-up fits")
    return state


def coreset(state, i: int):
    """Call i's rows and weights: coreset i mod ``coresets``, its weights
    times 2**e, e = ``exponent(state, i)``."""
    Yc, wc = state["coresets"][i % len(state["coresets"])]
    return Yc, wc * np.float32(2.0 ** exponent(state, i))


def exponent(state, i: int) -> int:
    """Call i's e: the first in the seed's order of 1 … EXPONENTS that no
    call of this run and no run with this persistent cache has taken (the
    record beside the cache), recorded there as it is taken. Past EXPONENTS
    calls with one cache, the seed's order repeats."""
    taken = state["exponents"]
    if i not in taken:
        record = state["record"]
        used = set(taken.values())
        if record and os.path.exists(record):
            with open(record) as f:
                used |= {int(e) for e in f.read().split()}
        order = [int(e) for e in
                 np.random.default_rng(state["seed"]).permutation(EXPONENTS) + 1]
        fresh = [e for e in order if e not in used]
        taken[i] = fresh[0] if fresh else order[i % EXPONENTS]
        if record:
            os.makedirs(os.path.dirname(record), exist_ok=True)
            with open(record, "a") as f:
                f.write(f"{taken[i]}\n")
    return taken[i]


def fit_rows(state, Yc, wc, tr: dict) -> dict:
    from repro.core import mctm_fit

    prog = state["program"]
    result = mctm_fit.fit_mctm_streaming(
        prog["model"], prog["scaler"], Yc, wc, method=tr["method"],
        steps=tr["steps"], gtol=tr["gtol"], history=tr["history"],
        chunk_size=tr["chunk_size"])
    return {"theta_raw": np.asarray(result.params.theta_raw, np.float64),
            "lam": np.asarray(result.params.lam, np.float64),
            "final_nll": float(result.final_nll), "steps": len(result.losses),
            "sweeps": dict(mctm_fit.LAST_LBFGS_SWEEPS)}


def call(ctx, state, i: int) -> dict:
    return {"i": i, "e": exponent(state, i),
            **fit_rows(state, *coreset(state, i), ctx.traffic)}


def work(ctx, result) -> dict:
    sw = result["sweeps"]
    return {"fits": 1, "sweeps": sw.get("vg", 0) + sw.get("hvp", 0),
            "iters": sw.get("iters", 0)}


def control_call(ctx, state, i: int) -> dict:
    from chipbench import control

    cfg, tr = ctx.config, ctx.traffic
    Yc, wc = coreset(state, i)
    out = control.fit({"Y": Yc, "w": wc, "low": state["low"], "high": state["high"],
                       "degree": cfg["degree"], "eta": cfg["eta"],
                       "min_slope": cfg["min_slope"], "steps": tr["steps"],
                       "gtol": tr["gtol"], "history": tr["history"]})
    out["i"] = i
    return out


def release(state) -> None:
    for k in ("program", "mesh"):
        state.pop(k, None)


def check(ctx, state, results, rng) -> list[tuple[str, float]]:
    """Numbers compared, each the worst over the sampled fits."""
    cfg = ctx.config
    J, d = cfg["J"], cfg["degree"] + 1
    n_check = min(len(results), int(ctx.traffic.get("check_calls", 2)))
    picks = sorted(rng.choice(len(results), size=n_check, replace=False).tolist())
    worst = collections.defaultdict(float)
    for p in picks:
        res = results[p]
        raw, lam = res["theta_raw"], res["lam"]
        ok_shape = (raw.shape == (J, d) and lam.shape == (J * (J - 1) // 2,)
                    and np.all(np.isfinite(raw)) and np.all(np.isfinite(lam))
                    and np.isfinite(res["final_nll"]))
        worst["shape_err"] = max(worst["shape_err"], 0.0 if ok_shape else 1.0)
        if not ok_shape:
            continue
        Yc, wc = coreset(state, res["i"])
        obj = R.MCTMObjective(Yc, wc, state["low"], state["high"], cfg["degree"],
                              cfg["eta"], cfg["min_slope"])
        f = obj.value(raw, lam)
        f_best = R.polish(obj, raw, lam)[2]
        worst["obj_dev"] = max(worst["obj_dev"], abs(res["final_nll"] - f) / abs(f))
        worst["fit_gap"] = max(worst["fit_gap"], (f - f_best) / abs(f_best))
    return list(worst.items())
