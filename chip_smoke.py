"""Bring-up smoke: the coreset → fit → serve main path on one TPU chip.

    python chip_smoke.py [--out DIR] [--seed N]
    python chip_smoke.py --four-chips   # the sharded build on a 4-chip mesh only

The default run drives the paper-scale experiment (J=2, Bernstein degree 6,
``normal_mixture``, n = 250,001, k = 2000 ``l2-hull``) in this one process,
on a data mesh over ``jax.devices()[:1]``, through the entry points a user
calls:

1. ``distributed_build_coreset`` with the exact ``two-pass`` strategy and the
   ``one-pass`` sketched strategy (sketch 4·D² = 784);
2. ``launch.train_mctm.run --ks 2000 --fit-method lbfgs`` per strategy: the
   coreset L-BFGS fit, the streamed full-data L-BFGS reference fit, the
   streamed NLLs, ε̂ and its (1±ε̂) band;
3. ``DensityServeEngine``: warm-up, then ``log_density`` and conditional
   ``sample`` requests served through ``step()``.

It exits 1 unless every check holds: the kernel dispatch resolves to the
compiled Pallas kernels and the build programs contain them; ε̂ sits inside
its band for both strategies; the two-pass leverage scores, a fitted NLL and
the served log-densities agree with a float64 NumPy reference computed on
the host from the same rows; steady-state serving compiles nothing. It
exits 2 and prints no result off a TPU or outside the repository. Phase
times are bring-up wall clock including compilation, not a benchmark.
Records go to ``--out`` (a git-ignored directory); the last line of
standard output is the JSON result.

``--four-chips`` builds both strategies on a 4-device data mesh and on
device 0 alone, and prints their score deviations (and the two-pass
deviation from the float64 reference), hull-point equality, and the
all-reduce count of each compiled sharded sweep.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

REPO_ROOT = os.path.dirname(os.path.abspath(__file__))

J, DEGREE, N, K, CHUNK = 2, 6, 250_001, 2000, 16_384
D = J * (DEGREE + 1)
SKETCH = 4 * D * D
STRATEGIES = ("two-pass", "one-pass")
# Tolerances against the float64 host reference.
# Scores: the CPU tests' bound for f32 Gram-accumulation noise on Gaussian-
# like data (tests/test_scoring.py::test_chunked_matches_dense_engine_gaussian,
# atol 1e-3). This data's degree-6 Gram has two eigenvalues at 1.3e-6 and
# 1.6e-6 of the largest, beside the 1e-6 cutoff, so an exact f32 path moves
# leverage by a few 1e-4 at n=250k; one bf16 pass moves it by ~2e-2.
# NLL total: the streamed-vs-dense bound (tests/test_mctm_fit.py, 1e-5 rel).
# Served log-densities: one f32 evaluation per point with no averaging,
# 1e-4 relative (floored at 1e-4 absolute); one bf16 pass gives ~4e-3.
SCORE_ATOL = 1e-3
NLL_RTOL = 1e-5
LOGD_RTOL = 1e-4
# what tests/test_distributed.py holds the sharded one-pass scores to
SHARDED_SCORE_ATOL = 1e-6


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out", default=os.path.join(REPO_ROOT, "chiprun_out", "chip_smoke"),
                    help="directory for the run's records (git-ignored)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded build on 4 chips and its comparison")
    return ap.parse_args(argv)


def fail_setup(msg: str):
    print(f"[chip_smoke] {msg}", file=sys.stderr, flush=True)
    sys.exit(2)


# ---------------------------------------------------------------------------
# float64 host reference (NumPy only, independent of the code under test)
# ---------------------------------------------------------------------------


def _binom(m: int) -> np.ndarray:
    from math import comb

    return np.array([comb(m, k) for k in range(m + 1)], np.float64)


def _bernstein64(t: np.ndarray, m: int) -> np.ndarray:
    t = np.clip(t, 0.0, 1.0)[..., None]
    k = np.arange(m + 1)
    return _binom(m) * t**k * (1.0 - t) ** (m - k)


def host_features(scaler, Y) -> tuple[np.ndarray, np.ndarray]:
    """(A, A′) of shape (n, J, d) in float64 from the float32 rows."""
    low = np.asarray(scaler.low, np.float64)
    high = np.asarray(scaler.high, np.float64)
    T = (np.asarray(Y, np.float64) - low) / (high - low)
    A = _bernstein64(T, DEGREE)
    lower = _bernstein64(T, DEGREE - 1)
    pad = np.zeros(lower.shape[:-1] + (1,))
    dA = DEGREE * (np.concatenate([pad, lower], -1) - np.concatenate([lower, pad], -1))
    return A, dA / (high - low)[:, None]


def host_leverage(A: np.ndarray, rcond: float = 1e-6) -> np.ndarray:
    """Exact leverage of the flattened basis, eigh pseudo-inverse with the
    engine's relative cutoff."""
    X = A.reshape(A.shape[0], -1)
    w, V = np.linalg.eigh(X.T @ X)
    inv = np.where(w > rcond * np.abs(w).max(), 1.0 / np.maximum(w, 1e-300), 0.0)
    return np.sum((X @ V) ** 2 * inv, axis=1)


def host_nll_terms(cfg, params, A: np.ndarray, dA: np.ndarray) -> np.ndarray:
    raw = np.asarray(params.theta_raw, np.float64)
    steps = np.logaddexp(0.0, raw[:, 1:]) + cfg.min_slope
    theta = np.concatenate([raw[:, :1], raw[:, :1] + np.cumsum(steps, -1)], -1)
    lam = np.eye(cfg.J)
    lam[np.tril_indices(cfg.J, -1)] = np.asarray(params.lam, np.float64)
    h = np.einsum("njd,jd->nj", A, theta)
    hp = np.einsum("njd,jd->nj", dA, theta)
    z = h @ lam.T
    per = 0.5 * z**2 - np.log(np.maximum(hp, cfg.eta)) + 0.5 * np.log(2 * np.pi)
    return per.sum(-1)


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------


class Checks:
    """Named pass/fail results; every phase runs, the verdict comes last."""

    def __init__(self):
        self.results: list[tuple[str, bool, str]] = []

    def add(self, name: str, ok: bool, detail: str = "") -> bool:
        self.results.append((name, bool(ok), detail))
        print(f"[chip_smoke] check {name}: {'PASS' if ok else 'FAIL'} {detail}", flush=True)
        return bool(ok)

    @property
    def ok(self) -> bool:
        return all(ok for _, ok, _ in self.results)


def timed(label: str, fn, *args, **kwargs):
    t0 = time.perf_counter()
    out = fn(*args, **kwargs)
    s = time.perf_counter() - t0
    print(f"[chip_smoke] phase {label}: {s:.2f} s wall (bring-up, not a benchmark)",
          flush=True)
    return out, s


def build_sweeps(cfg, scaler, mesh) -> dict:
    """The sharded sweep programs ``distributed_build_coreset`` runs at this
    layout, each with its argument shapes: name → (fn, args)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from repro.core.distributed_coreset import (
        DistributedScoringEngine,
        make_sharded_onepass_fn,
        make_sharded_pass_fns,
        shard_layout,
    )

    feat = DistributedScoringEngine(cfg, scaler, mesh=mesh).featurize
    chunk, cps, n_pad = shard_layout(mesh, "data", N, CHUNK)
    common = dict(chunk=chunk, chunks_per_shard=cps, rows_per_point=J, hull=True, D=D)
    pass1, pass2 = make_sharded_pass_fns(feat, mesh, ("data",), p=DEGREE + 1, **common)
    onepass = make_sharded_onepass_fn(feat, mesh, ("data",), q=None, sketch_size=SKETCH,
                                      **common)

    def sds(shape, spec, dt=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=NamedSharding(mesh, spec))

    rows = (sds((n_pad, J), P("data", None)), sds((n_pad,), P("data")),
            sds((n_pad,), P("data")))
    dirs = sds((4 * (K - int(0.8 * K)), DEGREE + 1), P())
    return {
        "two-pass pass 1 (gram)": (pass1, rows),
        "two-pass pass 2 (extremes)": (pass2, rows + (sds((D, D), P()), sds((D,), P()),
                                                      dirs)),
        "one-pass (sweep)": (onepass, rows + (sds((n_pad,), P("data"), jnp.int32),
                                              sds((n_pad,), P("data")), dirs)),
    }


def run_one_chip(args, checks: Checks, record: dict):
    import jax

    from repro.core import mctm as M
    from repro.core.bernstein import DataScaler
    from repro.core.distributed_coreset import distributed_build_coreset
    from repro.core.mctm_fit import fit_mctm_streaming, streamed_nll
    from repro.data.dgp import generate
    from repro.kernels.bernstein.ops import default_bernstein_backend
    from repro.kernels.extremes.ops import default_extremes_backend
    from repro.kernels.gram.ops import default_gram_backend
    from repro.kernels.sweep.ops import default_sweep_backend
    from repro.launch import train_mctm
    from repro.serve.density import DensityServeEngine
    from repro.utils.compat import make_mesh

    mesh = make_mesh((1,), ("data",), devices=jax.devices()[:1])
    backends = {
        "sweep": default_sweep_backend(),
        "gram": default_gram_backend(),
        "extremes": default_extremes_backend(),
        "bernstein": default_bernstein_backend(),
    }
    print(f"[chip_smoke] kernel backends: {backends}", flush=True)
    record["backends"] = backends
    checks.add("pallas backends", all(b == "pallas" for b in backends.values()),
               str(backends))

    cfg = M.MCTMConfig(J=J, degree=DEGREE)
    Y = generate("normal_mixture", N, seed=args.seed).astype(np.float32)
    scaler = DataScaler.fit(Y)
    A64, dA64 = host_features(scaler, Y)

    # the kernels are chosen at trace time, so the traced programs are what runs
    calls = {name: str(jax.make_jaxpr(fn)(*fargs)).count("pallas_call")
             for name, (fn, fargs) in build_sweeps(cfg, scaler, mesh).items()}
    print(f"[chip_smoke] pallas_call per build program: {calls}", flush=True)
    record["pallas_calls"] = calls
    checks.add("kernels in build programs", all(c >= 1 for c in calls.values()),
               str(calls))

    # ---- 1. coreset builds
    key = jax.random.PRNGKey(args.seed)
    builds, phases = {}, {}
    for strategy in STRATEGIES:
        cs, phases[f"build {strategy}"] = timed(
            f"build {strategy}", distributed_build_coreset,
            cfg, scaler, Y, K, "l2-hull", mesh=mesh, key=key, chunk_size=CHUNK,
            sketch_size=SKETCH if strategy == "one-pass" else 0,
        )
        builds[strategy] = cs
        valid = (cs.size == K and np.all(np.isfinite(cs.weights))
                 and np.all(cs.weights > 0) and np.all(np.isfinite(cs.scores))
                 and cs.indices.min() >= 0 and cs.indices.max() < N)
        checks.add(f"coreset {strategy}", valid, f"k={cs.size}")

    u_ref = host_leverage(A64)
    for strategy, cs in builds.items():
        u = np.asarray(cs.scores, np.float64) - 1.0 / N
        dev = float(np.abs(u - u_ref).max())
        rel = dev / float(u_ref.max())
        record[f"leverage_max_abs_dev_{strategy}"] = dev
        if strategy == "two-pass":
            checks.add("two-pass scores vs float64", dev <= SCORE_ATOL,
                       f"max |u - u_ref| = {dev:.3e} ({rel:.3e} of max u_ref), "
                       f"tol {SCORE_ATOL:g}")
        else:
            print(f"[chip_smoke] one-pass sketched scores vs exact float64 leverage: "
                  f"max |u - u_ref| = {dev:.3e} ({rel:.3e} of max u_ref); "
                  f"a sketch estimate, no tolerance", flush=True)

    # ---- 2. fits + ε̂ through the experiment driver, in this process
    os.makedirs(args.out, exist_ok=True)
    for strategy in STRATEGIES:
        targs = train_mctm.parse_args([
            "--ks", str(K), "--fit-method", "lbfgs", "--strategy", strategy,
            "--chunk", str(CHUNK), "--n", str(N), "--seed", str(args.seed),
            "--out", os.path.join(args.out, f"train_mctm_{strategy}.json"),
        ])
        rec, phases[f"train_mctm {strategy}"] = timed(
            f"train_mctm {strategy}", train_mctm.run, targs, mesh=mesh)
        r = rec["per_k"][0]
        record[f"train_mctm_{strategy}"] = r
        checks.add(f"eps band {strategy}", r["within_band"],
                   f"eps_hat={r['eps_hat']:.5f} ratio={r['ratio']:.5f} "
                   f"band=({r['band'][0]:.5f}, {r['band'][1]:.5f})")

    cs = builds["two-pass"]
    fit, phases["coreset fit"] = timed(
        "coreset fit", fit_mctm_streaming, cfg, scaler, Y[cs.indices],
        weights=np.asarray(cs.weights, np.float32), key=key, steps=400,
        method="lbfgs", gtol=1e-5, mesh=mesh, chunk_size=CHUNK,
    )
    nll_dev = streamed_nll(cfg, scaler, fit.params, Y, chunk=CHUNK, mesh=mesh)
    nll_ref = float(host_nll_terms(cfg, fit.params, A64, dA64).sum())
    rel = abs(nll_dev - nll_ref) / abs(nll_ref)
    record["nll_rel_dev"] = rel
    checks.add("streamed NLL vs float64", rel <= NLL_RTOL,
               f"device {nll_dev:.6f} host {nll_ref:.6f} rel {rel:.3e} tol {NLL_RTOL:g}")

    # ---- 3. serving
    engine = DensityServeEngine(cfg, fit.params, scaler, max_batch=256, min_bucket=8,
                                sample_key=jax.random.fold_in(key, 7))
    n_warm, phases["serve warmup"] = timed("serve warmup", engine.warmup)
    warm = engine.compile_count
    rng = np.random.default_rng(args.seed)
    q_idx = rng.choice(N, size=300, replace=False)
    t0 = time.perf_counter()
    logd = engine.submit_log_density(Y[q_idx])
    samples = []
    for i, row in enumerate(q_idx[:100]):
        samples += engine.submit_sample(1, y_obs=Y[row], n_obs=1 if i % 2 else 0,
                                        seeds=[int(row)])
    ticks = 0
    while any(engine.queues.values()):
        engine.step()
        ticks += 1
    phases["serve traffic"] = time.perf_counter() - t0
    print(f"[chip_smoke] phase serve traffic: {phases['serve traffic']:.2f} s wall "
          f"for {len(logd) + len(samples)} requests in {ticks} ticks "
          "(bring-up, not a benchmark)", flush=True)
    recompiles = engine.compile_count - warm
    record["serve"] = {"warmup_executables": n_warm, "ticks": ticks,
                       "steady_state_recompiles": recompiles}
    checks.add("serve all answered",
               all(r.done for r in logd + samples), f"{len(logd) + len(samples)} requests")
    checks.add("serve 0 recompiles", recompiles == 0, f"recompiles={recompiles}")

    got = np.array([r.result for r in logd], np.float64)
    Aq, dAq = host_features(scaler, Y[q_idx])
    want = -host_nll_terms(cfg, fit.params, Aq, dAq)
    dev = np.abs(got - want)
    tol = LOGD_RTOL * np.maximum(np.abs(want), 1.0)
    record["logd_max_abs_dev"] = float(dev.max())
    checks.add("served log-density vs float64", bool(np.all(dev <= tol)),
               f"max |dev| = {dev.max():.3e} (max rel {np.max(dev / np.abs(want)):.3e}), "
               f"tol {LOGD_RTOL:g}·max(|ref|, 1)")
    out = np.stack([r.result for r in samples])
    lo, hi = np.asarray(scaler.low), np.asarray(scaler.high)
    obs_kept = all(np.float32(r.result[0]) == r.y[0] for r in samples if r.n_obs)
    checks.add("served samples", bool(np.all(np.isfinite(out)) and obs_kept
                                      and np.all((out >= lo) & (out <= hi))),
               "finite, inside the scaler range, observed prefix kept")
    record["phases_s"] = phases


def run_four_chips(args, checks: Checks, record: dict):
    import jax

    from repro.core import mctm as M
    from repro.core.bernstein import DataScaler
    from repro.core.distributed_coreset import distributed_build_coreset
    from repro.data.dgp import generate
    from repro.utils.compat import make_mesh
    from repro.utils.hlo import collective_stats

    if len(jax.devices()) < 4:
        fail_setup(f"--four-chips needs 4 devices, found {len(jax.devices())}")
    mesh4 = make_mesh((4,), ("data",), devices=jax.devices()[:4])
    mesh1 = make_mesh((1,), ("data",), devices=jax.devices()[:1])
    cfg = M.MCTMConfig(J=J, degree=DEGREE)
    Y = generate("normal_mixture", N, seed=args.seed).astype(np.float32)
    scaler = DataScaler.fit(Y)
    u_ref = host_leverage(host_features(scaler, Y)[0])
    key = jax.random.PRNGKey(args.seed)
    k_sample = int(np.floor(0.8 * K))
    phases = {}
    for strategy in STRATEGIES:
        sketch = SKETCH if strategy == "one-pass" else 0
        res = {}
        for name, mesh in (("4 chips", mesh4), ("device 0", mesh1)):
            res[name], phases[f"build {strategy} {name}"] = timed(
                f"build {strategy} on {name}", distributed_build_coreset,
                cfg, scaler, Y, K, "l2-hull", mesh=mesh, key=key, chunk_size=CHUNK,
                sketch_size=sketch)
        a, b = res["4 chips"], res["device 0"]
        dev = float(np.abs(a.scores - b.scores).max())
        hull_eq = bool(np.array_equal(a.indices[k_sample:], b.indices[k_sample:]))
        same_idx = bool(np.array_equal(a.indices, b.indices))
        rec = {"max_abs_dev_4_vs_1": dev, "hull_points_equal": hull_eq,
               "indices_equal": same_idx}
        print(f"[chip_smoke] {strategy}: 4 chips vs device 0 max |Δscore| = {dev:.3e} "
              f"(tests/test_distributed.py holds one-pass to {SHARDED_SCORE_ATOL:g}: "
              f"{'met' if dev <= SHARDED_SCORE_ATOL else 'not met'}); "
              f"hull points equal: {hull_eq}; all indices equal: {same_idx}", flush=True)
        checks.add(f"coreset {strategy} 4 chips",
                   a.size == K and bool(np.all(np.isfinite(a.scores))), f"k={a.size}")
        if strategy == "two-pass":
            for name, cs in res.items():
                d = float(np.abs(np.asarray(cs.scores, np.float64) - 1.0 / N - u_ref).max())
                rec[f"max_abs_dev_vs_float64_{name}"] = d
                checks.add(f"two-pass scores vs float64 ({name})", d <= SCORE_ATOL,
                           f"max |u - u_ref| = {d:.3e}, tol {SCORE_ATOL:g}")
        record[strategy] = rec

    # one fused all-reduce per sweep, counted in the programs compiled for
    # this mesh at this layout
    sweeps = build_sweeps(cfg, scaler, mesh4)
    for name in ("two-pass pass 1 (gram)", "one-pass (sweep)"):
        fn, fargs = sweeps[name]
        text = jax.jit(fn).lower(*fargs).compile().as_text()
        n_ar = collective_stats(text)["by_op"].get("all-reduce", {"count": 0})["count"]
        n_kernels = text.count("tpu_custom_call")
        record[f"all_reduce {name}"] = n_ar
        checks.add(f"one psum in {name}", n_ar == 1 and n_kernels >= 1,
                   f"all-reduce ops in compiled HLO: {n_ar}; "
                   f"tpu_custom_call: {n_kernels}")
    record["phases_s"] = phases


def main(argv=None) -> int:
    args = parse_args(argv)
    src = os.path.join(REPO_ROOT, "src")
    sys.path.insert(0, src)
    try:
        import repro
        from repro.utils.compile_cache import enable_compile_cache
    except ImportError as e:
        fail_setup(f"cannot import the repro package next to this script: {e}")
    if not os.path.abspath(repro.__file__).startswith(src + os.sep):
        fail_setup(f"cannot import the repro package next to this script: "
                   f"found {repro.__file__} instead")
    cache_dir = enable_compile_cache()
    import jax

    if jax.default_backend() != "tpu":
        fail_setup(f"no TPU found (JAX backend is {jax.default_backend()!r}); "
                   "this smoke runs only on the chip")
    n_dev = 4 if args.four_chips else 1
    dev = jax.devices()[0]
    print(f"[chip_smoke] device_kind={dev.device_kind} platform={dev.platform} "
          f"using {n_dev} of {len(jax.devices())} devices; "
          f"jax {jax.__version__}; compile cache {cache_dir}", flush=True)
    checks, record = Checks(), {"device_kind": dev.device_kind, "chips": n_dev,
                                "compile_cache": cache_dir}
    t0 = time.perf_counter()
    try:
        (run_four_chips if args.four_chips else run_one_chip)(args, checks, record)
    except Exception as e:  # noqa: BLE001 — a phase that raised is a failed check
        import traceback

        traceback.print_exc()
        checks.add("phases completed", False, f"{type(e).__name__}: {e}")
    record["total_s"] = time.perf_counter() - t0
    record["checks"] = [{"name": n, "ok": ok, "detail": d} for n, ok, d in checks.results]
    os.makedirs(args.out, exist_ok=True)
    name = "four_chips.json" if args.four_chips else "one_chip.json"
    with open(os.path.join(args.out, name), "w") as f:
        json.dump(record, f, indent=1, default=float)
    print(f"[chip_smoke] {sum(ok for _, ok, _ in checks.results)}/{len(checks.results)} "
          f"checks passed in {record['total_s']:.1f} s; record in {args.out}", flush=True)
    print(json.dumps({"ok": checks.ok, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": n_dev}}), flush=True)
    return 0 if checks.ok else 1


if __name__ == "__main__":
    sys.exit(main())
