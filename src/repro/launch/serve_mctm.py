"""End-to-end serving driver: DGP stream → coreset → fit → serve → refresh.

``python -m repro.launch.serve_mctm --smoke``

The live-service loop of ROADMAP item 1, wired over the paper's pipeline:

  1. A DGP stream is consumed chunk-by-chunk into ``MergeReduceCoreset``
     (the first half of the stream seeds the initial model).
  2. Streamed L-BFGS fit on the maintained coreset
     (``core.mctm_fit.fit_mctm_streaming``) → initial publish.
  3. ``DensityServeEngine`` warms its bucket ladder and serves mixed
     open-loop traffic (``log_density`` + conditional ``sample``).
  4. Mid-traffic, the rest of the stream arrives; a background refit on the
     refreshed coreset publishes atomically while queries are in flight
     (the refresh cycle: cheap refits are the coreset's economics).

Prints a latency/throughput/consistency summary and exits nonzero if any
query was dropped, served with mixed params, or the steady state recompiled.
``benchmarks/serve_bench.py`` is the measured version of this loop.
"""
from __future__ import annotations

import argparse
import os
import sys
import time

REPO_ROOT = os.path.normpath(os.path.join(os.path.dirname(__file__), "..", "..", ".."))


def parse_args(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--dgp", default="normal_mixture")
    ap.add_argument("--n", type=int, default=200_000,
                    help="total stream length (first half seeds the model)")
    ap.add_argument("--k", type=int, default=1000, help="coreset size")
    ap.add_argument("--degree", type=int, default=6)
    ap.add_argument("--steps", type=int, default=200, help="fit iterations")
    ap.add_argument("--chunk", type=int, default=16_384,
                    help="stream chunk size (also the fit chunk)")
    ap.add_argument("--queries", type=int, default=4096,
                    help="total queries of mixed traffic")
    ap.add_argument("--sample-frac", type=float, default=0.25,
                    help="fraction of traffic that is conditional-sample")
    ap.add_argument("--max-batch", type=int, default=256)
    ap.add_argument("--min-bucket", type=int, default=8)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny end-to-end run (seconds — the CI job)")
    args = ap.parse_args(argv)
    if args.smoke:
        args.n = min(args.n, 20_000)
        args.k = min(args.k, 400)
        args.steps = min(args.steps, 60)
        args.chunk = min(args.chunk, 4096)
        args.queries = min(args.queries, 1024)
        args.max_batch = min(args.max_batch, 64)
    return args


def run(args) -> dict:
    import jax
    import numpy as np

    from repro.core import mctm as M
    from repro.core.bernstein import DataScaler
    from repro.core.mctm_fit import fit_mctm_streaming
    from repro.core.streaming import MergeReduceCoreset
    from repro.data.dgp import generate
    from repro.serve.density import DensityServeEngine, start_background_refit

    rng = np.random.default_rng(args.seed)
    key = jax.random.PRNGKey(args.seed)
    k_cs, k_fit, k_refit, k_serve = jax.random.split(key, 4)

    cfg = M.MCTMConfig(J=2, degree=args.degree)
    Y = generate(args.dgp, args.n, seed=args.seed).astype(np.float32)
    scaler = DataScaler.fit(Y)  # full-range scaler, shared by every fit
    half = args.n // 2

    # ---- 1+2: stream first half into the coreset, fit, publish v0
    t0 = time.perf_counter()
    stream = MergeReduceCoreset(cfg, scaler, args.k, k_cs)
    for s in range(0, half, args.chunk):
        stream.push(Y[s:s + args.chunk])
    ws = stream.result()
    fit = fit_mctm_streaming(
        cfg, scaler, ws.Y, weights=np.asarray(ws.weights, np.float32),
        key=k_fit, steps=args.steps, method="lbfgs", chunk_size=args.chunk,
    )
    boot_s = time.perf_counter() - t0
    print(f"[serve_mctm] boot: {stream.n_seen} rows streamed → k={ws.size} "
          f"coreset → lbfgs fit in {boot_s:.1f}s", flush=True)

    # ---- 3: serve mixed open-loop traffic
    engine = DensityServeEngine(
        cfg, fit.params, scaler, max_batch=args.max_batch,
        min_bucket=args.min_bucket, sample_key=k_serve,
    )
    compiled = engine.warmup()
    warm_compiles = engine.compile_count
    print(f"[serve_mctm] warmup: {compiled} executables over buckets "
          f"{engine.buckets}", flush=True)

    n_sample = int(args.queries * args.sample_frac)
    n_logd = args.queries - n_sample
    qY = Y[rng.integers(0, args.n, size=max(n_logd, 1))]
    refit_thread = None
    refit_at = args.queries // 3
    submitted = 0
    all_reqs = []
    si = li = 0
    serve_t0 = time.perf_counter()
    while (
        submitted < args.queries
        or any(engine.queues.values())
        # keep traffic flowing until the refit's publish is served live —
        # the whole point is a hot swap with queries in flight
        or (refit_thread is not None and engine.version < 1)
    ):
        # open-loop arrivals: a burst per tick, mixed kinds
        burst = min(args.max_batch // 2, max(args.queries - submitted, 4))
        for _ in range(burst):
            if (si + li) % 4 == 3 and (si < n_sample or li >= n_logd):
                all_reqs += engine.submit_sample(
                    1, y_obs=Y[si % args.n], n_obs=1, seeds=[si])
                si += 1
            else:
                all_reqs += engine.submit_log_density(qY[li % len(qY)][None])
                li += 1
            submitted += 1
        if refit_thread is None and submitted >= refit_at:
            # ---- 4: rest of the stream arrives → background refit+publish
            for s in range(half, args.n, args.chunk):
                stream.push(Y[s:s + args.chunk])
            ws2 = stream.result()

            def _refit(engine=engine):
                f2 = fit_mctm_streaming(
                    cfg, scaler, ws2.Y,
                    weights=np.asarray(ws2.weights, np.float32),
                    key=k_refit, steps=args.steps, method="lbfgs",
                    chunk_size=args.chunk,
                )
                engine.publish(f2.params)

            import threading

            refit_thread = threading.Thread(target=_refit, daemon=True)
            refit_thread.start()
        engine.step()
    if refit_thread is not None:
        refit_thread.join()
    serve_s = time.perf_counter() - serve_t0

    # ---- consistency + latency summary
    lat = np.asarray([r.latency_s for r in all_reqs], np.float64)
    versions = sorted({r.version for r in all_reqs})
    dropped = sum(1 for r in all_reqs if not r.done)
    recompiles = engine.compile_count - warm_compiles
    stall = [e["visible_s"] - e["published_s"]
             for e in engine.swap_events if e["visible_s"]]
    rec = {
        "queries": len(all_reqs),
        "dropped": dropped,
        "versions_served": versions,
        "steady_state_recompiles": recompiles,
        "qps": len(all_reqs) / max(serve_s, 1e-9),
        "p50_ms": float(np.percentile(lat, 50) * 1e3),
        "p99_ms": float(np.percentile(lat, 99) * 1e3),
        "swap_stall_ms": float(max(stall) * 1e3) if stall else 0.0,
        "final_version": engine.version,
    }
    print(f"[serve_mctm] served {rec['queries']} queries in {serve_s:.2f}s "
          f"({rec['qps']:.0f} QPS)  p50 {rec['p50_ms']:.2f}ms  "
          f"p99 {rec['p99_ms']:.2f}ms", flush=True)
    print(f"[serve_mctm] hot swap: versions {versions} served, "
          f"publish→visible {rec['swap_stall_ms']:.2f}ms, "
          f"dropped={dropped}, steady-state recompiles={recompiles}",
          flush=True)
    return rec


def main(argv=None):
    from repro.utils.compile_cache import enable_compile_cache

    args = parse_args(argv)
    enable_compile_cache()
    rec = run(args)
    ok = (
        rec["dropped"] == 0
        and rec["steady_state_recompiles"] == 0
        and rec["final_version"] >= 1
        # the refit's publish was served LIVE: traffic straddled the swap
        and set(rec["versions_served"]) >= {0, 1}
    )
    if not ok:
        print("[serve_mctm] FAILED consistency checks", flush=True)
        sys.exit(1)


if __name__ == "__main__":
    main()
