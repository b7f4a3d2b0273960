"""End-to-end driver for the paper's experiment: DGP → distributed coreset →
sharded MCTM fit → streamed full-data (1±ε) NLL validation.

``python -m repro.launch.train_mctm --reduced --smoke``

Stages (every data-sized computation on the device mesh):
  1. DGP sample (paper §E.1.1 generators) + full-data scaler.
  2. ``distributed_build_coreset`` — any pass strategy (``--strategy
     two-pass`` exact, ``--strategy one-pass`` with ``--sketch-size``).
  3. Sharded weighted-NLL coreset fit (``core.mctm_fit`` on the trainer's
     SPMD step + ``repro.optim``; ``--ckpt-dir``/``--resume`` route through
     ``CheckpointManager``). ``--fit-method`` picks any fit mode of the
     ``core.mctm_fit`` method table: ``adam`` (default), ``lbfgs``
     (streaming-HVP quasi-Newton), or ``minibatch`` (``--batch-size``
     sampled weighted rows per step — for coresets beyond device memory).
  4. Full-data reference fit with the basis STREAMED microbatch-by-
     microbatch — never an (n, J, d) tensor — for wall-clock + quality.
     ``--ref-method`` defaults to the streaming ``lbfgs`` (the paper's
     experiments fit the full-data baseline quasi-Newton; streaming makes
     that ε̂ baseline scale past coreset-sized data).
  5. Streamed full-data NLL of both fits (strict η) through the one-psum
     shard_map sweep; per-k measured ε̂ (``coreset_epsilon``) and the
     likelihood-ratio check against the (1±ε̂) band: theory gives
     NLL(θ̂_C)/NLL(θ̂) ≤ (1+ε)/(1−ε) for exact minimizers, so the driver
     checks 1−ε̂−δ ≤ ratio ≤ (1+ε̂)/(1−ε̂)+δ with a small optimization
     slack δ (both sides are finite Adam runs, not exact minimizers).

Writes the ε-vs-k + wall-clock record to BENCH_mctm_fit.json at the repo
root (results/bench/BENCH_mctm_fit_smoke.json under ``--smoke``) and exits
nonzero if any ratio leaves its band.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

REPO_ROOT = os.path.normpath(os.path.join(os.path.dirname(__file__), "..", "..", ".."))


def parse_args(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--dgp", default="normal_mixture")
    ap.add_argument("--n", type=int, default=250_001)
    ap.add_argument("--ks", default=None,
                    help="coreset sizes (default by scale: 500,1000,2000,4000 "
                    "full / 500,2000 --reduced / 300,600 --smoke)")
    ap.add_argument("--steps", type=int, default=400)
    ap.add_argument("--fit-method", default="adam",
                    choices=("adam", "lbfgs", "minibatch"),
                    help="coreset-fit mode (core.mctm_fit method table)")
    ap.add_argument("--ref-method", default="lbfgs",
                    choices=("adam", "lbfgs", "minibatch"),
                    help="full-data reference-fit mode (default: streaming "
                    "lbfgs, the paper's quasi-Newton baseline)")
    ap.add_argument("--batch-size", type=int, default=4096,
                    help="minibatch-mode rows sampled per step")
    ap.add_argument("--gtol", type=float, default=1e-5,
                    help="lbfgs-mode gradient-norm early stop (the objective "
                    "is mean-normalized, so this is scale-free)")
    ap.add_argument("--lr", type=float, default=5e-2)
    ap.add_argument("--degree", type=int, default=6)
    ap.add_argument("--alpha", type=float, default=0.8)
    ap.add_argument("--chunk", type=int, default=16_384)
    ap.add_argument("--strategy", default="two-pass", choices=("two-pass", "one-pass"))
    ap.add_argument("--sketch-size", type=int, default=0,
                    help="one-pass CountSketch rows (0 → 4·(Jd)² auto)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--reduced", action="store_true",
                    help="CPU-container scale: fewer steps / fewer k points")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny end-to-end run (seconds — the CI job)")
    ap.add_argument("--fake-devices", type=int, default=8,
                    help="force N CPU devices when only one real device "
                    "exists (0 → use the devices jax reports)")
    ap.add_argument("--opt-slack", type=float, default=0.02,
                    help="likelihood-ratio tolerance for finite-step fits")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=0)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--log-every", type=int, default=0)
    ap.add_argument("--out", default=None)
    ap.add_argument("--inject-failures", nargs="?", const="scoring,fit,checkpoint",
                    default=None, metavar="PHASES",
                    help="failure-injected recovery drill: crash mid-scoring / "
                    "mid-fit / mid-checkpoint (comma list of phases; bare flag "
                    "= all three) and recover through the ft supervisor + "
                    "resumable sweeps; results tagged _ft, never gated")
    args = ap.parse_args(argv)
    if args.reduced:
        args.steps = min(args.steps, 250)
    if args.smoke:
        args.n = min(args.n, 30_001)
        args.steps = min(args.steps, 120)
        args.chunk = min(args.chunk, 4096)
        args.batch_size = min(args.batch_size, 1024)
    if args.ks is None:  # an explicitly passed --ks always wins
        args.ks = (
            "300,600" if args.smoke
            else "500,2000" if args.reduced
            else "500,1000,2000,4000"
        )
    return args


def run(args, mesh=None) -> dict:
    """The experiment (module doc) on ``mesh`` (default: a data mesh over
    every device). Returns the record it writes."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.checkpoint import CheckpointManager
    from repro.core import mctm as M
    from repro.core.bernstein import DataScaler
    from repro.core.distributed_coreset import distributed_build_coreset
    from repro.core.mctm_fit import (
        coreset_epsilon,
        fit_mctm_streaming,
        likelihood_ratio,
        streamed_nll,
    )
    from repro.data.dgp import generate
    from repro.ft import ElasticPlanner, FailureSimulator, RunSupervisor
    from repro.ft.config import get_ft_config
    from repro.launch.stages import data_mesh

    if mesh is None:
        mesh = data_mesh()
    devices = int(np.prod(list(mesh.shape.values())))
    dev0 = mesh.devices.flat[0]

    ft_cfg = get_ft_config()
    sim = None
    sup = None
    if args.inject_failures:
        import tempfile

        phases = [p.strip() for p in args.inject_failures.split(",") if p.strip()]
        if not args.ckpt_dir:
            args.ckpt_dir = tempfile.mkdtemp(prefix="ft_ckpt_")
        if not args.ckpt_every:
            args.ckpt_every = 20
        # several chunks per shard so mid-scoring checkpoints exist to resume
        args.chunk = min(args.chunk, 1024)
        sim = FailureSimulator()
        if "scoring" in phases:
            sim.inject("scoring", 2)
        if "fit" in phases:
            sim.inject("fit", max(args.steps // 3, 1))
        if "checkpoint" in phases:
            sim.inject("checkpoint", 2 * args.ckpt_every)
        ft_cfg.simulator = sim
        ft_cfg.sweep_ckpt_every_chunks = 2
        # the build has no internal supervisor — this outer one re-plans the
        # mesh (identity here: all devices stay alive) and replays the sweep
        # from its latest segment checkpoint via resume=ctx.resume
        sup = RunSupervisor(
            label="train_mctm",
            planner=ElasticPlanner(
                model_parallel=1,
                base_data_parallel=devices,
                base_global_batch=args.batch_size,
            ),
            remesh=lambda plan: mesh,
        )
    ks = [int(k) for k in args.ks.split(",")]
    cfg = M.MCTMConfig(J=2, degree=args.degree)
    D = cfg.J * cfg.d
    sketch = args.sketch_size
    if args.strategy == "one-pass" and sketch == 0:
        sketch = 4 * D * D

    print(f"[train_mctm] platform={dev0.platform} device_kind={dev0.device_kind} "
          f"dgp={args.dgp} n={args.n} devices={devices} "
          f"strategy={args.strategy} sketch={sketch} steps={args.steps} "
          f"fit={args.fit_method} ref={args.ref_method}",
          flush=True)
    Y = generate(args.dgp, args.n, seed=args.seed).astype(np.float32)
    scaler = DataScaler.fit(Y)
    key = jax.random.PRNGKey(args.seed)
    k_full_fit, k_build, k_cs_fit = jax.random.split(key, 3)

    def mgr(tag):
        if not args.ckpt_dir:
            return None
        return CheckpointManager(os.path.join(args.ckpt_dir, tag), keep=2)

    # ---- full-data reference fit: basis streamed, step sharded on the mesh
    # (default --ref-method lbfgs — the quasi-Newton full-data baseline the
    # paper's ε̂ comparison assumes, streaming-HVP so it scales with n)
    t0 = time.perf_counter()
    full = fit_mctm_streaming(
        cfg, scaler, Y, steps=args.steps, lr=args.lr, key=k_full_fit,
        method=args.ref_method, batch_size=args.batch_size, gtol=args.gtol,
        mesh=mesh, chunk_size=args.chunk,
        checkpoint=mgr("full"), ckpt_every=args.ckpt_every,
        resume=args.resume, log_every=args.log_every,
    )
    full_fit_s = time.perf_counter() - t0
    nll_full_at_full = streamed_nll(
        cfg, scaler, full.params, Y, chunk=args.chunk, mesh=mesh, eta=1e-9
    )
    print(f"[train_mctm] full fit {full_fit_s:.1f}s  "
          f"NLL/pt {nll_full_at_full / args.n:.4f}", flush=True)

    per_k = []
    for k in ks:
        kb = jax.random.fold_in(k_build, k)
        t0 = time.perf_counter()

        def build(ctx=None):
            return distributed_build_coreset(
                cfg, scaler, Y, k, "l2-hull", mesh=mesh, key=kb,
                alpha=args.alpha, sketch_size=sketch, chunk_size=args.chunk,
                sweep_ckpt=(os.path.join(args.ckpt_dir, f"build_k{k}")
                            if args.inject_failures else None),
                resume=bool(ctx is not None and ctx.resume),
            )

        # under --inject-failures the sweep crash is retried here, resuming
        # from the latest scoring-segment checkpoint on the re-planned mesh
        cs = sup.run(build) if sup is not None else build()
        build_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        fit = fit_mctm_streaming(
            cfg, scaler, Y[cs.indices],
            weights=np.asarray(cs.weights, np.float32),
            steps=args.steps, lr=args.lr, key=jax.random.fold_in(k_cs_fit, k),
            method=args.fit_method, batch_size=args.batch_size, gtol=args.gtol,
            mesh=mesh, chunk_size=args.chunk,
            checkpoint=mgr(f"k{k}"), ckpt_every=args.ckpt_every,
            resume=args.resume, log_every=args.log_every,
        )
        fit_s = time.perf_counter() - t0
        nll_full_at_cs = streamed_nll(
            cfg, scaler, fit.params, Y, chunk=args.chunk, mesh=mesh, eta=1e-9
        )
        eps = coreset_epsilon(
            cfg, scaler, Y, Y[cs.indices], np.asarray(cs.weights, np.float32),
            [fit.params, full.params],
            chunk=args.chunk, mesh=mesh, eta=1e-9,
            # full-data sweeps already ran for the ratio — don't pay them twice
            full_nlls=[nll_full_at_cs, nll_full_at_full],
        )
        ratio = likelihood_ratio(nll_full_at_cs, nll_full_at_full)
        lo = 1.0 - eps - args.opt_slack
        hi = (1.0 + eps) / max(1.0 - eps, 1e-6) + args.opt_slack
        within = lo <= ratio <= hi
        speedup = full_fit_s / max(build_s + fit_s, 1e-9)
        per_k.append({
            "k": k,
            "build_s": build_s,
            "fit_s": fit_s,
            "total_s": build_s + fit_s,
            "speedup_vs_full_fit": speedup,
            "eps_hat": eps,
            "ratio": ratio,
            "band": [lo, hi],
            "within_band": bool(within),
            "nll_full_at_cs_per_point": nll_full_at_cs / args.n,
        })
        print(f"[train_mctm] k={k:6d}  build {build_s:6.2f}s fit {fit_s:6.2f}s  "
              f"eps={eps:.4f}  ratio={ratio:.4f} in ({lo:.3f}, {hi:.3f}) "
              f"{'OK' if within else 'VIOLATION'}  "
              f"speedup {speedup:.1f}x", flush=True)

    rec = {
        "dgp": args.dgp,
        "n": args.n,
        "J": cfg.J,
        "degree": args.degree,
        "steps": args.steps,
        "fit_method": args.fit_method,
        "ref_method": args.ref_method,
        "batch_size": args.batch_size,
        "lr": args.lr,
        "chunk": args.chunk,
        "alpha": args.alpha,
        "strategy": args.strategy,
        "sketch_size": sketch,
        "devices": devices,
        "smoke": bool(args.smoke),
        "reduced": bool(args.reduced),
        "opt_slack": args.opt_slack,
        "full_fit_s": full_fit_s,
        "full_nll_per_point": nll_full_at_full / args.n,
        "per_k": per_k,
        "all_within_band": all(r["within_band"] for r in per_k),
        "coreset_beats_full_fit": all(
            r["total_s"] < full_fit_s for r in per_k
        ),
    }
    if sim is not None:
        rec["ft"] = {
            "injected": list(sim.log),
            "supervisor_events": list(sup.events),
        }
        print(f"[train_mctm] injected {len(sim.log)} failures "
              f"({args.inject_failures}); all recovered", flush=True)
    out = args.out
    if out is None:
        if args.smoke:
            # smoke runs land in results/ so they don't churn the committed
            # full-scale artifact at the repo root (kernel_bench convention);
            # non-default fit methods get their own file so the CI matrix's
            # per-method runs don't clobber the gated adam record, and
            # failure-injected drills (timings include crash+replay) get _ft
            tag = "" if args.fit_method == "adam" else f"_{args.fit_method}"
            if args.inject_failures:
                tag += "_ft"
            out = os.path.join(
                REPO_ROOT, "results", "bench", f"BENCH_mctm_fit_smoke{tag}.json"
            )
        else:
            tag = "_ft" if args.inject_failures else ""
            out = os.path.join(REPO_ROOT, f"BENCH_mctm_fit{tag}.json")
    os.makedirs(os.path.dirname(os.path.abspath(out)), exist_ok=True)
    with open(out, "w") as f:
        json.dump(rec, f, indent=1)
    print(f"[train_mctm] wrote {out}", flush=True)
    return rec


def main(argv=None):
    from repro.utils.compile_cache import enable_compile_cache

    args = parse_args(argv)
    enable_compile_cache()
    # force a multi-device CPU mesh BEFORE the first jax device query — the
    # sharded stages then genuinely shard on the container (same mechanism as
    # launch.dryrun); skipped when real accelerators are present
    if args.fake_devices and "--xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
        import jax

        if jax.default_backend() == "cpu" and len(jax.devices()) == 1:
            print("[train_mctm] single-device CPU backend: re-exec with "
                  f"{args.fake_devices} fake devices", flush=True)
            env = dict(os.environ)
            env["XLA_FLAGS"] = (
                env.get("XLA_FLAGS", "")
                + f" --xla_force_host_platform_device_count={args.fake_devices}"
            ).strip()
            os.execve(sys.executable,
                      [sys.executable, "-m", "repro.launch.train_mctm"]
                      + (argv if argv is not None else sys.argv[1:]), env)
    try:
        rec = run(args)
    finally:
        if args.inject_failures:
            from repro.ft.config import FTConfig, get_ft_config

            cfg = get_ft_config()
            cfg.simulator = None
            cfg.sweep_ckpt_every_chunks = FTConfig.sweep_ckpt_every_chunks
    if not rec["all_within_band"]:
        sys.exit(1)
    if args.inject_failures and not rec.get("ft", {}).get("injected"):
        print("[train_mctm] --inject-failures requested but nothing fired",
              flush=True)
        sys.exit(1)


if __name__ == "__main__":
    main()
