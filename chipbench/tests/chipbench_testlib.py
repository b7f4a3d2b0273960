"""Shared helpers of the benchmark's tests: a throwaway copy of the
benchmark with tiny cells added as new files, runnable on the CPU."""
from __future__ import annotations

import json
import os
import shutil
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
for _p in (ROOT, os.path.join(ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

# tiny sizes: a few seconds a run on the CPU
TINY = {
    "tiny_j2": ("mctm_j2_mixture", {"n": 20001, "k": 200, "chunk": 4096}),
    "tiny_j3": ("mctm_j10_covertype",
                {"J": 3, "degree": 4, "n": 4097, "k": 200, "chunk": 4096, "dgp": "covertype_j3"}),
}
# a three-column generator for the tiny fit, a new file beside the others:
# the first three Covertype columns
COVERTYPE_J3 = '''"""The first three columns of the Covertype stand-in."""
import os

from chipbench.run import load_module


def generate(rng, n):
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)), "covertype.py")
    return load_module(path, "chipbench_data_covertype").generate(rng, n)[:, :3]
'''
# (workload, config, traffic, limits, the cell whose metrics it reports),
# limits read off CPU runs at these sizes
TINY_CELLS = [
    ("tiny.build.two_pass", "tiny_j2", "tiny_two_pass",
     {"lev_tv": 4e-4, "w_dev": 6e-4, "draw_gap": 1e-2, "hull_gap": 4e-7, "shape_err": 0.0},
     "build.j2_mixture.two_pass"),
    ("tiny.build.one_pass", "tiny_j2", "build_one_pass",
     {"lev_tv": 1.5e-3, "w_dev": 2e-3, "draw_gap": 3e-2, "hull_gap": 4e-7, "shape_err": 0.0},
     "build.j2_mixture.one_pass"),
    ("tiny.fit.lbfgs", "tiny_j3", "tiny_fit_lbfgs",
     {"obj_dev": 1e-6, "fit_gap": 2e-4, "shape_err": 0.0},
     "fit.j10_covertype.lbfgs"),
]


def _dump(path: str, obj) -> None:
    with open(path, "w") as f:
        json.dump(obj, f, indent=1)


def tiny_bench(tmp_path) -> str:
    """A copy of ``BENCHMARK.json`` and ``chipbench/`` under ``tmp_path``,
    with the tiny configurations, mixes, cells and limits added as new
    files and entries. Returns the copy's root."""
    root = str(tmp_path)
    bench_dir = os.path.join(root, "chipbench")
    shutil.copytree(BENCH, bench_dir, ignore=shutil.ignore_patterns("tests", "__pycache__"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    with open(os.path.join(bench_dir, "data", "covertype_j3.py"), "w") as f:
        f.write(COVERTYPE_J3)
    for name, (base, changes) in TINY.items():
        with open(os.path.join(bench_dir, "configs", base + ".json")) as f:
            cfg = json.load(f)
        cfg.update(changes, name=name)
        _dump(os.path.join(bench_dir, "configs", name + ".json"), cfg)
        bench["configs"].append({"name": name, "source": "test", "reduced": ["n"],
                                 "file": f"chipbench/configs/{name}.json", "why": "test"})
    # a mix of its own: the two-pass mix, one build checked per run
    with open(os.path.join(bench_dir, "traffic", "build_two_pass.json")) as f:
        mix = json.load(f)
    mix.update(check_calls=1)
    _dump(os.path.join(bench_dir, "traffic", "tiny_two_pass.json"), mix)
    # the fit mix at a test's length: fewer steps, two coresets, one checked
    with open(os.path.join(bench_dir, "traffic", "fit_lbfgs.json")) as f:
        mix = json.load(f)
    mix.update(steps=60, coresets=2, check_calls=1)
    _dump(os.path.join(bench_dir, "traffic", "tiny_fit_lbfgs.json"), mix)
    for wl, cfg, traffic, limits, like in TINY_CELLS:
        bench["workloads"].append({"name": wl, "config": cfg, "traffic": traffic,
                                   "chips": 1, "why": "test"})
        _dump(os.path.join(bench_dir, "limits", wl + ".json"), limits)
        for m in bench["end_to_end"] + bench["per_layer"]:
            if like in m.get("workloads", ()):
                m["workloads"].append(wl)
    _dump(os.path.join(root, "BENCHMARK.json"), bench)
    return root


def run_tiny(root: str, workload: str, seed: int = 2**31 + 5, seconds: float = 0.5):
    from chipbench import run

    cell = run.find_cell(workload, root=root)
    return run.run_cell(cell, seed, seconds, False, root=ROOT, on_chip=False)
