"""Discovery by name, the refusals, and BENCHMARK.json's own consistency.

Runs on the CPU and loads no TPU library: the chip checks are driven with
JAX's CPU backend or with a stand-in device."""
from __future__ import annotations

import hashlib
import json
import os
import shutil
import subprocess
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import chipbench_testlib as lib  # noqa: E402

from chipbench import run  # noqa: E402


def _bench():
    with open(os.path.join(lib.ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_every_cell_resolves_to_its_files():
    bench = _bench()
    for wl in bench["workloads"]:
        cell = run.find_cell(wl["name"])
        assert cell.config["name"] == wl["config"]
        assert callable(cell.entry.call) and callable(cell.entry.check)
        assert cell.limits, f"{wl['name']} has no limits file"
        names = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in names and len(names) >= 2
        assert cell.per_layer
        for m in cell.end_to_end + cell.per_layer:
            if m["name"] != "setup_s":
                assert callable(run.metric_reader(cell, m["name"]).read)


def test_per_layer_metrics_move_a_metric_their_cells_report():
    bench = _bench()
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    for m in bench["per_layer"]:
        moved = e2e[m["moves"]]
        assert set(m["workloads"]) <= set(moved.get("workloads", m["workloads"]))


def test_throwaway_config_and_mix_are_found_from_new_files(tmp_path):
    root = lib.tiny_bench(tmp_path)
    cell = run.find_cell("tiny.build.two_pass", root=root)
    assert cell.config["name"] == "tiny_j2" and cell.config["n"] == 20001
    assert cell.traffic["entry"] == "build" and cell.traffic["check_calls"] == 1
    assert cell.limits["lev_tv"] == 4e-4
    assert [m["name"] for m in cell.end_to_end] == ["build_rows_per_s", "setup_s"]
    assert "idle_share.build" in [m["name"] for m in cell.per_layer]
    # the copy's files are the ones loaded, and nothing in the repo changed
    assert cell.bench_dir == os.path.join(root, "chipbench")
    assert not os.path.exists(os.path.join(lib.BENCH, "configs", "tiny_j2.json"))
    assert not os.path.exists(os.path.join(lib.BENCH, "traffic", "tiny_two_pass.json"))


def test_every_accepted_cell_runs_the_mctm_on_a_mesh_of_its_chips():
    run.import_program(lib.ROOT)
    for wl in _bench()["workloads"]:
        cell = run.find_cell(wl["name"])
        # no configuration names a model, so each runs the default
        assert "model_module" not in cell.config and run.DEFAULT_MODEL == "mctm"
        assert cell.model.__file__ == os.path.join(lib.BENCH, "models", "mctm.py")
        mesh = cell.data_mesh()
        assert mesh.axis_names == ("data",) and mesh.devices.size == wl["chips"]


def _digest(paths) -> str:
    """SHA-256 over the names and bytes of the files under ``paths``."""
    h = hashlib.sha256()
    for top in paths:
        files = [top] if os.path.isfile(top) else sorted(
            os.path.join(d, f) for d, dirs, fs in os.walk(top) for f in fs
            if "__pycache__" not in d)
        for path in files:
            h.update(os.path.relpath(path, lib.ROOT).encode())
            with open(path, "rb") as f:
                h.update(f.read())
    return h.hexdigest()


def test_new_generator_model_and_two_chip_cell_run_from_new_files(tmp_path):
    """A configuration that brings its own generator and model file, in a
    cell on two chips, is found, run and judged from new files alone: here
    renamed copies of ``normal_mixture.py`` and ``mctm.py``, run in a child
    process with two host CPU devices."""
    repo = [os.path.join(lib.ROOT, "BENCHMARK.json"), lib.BENCH]
    before = _digest(repo)
    root = lib.tiny_bench(tmp_path)
    bench_dir = os.path.join(root, "chipbench")
    shutil.copy(os.path.join(bench_dir, "data", "normal_mixture.py"),
                os.path.join(bench_dir, "data", "mixture_copy.py"))
    shutil.copy(os.path.join(bench_dir, "models", "mctm.py"),
                os.path.join(bench_dir, "models", "mctm_copy.py"))
    with open(os.path.join(bench_dir, "configs", "tiny_j2.json")) as f:
        cfg = json.load(f)
    cfg.update(name="tiny_j2_copy", dgp="mixture_copy", model_module="mctm_copy")
    with open(os.path.join(bench_dir, "configs", "tiny_j2_copy.json"), "w") as f:
        json.dump(cfg, f)
    shutil.copy(os.path.join(bench_dir, "limits", "tiny.build.two_pass.json"),
                os.path.join(bench_dir, "limits", "tiny.build.two_chips.json"))
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({"name": "tiny_j2_copy", "source": "test", "reduced": ["n"],
                             "file": "chipbench/configs/tiny_j2_copy.json", "why": "test"})
    bench["workloads"].append({"name": "tiny.build.two_chips", "config": "tiny_j2_copy",
                               "traffic": "tiny_two_pass", "chips": 2, "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "tiny.build.two_pass" in m.get("workloads", ()):
            m["workloads"].append("tiny.build.two_chips")
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)

    code = f"""
import json, sys
sys.path[:0] = [{lib.ROOT!r}, {os.path.join(lib.ROOT, "src")!r}]
from chipbench import run
cell = run.find_cell("tiny.build.two_chips", root={root!r})
rec = run.run_cell(cell, 2**31 + 9, 0.5, False, root={lib.ROOT!r}, on_chip=False)
print(json.dumps({{"model": cell.model.__file__, "dgp": cell.config["dgp"],
                  "mesh": int(cell.data_mesh().devices.size), **rec}}))
"""
    flags = os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=2"
    env = dict(os.environ, JAX_PLATFORMS="cpu", XLA_FLAGS=flags.strip())
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True, text=True,
                          env=env, timeout=600, cwd=root)
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["correct"], out["checks"]
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert out["mesh"] == 2 and out["device"]["count"] == 2
    assert out["model"] == os.path.join(bench_dir, "models", "mctm_copy.py")
    assert out["dgp"] == "mixture_copy"
    assert not os.path.exists(os.path.join(lib.BENCH, "data", "mixture_copy.py"))
    assert _digest(repo) == before


def test_run_refuses_a_cpu_backend():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(lib.BENCH, "run.py"), "--workload",
         "build.j2_mixture.two_pass", "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, timeout=120, cwd=lib.ROOT)
    assert proc.returncode == 2
    assert proc.stdout.strip() == ""
    assert "no TPU" in proc.stderr


class _Dev:
    platform = "tpu"
    device_kind = "TPU v99 imaginary"


def test_run_refuses_a_device_missing_from_the_peaks_table(monkeypatch):
    import jax

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(jax, "devices", lambda *a: [_Dev()])
    with pytest.raises(run.SetupError, match="not in"):
        run.device_info(1, os.path.join(lib.BENCH, "peaks.json"))
    monkeypatch.setattr(jax, "devices", lambda *a: [])
    with pytest.raises(run.SetupError, match="needs 1 chips"):
        run.device_info(1, os.path.join(lib.BENCH, "peaks.json"))


def test_run_refuses_a_checkout_without_the_program(tmp_path):
    root = lib.tiny_bench(tmp_path)
    with pytest.raises(run.SetupError, match="no program"):
        run.import_program(root)


def test_judge_fails_missing_out_of_limit_and_non_finite_numbers(tmp_path):
    root = lib.tiny_bench(tmp_path)
    ctx = run.RunContext(run.find_cell("tiny.build.two_pass", root=root), 1, 0.0, False)
    sound = {name: 0.0 for name in ctx.cell.limits}
    ok, checks = run.judge(ctx, {**sound, "lev_tv": 1e-4, "diagnostic": 5.0}.items())
    assert ok and checks["lev_tv"] == {"value": 1e-4, "limit": 4e-4}
    assert "diagnostic" not in checks
    assert not run.judge(ctx, {**sound, "lev_tv": float("nan")}.items())[0]
    assert not run.judge(ctx, {**sound, "lev_tv": 1e-3}.items())[0]
    del sound["hull_gap"]
    assert not run.judge(ctx, sound.items())[0]


def test_build_and_fit_metrics_stay_in_their_own_cells():
    for wl in _bench()["workloads"]:
        cell = run.find_cell(wl["name"])
        names = {m["name"] for m in cell.end_to_end + cell.per_layer}
        kind = cell.entry.NAME
        # a metric named for a kind of call applies only to cells of that kind
        suffixed = [n.rsplit(".", 1)[1] for n in names if "." in n]
        assert all(s == kind for s in suffixed), (wl["name"], names)
        if kind == "fit":
            assert {m["name"] for m in cell.end_to_end} == {"fit_s", "setup_s"}
            assert {n for n in names if n.endswith(".fit")} == {
                "idle_share.fit", "lowerings.fit", "compiles.fit", "sweeps.fit",
                "sweep_ms.fit", "mfu.fit"}
        else:
            assert "fit_s" not in names


def _fit_context(window_s: float):
    """A fit cell's context with three completed fits in a window."""
    cell = run.find_cell("fit.j10_covertype.lbfgs")
    ctx = run.RunContext(cell, 1, 10.0, True, peaks={"flops_per_s": 197e12})
    ctx.results = [{"sweeps": {"vg": vg, "hvp": 400, "iters": 400}}
                   for vg in (450, 480, 510)]
    ctx.window_s = window_s
    return ctx


def test_fit_readers_on_a_stand_in_context():
    ctx = _fit_context(21.6)
    read = {m: run.metric_reader(ctx.cell, m).read(ctx)
            for m in ("fit_s", "sweeps.fit", "sweep_ms.fit", "mfu.fit")}
    assert read["fit_s"] == pytest.approx(7.2)
    # (850 + 880 + 910) sweeps over 3 fits, and the window over all of them
    assert read["sweeps.fit"] == pytest.approx(880.0)
    assert read["sweep_ms.fit"] == pytest.approx(21600.0 / 2640)
    # 3 fits × 400 × 12.64 MFLOP over 21.6 s × 197 TFLOP/s
    assert read["mfu.fit"] == pytest.approx(100 * 3 * 400 * 12.64e6 / (21.6 * 197e12))
    # no fit, nothing to read; no peaks, no share of one
    ctx.results = []
    assert run.metric_reader(ctx.cell, "sweeps.fit").read(ctx) is None
    assert run.metric_reader(ctx.cell, "sweep_ms.fit").read(ctx) is None
    ctx = _fit_context(21.6)
    ctx.peaks = None
    assert run.metric_reader(ctx.cell, "mfu.fit").read(ctx) is None
