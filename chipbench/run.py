"""Run one benchmark cell once on the chip and print one JSON result line.

    python chipbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything about a cell is found by name from files of its own, so a cell,
a configuration, a traffic mix or a metric is added by adding files:

- ``BENCHMARK.json`` (checkout root): the cells and the metrics;
- ``chipbench/configs/<config>.json``: the deployment's sizes; its
  ``"dgp"`` names the generator ``chipbench/data/<dgp>.py`` of its rows,
  and its ``"model_module"`` (``mctm`` where absent) the model
  ``chipbench/models/<model>.py``: the program objects a build is given,
  the build call, its float64 reference and its widths;
- ``chipbench/traffic/<traffic>.json``: the mix; its ``"entry"`` names the
  loop in ``chipbench/entries/<entry>.py`` that drives the program, on a
  data mesh over the cell's ``chips`` devices;
- ``chipbench/limits/<workload>.json``: the limit of each compared number;
- ``chipbench/metrics/<metric>.py``: a ``read(ctx)`` returning the value,
  or None where the run has nothing to read;
- ``chipbench/costs/<kernel>.py`` and ``chipbench/peaks.json``: the
  operations and bytes the algorithm needs, and the chip's peaks.

Set-up (imports, chip check, data from ``--seed``, one warm-up call) counts
as ``setup_s``. The window then calls the entry back to back until
``--seconds`` have passed and the call in flight has returned; rates are
taken over all calls and all of that time. ``--trace 1`` profiles the
window and reports the per-layer metrics instead of the end-to-end ones.
After the window the device state is freed and calls drawn from the seed
are compared with the float64 reference (``chipbench/reference.py``).

Exit codes: 0 with a result line; 2, with no result line, when there is no
TPU, too few chips, a device missing from the peaks table, or no program
to import next to the benchmark.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import collections
import dataclasses
import importlib
import importlib.util
import json
import os
import shutil
import sys
import tempfile

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

# the model a configuration runs where it names none
DEFAULT_MODEL = "mctm"

LOWERING_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class SetupError(Exception):
    """The run cannot produce a result here (no chip, no program, …)."""


# ---------------------------------------------------------------------------
# discovery by name
# ---------------------------------------------------------------------------


def _load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: str, name: str):
    """Import a file that may carry dots in its name (``mfu.build.py``)."""
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    """One workload with everything found for it by name."""

    workload: dict
    config: dict
    traffic: dict
    limits: dict
    entry: object
    model: object
    end_to_end: list
    per_layer: list
    bench_dir: str

    @property
    def name(self) -> str:
        return self.workload["name"]

    @property
    def chips(self) -> int:
        return int(self.workload["chips"])

    def data_mesh(self):
        """The one-axis ``data`` mesh a call runs on: the first ``chips``
        devices."""
        import jax

        from repro.utils.compat import make_mesh

        return make_mesh((self.chips,), ("data",), devices=jax.devices()[:self.chips])


def _applies(metric: dict, workload: str, reported: set[str] | None) -> bool:
    if "workloads" in metric:
        return workload in metric["workloads"]
    return reported is None or metric.get("moves") in reported


def find_cell(workload: str, root: str = ROOT) -> Cell:
    """Everything for ``workload``: ``BENCHMARK.json`` at ``root`` and the
    files under ``<root>/chipbench``."""
    bench_dir = os.path.join(root, "chipbench")
    bench = _load_json(os.path.join(root, "BENCHMARK.json"))
    wl = next((w for w in bench["workloads"] if w["name"] == workload), None)
    if wl is None:
        raise SetupError(f"no workload {workload!r} in BENCHMARK.json")
    cfg_entry = next(c for c in bench["configs"] if c["name"] == wl["config"])
    config = _load_json(os.path.join(root, cfg_entry["file"]))
    traffic = _load_json(os.path.join(bench_dir, "traffic", wl["traffic"] + ".json"))
    limits_path = os.path.join(bench_dir, "limits", workload + ".json")
    limits = _load_json(limits_path) if os.path.exists(limits_path) else {}
    entry = load_module(os.path.join(bench_dir, "entries", traffic["entry"] + ".py"),
                        "chipbench_entry_" + traffic["entry"])
    model_name = config.get("model_module", DEFAULT_MODEL)
    model = load_module(os.path.join(bench_dir, "models", model_name + ".py"),
                        "chipbench_model_" + model_name)
    e2e = [m for m in bench["end_to_end"] if _applies(m, workload, None)]
    reported = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"] if _applies(m, workload, reported)]
    return Cell(wl, config, traffic, limits, entry, model, e2e, per_layer, bench_dir)


def metric_reader(cell: Cell, name: str):
    path = os.path.join(cell.bench_dir, "metrics", name + ".py")
    return load_module(path, "chipbench_metric_" + name.replace(".", "_"))


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class RunContext:
    """What an entry and a metric reader see of the run."""

    cell: Cell
    seed: int
    seconds: float
    trace: bool
    state: object = None
    config: dict = None
    traffic: dict = None
    results: list = dataclasses.field(default_factory=list)
    window_s: float = 0.0
    setup_s: float = 0.0
    counters: dict = dataclasses.field(default_factory=dict)
    trace_summary: object = None
    peaks: dict | None = None
    device: dict = dataclasses.field(default_factory=dict)

    def __post_init__(self):
        self.config = self.cell.config
        self.traffic = self.cell.traffic
        self._t_phase = time.perf_counter()

    def phase(self, label: str) -> None:
        """Print how long the set-up phase just ended took."""
        now = time.perf_counter()
        print(f"[chipbench] setup phase {label}: {now - self._t_phase:.3f} s",
              file=sys.stderr, flush=True)
        self._t_phase = now

    def work_total(self, key: str) -> float:
        return float(sum(self.cell.entry.work(self, r).get(key, 0) for r in self.results))

    def cost(self, kernel: str):
        """The cost module of a kernel: ``flops``, ``bytes`` per call, of the
        configuration, the mix and (a build's) the model."""
        path = os.path.join(self.cell.bench_dir, "costs", kernel + ".py")
        return load_module(path, "chipbench_cost_" + kernel)


class EventCounter:
    """Counts JAX's compile-path events: traces, lowerings, backend
    compiles (JAX reports one per program obtained, also from the
    persistent cache) and persistent-cache hits and misses."""

    def __init__(self):
        self.counts = collections.Counter()

    def __call__(self, name, *args, **kwargs):
        self.counts[name] += 1

    def since(self, before: collections.Counter) -> dict:
        return dict(self.counts - before)


def import_program(root: str):
    src = os.path.join(root, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        raise SetupError(f"no program to benchmark: {src}/repro is missing")
    if src not in sys.path:
        sys.path.insert(0, src)
    import repro  # noqa: F401


def device_info(chips: int, peaks_path: str) -> tuple[dict, dict]:
    """The device record, and its peaks; raises unless the chips are TPUs
    in the peaks table and there are enough of them."""
    import jax

    if jax.default_backend() != "tpu":
        raise SetupError(f"no TPU: JAX's backend is {jax.default_backend()!r}")
    devs = jax.devices()
    if len(devs) < chips:
        raise SetupError(f"the cell needs {chips} chips, JAX finds {len(devs)}")
    kind = devs[0].device_kind
    peaks = _load_json(peaks_path)["devices"].get(kind)
    if peaks is None:
        raise SetupError(f"device kind {kind!r} is not in {peaks_path}")
    return {"platform": devs[0].platform, "kind": kind, "count": chips}, peaks


def _profiler_options():
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    return opts


def run_window(ctx: RunContext, counter: EventCounter) -> None:
    import jax

    entry = ctx.cell.entry
    span = f"chipbench.{entry.NAME}"
    before = collections.Counter(counter.counts)
    t0 = time.perf_counter()
    try:
        while True:
            with jax.profiler.TraceAnnotation(span):
                ctx.results.append(entry.call(ctx, ctx.state, len(ctx.results)))
            if time.perf_counter() - t0 >= ctx.seconds:
                break
    finally:
        ctx.window_s = time.perf_counter() - t0
        ctx.counters = counter.since(before)


def window_compiles(ctx: RunContext) -> int:
    """Programs the backend compiled in the window: JAX reports a backend
    compile for every program obtained, also for a persistent-cache hit."""
    return ctx.counters.get(COMPILE_EVENT, 0) - ctx.counters.get(CACHE_HIT_EVENT, 0)


def peak_memory(n: int) -> int:
    import jax

    stats = [d.memory_stats() or {} for d in jax.devices()[:n]]
    return int(max(s.get("peak_bytes_in_use", 0) for s in stats))


def judge(ctx: RunContext, numbers) -> tuple[bool, dict]:
    """Each number that has a limit, beside it. A limited number the check
    did not give, or one that is not finite, fails; numbers without a limit
    are diagnostics and are left out."""
    got = dict(numbers)
    out, ok = {}, bool(ctx.cell.limits)
    for name, limit in ctx.cell.limits.items():
        value = float(got.get(name, np.nan))
        ok &= bool(np.isfinite(value) and value <= limit)
        out[name] = {"value": value, "limit": limit}
    return ok, out


def read_metrics(ctx: RunContext, metrics: list) -> dict:
    out = {}
    if not ctx.results:
        return out
    for m in metrics:
        value = ctx.setup_s if m["name"] == "setup_s" else \
            metric_reader(ctx.cell, m["name"]).read(ctx)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, *,
             root: str = ROOT, on_chip: bool = True, t_start: float | None = None) -> dict:
    """Set-up, window and check of one run; returns the result record.

    ``t_start`` is when set-up began (the process's start in ``main``).
    ``on_chip=False`` skips the look for a chip (tests drive the rest of a
    run on the CPU that way)."""
    t_setup = time.perf_counter() if t_start is None else t_start
    import_program(root)
    import jax

    if on_chip:
        # the persistent cache as the program's entry points set it, JAX's
        # default thresholds included: a program quicker than a second to
        # compile is compiled again wherever users' calls would compile it
        from repro.utils.compile_cache import enable_compile_cache

        enable_compile_cache()
        device, peaks = device_info(cell.chips, os.path.join(cell.bench_dir, "peaks.json"))
    else:
        d = jax.devices()[0]
        device, peaks = {"platform": d.platform, "kind": d.device_kind,
                         "count": cell.chips}, None
    counter = EventCounter()
    jax.monitoring.register_event_duration_secs_listener(counter)
    jax.monitoring.register_event_listener(counter)
    ctx = RunContext(cell, seed, seconds, trace, peaks=peaks, device=device)
    ctx._t_phase = t_setup
    ctx.phase("start, imports and chip check")
    ctx.state = cell.entry.setup(ctx)
    ctx.setup_s = time.perf_counter() - t_setup
    print(f"[chipbench] setup compile events: {dict(counter.counts)}",
          file=sys.stderr, flush=True)

    trace_dir = None
    if trace:
        trace_dir = tempfile.mkdtemp(prefix="chipbench_trace_")
        jax.profiler.start_trace(trace_dir, profiler_options=_profiler_options())
    failed = 0
    try:
        run_window(ctx, counter)
    except Exception:  # noqa: BLE001 — a call that raised is a failed call
        import traceback

        traceback.print_exc()
        failed = 1
    finally:
        if trace:
            jax.profiler.stop_trace()
    device["memory_peak_bytes"] = peak_memory(device["count"])
    print(f"[chipbench] setup {ctx.setup_s:.3f} s; window {ctx.window_s:.3f} s, "
          f"{len(ctx.results)} calls, {ctx.counters.get(TRACE_EVENT, 0)} traces, "
          f"{ctx.counters.get(LOWERING_EVENT, 0)} lowerings, "
          f"{ctx.counters.get(COMPILE_EVENT, 0)} programs obtained, "
          f"{ctx.counters.get(CACHE_HIT_EVENT, 0)} from the persistent cache, "
          f"{window_compiles(ctx)} compiled",
          file=sys.stderr, flush=True)
    if trace:
        from chipbench import trace as T

        try:
            ctx.trace_summary = T.summarize(trace_dir, span_prefix="chipbench.")
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)
        device["busy_s"] = ctx.trace_summary.busy_s
        device["window_s"] = ctx.trace_summary.window_s
    cell.entry.release(ctx.state)
    jax.clear_caches()

    numbers = []
    if ctx.results and not failed:
        t_check = time.perf_counter()
        numbers = cell.entry.check(ctx, ctx.state, ctx.results,
                                   np.random.default_rng(seed))
        print(f"[chipbench] reference check of {len(ctx.results)} calls' sample: "
              f"{time.perf_counter() - t_check:.3f} s", file=sys.stderr, flush=True)
    ok, checks = judge(ctx, numbers)
    record = {
        "correct": bool(ok and numbers and not failed),
        "attempted": len(ctx.results) + failed,
        "failed": failed,
        "metrics": read_metrics(ctx, cell.per_layer if trace else cell.end_to_end),
        "device": device,
    }
    if trace:
        record["breakdown"] = ctx.trace_summary.breakdown()
    record["checks"] = checks
    return record


def print_record(record: dict) -> None:
    for name, c in record["checks"].items():
        verdict = "ok" if c["limit"] is not None and c["value"] <= c["limit"] else "FAIL"
        print(f"[chipbench] check {name} {c['value']:.6g} limit {c['limit']} {verdict}",
              file=sys.stderr, flush=True)
    print(json.dumps(record), flush=True)


def parse_args(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse_args(argv)
    try:
        cell = find_cell(args.workload)
        record = run_cell(cell, args.seed, args.seconds, bool(args.trace), t_start=T_START)
    except (SetupError, OSError, KeyError) as e:
        print(f"[chipbench] cannot run here: {type(e).__name__}: {e}",
              file=sys.stderr, flush=True)
        return 2
    print_record(record)
    return 0


def control_main(argv=None) -> int:
    """Program and control side by side on several seeds (see control.py)."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True,
                    help="seeds the program's calls run on")
    ap.add_argument("--control-seeds", type=int, nargs="*", default=None,
                    help="seeds the control runs on as well (default: all)")
    ap.add_argument("--calls", type=int, default=2)
    args = ap.parse_args(argv)
    cell = find_cell(args.workload)
    import_program(ROOT)
    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    device, peaks = device_info(cell.chips, os.path.join(cell.bench_dir, "peaks.json"))
    control_seeds = set(args.seeds if args.control_seeds is None else args.control_seeds)
    for seed in args.seeds:
        ctx = RunContext(cell, seed, 0.0, False, peaks=peaks, device=device)
        state = cell.entry.setup(ctx)
        sides = ("program", "control") if seed in control_seeds else ("program",)
        for side in sides:
            call = cell.entry.call if side == "program" else cell.entry.control_call
            t0 = time.perf_counter()
            ctx.results = [call(ctx, state, i) for i in range(args.calls)]
            t1 = time.perf_counter()
            numbers = dict(cell.entry.check(ctx, state, ctx.results,
                                            np.random.default_rng(seed)))
            print(json.dumps({"workload": cell.name, "seed": seed, "side": side,
                              "call_s": t1 - t0, "check_s": time.perf_counter() - t1,
                              "numbers": numbers, "limits": cell.limits}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
