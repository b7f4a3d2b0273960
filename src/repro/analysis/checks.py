"""Declarative checks over lowered/compiled jax programs.

Each check consumes one artifact of the AOT pipeline — all obtainable on
CPU, no TPU and no execution:

* ``jax.jit(fn).trace(*args).jaxpr``  — the closed jaxpr (materialization
  bound, callback primitives);
* ``jax.jit(fn).lower(*args).as_text()`` under x64 off/on — StableHLO text
  (dtype-promotion audit: an f32 program must lower identically-typed under
  both modes; any ``f64`` element type under x64 is a leaked np.float64 /
  python-float weak-type promotion);
* ``.lower().compile().as_text()``    — optimized per-device HLO (collective
  census via :mod:`repro.utils.hlo`, donation aliasing, host callbacks).

``audit_program`` runs all of them against a :class:`ProgramSpec`'s declared
budgets and returns a report dict: ``failures`` (empty = program honors its
contract) plus the measured ``metrics`` the analysis gate diffs against the
committed baseline.
"""
from __future__ import annotations

import re
from typing import Any, Iterator

import jax
import numpy as np

from repro.analysis.registry import ProgramSpec
from repro.utils.hlo import collective_stats, input_output_aliases

from jax.extend import core as _jcore

COLLECTIVE_OPS = (
    "all-reduce",
    "all-gather",
    "reduce-scatter",
    "all-to-all",
    "collective-permute",
)

# jaxpr-level host round-trips: anything here inside a hot path (worse, a
# scan body) serializes the device stream on every call
CALLBACK_PRIMITIVES = frozenset(
    {
        "pure_callback",
        "io_callback",
        "debug_callback",
        "host_callback_call",
        "outside_call",
    }
)

# compiled-HLO-level host transfers: python callbacks lower to custom-calls
# with a "callback" target; infeed/outfeed are direct host transfers
_HLO_CALLBACK_RE = re.compile(
    r'custom_call_target="[^"]*[Cc]allback[^"]*"|[%\s](?:infeed|outfeed)\('
)

# StableHLO element types introduced only by 64-bit promotion of float math.
# Ranked f64 tensors mean a DATA array was promoted (hard failure); scalar
# tensor<f64> constants are python-float/np.float64 weak types that convert
# straight back down to f32 — benign for the values, but tracked as a
# baseline metric so new weak-type hazards are visible as drift.
_F64_ANY_RE = re.compile(r"[<x](?:f64|complex<f64>)")
_F64_RANKED_RE = re.compile(r"tensor<(?:\?|\d)[x0-9?]*x(?:f64|complex<f64>)>")


def _as_jitted(fn: Any):
    return fn if hasattr(fn, "lower") else jax.jit(fn)


class ProgramArtifacts:
    """Lazily builds + caches the AOT artifacts for one program.

    The program is built and traced once under x64 OFF — the canonical f32
    contract every budget is written against, making the gate report
    identical in both CI x64 legs — and additionally *lowered* under x64 ON
    for the promotion diff.
    """

    def __init__(self, spec: ProgramSpec):
        self.spec = spec
        self._fn = None
        self._args: tuple | None = None
        self._jaxpr = None
        self._stablehlo: dict[bool, str] = {}
        self._compiled_text: str | None = None

    def _built(self):
        if self._fn is None:
            with jax.enable_x64(False):
                self._fn, self._args = self.spec.build()
            self._fn = _as_jitted(self._fn)
        return self._fn, self._args

    @property
    def jaxpr(self):
        if self._jaxpr is None:
            fn, args = self._built()
            with jax.enable_x64(False):
                self._jaxpr = fn.trace(*args).jaxpr
        return self._jaxpr

    def stablehlo(self, x64: bool) -> str:
        if x64 not in self._stablehlo:
            fn, args = self._built()
            with jax.enable_x64(x64):
                self._stablehlo[x64] = fn.lower(*args).as_text()
        return self._stablehlo[x64]

    @property
    def compiled_text(self) -> str:
        if self._compiled_text is None:
            fn, args = self._built()
            with jax.enable_x64(False):
                self._compiled_text = fn.lower(*args).compile().as_text()
        return self._compiled_text


# ---------------------------------------------------------------------------
# jaxpr walking
# ---------------------------------------------------------------------------


def _sub_jaxprs(value: Any) -> Iterator[Any]:
    """Recursively yield raw Jaxprs hiding inside an eqn param value."""
    if isinstance(value, _jcore.ClosedJaxpr):
        yield value.jaxpr
    elif isinstance(value, _jcore.Jaxpr):
        yield value
    elif isinstance(value, (list, tuple)):
        for v in value:
            yield from _sub_jaxprs(v)


def iter_eqn_avals(jaxpr) -> Iterator[tuple[str, Any]]:
    """Yield (primitive_name, output_aval) for every eqn, recursing through
    scan/while/cond/pjit/shard_map/custom-derivative sub-jaxprs."""
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        for v in eqn.outvars:
            aval = getattr(v, "aval", None)
            if aval is not None and hasattr(aval, "shape"):
                yield name, aval
        for val in eqn.params.values():
            for sub in _sub_jaxprs(val):
                yield from iter_eqn_avals(sub)


def iter_primitives(jaxpr) -> Iterator[str]:
    for eqn in jaxpr.eqns:
        yield eqn.primitive.name
        for val in eqn.params.values():
            for sub in _sub_jaxprs(val):
                yield from iter_primitives(sub)


# ---------------------------------------------------------------------------
# individual checks — each returns (metrics_fragment, failures)
# ---------------------------------------------------------------------------


def check_collectives(spec: ProgramSpec, compiled_text: str):
    stats = collective_stats(compiled_text)
    budget = spec.collectives.as_dict()
    failures = []
    counts = {
        op: stats["by_op"].get(op, {"count": 0})["count"] for op in COLLECTIVE_OPS
    }
    for op, want in budget.items():
        got = counts[op]
        if spec.collectives.exact:
            if got != want:
                failures.append(
                    f"collective census: {got} × {op}, budget declares exactly "
                    f"{want} — a refactor changed the program's reduction "
                    f"structure"
                )
        elif got > want:
            failures.append(
                f"collective census: {got} × {op} exceeds ceiling {want}"
            )
    if stats["async_unmatched"]:
        failures.append(
            f"unbalanced async collective pairs: {stats['async_unmatched']}"
        )
    metrics = {
        "collectives": counts,
        "collective_bytes": int(stats["total_bytes"]),
    }
    return metrics, failures


def check_materialization(spec: ProgramSpec, jaxpr):
    budget = spec.materialization
    max_elems = 0
    failures: list[str] = []
    if budget is None:
        return {"max_intermediate_elems": 0}, failures
    seen: set[tuple[str, str]] = set()
    for prim, aval in iter_eqn_avals(jaxpr.jaxpr):
        shape = tuple(int(d) for d in aval.shape if isinstance(d, (int, np.integer)))
        size = int(np.prod(shape)) if shape else 1
        max_elems = max(max_elems, size)
        ratio = size // max(shape) if shape else 1
        if ratio <= budget.row_elems or size <= budget.fixed_elems:
            continue
        key = (prim, aval.str_short())
        if key in seen:
            continue
        seen.add(key)
        failures.append(
            f"materialization: {prim} produces {aval.str_short()} "
            f"({size} elems, {ratio}/row) — wider than row budget "
            f"{budget.row_elems} and larger than chunk budget "
            f"{budget.fixed_elems}; an n-scaled basis block is being "
            f"materialized"
        )
    return {"max_intermediate_elems": max_elems}, failures


def check_dtypes(spec: ProgramSpec, text_x32: str, text_x64: str):
    n32 = len(_F64_ANY_RE.findall(text_x32))
    ranked64 = len(_F64_RANKED_RE.findall(text_x64))
    weak64 = len(_F64_ANY_RE.findall(text_x64)) - ranked64
    failures = []
    if not spec.allow_f64:
        if n32:
            failures.append(
                f"dtype audit: {n32} f64 tensor type(s) in the x64=off "
                f"lowering — hard-coded double precision"
            )
        if ranked64:
            failures.append(
                f"dtype audit: {ranked64} ranked f64 tensor(s) appear under "
                f"JAX_ENABLE_X64=1 with f32 inputs — an np.float64 constant "
                f"or python-float weak type promotes a data array"
            )
    metrics = {
        "f64_types_x32": n32,
        "f64_arrays_x64": ranked64,
        # scalar tensor<f64> weak-type constants (python floats / np.float64
        # scalars) that convert straight back to f32 — value-benign, but a
        # rising count is new weak-type hazards, caught by the baseline diff
        "weak_f64_consts_x64": weak64,
    }
    return metrics, failures


def check_donation(spec: ProgramSpec, compiled_text: str):
    aliases = input_output_aliases(compiled_text)
    failures = []
    if spec.donated_outputs is not None and len(aliases) != spec.donated_outputs:
        failures.append(
            f"donation audit: compiled executable aliases {len(aliases)} "
            f"output buffer(s), declared {spec.donated_outputs} — a donated "
            f"input is being silently copied (or a non-donated one aliased)"
        )
    return {"aliased_outputs": len(aliases)}, failures


def check_callbacks(spec: ProgramSpec, jaxpr, compiled_text: str):
    prim_hits = [p for p in iter_primitives(jaxpr.jaxpr) if p in CALLBACK_PRIMITIVES]
    hlo_hits = _HLO_CALLBACK_RE.findall(compiled_text)
    count = len(prim_hits) + len(hlo_hits)
    failures = []
    if count and not spec.allow_callbacks:
        what = ", ".join(sorted(set(prim_hits))) or "host transfer"
        failures.append(
            f"callback audit: {count} host round-trip(s) ({what}) inside a "
            f"jitted hot path — every call serializes the device stream"
        )
    return {"host_callbacks": count}, failures


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------


def audit_program(spec: ProgramSpec) -> dict:
    """Run every check against one registered program.

    Returns ``{"name", "ok", "failures": [...], "metrics": {...}}``;
    ``metrics`` is what the analysis gate diffs against the committed
    baseline. Never executes the program.
    """
    report: dict = {"name": spec.name, "failures": [], "metrics": {}}
    if jax.device_count() < spec.needs_devices:
        report["failures"].append(
            f"needs {spec.needs_devices} devices, have {jax.device_count()} "
            f"(set XLA_FLAGS=--xla_force_host_platform_device_count="
            f"{spec.needs_devices} before importing jax)"
        )
        report["ok"] = False
        return report
    art = ProgramArtifacts(spec)
    for metrics, failures in (
        check_collectives(spec, art.compiled_text),
        check_materialization(spec, art.jaxpr),
        check_dtypes(spec, art.stablehlo(False), art.stablehlo(True)),
        check_donation(spec, art.compiled_text),
        check_callbacks(spec, art.jaxpr, art.compiled_text),
    ):
        report["metrics"].update(metrics)
        report["failures"].extend(failures)
    report["ok"] = not report["failures"]
    return report
