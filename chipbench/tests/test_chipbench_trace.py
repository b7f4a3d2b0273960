"""The trace → metrics reduction, on a hand-made trace and on a small trace
recorded on a TPU v5e (two 12,289-row builds, two-pass then one-pass)."""
from __future__ import annotations

import json
import os
import sys
import types

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import chipbench_testlib as lib  # noqa: E402

from chipbench import roofline  # noqa: E402
from chipbench import trace as T  # noqa: E402

RECORDED = os.path.join(lib.HERE, "data", "recorded_trace.json")

EXT = ('%_extremes_pallas.3 = (f32[1,1664]) custom-call(f32[8192,128] %pad.1), '
       'custom_call_target="tpu_custom_call"')
GRAM = '%_gram_pallas.1 = f32[128,128] custom-call(f32[4096,128]), custom_call_target="tpu_custom_call"'
SWEEP = '%closed_call.7 = (f32[784,128]) custom-call(f32[4096,128]), custom_call_target="tpu_custom_call"'


def _hand_made():
    E = T.Event
    ops = [E("%while.1 = (s32[]) while(...)", 100, 300),  # a loop holding two ops
           E(EXT, 120, 100), E("%fusion.2 = f32[8]", 250, 100),
           E(GRAM, 500, 50), E(SWEEP, 700, 40),
           E("%copy.1 = f32[8]", 50, 20)]  # before the window: left out
    host = [E("chipbench.build", 90, 700), E("PjitFunction(pass1)", 400, 60),
            E("np.asarray(jax.Array)", 600, 80)]
    return T.TraceSummary({"/device:TPU:0": ops}, {"python": host})


def test_window_busy_and_gaps_by_hand():
    t = _hand_made()
    assert (t.t0, t.t1) == (90, 790)
    assert t.window_s == pytest.approx(700e-9)
    # busy: [100, 400] ∪ [500, 550] ∪ [700, 740] = 390 ns
    assert t.busy_s == pytest.approx(390e-9)
    assert t.idle_gaps() == [(90, 100), (400, 500), (550, 700), (740, 790)]
    b = t.breakdown()
    assert dict(b["idle_gaps"]) == pytest.approx({
        "chipbench.build": 60e-9,            # (90,100) and (740,790): only the window span
        "PjitFunction(pass1)": 100e-9,       # (400,500): middle 450 in the pjit span
        "np.asarray(jax.Array)": 150e-9})    # (550,700): middle 625 in the copy-back
    # the loop's own event is not an operation of its own in the ranking
    assert [k for k, _ in b["device_ops"]] == [
        "%_extremes_pallas.3", "%fusion.2", "%_gram_pallas.1", "%closed_call.7"]


def test_kernels_are_found_by_their_custom_call():
    t = _hand_made()
    for kernel, ns in (("extremes", 100), ("gram", 50), ("sweep", 40)):
        got = t.kernel_time_s(lambda name, k=kernel: roofline.is_kernel(k, name))
        assert got == pytest.approx(ns * 1e-9)
    assert t.kernel_time_s(lambda name: roofline.is_kernel("bernstein", name)) is None


def test_roofline_share_by_hand():
    t = _hand_made()
    cost = types.SimpleNamespace(flops=lambda c, tr, m: 1.0e3, bytes=lambda c, tr, m: 8.0e3)
    ctx = types.SimpleNamespace(
        trace_summary=t, results=[0, 1], config={}, traffic={},
        cell=types.SimpleNamespace(model=None),
        peaks={"flops_per_s": 1e12, "hbm_bytes_per_s": 1e12}, cost=lambda k: cost)
    # two calls: 2e3 flops → 2 ns, 16e3 bytes → 16 ns; extremes ran 100 ns
    assert roofline.least_time_s(ctx, "extremes") == (pytest.approx(16e-9), "bytes")
    assert roofline.share(ctx, "extremes") == pytest.approx(16.0)
    ctx.trace_summary = None
    assert roofline.share(ctx, "extremes") is None


def test_recorded_chip_trace():
    with open(RECORDED) as f:
        rec = json.load(f)
    E = T.Event
    t = T.TraceSummary({k: [E(*e) for e in v] for k, v in rec["device_ops"].items()},
                       {k: [E(*e) for e in v] for k, v in rec["host_spans"].items()})
    assert t.busy_s == pytest.approx(rec["busy_s"], rel=1e-9)
    assert t.window_s == pytest.approx(rec["window_s"], rel=1e-9)
    assert 0 < t.busy_s < t.window_s
    for kernel in ("extremes", "gram", "sweep"):
        assert t.kernel_time_s(lambda n, k=kernel: roofline.is_kernel(k, n)) > 0
    assert t.kernel_time_s(lambda n: roofline.is_kernel("bernstein", n)) is None
    b = t.breakdown()
    assert len(b["device_ops"]) == 10 and len(b["idle_gaps"]) <= 10
    every = t.breakdown(top=10**6)["idle_gaps"]
    assert sum(v for _, v in every) == pytest.approx(t.window_s - t.busy_s)
