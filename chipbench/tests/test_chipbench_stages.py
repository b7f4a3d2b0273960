"""The five idle-per-stage metrics: a hand-made trace with the program's
stage spans, and a small trace recorded on a TPU v5e (two 12,289-row J=2
builds, two-pass then one-pass, each inside a ``chipbench.build`` span)."""
from __future__ import annotations

import json
import os
import sys
import types

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import chipbench_testlib as lib  # noqa: E402

from chipbench import stages  # noqa: E402
from chipbench import trace as T  # noqa: E402
from chipbench.run import load_module  # noqa: E402

RECORDED = os.path.join(lib.HERE, "data", "recorded_trace_spans.json")
STAGES = ("launch_idle_ms.build", "transfer_idle_ms.build", "host_idle_ms.build",
          "sample_idle_ms.build", "untraced_idle_ms.build")


def _metric(name):
    return load_module(os.path.join(lib.BENCH, "metrics", name + ".py"),
                       "test_stage_metric_" + name.replace(".", "_"))


def _readings(t, builds):
    ctx = types.SimpleNamespace(trace_summary=t, results=list(range(builds)))
    return {m: _metric(m).read(ctx) for m in STAGES}, ctx


def _idle_ms_per_build(ctx):
    share = _metric("idle_share.build").read(ctx)
    return share / 100.0 * ctx.trace_summary.window_s * 1e3 / len(ctx.results)


def _hand_made(program_spans: bool = True):
    """Two builds. Device busy on [50,150], [250,400], [650,700], [1200,1450];
    idle gaps [0,50], [150,250], [400,650], [700,1200], [1450,1500]."""
    E = T.Event
    host = [E("chipbench.build", 0, 1000), E("chipbench.build", 1100, 400)]
    if program_spans:
        host += [
            E("repro.build", 10, 980),
            E("repro.build.engine", 10, 10),            # launch: gap [10,20]
            E("repro.build.put_rows", 20, 80),          # transfer: gap [20,50]
            E("repro.scoring.pass1", 100, 200),         # launch: gap [150,250] ...
            E("lower_sharding_computation", 120, 160),  # ... under JAX's own span
            E("repro.scoring.gather.gram", 300, 200),   # transfer: gap [400,500]
            E("repro.scoring.projection", 500, 100),    # host: [500,600]
            E("repro.coreset.sample", 600, 300),        # sample: [600,650], [700,900]
            E("repro.coreset.hull_points", 900, 90),    # host: [900,990]
            E("repro.build", 1110, 380),
            E("repro.scoring.sweep", 1110, 190),        # launch: [1110,1200]
            E("repro.scoring.finalize", 1300, 190),     # host: [1450,1490]
        ]
    ops = [E("%copy.1 = f32[8]", 50, 100), E("%fusion.1 = f32[8]", 250, 150),
           E("%fusion.2 = f32[8]", 650, 50), E("%_sweep_pallas.3 = f32[8]", 1200, 250)]
    return T.TraceSummary({"/device:TPU:0": ops}, {"python": host})


def test_stage_readings_by_hand():
    t = _hand_made()
    assert t.idle_gaps() == [(0, 50), (150, 250), (400, 650), (700, 1200), (1450, 1500)]
    got, ctx = _readings(t, builds=2)
    ns = {"launch_idle_ms.build": 10 + 100 + 90,
          "transfer_idle_ms.build": 30 + 100,
          "host_idle_ms.build": 100 + 90 + 40,
          "sample_idle_ms.build": 50 + 200,
          # [0,10] before the first build's span, [990,1110] between the two
          # builds, [1490,1500] after the last
          "untraced_idle_ms.build": 10 + 120 + 10}
    assert got == pytest.approx({m: v * 1e-6 / 2 for m, v in ns.items()})
    assert sum(got.values()) == pytest.approx(_idle_ms_per_build(ctx))


def test_nothing_to_read_gives_none():
    for t, builds in ((None, 2), (_hand_made(), 0), (_hand_made(program_spans=False), 2)):
        got, _ = _readings(t, builds)
        assert got == dict.fromkeys(STAGES)


def test_stage_groups_are_disjoint_and_name_spans_of_the_program():
    spans = stages.group_spans(STAGES[:4])
    assert len(spans) == len(set(spans)) == 18
    assert all(s.startswith(("repro.build.", "repro.scoring.", "repro.coreset.")) for s in spans)


def test_recorded_chip_trace_with_the_program_spans():
    with open(RECORDED) as f:
        rec = json.load(f)
    E = T.Event
    t = T.TraceSummary({k: [E(*e) for e in v] for k, v in rec["device_ops"].items()},
                       {k: [E(*e) for e in v] for k, v in rec["host_spans"].items()})
    assert t.window_s == pytest.approx(rec["window_s"], rel=1e-9)
    assert t.busy_s == pytest.approx(rec["busy_s"], rel=1e-9)
    got, ctx = _readings(t, builds=rec["builds"])
    assert all(v is not None and v >= 0 for v in got.values())
    for m in STAGES[:4]:
        assert got[m] > 0, m
    assert sum(got.values()) == pytest.approx(_idle_ms_per_build(ctx), rel=1e-9)
    names = {e.name for e in t.main_thread()}
    for s in ("repro.scoring.pass1", "repro.scoring.pass2", "repro.scoring.sweep",
              "repro.scoring.readoff", "repro.scoring.gather.sketch",
              "repro.coreset.sample"):
        assert s in names, s
