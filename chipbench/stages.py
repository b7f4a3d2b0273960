"""Idle device time per build under a group of the program's stage spans.

The program marks each stage of a coreset build with a host span
(``jax.profiler.TraceAnnotation``: ``repro.build.*``, ``repro.scoring.*``,
``repro.coreset.*``) on the thread that calls it, the thread that also holds
the benchmark's own span. The stages are siblings inside ``repro.build``, so
no instant of a build lies under two of them. A stage group's reading is the
overlap of every idle gap of the traced window (all of them, not only the
longest the breakdown names) with the union of the group's spans, per
completed build, in ms. A JAX span nested inside a stage (a lowering inside
a pass call) counts for that stage.

Nothing to read (no trace, no build, or a program without the spans) gives
None.
"""
from __future__ import annotations

import os

from chipbench.run import load_module
from chipbench.trace import _clip, _union

# the span that encloses every stage of one build
BUILD_SPAN = "repro.build"
METRICS_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)), "metrics")


def _overlap_ns(a, b) -> float:
    """Length of the intersection of two sorted lists of disjoint intervals."""
    i = j = 0
    total = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] <= b[j][1]:
            i += 1
        else:
            j += 1
    return total


def _readable(ctx):
    t = ctx.trace_summary
    if t is None or not t.device_ops or not ctx.results:
        return None
    thread = t.main_thread()
    if not any(e.name == BUILD_SPAN for e in thread):
        return None
    return t, thread


def _idle_under(t, thread, names) -> float:
    names = set(names)
    spans = _union(_clip([(e.start_ns, e.end_ns) for e in thread if e.name in names],
                         t.t0, t.t1))
    return _overlap_ns(t.idle_gaps(), spans)


def idle_ms(ctx, names) -> float | None:
    """Idle device time per build under the spans named ``names`` (ms)."""
    got = _readable(ctx)
    if got is None:
        return None
    t, thread = got
    return 1e-6 * _idle_under(t, thread, names) / len(ctx.results)


def untraced_idle_ms(ctx, names) -> float | None:
    """Idle device time per build under none of the spans named ``names``
    (ms): between calls, and in any stage of a build left without a span."""
    got = _readable(ctx)
    if got is None:
        return None
    t, thread = got
    idle = sum(e - s for s, e in t.idle_gaps())
    return 1e-6 * (idle - _idle_under(t, thread, names)) / len(ctx.results)


def group_spans(metrics) -> list[str]:
    """The span names of the stage metrics named ``metrics`` (their ``SPANS``)."""
    return [s for m in metrics
            for s in load_module(os.path.join(METRICS_DIR, m + ".py"),
                                 "chipbench_stages_" + m.replace(".", "_")).SPANS]
