"""Readings that cells of every kind of call report, each under the suffix
of its kind (``idle_share.build``, ``idle_share.fit``): one body for all."""
from __future__ import annotations

from chipbench.run import LOWERING_EVENT, window_compiles


def idle_share(ctx):
    """Share of the traced window in which no operation ran on the device (%)."""
    t = ctx.trace_summary
    if t is None or t.window_s <= 0 or not t.device_ops:
        return None
    return 100.0 * (1.0 - t.busy_s / t.window_s)


def lowerings_per_call(ctx):
    """Programs traced and lowered anew per call in the window: JAX's
    jaxpr-to-MLIR events over the calls."""
    if not ctx.results:
        return None
    return ctx.counters.get(LOWERING_EVENT, 0) / len(ctx.results)


def compiles_per_call(ctx):
    """Programs the backend compiled anew per call in the window: backend
    compiles less persistent-cache hits."""
    if not ctx.results:
        return None
    return window_compiles(ctx) / len(ctx.results)
