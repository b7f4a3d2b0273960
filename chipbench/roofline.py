"""Roofline share of one kernel over a traced window.

The least time the chip could take for the work the window's calls needed
is the larger of operations / peak FLOP/s and bytes / peak HBM bytes/s
(``costs/<kernel>.py``, ``peaks.json``); the share is that over the
kernel's device time in the trace. Nothing to read (no trace, no such
kernel on the device) gives None.
"""
from __future__ import annotations

# A kernel shows in the trace as a ``tpu_custom_call`` HLO instruction named
# after the jitted wrapper that called ``pallas_call``. The sweep kernel's
# instruction is ``%_sweep_pallas.N``; it has no entry here and is matched as
# the custom call that no named kernel claims.
NAMED = {"gram": "%_gram_pallas", "extremes": "%_extremes_pallas",
         "bernstein": "%_bernstein_pallas"}


def is_kernel(kernel: str, op_name: str) -> bool:
    if "tpu_custom_call" not in op_name:
        return False
    if kernel in NAMED:
        return op_name.startswith(NAMED[kernel])
    return not any(op_name.startswith(p) for p in NAMED.values())


def least_time_s(ctx, kernel: str) -> tuple[float, str]:
    cost = ctx.cost(kernel)
    calls = len(ctx.results)
    model = ctx.cell.model
    t_flops = calls * cost.flops(ctx.config, ctx.traffic, model) / ctx.peaks["flops_per_s"]
    t_bytes = calls * cost.bytes(ctx.config, ctx.traffic, model) / ctx.peaks["hbm_bytes_per_s"]
    return (t_flops, "flops") if t_flops >= t_bytes else (t_bytes, "bytes")


def share(ctx, kernel: str) -> float | None:
    if ctx.trace_summary is None or ctx.peaks is None or not ctx.results:
        return None
    t = ctx.trace_summary.kernel_time_s(lambda name: is_kernel(kernel, name))
    if not t:
        return None
    least, _ = least_time_s(ctx, kernel)
    return 100.0 * least / t
