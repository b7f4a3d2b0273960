"""The whole build's share of the chip's peak: the operations the builds
completed in the traced window needed (``costs/build.py``) over the
window's length times the peak FLOP/s (%)."""


def read(ctx):
    if ctx.trace_summary is None or ctx.peaks is None or not ctx.results:
        return None
    need = len(ctx.results) * ctx.cost("build").flops(ctx.config, ctx.traffic, ctx.cell.model)
    return 100.0 * need / (ctx.window_s * ctx.peaks["flops_per_s"])
