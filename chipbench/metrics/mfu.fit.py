"""The whole fit's share of the chip's peak: the operations the fits
completed in the window needed (``costs/fit.py``, a fixed count per fit)
over the window's length times the peak FLOP/s (%)."""


def read(ctx):
    if ctx.peaks is None or not ctx.results:
        return None
    need = ctx.work_total("fits") * ctx.cost("fit").flops(ctx.config, ctx.traffic)
    return 100.0 * need / (ctx.window_s * ctx.peaks["flops_per_s"])
