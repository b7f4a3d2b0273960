"""The window's milliseconds over all oracle sweeps in it: what one sweep
costs end to end, its host round trip and the host's L-BFGS step included."""


def read(ctx):
    sweeps = ctx.work_total("sweeps") if ctx.results else 0
    if not sweeps:
        return None
    return 1e3 * ctx.window_s / sweeps
