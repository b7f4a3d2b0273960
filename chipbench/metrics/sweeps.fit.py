"""Oracle sweeps over the coreset per fit in the window: the fused
value-and-gradient and the Hessian-vector sweeps the program's L-BFGS
counts (``LAST_LBFGS_SWEEPS``, copied after each fit)."""


def read(ctx):
    if not ctx.results:
        return None
    return ctx.work_total("sweeps") / ctx.work_total("fits")
