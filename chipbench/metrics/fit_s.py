"""The window's seconds over the fits it completed: the mean time of one
whole user call, coreset rows in, fitted parameters and their NLL out."""


def read(ctx):
    return ctx.window_s / ctx.work_total("fits")
