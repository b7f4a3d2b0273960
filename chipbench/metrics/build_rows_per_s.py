"""Input rows of all builds completed in the window over the window's time."""


def read(ctx):
    return ctx.work_total("rows") / ctx.window_s
