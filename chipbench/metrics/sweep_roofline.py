"""Share of the sweep kernel's roofline over the traced window (%)."""
from chipbench import roofline


def read(ctx):
    return roofline.share(ctx, "sweep")
