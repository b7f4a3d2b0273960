"""Share of the extremes kernel's roofline over the traced window (%)."""
from chipbench import roofline


def read(ctx):
    return roofline.share(ctx, "extremes")
