"""Idle device time per build in the importance sample: the probabilities
over all n scores, their transfer, the draw and the weights (ms)."""
from chipbench import stages

SPANS = ("repro.coreset.sample",)


def read(ctx):
    return stages.idle_ms(ctx, SPANS)
