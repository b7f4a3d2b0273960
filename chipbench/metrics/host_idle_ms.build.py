"""Idle device time per build in the build's host algebra: the projection
from the Gram or sketch (float64 eigensolve), the hull direction net, the
candidate dedup and score finalize, and the exact hull points (ms)."""
from chipbench import stages

SPANS = ("repro.scoring.projection", "repro.scoring.directions",
         "repro.scoring.finalize", "repro.coreset.hull_points")


def read(ctx):
    return stages.idle_ms(ctx, SPANS)
