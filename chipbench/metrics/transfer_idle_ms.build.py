"""Idle device time per build while rows go to the device and results come
back: the host rows' transfer, the padded staging, and every gather of the
build's device results to the host (ms)."""
from chipbench import stages

SPANS = ("repro.build.put_rows", "repro.scoring.stage",
         "repro.scoring.gather.gram", "repro.scoring.gather.moments",
         "repro.scoring.gather.hull", "repro.scoring.gather.scores",
         "repro.scoring.gather.sketch")


def read(ctx):
    return stages.idle_ms(ctx, SPANS)
