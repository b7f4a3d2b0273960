"""Idle device time per build while the host launches the build's programs:
the scoring engine's construction, the one-pass plan, and each jitted pass
call (trace, lowering, cache fetch, enqueue) (ms)."""
from chipbench import stages

SPANS = ("repro.build.engine", "repro.scoring.plan", "repro.scoring.pass1",
         "repro.scoring.pass2", "repro.scoring.sweep", "repro.scoring.readoff")


def read(ctx):
    return stages.idle_ms(ctx, SPANS)
