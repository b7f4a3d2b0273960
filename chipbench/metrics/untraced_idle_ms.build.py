"""Idle device time per build under none of the build's stage spans:
between calls, and in any stage of a build left without a span (ms). With
the four stage groups it sums to the window's idle time per build."""
from chipbench import stages

GROUPS = ("launch_idle_ms.build", "transfer_idle_ms.build",
          "host_idle_ms.build", "sample_idle_ms.build")


def read(ctx):
    return stages.untraced_idle_ms(ctx, stages.group_spans(GROUPS))
