"""Share of the traced window in which no operation ran on the device (%):
the reading of ``idle_share.build``, over the fit cell's window."""
import os

from chipbench.run import load_module

read = load_module(os.path.join(os.path.dirname(os.path.abspath(__file__)), "idle_share.build.py"),
                   "chipbench_metric_idle_share_build").read
