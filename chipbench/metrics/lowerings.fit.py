"""Programs traced and lowered anew per fit in the window: the reading of
``lowerings.build``, a fit being the fit cell's call (each fit builds its own
jitted oracles)."""
import os

from chipbench.run import load_module

read = load_module(os.path.join(os.path.dirname(os.path.abspath(__file__)), "lowerings.build.py"),
                   "chipbench_metric_lowerings_build").read
