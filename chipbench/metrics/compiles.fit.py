"""Programs the backend compiled anew per fit in the window: the reading of
``compiles.build``, a fit being the fit cell's call (each fit's oracles bake
in the coreset's total weight, so a new coreset compiles them)."""
import os

from chipbench.run import load_module

read = load_module(os.path.join(os.path.dirname(os.path.abspath(__file__)), "compiles.build.py"),
                   "chipbench_metric_compiles_build").read
