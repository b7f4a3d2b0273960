"""Programs the backend compiled anew per fit in the window: the reading of
``compiles.build``, a fit being the fit cell's call (each fit's oracles bake
in the coreset's total weight, so a new coreset compiles them)."""
from chipbench.readers import compiles_per_call as read
